"""One small module a reader: `read(ev, **params)` takes a per-layer metric
from the run's evidence (stamps, request records, the program's counters and
spans, the reduced device trace) and returns a number, a dict with `value`
and `detail`, or None where there is nothing to read (the harness then
leaves the metric out).  layer_metrics/<metric>.json names its reader."""
