from ..arithmetic import percentile
from . import program_ring


def read(ev, span, less, q, **_):
    """Percentile, in ms, over the window's spans named `span` of the
    span's duration less its descendants named in `less` (patterns of
    fnmatch): what the span's own layer spent, without what it waited for
    below.  Expects the serving loop's names of
    paddle_tpu/observability/trace.py, e.g. `engine.decode_step` less
    `engine.decode.wait`, `engine.prefill` less `engine.prefill.wait`,
    `capture.call` less `capture.execute`.  A program that records the
    span and none of `less` would read the whole span: None there."""
    records, lo, hi, why_not = program_ring.window_records(ev)
    if records is None:
        return {"value": None, "detail": why_not}
    mine = [r for r in records if r["name"] == span and r["dur"] is not None
            and lo <= r["ts"] <= hi]
    if not mine:
        return {"value": None, "detail": f"no {span} span in the window"}
    if not any(program_ring.matches(r["name"], less) for r in records):
        return {"value": None,
                "detail": f"the program records no span of {list(less)}"}
    below = program_ring.less_by_ancestor(records, (span,), less,
                                          float("-inf"), float("inf"))
    own = [(r["dur"] - below.get(r["id"], 0.0)) / 1e6 for r in mine]
    return {"value": percentile(own, q),
            "detail": {"spans": len(own), "p50_ms": percentile(own, 50),
                       "p95_ms": percentile(own, 95),
                       "whole_p50_ms": percentile(
                           [r["dur"] / 1e6 for r in mine], 50)}}
