"""What the device-span readers share (no reader itself): the `device.*`
records of the program's span ring (paddle_tpu/observability/trace.py:
one record a call the serving engine launched, from the later of the
previous call's completion and its own dispatch to its completion as the
host saw it) and the time their union covers.  A program that records no
such span (the parent of the PR that added them) reads None, with the
reason.

A completion is stamped when the host looks, so a collector's pause, which
holds the whole interpreter, makes the stamp of a call in flight late by
the pause: the device finishes and idles while the host cannot look.  Where
a `gc.collect` span began inside a call's `late_ns` (after the last check
that found the call not done), the call is taken to end where the pause
began.  That end is early by at most what was left of the calls in flight
when the pause began (a decode step's tail); the stamp as seen would be
late by the whole pause."""
from __future__ import annotations

from .. import reduce_trace
from . import program_ring

PREFIX = "device."


def device_spans(ev):
    """(records, device spans, lo, hi, why_not): the ring, its `device.*`
    spans that overlap the window, and the window in the ring's
    nanoseconds; `why_not` says why there is nothing to read."""
    records, lo, hi, why_not = program_ring.window_records(ev)
    if records is None:
        return None, None, lo, hi, why_not
    dev = [r for r in records if r["name"].startswith(PREFIX)
           and r["dur"] is not None]
    dev = ended_at_pauses(dev, records)
    if not dev:
        return records, None, lo, hi, ("the program records no device.* "
                                       "span (observability/trace.py "
                                       "launched)")
    mine = [r for r in dev if r["ts"] < hi and r["ts"] + r["dur"] > lo]
    if not mine:
        return records, None, lo, hi, "no device.* span in the window"
    return records, mine, lo, hi, None


def ended_at_pauses(dev, records) -> list:
    """The device spans, each whose `late_ns` holds the start of a
    `gc.collect` span ended at the first such start (module docstring),
    with `late_ns` cut to the doubt before it and `paused_ns` the part taken
    off; the records themselves are left as they are."""
    pauses = sorted(r["ts"] for r in records
                    if r["name"] == "gc.collect" and r["dur"])
    out = []
    for r in dev:
        end = r["ts"] + r["dur"]
        seen = end - r["args"].get("late_ns", 0)     # the last check before
        g = next((p for p in pauses if seen < p < end), None)
        if g is None:
            out.append(r)
            continue
        g = max(g, r["ts"])
        out.append(dict(r, dur=g - r["ts"], args=dict(
            r["args"], late_ns=max(0, g - seen), paused_ns=end - g)))
    return out


def busy(spans, lo, hi) -> list:
    """The union of the spans inside [lo, hi], merged."""
    return reduce_trace.merged(reduce_trace.clipped(
        [(r["ts"], r["ts"] + r["dur"]) for r in spans], lo, hi))


def idle_share(spans, lo, hi) -> float:
    """Percent of [lo, hi] that no span covers."""
    return 100.0 * (1.0 - reduce_trace.measure(busy(spans, lo, hi))
                    / (hi - lo))


def perf_ns(ev, t: float) -> float:
    """A stamp of the benchmark's clock (perf_counter seconds) on the
    ring's."""
    return t * 1e9 + ev.get("clock_skew_ns", 0)


def late_share(spans, lo, hi) -> float:
    """Percent of [lo, hi] that the spans' `late_ns` leave in doubt: a
    completion lies between its stamp less `late_ns` and its stamp, so the
    true idle share lies between `idle_share` and it plus this."""
    doubt = 0.0
    for r in spans:
        end = r["ts"] + r["dur"]
        late = min(r["args"].get("late_ns", 0), r["dur"])
        doubt += max(0.0, min(end, hi) - max(end - late, lo))
    return 100.0 * doubt / (hi - lo)

