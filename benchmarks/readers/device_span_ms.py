from ..arithmetic import percentile
from . import device_ring


def read(ev, span, q, **_):
    """Percentile, in ms, of the durations of the ring's `device.*` spans
    named `span` that start inside the window: e.g. `device.decode_step`,
    the slot step on the device, where `engine.decode_step` is a turn of
    the host's loop."""
    records, dev, lo, hi, why_not = device_ring.device_spans(ev)
    if dev is None:
        return {"value": None, "detail": why_not}
    mine = [r for r in dev if r["name"] == span and lo <= r["ts"] <= hi]
    if not mine:
        return {"value": None, "detail": f"no {span} span in the window"}
    ms = [r["dur"] / 1e6 for r in mine]
    late = [r["args"].get("late_ns", 0) / 1e6 for r in mine]
    return {"value": percentile(ms, q),
            "detail": {"spans": len(ms), "p50_ms": percentile(ms, 50),
                       "p95_ms": percentile(ms, 95),
                       "late_ms_p50": percentile(late, 50),
                       "late_ms_max": max(late)}}
