from ..arithmetic import percentile
from . import program_ring


def read(ev, event, attr, q, **_):
    """Percentile, in ms, of a nanosecond attribute of the window's events
    named `event` in the program's span ring; expects `scheduler.join`'s
    `waited_ns` (submit to join) of paddle_tpu/observability/trace.py."""
    records, lo, hi, why_not = program_ring.window_records(ev)
    if records is None:
        return {"value": None, "detail": why_not}
    got = [r["args"][attr] / 1e6 for r in records
           if r["name"] == event and lo <= r["ts"] <= hi
           and attr in r["args"]]
    if not got:
        return {"value": None,
                "detail": f"no {event} event with {attr} in the window"}
    return {"value": percentile(got, q),
            "detail": {"events": len(got), "p50_ms": percentile(got, 50),
                       "max_ms": max(got)}}
