from ..arithmetic import percentile


def read(ev, span, q, **_):
    """Percentile of the durations of one of the program's spans
    (observability/trace.py ring, on under PT_TRACE=1) inside the window."""
    skew = ev.get("clock_skew_ns", 0)
    lo, hi = ev["t0"] * 1e9 + skew, ev["t1"] * 1e9 + skew
    durs = [s["dur"] / 1e6 for s in ev.get("spans", ())
            if s["name"] == span and s["dur"] is not None
            and lo <= s["ts"] <= hi]
    return percentile(durs, q)
