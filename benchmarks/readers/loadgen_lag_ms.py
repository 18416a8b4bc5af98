from ..loadgen import lag_ms_p99

STALL_MARGIN_S = 1.0


def read(ev, **_):
    """How late the generator sent (sent minus due, 99th percentile) over
    the requests due in the window.  Starting the profiler stalls the whole
    process for about a second (PR 23: requests due then were sent 1.1 s
    late), which is the profiler's lag and not the generator's, so in a
    traced run the requests due from a margin before the trace are left out."""
    end, trace = ev["t1"], ev.get("trace")
    if trace is not None and trace.started:
        end = min(end, trace.start_at - STALL_MARGIN_S)
    rows = [r for r in ev.get("requests", ()) if ev["t0"] <= r["due"] < end]
    return lag_ms_p99(rows)
