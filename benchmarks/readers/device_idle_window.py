from .. import reduce_trace
from ..arithmetic import percentile
from . import device_ring

NO_SPAN = "(no span)"


def read(ev, **_):
    """Percent of the whole window in which the device ran none of the
    calls the serving engine launched: 1 - the union of the ring's
    `device.*` spans over the window.  `detail` lays each idle interval
    (of at least the profiler reduction's MIN_GAP_NS) to the innermost host
    span open at its midpoint that lasts at least half the gap, as the
    profiler's `idle_gaps` do for its 3 s; gives the same share over the
    profiled interval beside the profiler's own; `late_ns` (the bound on
    a completion stamp's error): its median, and the share of the window
    it leaves in doubt (a stamp is late, never early: the true idle share
    lies between the value and the value plus `late_share`); and the calls
    taken to end where a collector's pause began (device_ring.py), with
    the share of the window that took off their stamps."""
    records, dev, lo, hi, why_not = device_ring.device_spans(ev)
    if dev is None:
        return {"value": None, "detail": why_not}
    busy = device_ring.busy(dev, lo, hi)
    host = [r for r in records if r["dur"] is not None
            and r["tid"] != dev[0]["tid"]]
    tids = {}
    for r in host:
        if r["name"] == "engine.step":
            tids[r["tid"]] = tids.get(r["tid"], 0) + 1
    main = max(tids, key=tids.get) if tids else None
    line = reduce_trace.HostLine([(r["name"], r["ts"], r["dur"])
                                  for r in host if r["tid"] == main])
    by = {}
    for s, e in reduce_trace.gaps_of(busy, lo, hi):
        if e - s < reduce_trace.MIN_GAP_NS:
            continue
        who = line.covering((s + e) / 2.0, 0.5 * (e - s)) or NO_SPAN
        by[who] = by.get(who, 0.0) + (e - s)
    detail = {
        "window_s": (hi - lo) / 1e9, "spans": len(dev),
        "idle_gaps": [[k, v / 1e9] for k, v in
                      sorted(by.items(), key=lambda kv: -kv[1])[:12]],
        "late_ms_p50": percentile([r["args"].get("late_ns", 0) / 1e6
                                   for r in dev], 50),
        "late_ms_p95": percentile([r["args"].get("late_ns", 0) / 1e6
                                   for r in dev], 95),
        "late_share": device_ring.late_share(dev, lo, hi),
        "paused_calls": sum("paused_ns" in r["args"] for r in dev),
        "paused_share": 100.0 * sum(r["args"].get("paused_ns", 0)
                                    for r in dev) / (hi - lo)}
    tr, red = ev.get("trace"), ev.get("reduced")
    t_start = getattr(tr, "t_start", None)
    t_stop = getattr(tr, "t_stop", None)
    if t_start is not None and t_stop is not None and t_stop > t_start:
        a, b = device_ring.perf_ns(ev, t_start), device_ring.perf_ns(ev, t_stop)
        inside = [r for r in dev if r["ts"] < b and r["ts"] + r["dur"] > a]
        detail["traced"] = {
            "window_s": (b - a) / 1e9,
            "idle_share": device_ring.idle_share(inside, a, b),
            "late_share": device_ring.late_share(inside, a, b),
            "profiler_idle_share": None if not red
            else 100.0 * red["idle_share"]}
    return {"value": device_ring.idle_share(dev, lo, hi), "detail": detail}
