from . import program_ring


def read(ev, **_):
    """Percent of the window inside the ring's `gc.collect` spans (Python's
    collector, paddle_tpu/observability/trace.py; generation 1 and 2: a
    collection of generation 0 is only counted there), clipped to the
    window; `detail` counts them and gives the longest by generation.  A
    program without the collector's hook (its trace_info() has no `gc`)
    reads None; one that has it and paused in no such collection reads 0."""
    from paddle_tpu.observability import trace as ptrace
    if "gc" not in ptrace.trace_info():
        return {"value": None,
                "detail": "the program records no gc.collect span"}
    records, lo, hi, why_not = program_ring.window_records(ev)
    if records is None:
        return {"value": None, "detail": why_not}
    mine = [r for r in records if r["name"] == "gc.collect"
            and r["dur"] is not None and r["ts"] < hi
            and r["ts"] + r["dur"] > lo]
    by_gen = {}
    for r in mine:
        g = by_gen.setdefault(str(r["args"].get("generation")),
                              {"count": 0, "longest_ms": 0.0})
        g["count"] += 1
        g["longest_ms"] = max(g["longest_ms"], r["dur"] / 1e6)
    inside = sum(program_ring.overlap(r, lo, hi) for r in mine)
    return {"value": 100.0 * inside / (hi - lo),
            "detail": {"pauses": len(mine), "inside_s": inside / 1e9,
                       "by_generation": by_gen}}
