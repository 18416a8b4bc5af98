from . import program_ring


def read(ev, spans, less=(), **_):
    """Percent of the window spent inside the spans named in `spans`, less
    their descendants matching a pattern of `less`; spans are clipped to
    the window.  Expects the serving loop's names of
    paddle_tpu/observability/trace.py: `engine.prefill` +
    `engine.prefill_chunk` for the window's prefill share; `engine.step`
    less `*.wait` for the share in which the host, not the device, sets
    the pace (an upper bound on the idle the host causes: the device may
    still run what was launched while the host goes on)."""
    records, lo, hi, why_not = program_ring.window_records(ev)
    if records is None:
        return {"value": None, "detail": why_not}
    mine = [r for r in records if r["name"] in spans and r["dur"] is not None
            and r["ts"] < hi and r["ts"] + r["dur"] > lo]
    if not mine:
        return {"value": None,
                "detail": f"no span of {list(spans)} in the window"}
    below = program_ring.less_by_ancestor(records, tuple(spans), less, lo, hi)
    inside = sum(program_ring.overlap(r, lo, hi) for r in mine)
    taken = sum(below.get(r["id"], 0.0) for r in mine)
    return {"value": 100.0 * (inside - taken) / (hi - lo),
            "detail": {"spans": len(mine), "inside_s": inside / 1e9,
                       "less_s": taken / 1e9, "window_s": (hi - lo) / 1e9}}
