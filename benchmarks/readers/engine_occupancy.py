def read(ev, **_):
    """Mean share of the engine's slots that decoded, over the decode steps
    of the window: the engine's own occupancy sum, as a difference."""
    info = ev.get("engine_info")
    if not info:
        return None
    a, b = info["before"], info["after"]
    steps = b["decode_steps"] - a["decode_steps"]
    if steps <= 0:
        return None
    occ = (b["avg_occupancy"] * b["decode_steps"]
           - a["avg_occupancy"] * a["decode_steps"])
    return 100.0 * occ / steps
