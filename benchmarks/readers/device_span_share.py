from . import device_ring


def read(ev, spans, **_):
    """Percent of the window the device spent in the calls named in `spans`
    (the ring's `device.*` spans, clipped to the window): e.g.
    `device.prefill` + `device.prefill_chunk` + `device.window`, every
    piece of a cut prompt counted on the device, where the host's
    `engine.prefill_chunk` span holds none of its device time."""
    records, dev, lo, hi, why_not = device_ring.device_spans(ev)
    if dev is None:
        return {"value": None, "detail": why_not}
    by = {name: 0.0 for name in spans}
    calls = 0
    for r in dev:
        if r["name"] in by:
            by[r["name"]] += min(r["ts"] + r["dur"], hi) - max(r["ts"], lo)
            calls += 1
    return {"value": 100.0 * sum(by.values()) / (hi - lo),
            "detail": {"calls": calls, "window_s": (hi - lo) / 1e9,
                       **{k + "_s": v / 1e9 for k, v in by.items()}}}
