def read(ev, **_):
    """Share of the positions the window's prefills computed that were
    padding (a bucket's right pad): the engine's two running sums
    `prefill_positions` / `prefill_positions_padded` as a difference over the
    window where the mode keeps them in `engine_info`, else the same sums
    over the window's `engine.prefill` spans (`pad` beside `bucket`).  A
    program with neither (the parent of the PR that added them) reads None."""
    info = ev.get("engine_info") or {}
    a, b = info.get("before") or {}, info.get("after") or {}
    if "prefill_positions_padded" in b:
        real = b["prefill_positions"] - a["prefill_positions"]
        padded = b["prefill_positions_padded"] - a["prefill_positions_padded"]
    else:
        skew = ev.get("clock_skew_ns", 0)
        lo, hi = ev["t0"] * 1e9 + skew, ev["t1"] * 1e9 + skew
        spans = [s["args"] for s in ev.get("spans") or ()
                 if s["name"] == "engine.prefill" and lo <= s["ts"] < hi
                 and "pad" in s["args"]]
        padded = sum(s["bucket"] for s in spans)
        real = padded - sum(s["pad"] for s in spans)
    if padded <= 0:
        return None
    return {"value": 100.0 * (1.0 - real / padded),
            "detail": {"positions": real, "with_padding": padded}}
