def read(ev, **_):
    """Imbalance over the held experts in the window: the tokens of the
    fullest held expert over the mean a held expert, from the engine's
    running sums `moe_expert_tokens` (one a held expert, over the expert
    layers) where the mode keeps them in `engine_info`.  1.0 is even; the
    grouped matmul's work follows the sum, its row tiles' padding the
    fullest.  A program without the counters reads None."""
    info = ev.get("engine_info") or {}
    a, b = info.get("before") or {}, info.get("after") or {}
    if "moe_expert_tokens" not in b:
        return None
    tokens = [y - x for x, y in zip(a["moe_expert_tokens"],
                                    b["moe_expert_tokens"])]
    if not tokens or sum(tokens) <= 0:
        return None
    return {"value": max(tokens) * len(tokens) / sum(tokens),
            "detail": {"tokens": tokens,
                       "local_share": (b["moe_assignments_local"]
                                       - a["moe_assignments_local"])
                       / max(b["moe_assignments"] - a["moe_assignments"], 1)}}
