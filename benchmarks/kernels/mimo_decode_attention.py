"""ops/pallas/decode_attention.py `ragged_decode_attention` over the two
kinds of cache of `models/mimo.py`: one query a slot over the live prefix of
a full layer's cache, and over the live part of a window layer's ring.
Memory-bound: what it needs is the live keys and values, at their REAL lane
counts (K is allocated with 256 lanes of which 192 are keys: the pad is the
layout's cost, not the algorithm's need)."""


def live_positions(requests, t0, t1, window):
    """(positions attended in a full layer, in a window layer) by the decode
    steps whose tokens were stamped in [t0, t1]: token j >= 1 of a request
    attends prompt + j positions, of which a ring holds the last `window`."""
    full = ring = 0
    for r in requests:
        for j, t in enumerate(r["token_times"]):
            if j >= 1 and t0 <= t <= t1:
                full += r["prompt_len"] + j
                ring += min(r["prompt_len"] + j, window)
    return full, ring


def work(ev, calls):
    cell, trace = ev["cell"], ev["trace"]
    cfg = cell.config
    pattern = cfg["hybrid_layer_pattern"][:cell.depth()]
    lanes = cfg["head_dim"] + cfg["v_head_dim"]              # K and V, bf16
    full, ring = live_positions(ev["requests"], trace.t_start, trace.t_stop,
                                cfg["sliding_window"])
    nbytes = 2 * lanes * (
        full * cfg["num_key_value_heads"] * pattern.count(0)
        + ring * cfg["swa_num_key_value_heads"] * pattern.count(1))
    # QK^T and PV: 2 x heads x (D_k + D_v) operations a position and layer
    flops = 2 * cfg["num_attention_heads"] * lanes * (
        full * pattern.count(0) + ring * pattern.count(1))
    return {"ragged_decode_attention": (flops, nbytes)}
