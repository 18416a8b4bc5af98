"""ops/pallas/decode_attention.py: one query a slot over the live prefix of
its cache.  Memory-bound: what it needs is the live keys and values."""


def live_positions(requests, t0, t1):
    """Cache positions attended by the decode steps whose tokens were
    stamped in [t0, t1]: token j >= 1 of a request attends prompt + j."""
    total = 0
    for r in requests:
        for j, t in enumerate(r["token_times"]):
            if j >= 1 and t0 <= t <= t1:
                total += r["prompt_len"] + j
    return total


def work(ev, calls):
    cell, trace = ev["cell"], ev["trace"]
    cfg = cell.config
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    per_position = 2 * cfg["num_key_value_heads"] * d * 2     # K and V, bf16
    pos = live_positions(ev["requests"], trace.t_start, trace.t_stop)
    nbytes = pos * per_position * cell.depth()
    # QK^T and PV: 4 x heads x D operations a position, 4 x kv heads x D bytes
    flops = nbytes * cfg["num_attention_heads"] / cfg["num_key_value_heads"]
    return {"ragged_decode_attention": (flops, nbytes)}
