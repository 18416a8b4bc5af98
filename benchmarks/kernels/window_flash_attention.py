"""ops/pallas/flash_attention.py's forward kernel as `models/mimo.py`
prefills through it (`windowed_flash_attention`): for every `engine.prefill`
span in the traced window, one causal call a full layer and one banded call
a window layer at the span's bucket S.  Counted a call: the score and value
matmuls of the visible (query, key) pairs only, 2 x heads x (D_k + D_v)
operations a pair (a causal call sees S (S + 1) / 2 pairs, a banded one the
band's: min(i + 1, window) keys for query i), and q, k, v and the output
once each in bfloat16."""
from .grouped_expert_matmul import steps_inside


def pairs(s: int, window=None) -> int:
    """Visible (query, key) pairs of S positions."""
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def per_call(s, heads, kv_heads, d_k, d_v, window=None, itemsize=2):
    flops = 2 * heads * (d_k + d_v) * pairs(s, window)
    nbytes = itemsize * s * (heads * (d_k + d_v) + kv_heads * (d_k + d_v))
    return flops, nbytes


def work(ev, calls):
    cell = ev["cell"]
    cfg = cell.config
    pattern = cfg["hybrid_layer_pattern"][:cell.depth()]
    h, dk, dv = (cfg["num_attention_heads"], cfg["head_dim"],
                 cfg["v_head_dim"])
    trace, skew = ev["trace"], ev.get("clock_skew_ns", 0)
    flops = nbytes = 0.0
    for kind, bucket, share in steps_inside(
            ev, trace.t_start * 1e9 + skew, trace.t_stop * 1e9 + skew):
        if kind != "prefill":
            continue
        for n, kv, window in (
                (pattern.count(0), cfg["num_key_value_heads"], None),
                (pattern.count(1), cfg["swa_num_key_value_heads"],
                 cfg["sliding_window"])):
            f, b = per_call(bucket, h, kv, dk, dv, window)
            flops += n * share * f
            nbytes += n * share * b
    return {"flash_attention_fwd": (flops, nbytes)}
