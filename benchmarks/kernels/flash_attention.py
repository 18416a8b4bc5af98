"""ops/pallas/flash_attention.py: causal, grouped-query, [B, S, H, D]."""


def shapes(ev):
    cell = ev["cell"]
    cfg, tr = cell.config, cell.traffic
    mesh = tr.get("mesh") or {}
    b = int(tr["sequences_per_step"]) // mesh.get("dp", 1)
    h = cfg["num_attention_heads"] // mesh.get("mp", 1)
    hkv = max(1, cfg["num_key_value_heads"] // mesh.get("mp", 1))
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    return b, int(tr["seq_len"]), h, hkv, d


def per_call(b, s, h, hkv, d, itemsize=2):
    """(flops, bytes) a call.  A matmul over the causal half of the S x S
    square is B*H*S*S*D operations (2 x half).  forward: QK^T, PV.
    bwd_dq: QK^T, dO V^T, dS K.  bwd_dkv: QK^T, P^T dO, dO V^T, dS^T Q.
    Bytes: each [B, H, S, D] tensor read or written once; the backward
    kernels take K and V expanded to all heads."""
    mm = b * h * s * s * d
    full, kv = b * h * s * d * itemsize, b * hkv * s * d * itemsize
    return {"flash_attention_fwd": (2 * mm, 2 * full + 2 * kv),
            "flash_attention_bwd_dq": (3 * mm, 5 * full),
            "flash_attention_bwd_dkv": (4 * mm, 6 * full)}


def work(ev, calls):
    return {k: (f * calls(k), n * calls(k))
            for k, (f, n) in per_call(*shapes(ev)).items()}
