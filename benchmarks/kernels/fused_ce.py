"""ops/pallas/fused_ce.py: output head and cross-entropy in one, the
[tokens, vocab] logits never stored."""


def per_call(n, h, v, itemsize=2):
    """(flops, bytes) a call for n tokens, hidden h, vocabulary v.  forward:
    the logits matmul.  bwd_dh: the logits again and d_logits W^T.  bwd_dw:
    the logits again and h^T d_logits.  Bytes: h and W read once, the
    result written once."""
    mm = 2 * n * h * v
    hb, wb = n * h * itemsize, h * v * itemsize
    return {"fused_ce_fwd": (mm, hb + wb),
            "fused_ce_bwd_dh": (2 * mm, 2 * hb + wb),
            "fused_ce_bwd_dw": (2 * mm, hb + 2 * wb)}


def work(ev, calls):
    cell = ev["cell"]
    cfg, tr = cell.config, cell.traffic
    n = int(tr["sequences_per_step"]) * int(tr["seq_len"])
    return {k: (f * calls(k), b * calls(k))
            for k, (f, b) in per_call(n, cfg["hidden_size"],
                                      cfg["vocab_size"]).items()}
