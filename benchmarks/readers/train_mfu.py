from ..arithmetic import (mfu_percent, train_flops_per_token,
                          whole_step_throughput)


def read(ev, **_):
    """Operations the model's forward and backward need a token (recompute
    not counted) x tokens/s (all whole steps over all their time, as
    `train_tokens_per_s`) over chips x the bf16 peak."""
    if "stamps" not in ev or ev.get("peaks") is None:
        return None
    cell = ev["cell"]
    tps = whole_step_throughput(ev["stamps"], ev["t0"], ev["t1"],
                                ev["tokens_per_step"])
    if tps is None:
        return None
    fpt = train_flops_per_token(cell.config, cell.depth(),
                                int(cell.traffic["seq_len"]))
    return {"value": mfu_percent(tps, fpt, cell.chips,
                                 ev["peaks"]["bf16_flops"]),
            "detail": {"flops_per_token": fpt, "tokens_per_s": tps}}
