"""ops/pallas/selective_scan.py `ssm_decode_update`: the Mamba recurrence's
one position a slot in the decode step, one call a Mamba layer a step (26 a
Jamba step, 9 a Phi-4-flash step), over every slot of the engine whether it
decodes or not.  Counted a call: every HBM array the kernel reads or writes
once (the float32 state in and out, A and D, u, z and y in the activations'
type, dt float32, B and C), and the recurrence's elementwise operations as
`selective_scan.py` counts them a position (7 a slot, channel and state; 7
a slot and channel).  Memory-bound: the state is 92.5% of the bytes."""
from .. import model_jamba


def per_call(slots, di, n, itemsize=2):
    nbytes = 2 * slots * n * di * 4 + n * di * 4 + di * 4 \
        + slots * di * (3 * itemsize + 4) + 2 * slots * n * 4
    return slots * di * (7 * n + 7), nbytes


def mamba_geometry(cell):
    """(Mamba layers, d_inner, d_state) of the cell's model as run."""
    depth = cell.depth()
    if cell.mode.startswith("phi4flash"):
        from ..model_phi4flash import phi4flash_config
        cfg = phi4flash_config(cell.config, depth)
        layers = sum(cfg.layer_kind(i) == "mamba" for i in range(depth))
    else:
        cfg = model_jamba.jamba_config(cell.config, depth)
        layers = sum(not cfg.is_attention(i) for i in range(depth))
    return layers, cfg.d_inner, cfg.mamba_d_state


def work(ev, calls):
    cell = ev["cell"]
    _, di, n = mamba_geometry(cell)
    flops, nbytes = per_call(int(cell.traffic["slots"]), di, n)
    k = calls("ssm_decode_update")
    return {"ssm_decode_update": (k * flops, k * nbytes)}
