"""ops/pallas/selective_scan.py: the Mamba recurrence over a prefill's
positions, one call a Mamba layer a prefill.  Counted a call, at the
prefill's bucket S: every HBM array the kernel reads or writes ONCE (u, z and
y in the activations' type, dt float32 as the kernel takes it, B and C, A, D,
the state in and out), and the recurrence's elementwise operations (7 a
position, channel and state: dt A, its exp, the state's update, the sum with
C; 7 more a position and channel: dt u, D u, the gate).  The VPU bounds the
kernel, and `kernel_roofline` divides operations by the MXU's peak, so the
share is set by the bytes and reads low."""
from .. import model_jamba


def per_call(s, di, n, itemsize=2):
    nbytes = s * di * (3 * itemsize + 4) + 2 * s * n * 4 \
        + di * n * 4 + di * 4 + 2 * n * di * 4
    return s * di * (7 * n + 7), nbytes


def traced_prefill_buckets(ev):
    """(bucket, share inside the traced window) of each `engine.prefill`
    span that overlaps it; the ring's clock is the monotonic one."""
    trace, skew = ev["trace"], ev.get("clock_skew_ns", 0)
    lo, hi = trace.t_start * 1e9 + skew, trace.t_stop * 1e9 + skew
    out = []
    for sp in ev["spans"]:
        if sp["name"] != "engine.prefill" or not sp["dur"]:
            continue
        inside = min(sp["ts"] + sp["dur"], hi) - max(sp["ts"], lo)
        if inside > 0:
            out.append((int(sp["args"]["bucket"]), inside / sp["dur"]))
    return out


def work(ev, calls):
    cell = ev["cell"]
    jcfg = model_jamba.jamba_config(cell.config, cell.depth())
    layers = sum(not jcfg.is_attention(i) for i in range(cell.depth()))
    flops = nbytes = 0.0
    for bucket, share in traced_prefill_buckets(ev):
        f, b = per_call(bucket, jcfg.d_inner, jcfg.mamba_d_state)
        flops += layers * share * f
        nbytes += layers * share * b
    return {"selective_scan": (flops, nbytes)}
