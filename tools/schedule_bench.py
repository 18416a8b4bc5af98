"""Pipeline-schedule comparison artifact (VERDICT r2 weak #3 / r3 item 5).

Times one full training step (loss + grads) under three schedules on the
same stage model and mesh:
  - gpipe:        forward scan + AD backward
  - 1f1b fused:   fused-round schedule (steady state = unconditional fwd+bwd
                  per round, no dispatch branch)
  - 1f1b compact: tick-switch schedule (tightest min(S,M) stash)

Run on the CPU mesh the numbers are ratios, not absolutes — single-chip
hardware cannot host a pp>1 mesh, so the wall-time RATIO at compute-bound
stage sizes is the decision artifact (the per-tick dispatch overhead being
measured is platform-independent program structure). The FLOP ratio is the
deterministic check that neither 1F1B variant burns redundant compute
(cost_analysis sums cond branches, so fused's edge conds over-count a
little — wall time is the metric that matters).

Usage: python tools/schedule_bench.py [--pp 4] [--mb 8] [--h 256] [--rows 32]
    -> one JSON line on stdout (also written to SCHEDULE_BENCH.json when
       --save is passed).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# schedule shapes on an 8-device virtual CPU mesh: never the chip, whatever
# the caller's JAX_PLATFORMS says
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def build(pp=4, M=8, mb=8, h=256):
    """Stage = one matmul+tanh over an (mb, h) microbatch; h is sized so the
    matmul dominates and per-tick dispatch shows up as a ratio, not noise."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import paddle_tpu.distributed as dist
    from paddle_tpu.parallel import mesh as mesh_mod
    from paddle_tpu.parallel.pipeline import spmd_pipeline, spmd_pipeline_1f1b

    dist.init_parallel_env({"pp": pp})
    mesh = mesh_mod.get_mesh()
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(pp, h, h).astype(np.float32) * 0.1),
              "b": jnp.asarray(rng.randn(pp, h).astype(np.float32) * 0.1)}
    head = {"wo": jnp.asarray(rng.randn(h, h).astype(np.float32) * 0.1)}
    x = jnp.asarray(rng.randn(M, mb, h).astype(np.float32))
    labels = jnp.asarray(rng.randn(M, mb, h).astype(np.float32))

    def stage_fn(p, v):
        return jnp.tanh(v @ p["w"][0] + p["b"][0])

    def head_loss(hp, y, lab):
        return jnp.mean((y @ hp["wo"] - lab) ** 2)

    def gpipe_step(params, head, x, labels):
        def loss(params, head):
            y = spmd_pipeline(stage_fn, params, x, n_microbatches=M,
                              mesh=mesh, schedule="gpipe")
            per = [head_loss(head, y[m], labels[m]) for m in range(M)]
            return sum(per) / M
        return jax.value_and_grad(loss, argnums=(0, 1))(params, head)

    def f1b_step(variant):
        def step(params, head, x, labels):
            loss, gs, gh, _ = spmd_pipeline_1f1b(
                stage_fn, head_loss, params, head, x, labels,
                n_microbatches=M, mesh=mesh, variant=variant)
            return loss, (gs, gh)
        return step

    raw = dict(gpipe=gpipe_step, f1b_fused=f1b_step("fused"),
               f1b_compact=f1b_step("compact"))
    return {k: jax.jit(v) for k, v in raw.items()}, raw, \
        (params, head, x, labels)


def measure(fn, args, iters=20):
    compiled = fn.lower(*args).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    flops = float(cost.get("flops", float("nan")))
    out = compiled(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = compiled(*args)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    loss = float(jax.tree_util.tree_leaves(out)[0])
    return flops, dt, loss


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pp", type=int, default=4)
    ap.add_argument("--mb", type=int, default=8, help="microbatches M")
    ap.add_argument("--h", type=int, default=256)
    ap.add_argument("--rows", type=int, default=32, help="rows per microbatch")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--save", help="also write JSON to this path")
    args = ap.parse_args()

    fns, raw, fargs = build(pp=args.pp, M=args.mb, mb=args.rows, h=args.h)
    from paddle_tpu.jit.passes import comm_schedule as _cs
    res = {}
    losses = {}
    for name, fn in fns.items():
        f, t, l = measure(fn, fargs, iters=args.iters)
        res[name] = {"flops": f, "step_ms": round(t * 1e3, 2)}
        # comm-volume + overlap-slot columns: the schedule's collective
        # equations as the capture-tier comm pass sees them (GC3-style
        # accounting — count, payload bytes, concurrently-issuable slots)
        try:
            res[name]["comm"] = _cs.analyze(jax.make_jaxpr(raw[name])(*fargs))
        except Exception as e:  # noqa: BLE001 — columns are best-effort
            res[name]["comm"] = {"error": str(e)[:120]}
        losses[name] = l
    for name, l in losses.items():
        assert abs(l - losses["gpipe"]) < 1e-5 * max(1.0, abs(losses["gpipe"])), \
            (name, l, losses["gpipe"])
    out = {
        "config": {"pp": args.pp, "microbatches": args.mb, "h": args.h,
                   "rows_per_microbatch": args.rows,
                   "platform": jax.devices()[0].platform},
        **res,
        "time_ratio_fused_over_gpipe":
            round(res["f1b_fused"]["step_ms"] / res["gpipe"]["step_ms"], 3),
        "time_ratio_compact_over_gpipe":
            round(res["f1b_compact"]["step_ms"] / res["gpipe"]["step_ms"], 3),
        "flops_ratio_compact_over_gpipe":
            round(res["f1b_compact"]["flops"] / res["gpipe"]["flops"], 3),
        "loss_parity": True,
        "stash_microbatches": {
            "gpipe": args.mb + args.pp - 1,
            "1f1b_fused": min(2 * args.pp - 1, args.mb),
            "1f1b_compact": min(args.pp, args.mb)},
    }
    print(json.dumps(out))
    if args.save:
        with open(args.save, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
