"""mode `train`: the hybrid train step as a training loop drives it."""
from __future__ import annotations

import math
import time

import numpy as np

from .. import harness, model as bmodel, reference

# Tolerances, with their reasons.  The step computes in bf16 with f32
# accumulation, the reference in f32 throughout.  Measured on the v5e over
# PR 23's 27 runs of the two training cells (six seeds): the loss was off by
# at most 3.4e-5 of itself and the embedding's gradient norm by at most
# 3.1e-4.  Each tolerance is about fifteen times its worst reading: room for
# a seed that rounds unluckily, little for anything coarser than bf16.  With
# random weights the loss sits near ln(vocab) whatever the layers compute,
# so it is the gradient norm, which has run back through every layer, that
# does the work.  Both are single numbers in which errors can cancel: what
# a lower precision in one layer does to them at these sizes is not measured.
LOSS_RTOL = 5e-4
GRAD_NORM_RTOL = 5e-3


def run(cell, env) -> dict:
    import jax
    import jax.numpy as jnp

    import paddle_tpu as P
    from paddle_tpu.models import build_hybrid_train_step
    from paddle_tpu.ops.pallas._common import kernel_names
    from paddle_tpu.parallel import mesh as mesh_mod

    tr, cfg, depth = cell.traffic, cell.config, cell.depth()
    seq, nseq = int(tr["seq_len"]), int(tr["sequences_per_step"])
    mesh_shape = tr.get("mesh")
    mesh = mesh_mod.init_mesh(dict(mesh_shape)) if mesh_shape else None
    dp = mesh_shape.get("dp", 1) if mesh_shape else 1
    checks = []
    try:
        model = bmodel.build_model(cfg, depth, env.seed, jnp.float32)
        jax.block_until_ready(bmodel.weights_of(model))
        env.phase("model and seeded weights")
        o = tr["optimizer"]
        opt = getattr(P.optimizer, o["name"])(
            learning_rate=o["learning_rate"], weight_decay=o["weight_decay"],
            parameters=model.parameters())
        step = build_hybrid_train_step(
            model, opt, mesh=mesh, amp=tr["amp"], remat=tr["remat"],
            fused_loss=tr["fused_loss"])
        rng = np.random.default_rng(env.seed)
        env.phase("optimizer and train step built")

        def batch(n, s):
            ids = rng.integers(0, cfg["vocab_size"], (n, s + 1))
            return ids, {"input_ids": P.to_tensor(ids[:, :-1]),
                         "labels": P.to_tensor(ids[:, 1:])}

        # -- correct: loss and a gradient norm against the reference, on a
        # short batch, BEFORE the weights move.  The step's first AdamW
        # moment is (1 - beta1) x gradient, so the gradient is read from the
        # optimizer state the step itself keeps.
        cs = int(tr["check_seq_len"])
        ids, b = batch(dp, cs)
        want_loss, want_gn = reference.loss_and_embed_grad_norm(
            cfg, bmodel.weights_of(model), depth,
            jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:]))
        env.phase("reference loss and gradient")
        got_loss = float(step(b).numpy())
        outer = [n for n, _ in model.named_parameters() if ".layers." not in n]
        m1 = step.state["opt"][0][outer.index("llama.embed_tokens.weight")][
            "moment1"]
        got_gn = float(jnp.sqrt(jnp.sum(jnp.square(m1.astype(jnp.float32))))
                       ) / (1.0 - opt._beta1)
        checks.append(("loss vs reference", got_loss, want_loss,
                       abs(got_loss - want_loss) <= LOSS_RTOL * abs(want_loss)))
        checks.append(("embedding gradient norm vs reference", got_gn, want_gn,
                       abs(got_gn - want_gn) <= GRAD_NORM_RTOL * want_gn))

        env.phase("check step")
        # -- the kernels the cell is about are in the step that is timed
        _, b = batch(nseq, seq)
        if tr["kernels_expected"]:
            found = set(kernel_names(step.lower_text(b)))
            missing = [k for k in tr["kernels_expected"] if k not in found]
            checks.append(("kernels in the step", sorted(found),
                           tr["kernels_expected"], not missing))

        env.phase("kernels found in the step's HLO")
        # -- warm-up: the one shape the window uses
        losses = []
        for _ in range(int(tr["warmup_steps"])):
            losses.append(float(step(b).numpy()))
            _, b = batch(nseq, seq)

        # -- the window.  Steps are dispatched one ahead, as a training
        # loop does: step i goes to the device, then step i-1's loss is
        # waited for and stamped.  The device never drains, and every step
        # gets a completion stamp.
        before = env.compiles.snapshot()
        stamps, pending = [], None
        t0 = time.perf_counter()
        t1 = t0 + env.seconds
        trace = harness.TraceSession(env.trace, env.out_dir, t1,
                                     float(tr["trace_seconds"]))
        env.window_opens(t0)
        while True:
            trace.poll(time.perf_counter())
            with harness.annotate("bench.make_batch"):
                _, b = batch(nseq, seq)
            with harness.annotate("bench.train_step"):
                loss = step(b)
            if pending is not None:
                with harness.annotate("bench.wait_prev_step"):
                    losses.append(float(pending.numpy()))
                stamps.append(time.perf_counter())
            pending = loss
            if time.perf_counter() >= t1:
                break
        losses.append(float(pending.numpy()))
        stamps.append(time.perf_counter())
        trace.stop()
        after = env.compiles.snapshot()
    finally:
        if mesh is not None:
            mesh_mod.set_mesh(None)

    finite = [math.isfinite(l) for l in losses]
    checks.append(("losses finite", sum(finite), len(losses), all(finite)))
    delta = harness.counter_delta(before, after)
    checks.append(("lowerings in the window", delta, 0,
                   not any(delta.values())))
    return {
        "t0": t0, "t1": t1, "stamps": stamps,
        "tokens_per_step": nseq * seq,
        "attempted": len(stamps),
        "failed": len(stamps) - sum(finite[-len(stamps):]),
        "checks": checks, "counters": delta, "trace": trace,
        "records": {"stamps": stamps, "t0": t0, "t1": t1, "losses": losses},
    }
