"""Microbenchmark: whole-step capture vs per-op cache vs hand-written jit.

Measures the three execution tiers on the SAME llama-proxy train step
(forward + CE loss + backward + SGD update), CPU-runnable so the number
stays measurable when the TPU backend probe reports `tpu-unavailable`:

  per_op    — eager step: every op dispatched through apply(), served by
              the PR-3 compiled-op cache (PT_OP_CACHE=1). The tier whole-
              step capture is supposed to beat.
  captured  — the same eager step wrapped in jit.capture_step: traced
              once, graft passes run, lowered to ONE executable
              (donation inferred for the param buffers).
  hand_jit  — a hand-written single-jax.jit step (jax.value_and_grad +
              SGD, donated params): the floor a capture tier can hope
              to reach.

Prints ONE JSON line:
  {"metric": "step_capture_speedup_vs_perop", "value": <x>, "unit": "x",
   "vs_baseline": <value/2.0>, "captured_vs_handjit": <ratio>, ...}
(acceptance: value >= 2.0 and captured_vs_handjit <= 1.10) and writes a
BENCH_SELF_STEP_<ts>.json artifact with per-tier steps/sec, the capture
counters, and the pass-pipeline report.

Env: PT_STEP_BENCH_ITERS (default 60), PT_STEP_BENCH_WARMUP (5).
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax

# step-dispatch overhead is the subject — always on the CPU, and never holding
# the chip whatever JAX_PLATFORMS says
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import paddle_tpu as P  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.jit import capture_step, capture_clear, capture_info  # noqa: E402
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.ops import dispatch  # noqa: E402

LR = 0.05
BATCH, SEQ = 4, 32


def _build():
    P.seed(0)
    cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                           inter=128, seq=SEQ)
    model = LlamaForCausalLM(cfg)
    params = [p for p in model.parameters() if not p.stop_gradient]
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (BATCH, SEQ + 1))
    x = P.to_tensor(ids[:, :-1])
    y = P.to_tensor(ids[:, 1:])
    return model, params, x, y


def _eager_step_fn(model, params):
    """Functional eager train step: Tensor param values in, new values out.
    Runs the define-by-run tape (backward()) exactly like user eager code —
    the body whole-step capture records."""

    def step(param_vals, x, y):
        saved = [p._value for p in params]
        try:
            for p, t in zip(params, param_vals):
                p._value = t._value if isinstance(t, Tensor) else t
            loss = model.compute_loss(x, y)
            loss.backward()
            with P.no_grad():
                new_vals = [p - LR * p.grad for p in params]
            return loss, new_vals
        finally:
            for p, v in zip(params, saved):
                p._value = v
                p.grad = None

    return step


def _hand_jit_step_fn(model, params):
    """The hand-written reference: one jax.jit over value_and_grad + SGD."""

    def loss_of(param_vals, ids, labels):
        saved = [p._value for p in params]
        try:
            for p, v in zip(params, param_vals):
                p._value = v
            with P.no_grad():
                return model.compute_loss(Tensor(ids), Tensor(labels))._value
        finally:
            for p, v in zip(params, saved):
                p._value = v

    def step(param_vals, ids, labels):
        loss, grads = jax.value_and_grad(loss_of)(param_vals, ids, labels)
        return loss, [v - LR * g for v, g in zip(param_vals, grads)]

    return jax.jit(step, donate_argnums=(0,))


def _time_tier(run_one, param_vals, iters, warmup, reps=3):
    """-> (iters/sec, final params). run_one(param_vals) -> (loss, new).

    Best-of-`reps` with a gc.collect() before each timed rep: the box this
    runs on is a single shared core, so the best rep is the noise floor and
    collector pauses from a previous tier's tape garbage must not land in
    this tier's window."""
    import gc

    for _ in range(max(warmup, 1)):   # >=1: the first call compiles
        loss, param_vals = run_one(param_vals)
    jax.block_until_ready([loss if not isinstance(loss, Tensor)
                           else loss._value])
    best = float("inf")
    for _ in range(reps):
        gc.collect()
        t0 = time.perf_counter()
        for _ in range(iters):
            loss, param_vals = run_one(param_vals)
        lv = loss._value if isinstance(loss, Tensor) else loss
        pv = param_vals[0]
        pv = pv._value if isinstance(pv, Tensor) else pv
        jax.block_until_ready([lv, pv])
        best = min(best, time.perf_counter() - t0)
    return iters / best, param_vals, float(np.asarray(lv))


def main() -> dict:
    iters = int(os.environ.get("PT_STEP_BENCH_ITERS", "60"))
    warmup = int(os.environ.get("PT_STEP_BENCH_WARMUP", "5"))

    model, params, x, y = _build()
    eager_step = _eager_step_fn(model, params)
    detail = {"iters": iters, "warmup": warmup,
              "config": {"batch": BATCH, "seq": SEQ,
                         "n_params": int(sum(int(np.prod(p.shape))
                                             for p in params))},
              "tiers": {}}

    # host snapshot of the initial params: every tier starts from its own
    # fresh device arrays (the captured tier DONATES its inputs)
    base_np = [np.asarray(p._value) for p in params]

    def fresh_vals():
        return [jax.numpy.asarray(a) for a in base_np]

    # --- per-op cache tier (fresh counters, capture off for this leg) ---
    dispatch.cache_clear()

    def perop_one(pv):
        loss, new = eager_step(pv, x, y)   # raw array leaves: same contract
        return loss, [t._value for t in new]

    ips_perop, _, loss_perop = _time_tier(perop_one, fresh_vals(),
                                          iters, warmup)
    detail["tiers"]["per_op"] = {"iters_per_sec": round(ips_perop, 2),
                                 "final_loss": loss_perop,
                                 "cache_info": {
                                     k: v for k, v in
                                     dispatch.cache_info().items()
                                     if k != "per_op"}}

    # --- captured tier ---
    capture_clear()
    captured = capture_step(eager_step, donate="auto")

    def captured_one(pv):
        loss, new = captured(pv, x, y)
        return loss, [t._value for t in new]

    ips_cap, _, loss_cap = _time_tier(captured_one, fresh_vals(),
                                      iters, warmup)
    progs = captured.programs()
    detail["tiers"]["captured"] = {
        "iters_per_sec": round(ips_cap, 2), "final_loss": loss_cap,
        "capture_info": capture_info(), "step_info": captured.cache_info(),
        "pass_report": progs[0].pass_report.as_dict() if progs else None,
        "donated": list(progs[0].donate) if progs else None}

    # --- captured tier with tracing ON (the observability cost gate) ---
    # same executable, same workload, PT_TRACE flipped: the only delta is
    # the capture.execute span per step, so the ratio IS the span cost.
    # Documented ceiling: <= 1.25x (slow battery; smoke allows 1.5x for
    # tiny-iteration noise on the shared single-core box).
    from paddle_tpu.observability import trace as obs_trace

    obs_trace.enable(True)
    try:
        ips_cap_traced, _, _ = _time_tier(captured_one, fresh_vals(),
                                          iters, warmup)
    finally:
        obs_trace.enable(False)
        obs_trace.trace_clear()
    detail["tiers"]["captured_traced"] = {
        "iters_per_sec": round(ips_cap_traced, 2)}

    # --- hand-written single-jit tier ---
    hand = _hand_jit_step_fn(model, params)

    def hand_one(pv):
        return hand(pv, x._value, y._value)

    ips_hand, _, loss_hand = _time_tier(hand_one, fresh_vals(),
                                        iters, warmup)
    detail["tiers"]["hand_jit"] = {"iters_per_sec": round(ips_hand, 2),
                                   "final_loss": loss_hand}

    speedup = ips_cap / ips_perop
    vs_hand = ips_hand / ips_cap   # captured step time / hand-written time
    for name, ips in (("per_op", ips_perop), ("captured", ips_cap),
                      ("hand_jit", ips_hand)):
        print(f"# {name}: {ips:.1f} steps/s", file=sys.stderr)

    payload = {
        "metric": "step_capture_speedup_vs_perop",
        "value": round(speedup, 2),
        "unit": "x",
        # acceptance floor: captured >= 2x the per-op cached eager path
        "vs_baseline": round(speedup / 2.0, 4),
        "captured_vs_handjit": round(vs_hand, 4),
        "per_op_steps_per_sec": round(ips_perop, 1),
        "captured_steps_per_sec": round(ips_cap, 1),
        "hand_jit_steps_per_sec": round(ips_hand, 1),
        # trace-on / trace-off cost of the captured step (>= ~1.0; the
        # documented observability overhead ceiling is 1.25x)
        "trace_overhead": round(ips_cap / ips_cap_traced, 4),
    }
    print(json.dumps(payload), flush=True)

    ts = time.strftime("%Y%m%d_%H%M%S")
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_SELF_STEP_{ts}.json")
    try:
        with open(path, "w") as f:
            json.dump({**payload, "detail": detail}, f, indent=1)
        print(f"# artifact -> {path}", file=sys.stderr)
    except OSError as e:
        print(f"# artifact write failed: {e}", file=sys.stderr)
    return payload


if __name__ == "__main__":
    main()
