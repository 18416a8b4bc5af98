"""Microbenchmark: eager small-op dispatch throughput, CPU-runnable.

Measures the compiled-op cache (paddle_tpu/ops/_op_cache.py) against the
uncached path (`PT_OP_CACHE=0` equivalent) on a same-shape eager loop —
a host-side count-and-ratio script: the dispatch layer runs on the CPU
whichever device executes the ops.

Prints ONE JSON line:
  {"metric": "eager_dispatch_cached_speedup", "value": <geomean x>,
   "unit": "x", "vs_baseline": <value/3.0>, ...per-workload ops/sec...}
and writes a BENCH_SELF_DISPATCH_<ts>.json artifact with full detail
(per-workload iters/sec both ways + dispatch.cache_info() counters).

Workloads (batch 64, same shapes every iteration):
  softmax_fwd   — no-grad composite op (exp/max/sub/div chain)
  gelu_fwd      — no-grad, longer elementwise chain (tanh approximation)
  linear_train  — linear + mse fwd AND backward: the vjp-retrace-per-call
                  path the cache eliminates

Env: PT_DISPATCH_BENCH_ITERS (default 300), PT_DISPATCH_BENCH_WARMUP (20).
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax

# dispatch overhead is the subject — always on the CPU, and never holding
# the chip whatever JAX_PLATFORMS says
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import paddle_tpu as P  # noqa: E402
from paddle_tpu.ops import dispatch  # noqa: E402


def _workloads():
    rng = np.random.RandomState(0)
    x = P.to_tensor(rng.randn(64, 256).astype(np.float32))
    w = P.to_tensor(rng.randn(256, 64).astype(np.float32),
                    stop_gradient=False)
    b = P.to_tensor(np.zeros(64, np.float32), stop_gradient=False)
    tgt = P.to_tensor(rng.randn(64, 64).astype(np.float32))

    def softmax_fwd():
        return P.nn.functional.softmax(x, axis=-1)

    def gelu_fwd():
        return P.nn.functional.gelu(x, approximate=True)

    def linear_train():
        out = P.nn.functional.linear(x, w, b)
        loss = P.nn.functional.mse_loss(out, tgt)
        loss.backward()
        w.clear_grad()
        b.clear_grad()
        return loss

    return [("softmax_fwd", softmax_fwd), ("gelu_fwd", gelu_fwd),
            ("linear_train", linear_train)]


def _time_loop(fn, iters: int, warmup: int) -> float:
    """-> iterations/second, result-blocked at the end of each timed run."""
    for _ in range(warmup):
        out = fn()
    jax.block_until_ready(out._value)
    best = float("inf")
    for _ in range(2):  # two timed reps, keep the best (noise floor)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        jax.block_until_ready(out._value)
        best = min(best, time.perf_counter() - t0)
    return iters / best


def main() -> dict:
    iters = int(os.environ.get("PT_DISPATCH_BENCH_ITERS", "300"))
    warmup = int(os.environ.get("PT_DISPATCH_BENCH_WARMUP", "20"))

    detail = {"iters": iters, "warmup": warmup, "workloads": {}}
    speedups = []
    for name, fn in _workloads():
        per = {}
        for label, enabled in (("cached", True), ("uncached", False)):
            dispatch.cache_clear()
            dispatch.set_op_cache_enabled(enabled)
            per[f"{label}_iters_per_sec"] = round(_time_loop(fn, iters,
                                                             warmup), 1)
            if enabled:  # snapshot BEFORE the uncached leg clears counters
                per["cache_info"] = dispatch.cache_info()
        dispatch.set_op_cache_enabled(True)
        per["speedup"] = round(per["cached_iters_per_sec"]
                               / per["uncached_iters_per_sec"], 2)
        speedups.append(per["speedup"])
        detail["workloads"][name] = per
        print(f"# {name}: cached={per['cached_iters_per_sec']}/s "
              f"uncached={per['uncached_iters_per_sec']}/s "
              f"-> {per['speedup']}x", file=sys.stderr)

    geomean = float(np.exp(np.mean(np.log(speedups))))
    payload = {
        "metric": "eager_dispatch_cached_speedup",
        "value": round(geomean, 2),
        "unit": "x",
        # north-star proxy: the ISSUE-4 acceptance floor is 3x on a
        # same-shape CPU loop
        "vs_baseline": round(geomean / 3.0, 4),
        **{f"{k}_speedup": v["speedup"]
           for k, v in detail["workloads"].items()},
    }
    print(json.dumps(payload), flush=True)

    ts = time.strftime("%Y%m%d_%H%M%S")
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_SELF_DISPATCH_{ts}.json")
    try:
        with open(path, "w") as f:
            json.dump({**payload, "detail": detail}, f, indent=1)
        print(f"# artifact -> {path}", file=sys.stderr)
    except OSError as e:
        print(f"# artifact write failed: {e}", file=sys.stderr)
    return payload


if __name__ == "__main__":
    main()
