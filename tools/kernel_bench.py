"""Pallas-vs-XLA kernel A/B gate (VERDICT r3 item 4 / r4 item 1c).

Times each hand-written Pallas kernel against the straightforward jnp/XLA
formulation of the same math, steady-state under jit on the attached device.
The acceptance gate (reference analog: tools/ci_op_benchmark.sh's relative
regression gate) is speedup >= --gate (default 1.2x) for every kernel on
TPU hardware; on CPU the Pallas kernels run in interpret mode, so the run
is recorded as informational (gate not applied).

Usage:
    python tools/kernel_bench.py                     # table + one JSON line
    python tools/kernel_bench.py --save KERNEL_BENCH_<dev>.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time_fn(fn, *args, n=20, warmup=3):
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / n)
    return best


def _cases(on_tpu: bool):
    """Yields (name, pallas_fn, xla_fn, args, grad) A/B pairs.

    Shapes are bench-scale on TPU, miniature on CPU (interpret mode is
    ~1000x slower; CPU runs only prove the harness).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.decode_attention import ragged_decode_attention
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.fused_ce import fused_linear_cross_entropy

    rng = np.random.RandomState(0)

    def arr(*shape, dtype=jnp.bfloat16):
        return jnp.asarray(rng.randn(*shape), dtype=dtype)

    # --- flash attention: [B, S, H, D] causal self-attention fwd+bwd ------
    B, S, H, D = (4, 2048, 16, 128) if on_tpu else (1, 128, 2, 8)
    q, k, v = arr(B, S, H, D), arr(B, S, H, D), arr(B, S, H, D)

    def xla_attn(q, k, v):
        scale = 1.0 / (q.shape[-1] ** 0.5)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def grad_wrap(f):
        def loss(q, k, v):
            return jnp.sum(f(q, k, v).astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    yield ("flash_attention_fwd",
           jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True)),
           jax.jit(xla_attn), (q, k, v))
    yield ("flash_attention_grad",
           grad_wrap(lambda q, k, v: flash_attention(q, k, v, causal=True)),
           grad_wrap(xla_attn), (q, k, v))

    # --- fused lm-head + CE: [N, H] x [H, V] -> scalar loss fwd+bwd -------
    N, Hd, V = (4096, 4096, 32000) if on_tpu else (32, 64, 256)
    h = arr(N, Hd)
    w = arr(Hd, V)
    labels = jnp.asarray(rng.randint(0, V, (N,)), jnp.int32)

    def xla_ce(h, w, labels):
        logits = (h @ w).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return lse - gold  # per-row, matching the pallas kernel's output

    yield ("fused_linear_ce_fwd",
           jax.jit(fused_linear_cross_entropy), jax.jit(xla_ce),
           (h, w, labels))
    yield ("fused_linear_ce_grad",
           jax.jit(jax.grad(
               lambda h, w, l: jnp.mean(fused_linear_cross_entropy(h, w, l)),
               argnums=(0, 1))),
           jax.jit(jax.grad(
               lambda h, w, l: jnp.mean(xla_ce(h, w, l)), argnums=(0, 1))),
           (h, w, labels))

    # --- ragged decode attention: [B, 1, H, D] q vs [B, Smax, H, D] cache -
    B2, Smax, H2, D2 = (32, 4096, 16, 128) if on_tpu else (2, 128, 2, 8)
    q1 = arr(B2, 1, H2, D2)
    kc, vc = arr(B2, Smax, H2, D2), arr(B2, Smax, H2, D2)
    lengths = jnp.asarray(
        rng.randint(Smax // 8, Smax, (B2,)), jnp.int32)

    def xla_decode(q1, kc, vc, lengths):
        scale = 1.0 / (q1.shape[-1] ** 0.5)
        s = jnp.einsum("bqhd,bshd->bhqs", q1, kc).astype(jnp.float32) * scale
        mask = (jnp.arange(kc.shape[1])[None, None, None, :]
                < lengths[:, None, None, None])
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(q1.dtype)
        return jnp.einsum("bhqs,bshd->bqhd", p, vc)

    yield ("ragged_decode_attention",
           jax.jit(ragged_decode_attention), jax.jit(xla_decode),
           (q1, kc, vc, lengths))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--save", default=None)
    ap.add_argument("--gate", type=float, default=1.2,
                    help="required pallas/xla speedup on TPU")
    ap.add_argument("--n", type=int, default=20)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"  # off-TPU the kernels self-select
    # pallas interpret mode (ops/pallas/_common.py:_interpret), so CPU runs
    # prove the harness but are not gated.

    results = []
    for name, pall, xla, fargs in _cases(on_tpu):
        try:
            t_p = _time_fn(pall, *fargs, n=args.n)
            t_x = _time_fn(xla, *fargs, n=args.n)
            speedup = t_x / t_p
            results.append({"kernel": name,
                            "pallas_ms": round(t_p * 1e3, 4),
                            "xla_ms": round(t_x * 1e3, 4),
                            "speedup": round(speedup, 3),
                            "passes_gate": bool(speedup >= args.gate)})
            print(f"# {name}: pallas={t_p*1e3:.3f}ms xla={t_x*1e3:.3f}ms "
                  f"speedup={speedup:.2f}x", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — record, keep measuring
            results.append({"kernel": name, "error": str(e)[:300]})
            print(f"# {name}: FAILED {e}", file=sys.stderr)

    gated = [r for r in results if "speedup" in r]
    payload = {
        "device": getattr(dev, "device_kind", dev.platform),
        "platform": dev.platform,
        "gate": args.gate,
        "gate_applied": on_tpu,
        "all_pass": bool(on_tpu and gated
                         and all(r["passes_gate"] for r in gated)),
        "results": results,
    }
    print(json.dumps(payload), flush=True)
    if args.save:
        with open(args.save, "w") as f:
            json.dump(payload, f, indent=1)
    if on_tpu and not payload["all_pass"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
