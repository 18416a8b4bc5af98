"""Attention functionals.

Analog of the reference's flash-attn path (paddle/phi/kernels/gpu/flash_attn_kernel.h,
python/paddle/nn/functional/flash_attention.py). On TPU the memory-efficient path is
a Pallas flash-attention kernel (paddle_tpu/ops/pallas/flash_attention.py) selected
automatically when the default backend is a TPU; the reference implementation below is the
XLA-fused fallback used on CPU and for parity tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor
from ...ops.dispatch import apply

__all__ = ["scaled_dot_product_attention", "flash_attention", "sdp_attention_ref"]


def _sdpa_ref(q, k, v, mask, dropout_p, causal, scale):
    # q,k,v: [B, S, H, D] (paddle flash-attn layout)
    qT = jnp.swapaxes(q, 1, 2)  # [B,H,S,D]
    kT = jnp.swapaxes(k, 1, 2)
    vT = jnp.swapaxes(v, 1, 2)
    s = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    # grouped-query support: repeat kv heads if fewer than q heads
    if kT.shape[1] != qT.shape[1]:
        rep = qT.shape[1] // kT.shape[1]
        kT = jnp.repeat(kT, rep, axis=1)
        vT = jnp.repeat(vT, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qT, kT) * s
    if causal:
        qlen, klen = logits.shape[-2], logits.shape[-1]
        cmask = jnp.tril(jnp.ones((qlen, klen), bool), k=klen - qlen)
        logits = jnp.where(cmask, logits, jnp.finfo(logits.dtype).min)
    if mask is not None:
        logits = logits + mask.astype(logits.dtype)
    # softmax in >= fp32 (bf16/fp16 upcast) without DOWNcasting fp64
    acc = jnp.promote_types(logits.dtype, jnp.float32)
    probs = jax.nn.softmax(logits.astype(acc), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vT)
    return jnp.swapaxes(out, 1, 2)  # back to [B,S,H,D]


def sdp_attention_ref(q, k, v, mask=None, dropout_p=0.0, causal=False, scale=None):
    return _sdpa_ref(q, k, v, mask, dropout_p, causal, scale)


def _use_pallas(q_val) -> bool:
    # Backend check (not per-array device): under jit tracing arrays have no
    # device, but the pallas kernel is the right path whenever we target TPU.
    # Mosaic can't lower f64 (package default under x64), so gate on dtype too.
    from ...core.device import is_tpu_backend
    return is_tpu_backend() and q_val.dtype in (jnp.float32, jnp.bfloat16,
                                                jnp.float16)


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, name=None,
                                 scale=None):
    """Inputs [batch, seq, heads, head_dim] as in the reference flash-attn API."""
    def f(q, k, v, *m):
        mask = m[0] if m else None
        if mask is None and _use_pallas(q):  # staticcheck: ok[tracer-branch] — _use_pallas reads backend + q.dtype only (static under trace)
            from ...ops.pallas.flash_attention import flash_attention as fa
            from ...parallel.mesh import shard_kernel
            bshd = ("dp", None, "mp", None)
            return shard_kernel(lambda a, b, c: fa(a, b, c, is_causal, scale),
                                [bshd] * 3, bshd)(q, k, v)
        return _sdpa_ref(q, k, v, mask, dropout_p, is_causal, scale)
    if attn_mask is not None:
        return apply(f, query, key, value, attn_mask, op_name="sdpa")
    return apply(f, query, key, value, op_name="sdpa")


def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False,
                    name=None):
    out = scaled_dot_product_attention(query, key, value, None, dropout, causal)
    if return_softmax:
        return out, None
    return out, None
