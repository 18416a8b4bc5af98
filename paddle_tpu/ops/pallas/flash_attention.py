"""Flash attention as a Pallas TPU kernel (forward + custom-VJP backward).

TPU-native equivalent of the reference's flash-attn CUDA integration
(paddle/phi/kernels/gpu/flash_attn_kernel.h, third_party/flashattn;
python/paddle/nn/functional/flash_attention.py): online-softmax blockwise
attention that never materialises the [Sq, Sk] score matrix in HBM.

Layout follows the reference flash-attn API: q/k/v are [batch, seq, heads,
head_dim]; internally kernels run on [batch, heads, seq, head_dim] blocks with
q-block x k-block tiles sized for the MXU (128x128). Grouped-query attention
(fewer kv heads) is supported: the forward maps each q head onto its kv head via
the BlockSpec index map; the backward folds group gradients back down.

Selected by nn.functional.attention whenever the default backend is TPU and
the dtype is Mosaic-lowerable. On non-TPU backends the kernels run in Pallas
interpret mode so the same code
path is unit-testable on CPU (SURVEY §4: fake-backend testing discipline).
"""
from __future__ import annotations

import functools


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import _NEG_INF, _interpret, _x32




def _pad_axis(x, axis, multiple):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, bq, bk, sk_real,
                num_k, window=None, has_sink=False):
    """`window` (with `causal`): key j is visible to query i iff
    0 <= i - j < window, and the k blocks wholly outside the band are not
    visited.  `has_sink`: one learned logit a query head ([H, 1, 128]
    float32, every lane the same) joins the softmax's denominator and
    carries no value: the carry starts at m = sink, l = 1.  K's lanes and
    V's may differ (the output has V's)."""
    sink_ref = rest[0] if has_sink else None
    o_ref, lse_ref = rest[has_sink:]
    iq = pl.program_id(2)
    q = q_ref[0, 0, :, :]  # (bq, d) — keep input dtype so the MXU runs bf16
    d = v_ref.shape[-1]

    if causal:
        hi = jnp.minimum(jnp.int32(num_k),
                 ((iq + 1) * jnp.int32(bq) + jnp.int32(bk - 1)) // jnp.int32(bk))
    else:
        hi = jnp.int32(num_k)
    lo = jnp.int32(0)
    if window is not None:
        # the first key the block's first query sees is iq * bq - window + 1
        lo = jnp.maximum(iq * jnp.int32(bq) - jnp.int32(window - 1),
                         jnp.int32(0)) // jnp.int32(bk)

    def body(ik, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.ds(ik * bk, bk), :]  # (bk, d)
        v = v_ref[0, 0, pl.ds(ik * bk, bk), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kid = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kid < sk_real
        if causal:
            qid = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask = jnp.logical_and(mask, qid >= kid)
            if window is not None:
                mask = jnp.logical_and(mask, qid - kid < window)
        s = jnp.where(mask, s, jnp.float32(_NEG_INF))
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))  # (bq,1)
        p = jnp.exp(s - m_new)
        if window is not None:
            # a row wholly outside the band in this block has m_new at its
            # old value or _NEG_INF: exp(0) = 1 must not count
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                        preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((bq, d), jnp.float32)
    if has_sink:
        m0 = jnp.broadcast_to(sink_ref[0, :, :1], (bq, 1))
        l0 = jnp.ones((bq, 1), jnp.float32)
    else:
        m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((bq, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    l = jnp.maximum(l, jnp.float32(1e-30))
    o_ref[0, 0, :, :] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0, :, :] = m + jnp.log(l)  # (bq, 1)


def _fa_forward(q, k, v, causal, scale, bq, bk, sk_real, window=None,
                sink=None):
    """q,k: [B,H,S,Dk], v: [B,H,S,Dv], padded. Returns (out [B,H,Sq,Dv],
    lse [B,H,Sq])."""
    B, H, Sq, D = q.shape
    Hkv, Sk, Dv = k.shape[1], k.shape[2], v.shape[3]
    group = H // Hkv
    num_q, num_k = Sq // bq, Sk // bk

    def kv_index(b, h, i):
        # int32-safe h // group (x64 promotion breaks Mosaic lowering)
        if group == 1:
            return (b, h, 0, 0)
        return (b, jax.lax.div(h, jnp.int32(group)), 0, 0)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, sk_real=sk_real, num_k=num_k,
                               window=window, has_sink=sink is not None)
    operands, sink_spec = [q, k, v], []
    if sink is not None:
        operands.append(jnp.broadcast_to(
            sink.astype(jnp.float32)[:, None, None], (H, 1, 128)))
        sink_spec = [pl.BlockSpec((1, 1, 128), lambda b, h, i: (h, 0, 0))]
    with _x32():
        out, lse = pl.pallas_call(
            kernel,
            grid=(B, H, num_q),
            in_specs=[
                pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, Sk, D), kv_index),
                pl.BlockSpec((1, 1, Sk, Dv), kv_index),
            ] + sink_spec,
            out_specs=[
                pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
                jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
            ],
            interpret=_interpret(),
            name="flash_attention_fwd",
        )(*operands)
    return out, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   scale, causal, bq, bk, sk_real, num_k):
    iq = pl.program_id(2)
    q = q_ref[0, 0, :, :]
    do = do_ref[0, 0, :, :]
    lse = lse_ref[0, 0, :, :]      # (bq,1)
    delta = delta_ref[0, 0, :, :]  # (bq,1)

    if causal:
        hi = jnp.minimum(jnp.int32(num_k),
                 ((iq + 1) * jnp.int32(bq) + jnp.int32(bk - 1)) // jnp.int32(bk))
    else:
        hi = jnp.int32(num_k)

    def body(ik, dq):
        k = k_ref[0, 0, pl.ds(ik * bk, bk), :]
        v = v_ref[0, 0, pl.ds(ik * bk, bk), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kid = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kid < sk_real
        if causal:
            qid = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask = jnp.logical_and(mask, qid >= kid)
        s = jnp.where(mask, s, jnp.float32(_NEG_INF))
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + scale * jnp.dot(ds.astype(k.dtype), k,
                                    preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(jnp.int32(0), hi, body,
                           jnp.zeros(q.shape, jnp.float32))
    dq_ref[0, 0, :, :] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, bq, bk, num_q):
    ik = pl.program_id(2)
    k = k_ref[0, 0, :, :]  # (bk, d)
    v = v_ref[0, 0, :, :]

    lo = jax.lax.div(ik * jnp.int32(bk), jnp.int32(bq)) if causal else jnp.int32(0)

    def body(iq, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(iq * bq, bq), :]
        do = do_ref[0, 0, pl.ds(iq * bq, bq), :]
        lse = lse_ref[0, 0, pl.ds(iq * bq, bq), :]
        delta = delta_ref[0, 0, pl.ds(iq * bq, bq), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qid = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kid = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qid >= kid, s, jnp.float32(_NEG_INF))
        p = jnp.exp(s - lse)  # (bq, bk); padded-q rows have do=delta=0
        dv_new = dv + jax.lax.dot_general(p.astype(do.dtype), do,
                                          (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_new = dk + scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, jnp.int32(num_q), body, (dk0, dv0))
    dk_ref[0, 0, :, :] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0, :, :] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------

BLOCK_Q = 512
BLOCK_K = 512


def set_block_sizes(bq, bk):
    """Tune kernel tiling (tests/bench may override)."""
    global BLOCK_Q, BLOCK_K
    BLOCK_Q, BLOCK_K = bq, bk


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal=False, scale=None):
    """q,k,v: [batch, seq, heads, head_dim] → out [batch, seq, heads, head_dim]."""
    out, _ = _flash_fwd_impl(q, k, v, causal, scale)
    return out


def _block_sizes(sq, sk):
    """Clamp tile sizes for short sequences (blocks must stay 128-aligned)."""
    ru = lambda n: -(-n // 128) * 128
    return min(BLOCK_Q, ru(sq)), min(BLOCK_K, ru(sk))


def _prep(q, k, v, scale):
    """Transpose to [B,H,S,D] and pad seq/head_dim to kernel multiples."""
    s = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    qT = jnp.swapaxes(q, 1, 2)
    kT = jnp.swapaxes(k, 1, 2)
    vT = jnp.swapaxes(v, 1, 2)
    sq, sk, d = qT.shape[2], kT.shape[2], qT.shape[3]
    bq, bk = _block_sizes(sq, sk)
    qT = _pad_axis(_pad_axis(qT, 2, bq), 3, 128)
    kT = _pad_axis(_pad_axis(kT, 2, bk), 3, 128)
    vT = _pad_axis(_pad_axis(vT, 2, bk), 3, 128)
    return qT, kT, vT, float(s), sq, sk, d


def _flash_fwd_impl(q, k, v, causal, scale, window=None, sink=None):
    qT, kT, vT, s, sq, sk, _ = _prep(q, k, v, scale)
    bq, bk = _block_sizes(sq, sk)
    out, lse = _fa_forward(qT, kT, vT, causal, s, bq, bk, sk, window, sink)
    out = jnp.swapaxes(out[:, :, :sq, :v.shape[-1]], 1, 2)
    return out, lse


def _flash_fwd_rule(q, k, v, causal, scale):
    out, lse = _flash_fwd_impl(q, k, v, causal, scale)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, scale, res, g):
    return _flash_bwd_core(causal, scale, res, g, None)


def _flash_bwd_core(causal, scale, res, g, g_lse):
    q, k, v, out, lse = res
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    group = H // Hkv

    # GQA backward: expand kv to full heads, fold group grads afterwards.
    if group > 1:
        k_full = jnp.repeat(k, group, axis=2)
        v_full = jnp.repeat(v, group, axis=2)
    else:
        k_full, v_full = k, v

    qT, kT, vT, s, sq, sk, d = _prep(q, k_full, v_full, scale)
    BQ, BK = _block_sizes(sq, sk)
    doT = _pad_axis(_pad_axis(jnp.swapaxes(g, 1, 2), 2, BQ), 3, 128)
    outT = _pad_axis(_pad_axis(jnp.swapaxes(out, 1, 2), 2, BQ), 3, 128)
    delta = jnp.sum(doT.astype(jnp.float32) * outT.astype(jnp.float32), axis=-1,
                    keepdims=True)
    if g_lse is not None:
        # lse cotangent: d lse / d s = p, so it folds into ds = p*(dp - delta)
        # as delta -= g_lse (see _bwd_*_kernel's ds computation)
        gl = _pad_axis(g_lse.astype(jnp.float32)[..., None], 2, BQ)
        delta = delta - gl

    Bp, Hp, Sqp, Dp = qT.shape
    Skp = kT.shape[2]
    num_q, num_k = Sqp // BQ, Skp // BK
    interp = _interpret()

    dq_kernel = functools.partial(_bwd_dq_kernel, scale=s, causal=causal,
                                  bq=BQ, bk=BK, sk_real=sk, num_k=num_k)
    dkv_kernel = functools.partial(_bwd_dkv_kernel, scale=s, causal=causal,
                                   bq=BQ, bk=BK, num_q=num_q)
    with _x32():
        dq = pl.pallas_call(
            dq_kernel,
            grid=(Bp, Hp, num_q),
            in_specs=[
                pl.BlockSpec((1, 1, BQ, Dp), lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, Skp, Dp), lambda b, h, i: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, Skp, Dp), lambda b, h, i: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, BQ, Dp), lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, BQ, 1), lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, BQ, 1), lambda b, h, i: (b, h, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, BQ, Dp),
                                   lambda b, h, i: (b, h, i, 0)),
            out_shape=jax.ShapeDtypeStruct(qT.shape, q.dtype),
            interpret=interp,
            name="flash_attention_bwd_dq",
        )(qT, kT, vT, doT, lse, delta)

        dk, dv = pl.pallas_call(
            dkv_kernel,
            grid=(Bp, Hp, num_k),
            in_specs=[
                pl.BlockSpec((1, 1, Sqp, Dp), lambda b, h, i: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, BK, Dp), lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, BK, Dp), lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, Sqp, Dp), lambda b, h, i: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, Sqp, 1), lambda b, h, i: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, Sqp, 1), lambda b, h, i: (b, h, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, BK, Dp), lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, BK, Dp), lambda b, h, i: (b, h, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(kT.shape, k.dtype),
                jax.ShapeDtypeStruct(vT.shape, v.dtype),
            ],
            interpret=interp,
            name="flash_attention_bwd_dkv",
        )(qT, kT, vT, doT, lse, delta)

    dq = jnp.swapaxes(dq[:, :, :sq, :d], 1, 2)
    dk = jnp.swapaxes(dk[:, :, :sk, :d], 1, 2)
    dv = jnp.swapaxes(dv[:, :, :sk, :d], 1, 2)
    if group > 1:
        dk = dk.reshape(B, sk, Hkv, group, d).sum(axis=3)
        dv = dv.reshape(B, sk, Hkv, group, d).sum(axis=3)
    return dq, dk, dv


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_lse(q, k, v, causal=False, scale=None):
    """flash_attention that ALSO returns the per-row logsumexp [B, H, Sq]
    (fp32) — the merge state needed to combine partial attentions across
    K/V chunks (ring attention, two-pass decode). The custom VJP handles
    cotangents for BOTH outputs, so a downstream logsumexp merge
    differentiates exactly."""
    out, lse = _flash_fwd_impl(q, k, v, causal, scale)
    sq = q.shape[1]
    return out, lse[:, :, :sq, 0]


def _flash_lse_fwd_rule(q, k, v, causal, scale):
    out, lse = _flash_fwd_impl(q, k, v, causal, scale)
    sq = q.shape[1]
    return (out, lse[:, :, :sq, 0]), (q, k, v, out, lse)


def _flash_lse_bwd_rule(causal, scale, res, g):
    g_out, g_lse = g
    return _flash_bwd_core(causal, scale, res, g_out, g_lse)


flash_attention_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def flash_attention_fwd(q, k, v, causal=False, scale=None):
    """Back-compat alias of flash_attention (differentiable via custom VJP)."""
    return flash_attention(q, k, v, causal, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def windowed_flash_attention(q, k, v, sink=None, window=None, scale=None):
    """Causal attention through the same forward kernel, with what a
    sliding-window layer adds: q, k [batch, seq, heads, D_k] and v
    [batch, seq, kv heads, D_v] (the two lane counts may differ; the output
    has D_v); `window`: key j is visible to query i iff 0 <= i - j < window
    (None: every earlier key), k blocks outside the band skipped; `sink`
    [heads]: one learned logit a query head that joins the softmax's
    denominator and carries no value.  Forward only: the backward kernels
    know none of the three and this entry refuses to differentiate."""
    if k.shape[-1] != q.shape[-1]:
        raise ValueError(f"q and k differ in lanes: {q.shape} {k.shape}")
    out, _ = _windowed_fwd(q, k, v, True, scale, window, sink)
    return out


# one jitted forward, so a step that calls it once a layer traces the kernel
# once a (shape, window, sink or none)
_windowed_fwd = jax.jit(_flash_fwd_impl, static_argnums=(3, 4, 5))


def _windowed_no_backward(_window, _scale, _res, _g):
    raise NotImplementedError(
        "windowed_flash_attention is forward only: the flash backward "
        "kernels take neither a window, a sink nor D_k != D_v; run it under "
        "no_grad")


windowed_flash_attention.defvjp(
    lambda q, k, v, sink, window, scale: (
        windowed_flash_attention(q, k, v, sink, window, scale), None),
    _windowed_no_backward)
