"""Ragged KV-cache decode attention as a Pallas TPU kernel.

TPU-native equivalent of the reference's masked_multihead_attention decode
kernel (paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu,
incubate/nn/layer/fused_transformer.py FusedMultiTransformer decode path):
one new query token per sequence attends over a static-length KV cache
[B, S_max, H_kv, D] of which only the first `length[b]` positions are valid.

The jnp composition builds a [B, H, 1, S_max] additive mask and softmaxes
over the FULL padded S_max every step.  This kernel instead walks the cache
in chunks with an online softmax and STOPS at the last valid chunk — a
generation loop at position t does O(t) work, not O(S_max) — and never
materializes the [B, H, S_max] probability tensor.

Layout: q [B, 1, H, D] (the flash-attn API layout), caches
[B, S_max, H_kv, D].  One grid cell per sequence DMAs [chunk, H_kv, D]
slabs — every kv head of a position is one contiguous run of the cache, and
a slab is whole (sublane, lane) tiles of the (H_kv, D) minor dims for every
dtype, which a single-head slice of a packed 16-bit cache is not.  A decode
query is one vector per head, so q.K and p.V are batched mat-VECs: they run
on the VPU in the cache's own layout (heads on sublanes, D on lanes), with
no per-head re-tiling for the MXU.  Grouped-query (H > H_kv) loops the
group's query heads over the same slab.  `lengths` [B] int32 rides scalar
prefetch so the chunk loop bound is known before the body runs.
Inference-only (no VJP): the decode path runs under no_grad.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _NEG_INF, _interpret, _x32


# bytes of one K (or V) slab in VMEM; two slots each for K and V, plus the
# f32 temporaries of one slab, stay far inside the 16 MiB scoped default
_SLAB_BYTES = 512 * 1024


def _chunk_len(s_max, h_kv, d_pad, itemsize):
    """Cache positions per DMA slab: the power of two whose slab is at most
    _SLAB_BYTES, at least one packed-sublane tile (16 rows), at most S_max
    rounded up to that tile."""
    bk = 16
    while bk < 512 and 2 * bk * h_kv * d_pad * itemsize <= _SLAB_BYTES:
        bk *= 2
    return min(bk, -(-s_max // 16) * 16)


def _chunk_dma(k_hbm, v_hbm, k_buf, v_buf, sems, b, bk, ik, slot):
    """The two copies that bring chunk `ik` of row b's K and V into VMEM
    slot `slot`.  K/V refs are UNBLOCKED (memory_space=ANY): the sequence
    axis of this grid cell's row is sliced, every minor dim whole."""
    return (
        pltpu.make_async_copy(
            k_hbm.at[b, pl.ds(ik * bk, bk)], k_buf.at[slot],
            sems.at[slot, 0]),
        pltpu.make_async_copy(
            v_hbm.at[b, pl.ds(ik * bk, bk)], v_buf.at[slot],
            sems.at[slot, 1]),
    )


def _kernel(len_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, *,
            scale, bk, group):
    """K/V stay in HBM; only chunks the length bound reaches are DMA'd into
    the double-buffered VMEM scratch — HBM traffic per decode step is
    O(length), not O(S_max) (a BlockSpec copy of the whole cache slice would
    defeat the ragged point, since decode is bandwidth-bound)."""
    b = pl.program_id(0)
    length = len_ref[b]
    hkv, d = q_ref.shape[2], q_ref.shape[3]
    hi = pl.cdiv(length, bk)                    # chunks with any valid key

    chunk_dma = functools.partial(_chunk_dma, k_hbm, v_hbm, k_buf, v_buf, sems,
                                  b, bk)

    @pl.when(hi > 0)
    def _():
        for dma in chunk_dma(0, 0):
            dma.start()

    # one f32 [H_kv, D] query slab per member of the group, pre-scaled
    qs = [q_ref[0, g].astype(jnp.float32) * scale for g in range(group)]

    def body(ik, carry):
        slot = jax.lax.rem(ik, 2)

        @pl.when(ik + 1 < hi)
        def _():  # prefetch next chunk into the other slot
            for dma in chunk_dma(ik + 1, 1 - slot):
                dma.start()

        for dma in chunk_dma(ik, slot):
            dma.wait()  # staticcheck: ok[unbounded-blocking] — on-device DMA issued by this kernel's own schedule; completion is guaranteed by construction, there is no peer to time out on
        k = k_buf[slot].astype(jnp.float32)     # (bk, hkv, d)
        v = v_buf[slot].astype(jnp.float32)
        kid = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, hkv, 1), 0)
        live = kid < length
        out = []
        for g in range(group):
            acc, m, l = carry[g]
            s = jnp.sum(k * qs[g][None], axis=2, keepdims=True)  # (bk,hkv,1)
            s = jnp.where(live, s, jnp.float32(_NEG_INF))
            m_new = jnp.maximum(m, jnp.max(s, axis=0))           # (hkv, 1)
            p = jnp.exp(s - m_new[None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=0)
            acc_new = acc * alpha + jnp.sum(p * v, axis=0)
            out.append((acc_new, m_new, l_new))
        return tuple(out)

    init = tuple((jnp.zeros((hkv, d), jnp.float32),
                  jnp.full((hkv, 1), _NEG_INF, jnp.float32),
                  jnp.zeros((hkv, 1), jnp.float32)) for _ in range(group))
    final = jax.lax.fori_loop(jnp.int32(0), hi, body, init)
    for g, (acc, _, l) in enumerate(final):
        l = jnp.maximum(l, jnp.float32(1e-30))
        o_ref[0, g] = (acc / l).astype(o_ref.dtype)


def _ragged_ref(q, k_cache, v_cache, lengths, s):
    """jnp reference of the kernel's math (full-S_max masked softmax)."""
    B, _, H, D = q.shape
    Hkv, S = k_cache.shape[2], k_cache.shape[1]
    qg = q.reshape(B, Hkv, H // Hkv, D)
    scores = jnp.einsum("bhgd,bshd->bhgs", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * s
    valid = jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, _NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    # kernel parity for lengths[b] == 0: its chunk loop runs zero times and
    # returns zeros, while softmax over an all-masked row would go uniform
    out = jnp.where(lengths[:, None, None, None] > 0, out, 0.0)
    return out.reshape(B, 1, H, D).astype(q.dtype)


def ragged_decode_attention(q, k_cache, v_cache, lengths, scale=None):
    """q: [B, 1, H, D]; k_cache/v_cache: [B, S_max, H_kv, D]; lengths: [B]
    int32 (positions j < lengths[b] are attended). Returns [B, 1, H, D].
    float32 or bfloat16 (Mosaic has no float16 vectors)."""
    B, one, H, D = q.shape
    assert one == 1, "decode kernel takes exactly one query token"
    Hkv, S_max = k_cache.shape[2], k_cache.shape[1]
    group = H // Hkv
    s = float(scale) if scale is not None else 1.0 / (D ** 0.5)

    # [B, group, Hkv, D]: each member of the group is one [Hkv, D] slab in
    # the cache's own (heads on sublanes, D on lanes) layout
    qg = jnp.swapaxes(q.reshape(B, Hkv, group, D), 1, 2)
    d_pad = (-D) % 128
    if d_pad:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, d_pad)))
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, 0), (0, d_pad)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, 0), (0, d_pad)))
    Dp = D + d_pad
    bk = _chunk_len(S_max, Hkv, Dp, jnp.dtype(k_cache.dtype).itemsize)
    s_pad = (-S_max) % bk
    if s_pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, s_pad), (0, 0), (0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, group, Hkv, Dp), lambda b, *_: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # K cache stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # V cache stays in HBM
        ],
        out_specs=pl.BlockSpec((1, group, Hkv, Dp),
                               lambda b, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, bk, Hkv, Dp), k_cache.dtype),
            pltpu.VMEM((2, bk, Hkv, Dp), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(_kernel, scale=s, bk=bk, group=group)
    with _x32():
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, group, Hkv, Dp), q.dtype),
            interpret=_interpret(),
            name="ragged_decode_attention",
        )(lengths.astype(jnp.int32), qg, k_cache, v_cache)
    return jnp.swapaxes(out[..., :D], 1, 2).reshape(B, 1, H, D)


# ---------------------------------------------------------------------------
# One KV head (multi-query): the cache with its head axis folded away
# ---------------------------------------------------------------------------
# At H_kv = 1 a [chunk, 1, D] slab is NOT whole tiles of the (H_kv, D) minor
# dims (Mosaic: "Slice shape along dimension 2 must be aligned to tiling (2),
# but is 1"), and one head on the sublanes would leave 7 of 8 idle.  The
# cache is then kept as [B, S_max, D]: a slab is [chunk, D], positions on
# sublanes, and every query head shares it, so q.K^T and p.V are real
# [H, D] x [D, chunk] and [H, chunk] x [chunk, D] matmuls on the MXU.

def _mqa_kernel(len_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, *,
                scale, bk):
    b = pl.program_id(0)
    length = len_ref[b]
    hi = pl.cdiv(length, bk)

    chunk_dma = functools.partial(_chunk_dma, k_hbm, v_hbm, k_buf, v_buf, sems,
                                  b, bk)

    @pl.when(hi > 0)
    def _():
        for dma in chunk_dma(0, 0):
            dma.start()

    q = q_ref[0]                                    # [H, D], cache's type
    h, d = q.shape
    exact = jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None

    def body(ik, carry):
        acc, m, l = carry
        slot = jax.lax.rem(ik, 2)

        @pl.when(ik + 1 < hi)
        def _():
            for dma in chunk_dma(ik + 1, 1 - slot):
                dma.start()

        for dma in chunk_dma(ik, slot):
            dma.wait()  # staticcheck: ok[unbounded-blocking] — on-device DMA issued by this kernel's own schedule; completion is guaranteed by construction, there is no peer to time out on
        k, v = k_buf[slot], v_buf[slot]             # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=exact,
                                preferred_element_type=jnp.float32) * scale
        kid = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(kid < length, s, jnp.float32(_NEG_INF))    # [H, bk]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        # rows past the length hold whatever the cache held: p is 0 there,
        # and a 0 x NaN would still poison the sum, so they are zeroed
        rows = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        v = jnp.where(rows < length, v, jnp.zeros_like(v))
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())), precision=exact,
                                 preferred_element_type=jnp.float32)
        return acc * alpha + pv, m_new, l_new

    init = (jnp.zeros((h, d), jnp.float32),
            jnp.full((h, 1), _NEG_INF, jnp.float32),
            jnp.zeros((h, 1), jnp.float32))
    acc, _, l = jax.lax.fori_loop(jnp.int32(0), hi, body, init)
    o_ref[0] = (acc / jnp.maximum(l, jnp.float32(1e-30))).astype(o_ref.dtype)


def mqa_decode_attention(q, k_cache, v_cache, lengths, scale=None):
    """q: [B, 1, H, D]; k_cache/v_cache: [B, S_max, D], the one KV head every
    query head shares; lengths: [B] int32 (positions j < lengths[b] are
    attended; 0 gives zeros). Returns [B, 1, H, D]. float32 or bfloat16.
    As in `ragged_decode_attention`, a D that is not a multiple of 128 or an
    S_max that is not whole chunks pads (copies) the cache every call."""
    B, one, H, head = q.shape
    assert one == 1, "decode kernel takes exactly one query token"
    S_max = k_cache.shape[1]
    s = float(scale) if scale is not None else 1.0 / (head ** 0.5)
    itemsize = jnp.dtype(k_cache.dtype).itemsize
    D = head + (-head) % 128
    bk = _chunk_len(S_max, 1, D, itemsize)
    pad = ((0, 0), (0, (-S_max) % bk), (0, D - head))
    if pad[1][1] or pad[2][1]:
        k_cache, v_cache = jnp.pad(k_cache, pad), jnp.pad(v_cache, pad)
    # the query heads ride the sublanes: whole packed tiles of them
    h_pad = (-H) % (32 // itemsize)
    qh = jnp.pad(q[:, 0].astype(k_cache.dtype),
                 ((0, 0), (0, h_pad), (0, D - head)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H + h_pad, D), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # K cache stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # V cache stays in HBM
        ],
        out_specs=pl.BlockSpec((1, H + h_pad, D), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, bk, D), k_cache.dtype),
            pltpu.VMEM((2, bk, D), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(_mqa_kernel, scale=s, bk=bk)
    with _x32():
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H + h_pad, D), q.dtype),
            interpret=_interpret(),
            name="mqa_decode_attention",
        )(lengths.astype(jnp.int32), qh, k_cache, v_cache)
    return out[:, None, :H, :head]
