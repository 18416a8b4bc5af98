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
[B, S_max, H_kv, D].  Every KV head of a position is one contiguous run of
the cache, so the cache is seen as [B, S_max * H_kv, D], (position, KV head)
rows of D lanes (a view: the two axes are adjacent), and one grid cell per
sequence DMAs chunks of whole positions: whole (sublane, lane) tiles for
every dtype, which a single-head slice of a packed 16-bit cache is not.  A
chunk's q.K^T and p.V are two matmuls on the MXU in the cache's own dtype,
[H, D] x [D, rows] and [H, rows] x [rows, D] with an f32 online-softmax
carry: every query head meets every KV head's rows and one mask keeps its
own head's live positions.  The MXU does H_kv times the useful multiplies
and is still far under the copy's time; what that buys is a body with no
loop over heads or group members, the same length at any H, H_kv and group
(2 x group mat-vecs on the VPU over f32 copies of the slab are bound by
vector arithmetic, not by the cache's bytes, and are traced once a head).
`lengths` [B] int32 rides scalar prefetch so the chunk loop bound is known
before the body runs.
Inference-only (no VJP): the decode path runs under no_grad.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _NEG_INF, _interpret, _x32


# bytes of one K (or V) chunk in VMEM: two slots each for K and V, with the
# [H, rows] f32 scores and mask beside them, stay far inside the 16 MiB
# scoped default
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
    """The two copies that bring chunk `ik` (of `bk` rows) of sequence b's
    K and V into VMEM slot `slot`.  K/V refs are UNBLOCKED
    (memory_space=ANY): the sequence's second axis is sliced, every minor
    dim whole."""
    return (
        pltpu.make_async_copy(
            k_hbm.at[b, pl.ds(ik * bk, bk)], k_buf.at[slot],
            sems.at[slot, 0]),
        pltpu.make_async_copy(
            v_hbm.at[b, pl.ds(ik * bk, bk)], v_buf.at[slot],
            sems.at[slot, 1]),
    )


def _kernel(len_ref, q_ref, *rest, scale, bk, hkv, group, has_sink):
    """K/V stay in HBM; only chunks the length bound reaches are DMA'd into
    the double-buffered VMEM scratch — HBM traffic per decode step is
    O(length), not O(S_max) (a BlockSpec copy of the whole cache slice would
    defeat the ragged point, since decode is bandwidth-bound).

    A chunk is `bk` positions = `bk * hkv` (position, KV head) rows.  Every
    query head meets every row on the MXU and one mask keeps its own KV
    head's: no loop over heads, so the body is as long at 32 heads as at 8.

    The chunks of all sequences are ONE stream through the two slots: a
    sequence's last chunk prefetches the next sequence's first, so the copies
    never drain between grid cells (a sequence is two or three chunks long,
    and a pipeline refilled for each spent a third of its time filling).
    `first` carries the slot of this cell's chunk 0 from cell to cell; every
    sequence brings its chunk 0, an empty one too, and reads nothing of it.

    K and V may differ in lanes (q has K's, the output V's).  With a sink
    (`has_sink`: one learned logit a query head, [H, 128] float32, every lane
    the same) the softmax's denominator starts at exp(sink - m) with m the
    sink itself: the sink takes its share of the weight and carries no
    value."""
    sink_ref = rest[0] if has_sink else None
    k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, first = rest[has_sink:]
    b = pl.program_id(0)
    length = len_ref[b]
    rows = bk * hkv
    n = jnp.maximum(pl.cdiv(length, bk), 1)     # chunks brought for this row

    chunk_dma = functools.partial(_chunk_dma, k_hbm, v_hbm, k_buf, v_buf, sems)

    @pl.when(b == 0)
    def _():
        first[0] = 0
        for dma in chunk_dma(0, rows, 0, 0):
            dma.start()

    slot0 = first[0]
    q = q_ref[0]                                # [H, D], the cache's dtype
    h, d = q.shape[0], v_buf.shape[2]
    exact = jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
    # column c of a chunk is position c // hkv of KV head c % hkv; a query
    # head sees its position there and "never live" at the other heads' (and
    # a padded query head, whose KV head does not exist, nowhere)
    col = jax.lax.broadcasted_iota(jnp.int32, (h, rows), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (h, rows), 0)
    cpos = jnp.where(jax.lax.rem(col, hkv) == jax.lax.div(head, group),
                     jax.lax.div(col, hkv), jnp.int32(2 ** 30))

    def body(ik, carry):
        acc, m, l = carry
        slot = jax.lax.rem(slot0 + ik, 2)
        left = length - ik * bk                 # live positions from here on
        more = ik + 1 < n

        @pl.when(more | (b + 1 < pl.num_programs(0)))
        def _():  # prefetch the stream's next chunk into the other slot
            for dma in chunk_dma(jnp.where(more, b, b + 1), rows,
                                 jnp.where(more, ik + 1, 0), 1 - slot):
                dma.start()

        for dma in chunk_dma(b, rows, ik, slot):
            dma.wait()  # staticcheck: ok[unbounded-blocking] — on-device DMA issued by this kernel's own schedule; completion is guaranteed by construction, there is no peer to time out on

        @pl.when(left < bk)
        def _():
            # the last chunk's rows past the length hold whatever the cache
            # held: p is 0 there, and a 0 x NaN would still poison the sum
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, d), 0)
            v_buf[slot] = jnp.where(row < left * hkv, v_buf[slot], 0)

        k, v = k_buf[slot], v_buf[slot]         # [rows, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=exact,
                                preferred_element_type=jnp.float32) * scale
        live = cpos < left
        s = jnp.where(live, s, jnp.float32(_NEG_INF))           # [H, rows]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        # where a head has no live column m_new is _NEG_INF and exp() is 1
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())), precision=exact,
                                 preferred_element_type=jnp.float32)
        return acc * alpha + pv, m_new, l_new

    if has_sink:
        init = (jnp.zeros((h, d), jnp.float32), sink_ref[:, :1],
                jnp.ones((h, 1), jnp.float32))
    else:
        init = (jnp.zeros((h, d), jnp.float32),
                jnp.full((h, 1), _NEG_INF, jnp.float32),
                jnp.zeros((h, 1), jnp.float32))
    acc, _, l = jax.lax.fori_loop(jnp.int32(0), n, body, init)
    first[0] = jax.lax.rem(slot0 + n, 2)
    o_ref[0] = (acc / jnp.maximum(l, jnp.float32(1e-30))).astype(o_ref.dtype)


class CacheLayoutUnsupported(ValueError):
    """A cache the decode kernel cannot read IN PLACE on the chip: its lanes
    are not whole 128-lane tiles.  The kernel used to pad, and so copy, such
    a cache on every call (5 GB a step at 192 lanes and 128 slots x 8192);
    the pad belongs to the allocation, made once: `cache_lanes(D)` lanes,
    the real ones first and zeros behind (zero key lanes add nothing to a
    score; the value's extra lanes are cut from the output).  Raised in
    interpret mode too, so that the CPU tests see what the chip would: a
    caller asks `reads_in_place` first and keeps its masked attention for a
    cache the kernel refuses."""

    def __init__(self, which: str, lanes: int):
        self.which, self.lanes = which, lanes
        super().__init__(
            f"ragged_decode_attention: the {which} cache has {lanes} lanes, "
            f"not whole tiles of 128; allocate it with {cache_lanes(lanes)} "
            f"lanes (ops.pallas.decode_attention.cache_lanes) instead of "
            f"having it padded and copied every step")


def cache_lanes(head_dim: int) -> int:
    """Lanes to allocate a cache of `head_dim` with, so that the decode
    kernel reads it in place on the chip."""
    return head_dim + (-head_dim) % 128


def reads_in_place(*shapes) -> bool:
    """Whether `ragged_decode_attention` takes caches of these shapes: every
    one in whole tiles of lanes."""
    return all(s[-1] % 128 == 0 for s in shapes)


def _ragged_ref(q, k_cache, v_cache, lengths, s, sink=None):
    """jnp reference of the kernel's math (full-S_max masked softmax);
    caches [B, S_max, H_kv, D_k] and [B, S_max, H_kv, D_v], D_k at least
    q's (lanes beyond it are the allocation's zeros)."""
    B, _, H, D = q.shape
    Hkv, S, Dv = k_cache.shape[2], k_cache.shape[1], v_cache.shape[3]
    qg = q.reshape(B, Hkv, H // Hkv, D)
    scores = jnp.einsum("bhgd,bshd->bhgs", qg.astype(jnp.float32),
                        k_cache[..., :D].astype(jnp.float32)) * s
    valid = jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, _NEG_INF)
    if sink is not None:
        # one more column that joins the denominator and carries no value
        col = jnp.broadcast_to(sink.astype(jnp.float32).reshape(
            1, Hkv, H // Hkv, 1), scores.shape[:3] + (1,))
        p = jax.nn.softmax(jnp.concatenate([scores, col], -1), -1)[..., :S]
    else:
        p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    # kernel parity for lengths[b] == 0: its chunk loop runs zero times and
    # returns zeros, while softmax over an all-masked row would go uniform
    out = jnp.where(lengths[:, None, None, None] > 0, out, 0.0)
    return out.reshape(B, 1, H, Dv).astype(q.dtype)


def ragged_decode_attention(q, k_cache, v_cache, lengths, scale=None,
                            sink=None, num_kv_heads=None):
    """q: [B, 1, H, D]; k_cache: [B, S_max, H_kv, D_k], v_cache:
    [B, S_max, H_kv, D_v], or both as the (position, KV head) rows the kernel
    reads, [B, S_max * H_kv, D] with `num_kv_heads` given; lengths: [B] int32
    (positions j < lengths[b] are attended).  D_k may exceed D (a cache
    allocated with `cache_lanes`: zeros behind the real lanes) and D_v may
    differ from both.  `sink` [H], optional: one learned logit a query head
    that joins the softmax's denominator and carries no value.  Returns
    [B, 1, H, D_v].  float32 or bfloat16 (Mosaic has no float16 vectors).
    A cache whose lanes are not whole tiles raises the typed
    CacheLayoutUnsupported (it is never padded and copied here)."""
    assert q.shape[1] == 1, "decode kernel takes exactly one query token"
    s = float(scale) if scale is not None else 1.0 / (q.shape[3] ** 0.5)
    if k_cache.ndim == 4:
        num_kv_heads = k_cache.shape[2]
    elif num_kv_heads is None:
        raise ValueError("a cache given as [B, S_max * H_kv, D] rows needs "
                         "num_kv_heads")
    for which, c in (("key", k_cache), ("value", v_cache)):
        if not reads_in_place(c.shape):
            raise CacheLayoutUnsupported(which, c.shape[-1])
    return _ragged(q, k_cache, v_cache, lengths, s, _interpret(),
                   int(num_kv_heads), sink)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _ragged(q, k_cache, v_cache, lengths, s, interpret, Hkv, sink=None):
    """One jitted function for every layer of a step: a model calls the
    kernel once a layer with the same shapes, and a bare `pallas_call`
    traces its body and lowers it to Mosaic anew each time, in every
    process, before any compile cache can be asked."""
    B, _, H, D = q.shape
    Dk, Dv = k_cache.shape[-1], v_cache.shape[-1]
    S_max = k_cache.shape[1] // (1 if k_cache.ndim == 4 else Hkv)
    itemsize = jnp.dtype(k_cache.dtype).itemsize
    bk = _chunk_len(S_max, Hkv, max(Dk, Dv), itemsize)
    if (-S_max) % bk:
        pad = ((0, 0), (0, ((-S_max) % bk) * (
            1 if k_cache.ndim == 4 else Hkv))) + ((0, 0),) * (k_cache.ndim - 2)
        k_cache, v_cache = jnp.pad(k_cache, pad), jnp.pad(v_cache, pad)
    # the query heads ride the sublanes: whole packed tiles of them
    h_pad = (-H) % (32 // itemsize)
    qh = jnp.pad(q[:, 0].astype(k_cache.dtype),
                 ((0, 0), (0, h_pad), (0, Dk - D)))
    heads = lambda lanes: pl.BlockSpec((1, H + h_pad, lanes),
                                       lambda b, *_: (b, 0, 0))
    operands = [lengths.astype(jnp.int32), qh]
    in_specs = [heads(Dk)]
    if sink is not None:
        operands.append(jnp.broadcast_to(jnp.pad(
            sink.astype(jnp.float32), (0, h_pad))[:, None], (H + h_pad, 128)))
        in_specs.append(pl.BlockSpec((H + h_pad, 128), lambda b, *_: (0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=in_specs + [
            pl.BlockSpec(memory_space=pl.ANY),   # K cache stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # V cache stays in HBM
        ],
        out_specs=heads(Dv),
        scratch_shapes=[
            pltpu.VMEM((2, bk * Hkv, Dk), k_cache.dtype),
            pltpu.VMEM((2, bk * Hkv, Dv), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    kernel = functools.partial(_kernel, scale=s, bk=bk, hkv=Hkv,
                               group=H // Hkv, has_sink=sink is not None)
    with _x32():
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H + h_pad, Dv), q.dtype),
            # the stream of chunks crosses grid cells: they run in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="ragged_decode_attention",
        # (position, KV head) rows: the two axes are adjacent, so a view
        )(*operands, k_cache.reshape(B, -1, Dk), v_cache.reshape(B, -1, Dv))
    return out[:, None, :H]


# ---------------------------------------------------------------------------
# One KV head (multi-query): the cache with its head axis folded away
# ---------------------------------------------------------------------------
# The H_kv = 1 case of the same form, for a cache kept as [B, S_max, D]
# (models/jamba.py folds the head axis away): a chunk is [positions, D],
# every query head shares every row, and the mask is the length alone.

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
    A D that is not a multiple of 128 or an S_max that is not whole chunks
    pads (copies) the cache every call."""
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
