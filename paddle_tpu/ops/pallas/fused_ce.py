"""Fused linear + softmax-cross-entropy as Pallas TPU kernels.

TPU-native equivalent of the reference's fused softmax-with-cross-entropy
kernels (paddle/phi/kernels/fusion/, softmax_with_cross_entropy op) applied
at the LLaMA lm-head boundary: for hidden states h [N, H], vocab projection
W [H, V] and integer labels [N], computes per-row
    loss = logsumexp(h @ W) - (h @ W)[label]
WITHOUT ever materializing the [N, V] logits — or, in the backward, the
[N, V] logits cotangent — in HBM.  At LLaMA-7B shapes (N = B*S = 16k,
V = 32k) those two buffers are ~2 GB fp32 each and dominate the training
step's memory traffic (VERDICT r3 item 6).

Structure:
- forward: grid (row_tiles, vocab_tiles), vocab innermost; an online
  (max, sum-exp, label-logit) triple accumulates in VMEM scratch across the
  vocab tiles of each row tile (flash-attention-style online softmax over
  the vocab axis).  Emits per-row (m, l, z) partials so a TP-vocab-sharded
  caller can psum-merge across shards before forming the loss.
- backward: dh kernel, grid (row_tiles, vocab_tiles): recomputes each
  logits tile, forms the tile's cotangent (softmax - onehot) * g in VMEM
  and immediately contracts it with W^T into a dh accumulator; dW kernel,
  grid (vocab_tiles, row_tiles): same tile cotangent contracted with h^T
  into a dW accumulator.  The [N, V] cotangent only ever exists one
  [BR, BV] tile at a time in VMEM.

On non-TPU backends the kernels run in Pallas interpret mode (unit-testable
on CPU); `fused_linear_cross_entropy` carries a custom VJP, so it drops into
any differentiable loss composition.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _NEG_INF, _interpret, _x32


# Row/vocab tile ceilings. The vocab tile is what amortizes re-streaming h
# (dW kernel) and W (fwd / dh kernels) from HBM, so it is kept as wide as
# VMEM allows: _tiles halves it until the widest kernel's working set fits
# _VMEM_BUDGET, and each call raises its scoped-VMEM limit to its own
# working set (the 16 MiB scoped default refuses H=4096 in the backward).
BLOCK_R = 128
BLOCK_V = 512
_VMEM_BUDGET = 48 << 20   # of the v5e's 128 MiB VMEM


def _working_set(br, bv, hd, itemsize, *, dw):
    """Bytes one grid step of a kernel holds in VMEM: double-buffered h and
    W blocks, the f32 [br, bv] logits tile and its few elementwise
    temporaries, and either the dW kernel's double-buffered [hd, bv] output
    + f32 accumulator or the dh kernel's [br, hd] ones (the forward holds
    neither, so the dh figure bounds it)."""
    blocks = 2 * (br * hd + hd * bv) * itemsize + 4 * br * bv * 4
    out = hd * bv if dw else br * hd
    return blocks + out * (2 * itemsize + 4)


def _tiles(n, hd, itemsize):
    # row block must be a multiple of the fp32 sublane count (8): an
    # unaligned N (e.g. 13) would otherwise hand Mosaic a 13-row block
    # (ADVICE r4 #1); padded rows are masked out via g=0 / label shift
    br = min(BLOCK_R, -(-max(8, n) // 8) * 8)
    bv = BLOCK_V
    while bv > 128 and _working_set(br, bv, hd, itemsize,
                                    dw=True) > _VMEM_BUDGET:
        bv //= 2
    return br, bv


def _itemsize(h, w):
    return max(h.dtype.itemsize, w.dtype.itemsize)


def _params(br, bv, h, w, *, dw=False):
    ws = _working_set(br, bv, h.shape[1], _itemsize(h, w), dw=dw)
    return pltpu.CompilerParams(vmem_limit_bytes=max(16 << 20, ws + (4 << 20)))


def _pad_to(x, axis, multiple):
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# forward: per-row (m, l, z) partials
# ---------------------------------------------------------------------------

def _fwd_kernel(h_ref, w_ref, lab_ref, m_ref, l_ref, z_ref, *,
                bv, v_real, num_v):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[:, :] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[:, :] = jnp.zeros(l_ref.shape, jnp.float32)
        z_ref[:, :] = jnp.zeros(z_ref.shape, jnp.float32)

    h = h_ref[:, :]
    w = w_ref[:, :]
    s = jax.lax.dot_general(h, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (br, bv)
    br = s.shape[0]
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, (br, bv), 1)
    s = jnp.where(col < v_real, s, jnp.float32(_NEG_INF))

    m_old = m_ref[:, :]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
    l_ref[:, :] = (l_ref[:, :] * jnp.exp(m_old - m_new)
                   + jnp.sum(jnp.exp(s - m_new), axis=1, keepdims=True))
    m_ref[:, :] = m_new
    # label logit: global label index is local col + vocab_offset
    lab = lab_ref[:, :]  # (br, 1) int32, already shifted to local indexing
    hit = (col == lab) & (col < v_real)
    z_ref[:, :] = z_ref[:, :] + jnp.sum(jnp.where(hit, s, 0.0), axis=1,
                                        keepdims=True)


def _fwd_partials(h, w, labels_local, v_real, br, bv):
    n, hd = h.shape
    v_pad = w.shape[1]
    num_r, num_v = n // br, v_pad // bv
    kernel = functools.partial(_fwd_kernel, bv=bv, v_real=v_real,
                               num_v=num_v)
    with _x32():
        m, l, z = pl.pallas_call(
            kernel,
            grid=(num_r, num_v),
            in_specs=[
                pl.BlockSpec((br, hd), lambda i, j: (i, 0)),
                pl.BlockSpec((hd, bv), lambda i, j: (0, j)),
                pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n, 1), jnp.float32),
                jax.ShapeDtypeStruct((n, 1), jnp.float32),
                jax.ShapeDtypeStruct((n, 1), jnp.float32),
            ],
            compiler_params=_params(br, bv, h, w),
            interpret=_interpret(),
            name="fused_ce_fwd",
        )(h, w, labels_local)
    return m[:, 0], l[:, 0], z[:, 0]


# ---------------------------------------------------------------------------
# backward: dh and dW without a materialized [N, V] cotangent
# ---------------------------------------------------------------------------

def _bwd_dh_kernel(h_ref, w_ref, lab_ref, lse_ref, g_ref, dh_ref, acc_ref, *,
                   bv, v_real, num_v):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[:, :] = jnp.zeros(acc_ref.shape, jnp.float32)

    h = h_ref[:, :]
    w = w_ref[:, :]
    s = jax.lax.dot_general(h, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    br = s.shape[0]
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, (br, bv), 1)
    p = jnp.where(col < v_real, jnp.exp(s - lse_ref[:, :]), 0.0)
    dl = (p - jnp.where(col == lab_ref[:, :], 1.0, 0.0)) * g_ref[:, :]
    acc_ref[:, :] = acc_ref[:, :] + jax.lax.dot_general(
        dl.astype(w.dtype), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == num_v - 1)
    def _():
        dh_ref[:, :] = acc_ref[:, :].astype(dh_ref.dtype)


def _bwd_dw_kernel(h_ref, w_ref, lab_ref, lse_ref, g_ref, dw_ref, acc_ref, *,
                   bv, v_real, num_r):
    j = pl.program_id(0)   # vocab tile
    i = pl.program_id(1)   # row tile (innermost: accumulate rows)

    @pl.when(i == 0)
    def _():
        acc_ref[:, :] = jnp.zeros(acc_ref.shape, jnp.float32)

    h = h_ref[:, :]
    w = w_ref[:, :]
    s = jax.lax.dot_general(h, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    br = s.shape[0]
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, (br, bv), 1)
    p = jnp.where(col < v_real, jnp.exp(s - lse_ref[:, :]), 0.0)
    dl = (p - jnp.where(col == lab_ref[:, :], 1.0, 0.0)) * g_ref[:, :]
    acc_ref[:, :] = acc_ref[:, :] + jax.lax.dot_general(
        h, dl.astype(h.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == num_r - 1)
    def _():
        dw_ref[:, :] = acc_ref[:, :].astype(dw_ref.dtype)


def _bwd_impl(h, w, labels_local, lse, g, v_real, br, bv):
    n, hd = h.shape
    v_pad = w.shape[1]
    num_r, num_v = n // br, v_pad // bv
    interp = _interpret()
    dh_kernel = functools.partial(_bwd_dh_kernel, bv=bv, v_real=v_real,
                                  num_v=num_v)
    dw_kernel = functools.partial(_bwd_dw_kernel, bv=bv, v_real=v_real,
                                  num_r=num_r)
    with _x32():
        dh = pl.pallas_call(
            dh_kernel,
            grid=(num_r, num_v),
            in_specs=[
                pl.BlockSpec((br, hd), lambda i, j: (i, 0)),
                pl.BlockSpec((hd, bv), lambda i, j: (0, j)),
                pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            ],
            out_specs=pl.BlockSpec((br, hd), lambda i, j: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n, hd), h.dtype),
            scratch_shapes=[pltpu.VMEM((br, hd), jnp.float32)],
            compiler_params=_params(br, bv, h, w),
            interpret=interp,
            name="fused_ce_bwd_dh",
        )(h, w, labels_local, lse, g)
        dw = pl.pallas_call(
            dw_kernel,
            grid=(num_v, num_r),
            in_specs=[
                pl.BlockSpec((br, hd), lambda j, i: (i, 0)),
                pl.BlockSpec((hd, bv), lambda j, i: (0, j)),
                pl.BlockSpec((br, 1), lambda j, i: (i, 0)),
                pl.BlockSpec((br, 1), lambda j, i: (i, 0)),
                pl.BlockSpec((br, 1), lambda j, i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((hd, bv), lambda j, i: (0, j)),
            out_shape=jax.ShapeDtypeStruct((hd, v_pad), w.dtype),
            scratch_shapes=[pltpu.VMEM((hd, bv), jnp.float32)],
            compiler_params=_params(br, bv, h, w, dw=True),
            interpret=interp,
            name="fused_ce_bwd_dw",
        )(h, w, labels_local, lse, g)
    return dh, dw


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _prep(h, w, labels):
    n, hd = h.shape
    v = w.shape[1]
    br, bv = _tiles(n, -(-hd // 128) * 128, _itemsize(h, w))
    h_p = _pad_to(_pad_to(h, 0, br), 1, 128)
    w_p = _pad_to(_pad_to(w, 0, 128), 1, bv)
    lab = _pad_to(labels.astype(jnp.int32).reshape(-1, 1), 0, br)
    return h_p, w_p, lab, n, v, br, bv


def fused_linear_ce_partials(h, w, labels, vocab_offset=0):
    """Per-row online-softmax partials of logits = h @ w: (m, l, z) with
    m = rowmax, l = sum exp(s - m), z = logit at `labels` (labels are GLOBAL
    vocab ids; rows whose label falls outside [vocab_offset,
    vocab_offset + V_local) contribute z = 0).  A TP-vocab-sharded caller
    merges partials across shards:
        M = max_i m_i;  L = sum_i l_i * exp(m_i - M);  lse = M + log L
        loss = lse - sum_i z_i
    """
    h_p, w_p, lab, n, v, br, bv = _prep(h, w, labels)
    lab_local = lab - jnp.int32(vocab_offset)
    m, l, z = _fwd_partials(h_p, w_p, lab_local, v, br, bv)
    return m[:n], l[:n], z[:n]


@jax.custom_vjp
def fused_linear_cross_entropy(h, w, labels):
    """Per-row cross-entropy of softmax(h @ w) against integer labels,
    computed without materializing [N, V] logits (fwd) or their cotangent
    (bwd). h: [N, H]; w: [H, V]; labels: [N] int. Returns [N] fp32."""
    m, l, z = fused_linear_ce_partials(h, w, labels)
    return m + jnp.log(l) - z


def _flce_fwd(h, w, labels):
    m, l, z = fused_linear_ce_partials(h, w, labels)
    lse = m + jnp.log(l)
    return lse - z, (h, w, labels, lse)


def _flce_bwd(res, g):
    h, w, labels, lse = res
    h_p, w_p, lab, n, v, br, bv = _prep(h, w, labels)
    lse_p = _pad_to(lse.reshape(-1, 1).astype(jnp.float32), 0, br)
    # padded rows: g = 0 kills their (garbage-lse) contributions
    g_p = _pad_to(g.reshape(-1, 1).astype(jnp.float32), 0, br)
    dh, dw = _bwd_impl(h_p, w_p, lab, lse_p, g_p, v, br, bv)
    return (dh[:n, :h.shape[1]].astype(h.dtype),
            dw[:w.shape[0], :v].astype(w.dtype),
            None)


fused_linear_cross_entropy.defvjp(_flce_fwd, _flce_bwd)


# ---------------------------------------------------------------------------
# TP-vocab-sharded variant (use INSIDE shard_map over the mp axis)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_linear_cross_entropy_tp(h, w_shard, labels, axis="mp"):
    """Vocab-TP fused CE for use inside shard_map: each rank holds the
    lm-head shard w_shard [H, V/mp] (ColumnParallelLinear layout) and the
    REPLICATED h [N, H] + global labels [N]; per-rank online-softmax
    partials merge across `axis` with pmax/psum (the ParallelCrossEntropy
    max-shift trick, mp_layers.py, fused with the matmul). Returns the
    replicated per-row loss [N]."""
    loss, _ = _flce_tp_fwd_impl(h, w_shard, labels, axis)
    return loss


def _flce_tp_fwd_impl(h, w_shard, labels, axis):
    v_local = w_shard.shape[1]
    idx = jax.lax.axis_index(axis)
    off = idx.astype(jnp.int32) * jnp.int32(v_local)
    # labels arrive as global ids; fused_linear_ce_partials subtracts off
    m, l, z = fused_linear_ce_partials(h, w_shard, labels, vocab_offset=off)
    M = jax.lax.pmax(m, axis)  # staticcheck: ok[naked-collective] — kernel-internal partial merge, exact by construction
    L = jax.lax.psum(l * jnp.exp(m - M), axis)  # staticcheck: ok[naked-collective] — kernel-internal partial merge, exact by construction
    z_tot = jax.lax.psum(z, axis)  # staticcheck: ok[naked-collective] — kernel-internal partial merge, exact by construction
    lse = M + jnp.log(L)
    return lse - z_tot, lse


def _flce_tp_fwd(h, w_shard, labels, axis):
    loss, lse = _flce_tp_fwd_impl(h, w_shard, labels, axis)
    return loss, (h, w_shard, labels, lse)


def _flce_tp_bwd(axis, res, g):
    h, w_shard, labels, lse = res
    v_local = w_shard.shape[1]
    idx = jax.lax.axis_index(axis)
    off = idx.astype(jnp.int32) * jnp.int32(v_local)
    h_p, w_p, lab, n, v, br, bv = _prep(h, w_shard, labels)
    lab_local = lab - off
    lse_p = _pad_to(lse.reshape(-1, 1).astype(jnp.float32), 0, br)
    # shard_map(check_vma=False) transpose convention (the repo-wide mode):
    # a replicated OUTPUT's cotangent arrives SPLIT by the axis size, and a
    # replicated INPUT's returned cotangent is psum-reduced by the transpose
    # itself.  So: undo the split here, and do NOT psum dh ourselves.
    g_eff = g * jax.lax.psum(jnp.ones((), jnp.float32), axis)  # staticcheck: ok[naked-collective] — kernel-internal partial merge, exact by construction
    g_p = _pad_to(g_eff.reshape(-1, 1).astype(jnp.float32), 0, br)
    dh_local, dw = _bwd_impl(h_p, w_p, lab_local, lse_p, g_p, v, br, bv)
    return (dh_local[:n, :h.shape[1]].astype(h.dtype),
            dw[:w_shard.shape[0], :v].astype(w_shard.dtype), None)


fused_linear_cross_entropy_tp.defvjp(_flce_tp_fwd, _flce_tp_bwd)
