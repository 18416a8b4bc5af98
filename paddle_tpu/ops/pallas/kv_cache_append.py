"""The decode step's K/V cache write as one in-place Pallas TPU call.

A one-token decode step writes one new position a slot into the static
caches [B, S_max, H_kv, D], every slot at its own offset.  Written as
`jax.vmap(lax.dynamic_update_slice)` that is a `scatter`, and XLA's TPU
pipeline expands a scatter into a `while` loop of B turns, each a bounds
check and a guarded one-row update: a few microseconds of work spread over
thousands of sub-microsecond operations a step.  This kernel is the same
write and nothing else: the caches stay in HBM and are aliased to the
outputs, `off` [B] rides scalar prefetch, and B row copies for K and B for V
are all started before the first is waited for.

A position of the cache is `[H_kv, D]`; the copy of one position is legal
where that is whole (sublane, lane) tiles of the cache's HBM layout
(`whole_tiles`), which holds for 8 KV heads of 128 and not for one or two:
Mosaic refuses a one-row DMA into half a packed tile ("Slice shape along
dimension ... must be aligned to tiling").  `models/llama.py` takes the
kernel by that test and keeps the scatter elsewhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _interpret, _x32


def whole_tiles(h_kv: int, d: int, dtype) -> bool:
    """Whether one cache position `[h_kv, d]` of `dtype` is whole tiles of
    the TPU's HBM layout, so that a DMA may address it alone: 8 sublanes of
    128 lanes in float32 and in bfloat16 (whose second row of a packed pair
    lies inside the same tile: `T(8,128)(2,1)`)."""
    return (jnp.dtype(dtype).name in ("float32", "bfloat16")
            and h_kv % 8 == 0 and d % 128 == 0)


def _kernel(off_ref, kn_ref, vn_ref, _kc_in, _vc_in, kc_ref, vc_ref, sems):
    """`_kc_in` / `_vc_in` are the caches as inputs; they ARE `kc_ref` /
    `vc_ref` (input_output_aliases), so only the new rows are written."""
    batch, n = kn_ref.shape[0], kn_ref.shape[1]

    def row_dma(b):
        # a position is one row of a [B, S_max, H_kv, D] cache and H_kv rows
        # of a cache kept as (position, KV head) rows
        row = pl.ds(off_ref[b], 1) if n == 1 else pl.ds(off_ref[b] * n, n)
        return (
            pltpu.make_async_copy(kn_ref.at[b], kc_ref.at[b, row],
                                  sems.at[0, b]),
            pltpu.make_async_copy(vn_ref.at[b], vc_ref.at[b, row],
                                  sems.at[1, b]),
        )

    @pl.loop(0, batch)
    def _(b):
        for dma in row_dma(b):
            dma.start()

    @pl.loop(0, batch)
    def _(b):
        for dma in row_dma(b):
            dma.wait()  # staticcheck: ok[unbounded-blocking] — on-device DMA issued by this kernel's own schedule; completion is guaranteed by construction, there is no peer to time out on


def kv_cache_append(k_cache, v_cache, k_new, v_new, off):
    """Write `k_new[b]` / `v_new[b]` ([B, 1, H_kv, D]) into `k_cache[b]` /
    `v_cache[b]` ([B, S_max, H_kv, D]) at position `off[b]` ([B] int32),
    in place where the caller donates the caches; or, for caches kept as the
    (position, KV head) rows the decode kernel reads, `[B, H_kv, D]` into
    `[B, S_max * H_kv, D]` at rows `off[b] * H_kv` on.  K and V may differ
    in lanes.  Offsets read as the
    vmapped `dynamic_update_slice` this replaces reads them: a negative one
    counts from the end, and the result is clamped to [0, S_max - 1] (its
    scatter is `mode=CLIP`); a DMA out of range is a fault, not a skipped
    update (a ring's caller passes `off % ring`).  Returns the two caches."""
    B, n = k_new.shape[0], k_new.shape[1]
    assert n == 1 or k_cache.ndim == 3, (k_new.shape, k_cache.shape)
    S_max = k_cache.shape[1] // n
    for c, new in ((k_cache, k_new), (v_cache, v_new)):
        assert new.shape == (B, n) + c.shape[2:] \
            and c.shape[:2] == k_cache.shape[:2], (
            k_new.shape, v_new.shape, k_cache.shape, v_cache.shape)
    assert off.shape == (B,)
    off = off.astype(jnp.int32)
    off = jnp.clip(jnp.where(off < 0, off + S_max, off), 0, S_max - 1)
    rows = lambda new: pl.BlockSpec(new.shape,
                                    lambda i, *_: (0,) * new.ndim)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[rows(k_new), rows(v_new), hbm, hbm],
        out_specs=[hbm, hbm],
        scratch_shapes=[pltpu.SemaphoreType.DMA((2, B))],
    )
    with _x32():
        return tuple(pl.pallas_call(
            _kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                       jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)],
            # operands count the scalar-prefetch `off`: 3 and 4 are the caches
            input_output_aliases={3: 0, 4: 1},
            interpret=_interpret(),
            name="kv_cache_append",
        )(off, k_new.astype(k_cache.dtype), v_new.astype(v_cache.dtype),
          k_cache, v_cache))
