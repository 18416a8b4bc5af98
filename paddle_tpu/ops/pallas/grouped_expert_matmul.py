"""Grouped (ragged) matmul for routed experts as a Pallas TPU kernel.

`lhs [rows, K] x rhs [G, K, N]` with `group_sizes [G]`: the rows of group g
meet `rhs[g]` and no other.  A router decides the sizes at run time, the
shapes are static, and the work has to follow the LIVE rows (a dense matmul
over every held expert costs G times the routed work), so the layout makes
every row tile belong to one group:

- group g's rows start at a tile boundary, row
  `tile_rows * sum_{j<g} ceil(group_sizes[j] / tile_rows)` (`group_row_starts`);
  the rows between a group's size and its next boundary are padding, computed
  and never read;
- `tile_group [tiles]` and the live tile count ride scalar prefetch: the
  grid's row tiles at or past the live count do nothing and bring nothing
  (their block indices are the last live step's, so the pipeline issues no
  copy for them), and their output rows are left as they were: UNSPECIFIED.
  A caller reads only rows below a group's size.

`rows` is static and is what the worst case needs: every assignment live and
every group's last tile nearly empty, `assignments + G * (tile_rows - 1)`
rounded up to whole tiles (`padded_rows`).  An empty group takes no tile; one
group may hold every row.

K stays whole in a block (no accumulator carried across grid steps), N is cut
into column blocks, bfloat16 or float32 in, float32 accumulate, the input's
type out.  One jitted wrapper, so a step that calls it once a layer traces
the kernel once a shape.  Forward only: the backward pass raises.
(`jax.experimental.pallas.ops.tpu.megablox` solves the general case, groups
that straddle tiles, with a tile visited once a group it touches; aligning
the groups is what a caller that builds the layout itself can afford.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _interpret, _x32

TILE_ROWS = 128
_BLOCK_BYTES = 4 * 1024 * 1024      # one [K, block_n] block of an expert


def padded_rows(assignments: int, groups: int, tile_rows: int = TILE_ROWS):
    """Rows the tile-aligned layout needs for `assignments` live rows spread
    over `groups` in the worst way."""
    worst = assignments + groups * (tile_rows - 1)
    return -(-worst // tile_rows) * tile_rows


def group_row_starts(group_sizes, tile_rows: int = TILE_ROWS):
    """First row of every group in the tile-aligned layout, [G] int32."""
    tiles = -(-group_sizes.astype(jnp.int32) // tile_rows)
    return (jnp.cumsum(tiles) - tiles) * tile_rows


def _block_n(k: int, n: int, itemsize: int) -> int:
    """Columns of an expert's matrix a grid step holds: all of them when
    they are few, else the largest multiple of 128 that divides N and keeps
    the [K, block] block within _BLOCK_BYTES."""
    if n % 128 or n <= 128:
        return n
    bn = n
    while bn > 128 and (k * bn * itemsize > _BLOCK_BYTES or n % bn):
        bn -= 128
    return bn


def _kernel(tg_ref, live_ref, lhs_ref, rhs_ref, out_ref):
    @pl.when(pl.program_id(0) < live_ref[0])
    def _():
        lhs = lhs_ref[...]
        exact = jax.lax.Precision.HIGHEST if lhs.dtype == jnp.float32 \
            else None
        out_ref[...] = jax.lax.dot_general(
            lhs, rhs_ref[0], (((1,), (0,)), ((), ())), precision=exact,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _gmm(lhs, rhs, group_sizes, tile_rows, interpret):
    rows, k = lhs.shape
    groups, _, n = rhs.shape
    tiles = rows // tile_rows
    itemsize = jnp.dtype(rhs.dtype).itemsize
    bn = _block_n(k, n, itemsize)
    n_blocks = n // bn
    per_group = -(-group_sizes.astype(jnp.int32) // tile_rows)
    ends = jnp.cumsum(per_group)
    live = ends[-1:].astype(jnp.int32)                       # [1]
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(tiles, dtype=jnp.int32),
                         side="right"), groups - 1).astype(jnp.int32)

    def tile(m, live_ref):
        # a tile past the live count stands on the last live one
        return jnp.maximum(jnp.minimum(m, live_ref[0] - 1), 0)

    def cols(m, j, live_ref):
        return jnp.where(m < live_ref[0], j, n_blocks - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(tiles, n_blocks),
        in_specs=[
            pl.BlockSpec((tile_rows, k),
                         lambda m, j, tg, lv: (tile(m, lv), 0)),
            pl.BlockSpec((1, k, bn),
                         lambda m, j, tg, lv: (tg[tile(m, lv)], 0,
                                               cols(m, j, lv))),
        ],
        out_specs=pl.BlockSpec(
            (tile_rows, bn),
            lambda m, j, tg, lv: (tile(m, lv), cols(m, j, lv))),
    )
    # two slots each of the expert's block, the row tile and the output,
    # and the float32 product before its cast
    need = 2 * (k * bn + tile_rows * k + tile_rows * bn) * itemsize \
        + 2 * tile_rows * bn * 4
    with _x32():
        return pl.pallas_call(
            _kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
            # dead steps stand on the last live block: the steps run in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=int(max(2 * need, 32 * 1024 * 1024))),
            interpret=interpret,
            name="grouped_expert_matmul",
        )(tile_group, live, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_expert_matmul(lhs, rhs, group_sizes, tile_rows=TILE_ROWS):
    """lhs [rows, K] in the tile-aligned layout (module docstring), rows a
    multiple of `tile_rows`; rhs [G, K, N] of lhs's type; group_sizes [G]
    int32.  Returns [rows, N]: row r of group g is `lhs[r] @ rhs[g]` for
    r below the group's size; every other row is unspecified."""
    if lhs.shape[0] % tile_rows or lhs.shape[1] != rhs.shape[1] \
            or group_sizes.shape != (rhs.shape[0],):
        raise ValueError(
            f"grouped_expert_matmul: lhs {lhs.shape} (rows in whole tiles of "
            f"{tile_rows}), rhs {rhs.shape}, group_sizes {group_sizes.shape}")
    return _gmm(lhs, rhs.astype(lhs.dtype), group_sizes, tile_rows,
                _interpret())


def _no_backward(_tile_rows, _res, _grads):
    raise NotImplementedError(
        "grouped_expert_matmul is forward only: the backward pass (a "
        "transposed grouped matmul and a grouped outer product) is not "
        "built; run it under no_grad")


grouped_expert_matmul.defvjp(
    lambda lhs, rhs, sizes, tile_rows: (
        grouped_expert_matmul(lhs, rhs, sizes, tile_rows), None),
    _no_backward)


def grouped_matmul_ref(lhs, rhs, group_sizes, tile_rows=TILE_ROWS):
    """The same result by a loop over groups in plain `jax.numpy`, rows past
    a group's size zero: what the kernel is tested against, beside
    `jax.lax.ragged_dot` on the rows laid end to end."""
    starts = group_row_starts(group_sizes, tile_rows)
    r = jnp.arange(lhs.shape[0])[:, None]
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for g in range(rhs.shape[0]):
        mine = (r >= starts[g]) & (r < starts[g] + group_sizes[g])
        out = out + jnp.where(mine, jnp.dot(
            lhs.astype(jnp.float32), rhs[g].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), 0.0)
    return out.astype(lhs.dtype)
