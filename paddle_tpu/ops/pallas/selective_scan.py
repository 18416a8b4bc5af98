"""Selective scan (the Mamba-1 recurrence) as a Pallas TPU kernel.

    h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * u_t) (x) B_t
    y_t = (h_t . C_t + D * u_t) * silu(z_t)

over the positions of a prefill, TPU-native stand-in for the CUDA
`selective_scan_fn` the published models call.  The state is d_inner x
d_state numbers a row, and a position's is never written to HBM: the jnp
composition (`lax.scan`, or an associative scan) materialises
[S, d_inner, d_state] (335 MB a layer at 1024 x 5120 x 16 f32), this kernel
carries `h` in VMEM across chunks of positions and writes `y` and the one
state at each row's `length`.

Layout: channels fill whole vregs.  d_inner is viewed as [rows, 128] and a
grid cell owns a block of `_ROWS` rows (1024 channels); its state is d_state
tiles of [rows, 128] float32, so a step is elementwise on whole tiles with
B_t[n] and C_t[n] as SCALARS read from SMEM: no transpose, no lane
broadcast, no cross-sublane reduction (y_t sums the d_state tiles).  Grid:
(batch row, chunk of positions, block of channels), the last innermost, so a
chunk's B and C are fetched once for all channel blocks; every block's state
waits in VMEM scratch for its next chunk.

Right padding is not causal-mask safe for a recurrence: positions at or
beyond `lengths[b]` run with dt = 0, which leaves `h` as it was
(exp(0) = 1, dt * u = 0), so the state returned is the state AT the length.
Their `y` is not meaningful.  Arithmetic is float32 whatever the inputs;
`y` takes `u`'s type, the state stays float32.  Forward only.

`ssm_decode_update` is the same step for ONE position a slot, the decode
step's: it reads each slot's state [d_state, d_inner] once, forms the new
state and `y` from it in VMEM and writes the state back into the buffer it
read (`input_output_aliases`), in the state's own tiled layout (d_state on
sublanes, d_inner on lanes), so no copy and no relayout of the state is
made.  Written in `jax.numpy` (`ssm_decode_ref`), the compiled decode step
moves each layer's whole state more than once: besides the update, `y`'s
reduction reads it again or XLA stages it in VMEM and copies it back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import _interpret, _x32

_LANES = 128
_ROWS = 8           # sublane rows of channels a block: 8 x 128 = one f32 vreg
_CHUNK = 64         # positions a grid step


def _kernel(len_ref, b_ref, c_ref, u_ref, dt_ref, z_ref, a_ref, d_ref,
            h0_ref, y_ref, ht_ref, h_scr, *, chunk, n_state):
    b, s, d = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    length = len_ref[b]

    @pl.when(s == 0)
    def _():
        h_scr[d] = h0_ref[0]

    a = [a_ref[n] for n in range(n_state)]          # [rows, 128] each
    skip = d_ref[...]

    def step(t, hs):
        live = s * chunk + t < length
        dt = jnp.where(live, dt_ref[0, t], 0.0)
        u = u_ref[0, t].astype(jnp.float32)
        dtu = dt * u
        y = skip * u
        out = []
        for n in range(n_state):
            h = jnp.exp(dt * a[n]) * hs[n] + dtu * b_ref[0, 0, 0, t * n_state + n]
            y = y + h * c_ref[0, 0, 0, t * n_state + n]
            out.append(h)
        z = z_ref[0, t].astype(jnp.float32)
        y_ref[0, t] = (y * z * jax.nn.sigmoid(z)).astype(y_ref.dtype)
        return tuple(out)

    hs = jax.lax.fori_loop(0, chunk, step,
                           tuple(h_scr[d, n] for n in range(n_state)))
    for n in range(n_state):
        h_scr[d, n] = hs[n]

    @pl.when(s == pl.num_programs(1) - 1)
    def _():
        for n in range(n_state):
            ht_ref[0, n] = hs[n]


def selective_scan_ref(u, dt, a, b, c, d, z, h0, lengths):
    """The same recurrence as a `lax.scan` a position, in float32 `jax.numpy`
    (the tests' oracle; shapes as `selective_scan`)."""
    f32 = jnp.float32
    a_t = a.astype(f32).T                                      # [N, Di]
    live = jnp.arange(u.shape[1])[None, :] < lengths[:, None]   # [B, S]
    dt = jnp.where(live[..., None], dt.astype(f32), 0.0)

    def step(h, xs):
        u_t, dt_t, b_t, c_t = xs                               # [B, Di|N]
        h = jnp.exp(dt_t[:, None, :] * a_t[None]) * h \
            + (dt_t * u_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    tm = lambda x: jnp.swapaxes(x.astype(f32), 0, 1)           # time-major
    h, y = jax.lax.scan(step, h0.astype(f32), (tm(u), tm(dt), tm(b), tm(c)))
    zf = z.astype(f32)
    y = (jnp.swapaxes(y, 0, 1) + d.astype(f32) * u.astype(f32)) \
        * zf * jax.nn.sigmoid(zf)
    return y.astype(u.dtype), h


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan(u, dt, a, b, c, d, z, h0, lengths, interpret):
    """`selective_scan`'s body, jitted: inside a step's trace every layer's
    call after the first finds its jaxpr (tracing the kernel is 0.4 s, and a
    model calls it once a Mamba layer a prefill bucket)."""
    f32 = jnp.float32
    bsz, s_len, di = u.shape
    n_state = a.shape[1]
    chunk = min(_CHUNK, -(-s_len // 8) * 8)
    s_pad, c_pad = (-s_len) % chunk, (-di) % _LANES
    rows = (di + c_pad) // _LANES
    blk = _ROWS if rows % _ROWS == 0 else rows

    def channels(x, dtype=None, seq=False):
        """[..., Di] -> [..., rows, 128]; a [B, S, Di] input (`seq`) with
        its positions padded to whole chunks."""
        x = x if dtype is None else x.astype(dtype)
        pad = [(0, 0)] * (x.ndim - 1) + [(0, c_pad)]
        if seq:
            pad[1] = (0, s_pad)
        x = jnp.pad(x, pad)
        return x.reshape(x.shape[:-1] + (rows, _LANES))

    def scalars(x):
        """[B, S, N] -> [B, chunks, 1, chunk * N] float32, for SMEM (a block
        is the last two dimensions whole)."""
        x = jnp.pad(x.astype(f32), ((0, 0), (0, s_pad), (0, 0)))
        return x.reshape(bsz, -1, 1, chunk * n_state)

    n_chunks = (s_len + s_pad) // chunk
    seq = pl.BlockSpec((1, chunk, blk, _LANES),
                       lambda b_, s_, d_, *_: (b_, s_, d_, 0))
    smem = pl.BlockSpec((1, 1, 1, chunk * n_state),
                        lambda b_, s_, d_, *_: (b_, s_, 0, 0),
                        memory_space=pltpu.SMEM)
    state = pl.BlockSpec((1, n_state, blk, _LANES),
                         lambda b_, s_, d_, *_: (b_, 0, d_, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, n_chunks, rows // blk),
        in_specs=[
            smem, smem, seq, seq, seq,
            pl.BlockSpec((n_state, blk, _LANES),
                         lambda b_, s_, d_, *_: (0, d_, 0)),
            pl.BlockSpec((blk, _LANES), lambda b_, s_, d_, *_: (d_, 0)),
            state,
        ],
        out_specs=[seq, state],
        scratch_shapes=[
            pltpu.VMEM((rows // blk, n_state, blk, _LANES), f32)],
    )
    kernel = functools.partial(_kernel, chunk=chunk, n_state=n_state)
    with _x32():
        y, h = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((bsz, s_len + s_pad, rows, _LANES),
                                     u.dtype),
                jax.ShapeDtypeStruct((bsz, n_state, rows, _LANES), f32)],
            interpret=interpret,
            name="selective_scan",
        )(lengths.astype(jnp.int32), scalars(b), scalars(c),
          channels(u, seq=True), channels(dt, f32, seq=True),
          channels(z, seq=True), channels(a.astype(f32).T),
          channels(d, f32), channels(h0, f32))
    y = y.reshape(bsz, s_len + s_pad, -1)[:, :s_len, :di]
    return y, h.reshape(bsz, n_state, -1)[:, :, :di]


@jax.custom_vjp
def selective_scan(u, dt, a, b, c, d, z, h0, lengths):
    """u, dt, z: [B, S, Di] (dt after its softplus); a: [Di, N] (negative);
    b, c: [B, S, N]; d: [Di]; h0: [B, N, Di] float32; lengths: [B] int32.
    Returns y [B, S, Di] in u's type and the state [B, N, Di] float32 after
    position lengths[b] - 1 of each row."""
    return _scan(u, dt, a, b, c, d, z, h0, lengths, interpret=_interpret())


def _no_backward(_, grads):
    raise NotImplementedError(
        "selective_scan and ssm_decode_update are forward only: the scan's "
        "backward pass is not built (ROADMAP A3); run them under no_grad")


selective_scan.defvjp(lambda *args: (selective_scan(*args), None),
                      _no_backward)


# ---- one position a slot: the decode step's update, in place ---------------

_SLOT_ROWS = 16             # slots a block: one packed bf16 tile of u, z, y
# of state a grid step (read; as much is written): on a TPU v5e blocks of 1
# to 8 MiB time within 3% of one another at 64 and 192 slots
_BLOCK_BYTES = 4 << 20


def decode_rows(slots: int):
    """Slots a block of `ssm_decode_update`: all of them up to 16, else 16
    where that cuts them into whole blocks; None where it does not."""
    if slots <= _SLOT_ROWS:
        return slots
    return _SLOT_ROWS if slots % _SLOT_ROWS == 0 else None


def updates_in_place(shape, dtype) -> bool:
    """Whether `ssm_decode_update` takes a state [slots, d_state, d_inner] of
    `dtype`: float32 (the kernel neither rounds nor widens the state),
    d_inner whole 128-lane tiles, the slots whole blocks."""
    slots, _, di = shape
    return (jnp.dtype(dtype) == jnp.float32 and di % _LANES == 0
            and decode_rows(slots) is not None)


def _decode_lanes(rows, n_state, di):
    """Channels a block: the most whole tiles of lanes that divide d_inner
    with a block of state within `_BLOCK_BYTES`."""
    tiles = di // _LANES
    fits = [t for t in range(1, tiles + 1) if tiles % t == 0
            and rows * n_state * t * _LANES * 4 <= _BLOCK_BYTES]
    return max(fits, default=1) * _LANES


def ssm_decode_ref(u, dt, a, b, c, d, z, h0):
    """One position a slot in float32 `jax.numpy`: the decode step's update
    where `ssm_decode_update` is not taken, and its tests' oracle.  u, dt,
    z: [B, Di] (dt after its softplus); a: [Di, N]; b, c: [B, N]; d: [Di];
    h0: [B, N, Di].  Returns y * silu(z) [B, Di] in u's type and the state
    in h0's."""
    f32 = jnp.float32
    u1 = u.astype(f32)
    h = jnp.exp(dt[:, None, :] * a.astype(f32).T[None]) * h0.astype(f32) \
        + (dt * u1)[:, None, :] * b.astype(f32)[:, :, None]
    y = jnp.sum(h * c.astype(f32)[:, :, None], axis=1) + d.astype(f32) * u1
    zf = z.astype(f32)
    return (y * zf * jax.nn.sigmoid(zf)).astype(u.dtype), h.astype(h0.dtype)


def _decode_kernel(u_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref, h0_ref,
                   y_ref, h_ref, b_scr, c_scr):
    """A block of slots x a block of channels.  In HBM `h0_ref`'s array IS
    `h_ref`'s (input_output_aliases), so the state is written back where it
    was read; in VMEM the two blocks are apart (the output's is not loaded),
    so the state is read from `h0_ref` only.  B and C arrive as columns a slot
    ([N, rows]) and are spread along the lanes once a grid step; the slots
    of a block are unrolled (at most 16: one packed tile of u, z and y), the
    channels run in a loop, so the body is the same at any B and d_inner."""
    rows, n_state = h_ref.shape[0], h_ref.shape[1]
    f32 = jnp.float32
    bt, ct = b_ref[0], c_ref[0]
    for r in range(rows):
        b_scr[r] = jnp.broadcast_to(bt[:, r:r + 1], (n_state, _LANES))
        c_scr[r] = jnp.broadcast_to(ct[:, r:r + 1], (n_state, _LANES))
    slot = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 0)

    def tile(k, carry):
        at = pl.ds(pl.multiple_of(k * _LANES, _LANES), _LANES)
        a, dt = a_ref[:, at], dt_ref[:, at]
        u = u_ref[:, at].astype(f32)
        dtu = dt * u
        y = jnp.zeros((rows, _LANES), f32)
        for r in range(rows):
            h = jnp.exp(dt[r:r + 1] * a) * h0_ref[r, :, at] \
                + dtu[r:r + 1] * b_scr[r]
            h_ref[r, :, at] = h
            y = jnp.where(slot == r,
                          jnp.sum(h * c_scr[r], axis=0, keepdims=True), y)
        z = z_ref[:, at].astype(f32)
        y = (y + d_ref[:, at] * u) * z * jax.nn.sigmoid(z)
        y_ref[:, at] = y.astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, h_ref.shape[2] // _LANES, tile, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode(u, dt, a, b, c, d, z, h0, interpret):
    """`ssm_decode_update`'s body, jitted: every Mamba layer of a step finds
    the one jaxpr of its geometry (26 calls a Jamba decode step)."""
    f32 = jnp.float32
    slots, n_state, di = h0.shape
    rows = decode_rows(slots)
    lanes = _decode_lanes(rows, n_state, di)
    blocks = slots // rows

    def columns(x):
        """[B, N] -> [blocks, N, rows]: a block's B (or C) a column a slot."""
        return x.astype(f32).reshape(blocks, rows, n_state).transpose(0, 2, 1)

    # grid (channel block, slot block), slots innermost: A and D are
    # fetched once a channel block
    row = pl.BlockSpec((rows, lanes), lambda j, i: (i, j))
    col = pl.BlockSpec((1, n_state, rows), lambda j, i: (i, 0, 0))
    state = pl.BlockSpec((rows, n_state, lanes), lambda j, i: (i, 0, j))
    block = rows * n_state * lanes * 4
    with _x32():
        return pl.pallas_call(
            _decode_kernel,
            grid=(di // lanes, blocks),
            in_specs=[row, row, row, col, col,
                      pl.BlockSpec((n_state, lanes), lambda j, i: (0, j)),
                      pl.BlockSpec((1, lanes), lambda j, i: (0, j)),
                      state],
            out_specs=[row, state],
            out_shape=[jax.ShapeDtypeStruct((slots, di), u.dtype),
                       jax.ShapeDtypeStruct(h0.shape, f32)],
            scratch_shapes=[pltpu.VMEM((rows, n_state, _LANES), f32)] * 2,
            # operand 7, the state, is output 1
            input_output_aliases={7: 1},
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=max(32 << 20, 6 * block)),
            interpret=interpret,
            name="ssm_decode_update",
        )(u, dt.astype(f32), z, columns(b), columns(c),
          a.astype(f32).T, d.astype(f32).reshape(1, di), h0)


@jax.custom_vjp
def ssm_decode_update(u, dt, a, b, c, d, z, h0):
    """One position a slot, shapes as `ssm_decode_ref`, where
    `updates_in_place(h0.shape, h0.dtype)`: y * silu(z) [B, Di] in u's type
    and the new state [B, N, Di] float32, written into h0's buffer (in place
    where the caller donates it)."""
    return _decode(u, dt, a, b, c, d, z, h0, interpret=_interpret())


ssm_decode_update.defvjp(lambda *args: (ssm_decode_update(*args), None),
                         _no_backward)
