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
        "selective_scan is forward only: the scan's backward pass is not "
        "built (ROADMAP A3); run it under no_grad")


selective_scan.defvjp(lambda *args: (selective_scan(*args), None),
                      _no_backward)
