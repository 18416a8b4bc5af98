"""The Mamba decode step's one-position update as one Pallas kernel
(`ops/pallas/selective_scan.py ssm_decode_update`, interpreted here) against
the `jax.numpy` fusion it replaces (`ssm_decode_ref`), through the mixer
that Jamba and Phi-4-flash share; the state written in place; and the
engine's count of which update each Mamba layer of the decode step took.

Tolerances: kernel and fusion are the same float32 operations and differ
only in the order of the d_state sum, so they agree to a few float32 ulps;
TOL is 1e-5.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import (
    JambaConfig, JambaForCausalLM, JambaMambaMixer)
from paddle_tpu.ops.pallas import selective_scan as ss

TOL = 1e-5


def _mixer(di, inner):
    """A mixer of d_inner `di`: Jamba's (its norms on dt, B and C) or, with
    `inner`, Phi-4-flash's (no norms; its gated inner output is the GMU's
    memory)."""
    P.seed(3)
    cfg = dataclasses.replace(JambaConfig.tiny(hidden=di // 2, d_state=16),
                              mamba_inner_norms=not inner)
    return JambaMambaMixer(cfg), cfg


def _one_position(mixer, cfg, slots, inner):
    rng = np.random.RandomState(slots)
    x = P.to_tensor(rng.randn(slots, 1, cfg.hidden_size).astype("float32"))
    conv = rng.randn(slots, 3 * cfg.d_inner).astype("float32")
    ssm = rng.randn(slots, cfg.mamba_d_state, cfg.d_inner).astype("float32")
    with P.no_grad():
        out = mixer(x, (P.to_tensor(conv), P.to_tensor(ssm)),
                    jnp.ones((slots,), jnp.int32), inner=inner)
    got = [out[0].numpy(), np.asarray(out[1][1]._value)]
    return got + ([out[2].numpy()] if inner else [])


@pytest.mark.parametrize("inner", [False, True], ids=["jamba", "inner"])
@pytest.mark.parametrize("di", [128, 256])
@pytest.mark.parametrize("slots", [1, 3, 8])
def test_the_kernel_matches_the_fusion_it_replaces(slots, di, inner,
                                                   monkeypatch):
    """The mixer's output, the new state and (inner) the gated output the
    GMU reads, through the kernel and through today's fusion."""
    mixer, cfg = _mixer(di, inner)
    assert ss.updates_in_place((slots, 16, di), jnp.float32)
    got = _one_position(mixer, cfg, slots, inner)
    monkeypatch.setattr(ss, "updates_in_place", lambda *a: False)
    want = _one_position(mixer, cfg, slots, inner)
    assert len(got) == (3 if inner else 2)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype == np.float32
        np.testing.assert_allclose(g, w, rtol=TOL, atol=TOL)


def test_the_state_is_written_into_the_buffer_it_was_read_from():
    """Donated, the state comes back in the same buffer: one read and one
    write, no copy."""
    rng = np.random.RandomState(0)
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    b, n, di = 3, 16, 128
    # the state made by a computation: a CPU array put from numpy may share
    # numpy's memory, which the runtime does not hand over to an output
    args = [f(b, di), jax.nn.softplus(f(b, di)), -jnp.exp(f(di, n)),
            f(b, n), f(b, n), f(di), f(b, di), f(b, n, di) * 1.0]
    want = ss.ssm_decode_ref(*args)
    # nothing may still be reading the state when it is donated
    jax.block_until_ready((args, want))
    h0 = args[-1]
    where = h0.unsafe_buffer_pointer()
    y, h = jax.jit(ss.ssm_decode_update, donate_argnums=(7,))(*args)
    assert h0.is_deleted() and h.unsafe_buffer_pointer() == where
    np.testing.assert_allclose(np.asarray(h), np.asarray(want[1]), rtol=TOL,
                               atol=TOL)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want[0]), rtol=TOL,
                               atol=TOL)


def test_the_old_state_is_read_from_the_input_block_only(monkeypatch):
    """On the chip the state's output block is NOT loaded from HBM (the
    alias is HBM's, not VMEM's): the kernel must read the old state from its
    input block.  The TPU interpreter leaves unwritten VMEM as NaN, so a read
    of the output block shows; small blocks make several of each kind."""
    from jax.experimental.pallas import tpu as pltpu
    rng = np.random.RandomState(1)
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    b, n, di = 32, 16, 512
    args = [f(b, di).astype(jnp.bfloat16), jax.nn.softplus(f(b, di)),
            -jnp.exp(f(di, n)), f(b, n), f(b, n), f(di),
            f(b, di).astype(jnp.bfloat16), f(b, n, di)]
    monkeypatch.setattr(ss, "_BLOCK_BYTES", 16 * n * 128 * 4)
    assert ss._decode_lanes(ss.decode_rows(b), n, di) == 128
    y, h = ss._decode(*args, interpret=pltpu.InterpretParams(
        uninitialized_memory="nan"))
    want = ss.ssm_decode_ref(*args)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want[1]), rtol=TOL,
                               atol=TOL)
    # y is bfloat16 here: the two may round a last bit apart
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want[0], np.float32), rtol=1e-2,
                               atol=1e-2)


def test_which_states_the_kernel_takes():
    """Float32, d_inner whole lane tiles, slots whole blocks of 16 (or 16 at
    most); everything else keeps the fusion."""
    f32 = jnp.float32
    assert ss.decode_rows(192) == ss.decode_rows(64) == 16
    assert ss.decode_rows(3) == 3 and ss.decode_rows(24) is None
    assert ss.updates_in_place((192, 16, 5120), f32)
    assert not ss.updates_in_place((192, 16, 5120), jnp.bfloat16)
    assert not ss.updates_in_place((4, 8, 96), f32)
    assert not ss.updates_in_place((24, 16, 5120), f32)
    # a block of the cells' state is 4 MiB: 16 slots x 16 x 4096 lanes does
    # not divide 5120, so 2560 lanes
    assert ss._decode_lanes(16, 16, 5120) == 2560
    assert ss._decode_lanes(3, 8, 128) == 128


def test_the_engine_keeps_the_fusion_where_lanes_are_not_whole_tiles():
    """A tiny Jamba of d_inner 96 (not whole 128-lane tiles): every one of
    its 6 Mamba layers keeps the fusion, and the engine says so (at d_inner
    128 and 256 the engine tests of Jamba and Phi-4-flash read the kernel in
    every layer)."""
    P.seed(0)
    m = JambaForCausalLM(JambaConfig.tiny(hidden=48))
    m.eval()
    eng = ServingEngine(m, max_batch=2, max_seq_len=32, prefill_buckets=[16])
    assert eng.info()["step"]["ssm_update"] == {}          # not captured yet
    eng.generate([np.arange(5) % 100], max_new_tokens=2)
    step = eng.info()["step"]
    assert step["ssm_update"] == {"kernel": 0, "fusion": 6}
    assert step["kv_write"] == {"kernel": 0, "scatter": 0}  # no Llama layer
