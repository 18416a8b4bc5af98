"""Comms subsystem (distributed/comms): quantized + schedule-aware
collectives.

Four layers:
1. wire-format known answers — blockwise quantize/dequantize round trips,
   the all-zero-block / inf-nan-guard / odd-tail contracts, stochastic
   rounding, fp8, and the bytes accounting;
2. the opt-in context + the collectives built on it (local round trip,
   grad_sync's bitwise-off guarantee);
3. the schedule layer — CommOp records, per-step scoping, comm_summary;
4. the capture-tier comm pass (jit/passes/comm_schedule.py) — tagging,
   overlap slots, the earliest-issue hoist staying value-exact — plus the
   recompile-count guard: a captured step containing a quantized
   collective lowers ONCE and records its CommOps once, not per call.

5. the wired path: a Llama train step on a dp2 mesh, its gradient sync's
   wire format at int8 (>= 3.5x fewer bytes, padding counted), the
   bitwise-off guarantee and loss parity.

The chaos/no-hang story for the comm.* fault sites lives in
tests/test_no_hang.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401 — x64 + shard_map compat
from paddle_tpu.distributed import comms
from paddle_tpu.utils.deadline import CommTimeout  # noqa: F401 — re-export sanity


@pytest.fixture(autouse=True)
def _clean_registry():
    comms.comm_clear()
    yield
    comms.comm_clear()


# ---------------- wire format: known answers ----------------

def test_roundtrip_small_known_values():
    # one block, absmax 2 -> scale 2/127; quantized levels are exact ints
    x = jnp.asarray([2.0, -2.0, 1.0, 0.0], jnp.float32)
    q, s = comms.quantize_blockwise(x, "int8", block=4)
    assert q.dtype == jnp.int8 and q.shape == (4,)
    np.testing.assert_array_equal(np.asarray(q), [127, -127, 64, 0])
    np.testing.assert_allclose(np.asarray(s), [2.0 / 127], rtol=1e-6)
    y = comms.dequantize_blockwise(q, s, (4,), jnp.float32, block=4)
    np.testing.assert_allclose(np.asarray(y), [2.0, -2.0, 64 * 2 / 127, 0.0],
                               rtol=1e-6)


def test_roundtrip_error_bound():
    # |err| <= scale/2 per element = absmax/254 per block
    rng = np.random.RandomState(0)
    x = rng.randn(4096).astype(np.float32)
    q, s = comms.quantize_blockwise(jnp.asarray(x), "int8", block=128)
    y = np.asarray(comms.dequantize_blockwise(q, s, x.shape, jnp.float32,
                                              block=128))
    blocks = x.reshape(-1, 128)
    bound = (np.abs(blocks).max(axis=1, keepdims=True) / 254) + 1e-7
    assert np.all(np.abs((y.reshape(-1, 128) - blocks)) <= bound)


def test_all_zero_block_exact_and_finite_scale():
    x = jnp.zeros((300,), jnp.float32)  # 2 blocks of 256: one all-pad tail
    q, s = comms.quantize_blockwise(x, "int8", block=256)
    assert np.all(np.asarray(s) == 1.0)  # clamped, not 0/0
    y = comms.dequantize_blockwise(q, s, (300,), jnp.float32, block=256)
    np.testing.assert_array_equal(np.asarray(y), np.zeros(300))


def test_inf_nan_guard():
    """Non-finite inputs must not poison the block scale: nan -> 0,
    +/-inf saturates at the block's FINITE absmax, neighbors keep full
    resolution."""
    x = jnp.asarray([1.0, -2.0, np.inf, np.nan, -np.inf, 3.0], jnp.float32)
    q, s = comms.quantize_blockwise(x, "int8", block=4)
    y = np.asarray(comms.dequantize_blockwise(q, s, (6,), jnp.float32,
                                              block=4))
    assert np.all(np.isfinite(y))
    # block 1 = [1, -2, inf, nan]: finite absmax 2 -> inf saturates to 2
    np.testing.assert_allclose(y[1], -2.0, rtol=1e-6)
    np.testing.assert_allclose(y[2], 2.0, rtol=1e-6)
    assert y[3] == 0.0
    # block 2 = [-inf, 3, pad, pad]: -inf saturates to -3
    np.testing.assert_allclose(y[4], -3.0, rtol=1e-6)
    np.testing.assert_allclose(y[5], 3.0, rtol=1e-6)
    # the finite neighbor kept its resolution (scale from 2, not inf)
    np.testing.assert_allclose(y[0], 1.0, atol=2.0 / 127)


def test_odd_tail_block_roundtrip():
    # 777 = 3*256 + 9: the tail block is short and zero-padded internally
    rng = np.random.RandomState(1)
    x = rng.randn(777).astype(np.float32)
    q, s = comms.quantize_blockwise(jnp.asarray(x), "int8", block=256)
    assert q.shape == (4 * 256,) and s.shape == (4,)
    y = np.asarray(comms.dequantize_blockwise(q, s, (777,), jnp.float32,
                                              block=256))
    assert y.shape == (777,)
    assert np.max(np.abs(y - x)) <= np.abs(x).max() / 100


def test_roundtrip_preserves_shape_and_dtype():
    x = jnp.asarray(np.random.RandomState(2).randn(3, 5, 7), jnp.bfloat16)
    q, s = comms.quantize_blockwise(x, "int8", block=32)
    y = comms.dequantize_blockwise(q, s, (3, 5, 7), jnp.bfloat16, block=32)
    assert y.shape == (3, 5, 7) and y.dtype == jnp.bfloat16


def test_fp8_wire_format():
    if not hasattr(jnp, "float8_e4m3fn"):
        pytest.skip("no float8 on this jax")
    rng = np.random.RandomState(3)
    x = rng.randn(1024).astype(np.float32)
    q, s = comms.quantize_blockwise(jnp.asarray(x), "fp8", block=128)
    assert q.dtype == jnp.float8_e4m3fn
    y = np.asarray(comms.dequantize_blockwise(q, s, x.shape, jnp.float32,
                                              block=128))
    # e4m3 keeps ~2 decimal digits near the block max
    assert np.max(np.abs(y - x)) / np.abs(x).max() < 0.1


def test_stochastic_rounding_unbiased_and_deterministic():
    # a value exactly between two levels: round-to-nearest always picks one
    # side; SR picks both with ~equal probability -> the MEAN converges
    scale_target = 2.0  # absmax -> scale 2/127; 0.5 level gap around 1/127
    x = jnp.full((4096,), scale_target * 64.5 / 127, jnp.float32)
    x = x.at[0].set(scale_target)  # pin the scale
    key = jax.random.key(0)
    q, s = comms.quantize_blockwise(x, "int8", block=4096, stochastic=True,
                                    key=key)
    y = np.asarray(comms.dequantize_blockwise(q, s, x.shape, jnp.float32,
                                              block=4096))
    mean_err = abs(float(np.mean(y[1:])) - float(x[1]))
    halfstep = scale_target / 127 / 2
    assert mean_err < halfstep / 5  # nearest-rounding would sit AT halfstep
    # deterministic under the same key
    q2, _ = comms.quantize_blockwise(x, "int8", block=4096, stochastic=True,
                                     key=key)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    with pytest.raises(ValueError, match="key"):
        comms.quantize_blockwise(x, stochastic=True)
    # SR is int8-only: fp8's non-uniform grid would turn the half-step
    # noise into bias — typed rejection at the kernel AND the context
    with pytest.raises(ValueError, match="int8"):
        comms.quantize_blockwise(x, dtype="fp8", stochastic=True, key=key)
    with pytest.raises(ValueError, match="int8"):
        with comms.quantized("fp8", stochastic=True):
            pass


def test_bytes_accounting():
    assert comms.logical_bytes(1000, 4) == 4000
    # int8 payload + one fp32 scale per 256-block (4 blocks for 1000)
    assert comms.wire_bytes(1000, "int8", 256) == 1000 + 4 * 4
    assert comms.wire_bytes(1000, "int8", 256) * 3.5 < 4000
    with pytest.raises(ValueError):
        comms.wire_bytes(10, "int4")


# ---------------- context + collectives ----------------

def test_context_scoping_and_validation():
    assert comms.quant_state().dtype is None
    with comms.quantized("int8", block=128) as st:
        assert st.dtype == "int8" and st.block == 128
        with comms.quantized("int8", block=64):
            assert comms.quant_state().block == 64
        assert comms.quant_state().block == 128
    assert comms.quant_state().dtype is None
    with pytest.raises(ValueError, match="wire dtype"):
        with comms.quantized("int4"):
            pass


def test_quantized_all_reduce_requires_context():
    with pytest.raises(ValueError, match="quantized"):
        comms.quantized_all_reduce(jnp.ones((8,)))


def test_local_roundtrip_collective_and_record():
    x = jnp.asarray(np.random.RandomState(0).randn(1000).astype(np.float32))
    with comms.quantized("int8"):
        out = comms.quantized_all_reduce(x, owner="unit")
    assert np.max(np.abs(np.asarray(out) - np.asarray(x))) < 0.05
    info = comms.comm_info()
    site = info["sites"]["unit/all_reduce/local"]
    assert site["count"] == 1 and site["quantized"] == "int8"
    # nothing crossed a wire: the local leg records ZERO bytes both ways
    # (no fictitious savings) — the dp>=2 wired path is where bytes live
    # (test_dp2_grad_sync_int8_wire_ratio_* asserts its >=3.5x there)
    assert site["bytes_logical"] == 0 and site["bytes_wire"] == 0


def test_grad_sync_off_is_the_same_objects():
    """The bitwise-off contract: without the context, grad_sync returns
    the SAME list — nothing traced, nothing recorded."""
    gs = [jnp.ones((64,)), jnp.zeros((3, 3))]
    out = comms.grad_sync(gs)
    assert out is gs
    assert comms.comm_info()["collectives"] == 0


def test_grad_sync_on_without_mesh_unchanged():
    from paddle_tpu.parallel import mesh as mesh_mod
    prev = mesh_mod.get_mesh()
    mesh_mod.set_mesh(None)
    try:
        gs = [jnp.ones((64,))]
        with comms.quantized("int8"):
            out = comms.grad_sync(gs)
        assert out is gs  # no dp axis -> nothing to sync, bitwise
    finally:
        mesh_mod.set_mesh(prev)


def test_step_schedule_scoping():
    x = jnp.ones((256,), jnp.float32)
    with comms.quantized("int8"):
        with comms.step_schedule("step-A") as sched:
            comms.quantized_all_reduce(x, owner="a")
            comms.quantized_all_reduce(x, owner="b")
        comms.quantized_all_reduce(x, owner="global")
    assert [o.owner for o in sched.ops] == ["a", "b"]
    assert [o.seq for o in sched.ops] == [0, 1]
    assert all(o.quantized == "int8" for o in sched.ops)
    # the global schedule got only the out-of-scope op
    assert [o.owner for o in comms.current_schedule().ops] == ["global"]
    # the per-site aggregate saw all three
    assert comms.comm_info()["collectives"] == 3


def test_comm_summary_renders():
    from paddle_tpu import profiler
    assert "no recorded collectives" in profiler.comm_summary()
    with comms.quantized("int8"):
        comms.quantized_all_reduce(jnp.ones((512,), jnp.float32),
                                   owner="render")
    text = profiler.comm_summary()
    assert "render/all_reduce/local" in text
    assert "int8" in text and "Logical" in text and "Wire" in text


# ---------------- the capture-tier comm pass ----------------

def _mesh1():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]), ("dp",))


def test_comm_pass_tags_and_slots():
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.jit.passes import PassReport, run_pipeline
    from paddle_tpu.jit.passes import comm_schedule as cs
    mesh = _mesh1()
    eye = jnp.eye(8, dtype=jnp.float32)

    def body(v, w):
        a = jnp.tanh(v)
        g = jax.lax.psum(v, "dp")           # depends only on the arg
        c = (a @ eye) @ eye                  # compute chain
        h = jax.lax.pmax(w, "dp")           # issued late, hoistable
        return g + c + h

    f = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                      check_vma=False)
    closed = jax.make_jaxpr(f)(jnp.ones((8, 8), jnp.float32),
                               jnp.ones((8, 8), jnp.float32))
    out, rep = run_pipeline(closed, passes=("comm",), report=PassReport())
    assert "comm" in rep.passes_run
    assert rep.comm_tagged == 2
    assert rep.comm_hoisted >= 1          # pmax moves ahead of the matmuls
    assert rep.comm_slots >= 1
    # both collectives now sit before the compute chain
    inner = out.jaxpr.eqns[0].params["jaxpr"]
    names = [e.primitive.name for e in inner.eqns]
    assert names.index("pmax") < names.index("dot_general")
    # value semantics bitwise preserved
    import jax.core as jcore
    v = jnp.asarray(np.random.RandomState(0).randn(8, 8).astype(np.float32))
    w = jnp.asarray(np.random.RandomState(1).randn(8, 8).astype(np.float32))
    r0 = jcore.eval_jaxpr(closed.jaxpr, closed.consts, v, w)
    r1 = jcore.eval_jaxpr(out.jaxpr, out.consts, v, w)
    for x0, x1 in zip(jax.tree_util.tree_leaves(r0),
                      jax.tree_util.tree_leaves(r1)):
        np.testing.assert_array_equal(np.asarray(x0), np.asarray(x1))
    # the read-only analyzer sees the same program
    analysis = cs.analyze(closed)
    assert analysis["collectives"] == 2
    assert analysis["by_kind"] == {"pmax": 1, "psum": 1}
    assert analysis["overlap_slots"] >= 1


def test_comm_pass_registers_xla_sites():
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.jit.passes import PassReport, run_pipeline
    mesh = _mesh1()
    f = jax.shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                      in_specs=P(), out_specs=P(), check_vma=False)
    closed = jax.make_jaxpr(f)(jnp.ones((64,), jnp.float32))
    run_pipeline(closed, passes=("comm",), report=PassReport())
    sites = comms.comm_info()["sites"]
    assert "xla/psum/dp" in sites
    assert sites["xla/psum/dp"]["bytes_logical"] == 64 * 4


def test_recompile_guard_quantized_step_lowers_once():
    """The quantized context must not retrace the captured step per
    invocation: one lowering, CommOps recorded once (at capture), hits
    climbing — the context is a trace-time regime like amp."""
    from paddle_tpu.jit import capture_step

    def step(x):
        return comms.quantized_all_reduce(x, owner="guard") * 2.0

    wrapped = capture_step(step)
    x = jnp.asarray(np.random.RandomState(0).randn(512).astype(np.float32))
    with comms.quantized("int8"):
        outs = [np.asarray(wrapped(x)) for _ in range(5)]
    info = wrapped.cache_info()
    assert info["lowerings"] == 1, info
    assert info["hits"] == 4, info
    assert info["bailouts"] == 0, info
    # registry: ONE record from the capture trace, not five
    assert comms.comm_info()["sites"]["guard/all_reduce/local"]["count"] == 1
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])


def test_overflow_still_detected_under_quantized_sync():
    """Review regression: the wire format's inf/nan guard (nan->0, inf
    saturates) must not mask an overflowed step from the trainer's
    grad-finite skip — the finite flag judges the RAW gradients, the
    quantized sync rides the sanitized ones.  A nan batch inside the
    context must still skip the update (params bit-exact) and back the
    loss scale off."""
    from paddle_tpu.parallel.trainer import compile_train_step
    import paddle_tpu as P

    P.seed(0)
    model = P.nn.Sequential(P.nn.Linear(8, 8), P.nn.Linear(8, 2))
    opt = P.optimizer.SGD(learning_rate=0.1,
                          parameters=model.parameters())
    scaler = P.amp.GradScaler(init_loss_scaling=1024.0)
    rng = np.random.RandomState(0)
    good = (P.to_tensor(rng.randn(8, 8).astype(np.float32)),
            P.to_tensor(rng.randn(8, 2).astype(np.float32)))
    bad_x = rng.randn(8, 8).astype(np.float32)
    bad_x[0, 0] = np.nan
    bad = (P.to_tensor(bad_x), good[1])

    def loss_fn(m, b):
        return ((m(b[0]) - b[1]) ** 2).mean()

    # single-device mesh-less build: grad_sync no-ops on the wire but the
    # ordering contract (finite BEFORE sync) is what this test pins — the
    # dp2 wired variant is test_dp2_grad_sync_int8_wire_ratio_* below
    with comms.quantized("int8"):
        step = compile_train_step(model, loss_fn, opt, scaler=scaler)
        step(good)
        before = [np.asarray(p._value).copy() for p in model.parameters()]
        scale0 = step.loss_scale
        step(bad)
        after = [np.asarray(p._value) for p in model.parameters()]
    assert step.skipped_steps == 1
    assert step.loss_scale < scale0
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b, a)


def test_regime_is_a_capture_key_not_a_retrace():
    """Toggling the context across calls of one captured step gives one
    lowering PER REGIME (amp-style cache key), never a per-invocation
    retrace — and never serves the wrong regime's executable."""
    from paddle_tpu.jit import capture_step

    def step(x):
        if comms.quant_state().dtype is not None:
            return comms.quantized_all_reduce(x, owner="regime") + 1.0
        return x + 1.0

    wrapped = capture_step(step)
    x = jnp.asarray(np.random.RandomState(0).randn(300).astype(np.float32))
    exact = [np.asarray(wrapped(x)) for _ in range(2)]
    with comms.quantized("int8"):
        quant = [np.asarray(wrapped(x)) for _ in range(2)]
    exact2 = np.asarray(wrapped(x))
    info = wrapped.cache_info()
    assert info["lowerings"] == 2, info      # one per regime
    assert info["hits"] == 3, info           # repeats served from cache
    np.testing.assert_array_equal(exact[0], exact2)
    assert not np.array_equal(exact[0], quant[0])  # regimes really differ


# ---------------- the wired path: a dp2 Llama train step ----------------

def _dp2_llama_run(steps, quant):
    """A fresh identically-seeded tiny Llama and its TrainStep on a dp2
    mesh: the loss curve and the captured program's pass report."""
    import paddle_tpu as P
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.parallel import mesh as mesh_mod
    from paddle_tpu.parallel.trainer import compile_train_step

    mesh = mesh_mod.init_mesh({"dp": 2}, devices=jax.devices()[:2])
    try:
        P.seed(0)
        cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                               inter=128, seq=32)
        model = LlamaForCausalLM(cfg)
        opt = P.optimizer.SGD(learning_rate=0.05,
                              parameters=model.parameters())
        step = compile_train_step(
            model, lambda m, b: m.compute_loss(b["input_ids"], b["labels"]),
            opt, mesh=mesh)
        ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (8, 33))
        batch = {"input_ids": P.to_tensor(ids[:, :-1]),
                 "labels": P.to_tensor(ids[:, 1:])}

        def drive():
            return [float(step(batch).numpy()) for _ in range(steps)]

        if quant:
            with comms.quantized("int8"):
                losses = drive()
        else:
            losses = drive()
        prog = step.captured_program
        return losses, None if prog is None else prog.pass_report
    finally:
        mesh_mod.set_mesh(None)


def test_dp2_grad_sync_int8_wire_ratio_bitwise_off_and_loss_parity():
    """The acceptance of the quantized gradient sync, on the program's own
    accounting of its wire format (int8 payload + per-block f32 scales,
    padding counted; no timing): >= 3.5x fewer bytes than the f32 sync it
    replaces.  Context off, two runs are bitwise one curve (the hook adds
    nothing); context on, the curve stays finite and close."""
    off_a, _ = _dp2_llama_run(4, quant=False)
    off_b, _ = _dp2_llama_run(4, quant=False)
    assert off_a == off_b
    assert "trainer.grad_sync/all_reduce/dp" not in comms.comm_info()["sites"]
    on, report = _dp2_llama_run(4, quant=True)
    site = comms.comm_info()["sites"]["trainer.grad_sync/all_reduce/dp"]
    assert site["quantized"] == "int8"
    assert site["bytes_logical"] > site["bytes_wire"] > 0
    assert site["bytes_logical"] / site["bytes_wire"] >= 3.5, site
    # the captured step's comm pass saw the quantized wire legs
    assert report is None or report.comm_tagged >= 2, report.as_dict()
    assert np.isfinite(on[-1])
    assert abs(on[-1] - off_a[-1]) / abs(off_a[-1]) <= 0.05, (on, off_a)


def test_public_all_reduce_routes_through_the_quantized_two_shot():
    """The routed PUBLIC global-view collective works inside the context:
    replicated over dp2, a psum of ones reads ~2 everywhere."""
    import paddle_tpu as P
    import paddle_tpu.distributed as dist
    from paddle_tpu.parallel import mesh as mesh_mod

    mesh_mod.init_mesh({"dp": 2}, devices=jax.devices()[:2])
    try:
        with comms.quantized("int8"):
            t = P.to_tensor(np.ones(600, np.float32))
            dist.all_reduce(t)
        np.testing.assert_allclose(np.asarray(t._value), 2.0, atol=0.05)
    finally:
        mesh_mod.set_mesh(None)
