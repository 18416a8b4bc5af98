"""Whole-step capture tier (jit/capture.py + jit/passes/) guard tests.

The contract under test (README "Whole-step capture"):
- a repeated same-signature step lowers EXACTLY once (counters prove it);
  a new aval signature lowers exactly once more;
- every bailout condition (host sync in the step, global-RNG draw,
  unhashable statics) silently falls back to the eager tier, where the
  per-op compiled cache serves individual ops — same values, no error;
- the pass pipeline is value-preserving and actually fires (fusion
  inlines jitted call regions, CSE folds duplicates, DVE drops dead
  values, donation inference marks update-in-place params);
- TrainStep routed through capture is bit-identical to the plain-jit
  path, INCLUDING the in-jit grad-skip/loss-scale semantics;
- the decode-offset threading (models/llama.py) keeps per-token decode
  ops on ONE per-op cache entry across token positions.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
import paddle_tpu.nn.functional as F
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit import capture, capture_step
from paddle_tpu.ops import dispatch


@pytest.fixture(autouse=True)
def _fresh():
    dispatch.cache_clear()
    capture.capture_clear()
    capture.set_step_capture_enabled(True)
    yield
    dispatch.cache_clear()
    capture.capture_clear()
    capture.set_step_capture_enabled(True)


def _mk(shape, sg=True):
    return P.to_tensor(np.random.randn(*shape).astype(np.float32),
                       stop_gradient=sg)


# ---------------------------------------------------------------------------
# recompile-count guards
# ---------------------------------------------------------------------------

def test_exactly_one_lowering_per_signature():
    @capture_step
    def step(x):
        return P.tanh(x) * 2.0

    x = _mk((4, 8))
    outs = [step(x) for _ in range(6)]
    info = step.cache_info()
    assert info["lowerings"] == 1, info
    assert info["hits"] == 5, info
    assert info["bailouts"] == 0 and info["fallback_calls"] == 0, info
    ref = np.tanh(x.numpy()) * 2.0
    for o in outs:
        np.testing.assert_allclose(o.numpy(), ref, rtol=1e-6)


def test_new_aval_signature_lowers_once_more():
    @capture_step
    def step(x):
        return P.exp(x)

    a, b = _mk((4, 4)), _mk((2, 4))        # distinct shapes
    c = P.to_tensor(np.random.randn(4, 4))  # distinct dtype (f64 input)
    for t in (a, a, b, b, c, c):
        step(t)
    info = step.cache_info()
    assert info["lowerings"] == 3, info
    assert info["hits"] == 3, info


@pytest.mark.parametrize("donate", ["default", "auto"])
def test_full_train_step_capture_parity_with_eager(donate):
    """fwd + tape backward + SGD update, captured vs pure eager (whose ops
    ride the per-op cache).  With `donate="auto"` the parameters are
    inferred donatable: each comes back as a same-shaped output."""
    P.seed(11)
    lin1 = P.nn.Linear(8, 64)      # 2 KiB of weight: over donation's floor
    lin2 = P.nn.Linear(64, 2)
    params = list(lin1.parameters()) + list(lin2.parameters())

    def step(param_vals, x, y):
        saved = [p._value for p in params]
        try:
            for p, v in zip(params, param_vals):
                p._value = v._value if isinstance(v, Tensor) else v
            loss = F.mse_loss(lin2(F.relu(lin1(x))), y)
            loss.backward()
            with P.no_grad():
                new = [p - 0.1 * p.grad for p in params]
            return loss, new
        finally:
            for p, v in zip(params, saved):
                p._value = v
                p.grad = None

    cap = capture_step(step, donate=donate)
    x, y = _mk((8, 8)), _mk((8, 2))
    base = [np.asarray(p._value) for p in params]

    def run(fn, n=3):
        vals = [jnp.asarray(a) for a in base]
        for _ in range(n):
            loss, new = fn(vals, x, y)
            vals = [t._value for t in new]
        return float(loss.numpy()), [np.asarray(v) for v in vals]

    l_eager, p_eager = run(step)
    assert dispatch.cache_info()["hits"] > 0      # the eager leg's ops
    l_cap, p_cap = run(cap)
    info = cap.cache_info()
    assert info["lowerings"] == 1 and info["hits"] == 2, info
    assert info["bailouts"] == 0 and info["fallback_calls"] == 0, info
    prog = cap.programs()[0]
    assert prog.pass_report is not None
    assert bool(prog.donate) == (donate == "auto"), prog.donate
    assert abs(l_eager - l_cap) < 1e-5
    for a, b in zip(p_eager, p_cap):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# bailouts -> per-op-cache fallback tier
# ---------------------------------------------------------------------------

def test_host_sync_bails_out_and_per_op_cache_serves():
    @capture_step
    def step(x):
        s = x.sum()
        if float(s.numpy()) > -1e30:   # host sync: uncapturable
            return P.tanh(x)
        return x

    x = _mk((4, 4))
    outs = [step(x) for _ in range(4)]
    info = step.cache_info()
    assert info["bailouts"] == 1, info          # capture abandoned once...
    assert info["fallback_calls"] == 4, info    # ...every call ran eager
    assert info["lowerings"] == 0 and info["hits"] == 0, info
    for o in outs:
        np.testing.assert_allclose(o.numpy(), np.tanh(x.numpy()), rtol=1e-6)
    # the fallback tier is the PR-3 per-op cache, and it compiled tanh
    s = dispatch.cache_info()["per_op"]["tanh"]
    assert s["hits"] >= 1 and s["retraces"] == 1, s
    assert capture.capture_info()["last_bailout"], capture.capture_info()


def test_global_rng_draw_bails_out():
    @capture_step
    def step(x):
        return x + P.rand([4, 4])   # global-RNG draw would be baked

    x = _mk((4, 4))
    a, b = step(x), step(x)
    assert step.cache_info()["bailouts"] == 1
    # eager fallback keeps drawing fresh randomness (no baked keys)
    assert not np.allclose(a.numpy(), b.numpy())


def test_to_static_call_inside_captured_step_bails_via_rng_guard():
    """A to_static function draws a per-call host RNG key; replaying a
    captured step would bake it (wrong for random inner fns), so the RNG
    guard conservatively bails and the eager tier serves — value-correct
    either way."""
    lin = P.nn.Linear(4, 4)

    @P.jit.to_static
    def inner(x):
        return F.relu(lin(x))

    @capture_step
    def step(x):
        return inner(x) + 1.0

    x = _mk((2, 4))
    o1, o2 = step(x), step(x)
    np.testing.assert_allclose(o2.numpy(), o1.numpy())
    ref = np.maximum(x.numpy() @ np.asarray(lin.weight._value)
                     + np.asarray(lin.bias._value), 0) + 1.0
    np.testing.assert_allclose(o1.numpy(), ref, rtol=1e-5, atol=1e-6)
    info = step.cache_info()
    assert info["bailouts"] == 1 and info["fallback_calls"] == 2, info


def test_failing_step_raises_same_error_as_eager():
    @capture_step
    def step(x):
        return x @ x   # invalid for non-square inputs

    bad = _mk((2, 4))
    with pytest.raises(TypeError):
        step(bad)


def test_static_mode_and_nested_trace_stay_transparent():
    @capture_step
    def step(x):
        return P.tanh(x)

    P.enable_static()
    try:
        v = P.static.data("capx", [2, 3], "float32")
        out = step(v)
        assert isinstance(out, P.static.Variable)
    finally:
        P.disable_static()
    assert step.cache_info()["lowerings"] == 0

    # under an enclosing jax trace the wrapper inlines (no keying on tracers)
    def traced(a):
        return step(Tensor(a))._value

    x = np.random.randn(2, 3).astype(np.float32)
    out = jax.jit(traced)(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), np.tanh(x), rtol=1e-6)
    assert step.cache_info()["lowerings"] == 0


# ---------------------------------------------------------------------------
# pass pipeline
# ---------------------------------------------------------------------------

def test_passes_fuse_cse_dve_and_preserve_values():
    helper = jax.jit(lambda v: jnp.tanh(v) + 1.0)

    @capture_step
    def step(x):
        a = P.sin(x) * 2.0
        b = P.sin(x) * 2.0                    # CSE target
        dead = P.cos(x) @ P.cos(x)            # DVE target  # noqa: F841
        return dispatch.apply(helper, a + b, op_name="helper")  # fusion

    x = _mk((4, 4))
    out = step(x)
    np.testing.assert_allclose(
        out.numpy(), np.tanh(np.sin(x.numpy()) * 4.0) + 1.0,
        rtol=1e-5, atol=1e-6)
    prog = step.programs()[0]
    rep = prog.pass_report
    assert rep.inlined_calls >= 1, rep.as_dict()
    assert rep.cse_folded >= 1, rep.as_dict()
    assert rep.dve_removed >= 2, rep.as_dict()   # dead matmul + dead cos
    assert rep.eqns_after < rep.eqns_before
    # op-level record reuses the static-world Program representation
    assert prog.op_counts().get("helper") == 1
    assert prog.as_program().ops, prog.describe()


def test_donation_inference_flat_positions():
    from paddle_tpu.jit.passes.donation import infer_donation
    SA = jax.core.ShapedArray
    ins = [SA((64, 64), jnp.float32), SA((32,), jnp.float32),
           SA((), jnp.float32), SA((64, 64), jnp.float32)]
    outs = [SA((), jnp.float32), SA((64, 64), jnp.float32),
            SA((64, 64), jnp.float32)]
    # both big inputs alias the two matching outputs; the scalar and the
    # small vector are never donated
    assert infer_donation(ins, outs) == (0, 3)
    assert infer_donation(ins, outs, reserved=(0,)) == (3,)
    assert infer_donation(ins, outs[:2]) == (0,)   # multiset budget


def test_donate_auto_aliases_param_buffers():
    @capture_step(donate="auto")
    def upd(w, g):
        return w - 0.1 * g

    w = _mk((64, 64))
    g = _mk((64, 64))
    w2 = upd(w, g)
    assert upd.programs()[0].donate == (0,)   # w aliased, g kept
    w3 = upd(w2, g)                           # threading works post-donation
    assert w3.shape == [64, 64]
    with pytest.raises(RuntimeError):
        np.asarray(w._value)                  # the donated buffer is gone


def test_captured_ops_counted_not_bypassed():
    @capture_step
    def step(x):
        return P.tanh(P.exp(x))

    x = _mk((3, 3))
    step(x)
    info = dispatch.cache_info()
    assert info["captured"] >= 2, info       # tanh + exp absorbed by capture
    per = info["per_op"]["tanh"]
    assert per["captured"] >= 1 and per["bypasses"] == 0, per


# ---------------------------------------------------------------------------
# TrainStep integration (grad-skip / loss-scale semantics preserved)
# ---------------------------------------------------------------------------

def _train_run(steps=4, inject_inf_at=2):
    from paddle_tpu.parallel.trainer import compile_train_step
    P.seed(5)
    np.random.seed(5)
    m = P.nn.Sequential(P.nn.Linear(8, 16), P.nn.ReLU(), P.nn.Linear(16, 2))
    opt = P.optimizer.AdamW(learning_rate=1e-2, parameters=m.parameters())
    scaler = P.amp.GradScaler(init_loss_scaling=2.0 ** 8)

    def loss_fn(model, batch):
        x, y = batch
        return F.mse_loss(model(x), y)

    step = compile_train_step(m, loss_fn, opt, scaler=scaler)
    rng = np.random.RandomState(3)
    losses = []
    for i in range(steps):
        x = rng.randn(4, 8).astype(np.float32)
        y = rng.randn(4, 2).astype(np.float32)
        if i == inject_inf_at:
            x = x.copy()
            x[0, 0] = np.inf
        losses.append(float(step((P.to_tensor(x), P.to_tensor(y))).numpy()))
    return (losses, step.skipped_steps, step.loss_scale,
            [p.numpy().copy() for p in step._params], step)


def test_trainstep_captured_matches_plain_jit_incl_grad_skip():
    l1, sk1, sc1, p1, step = _train_run()
    assert step.captured_program is not None     # capture tier engaged
    assert step.captured_program.pass_report.inlined_calls >= 1
    capture.set_step_capture_enabled(False)
    l0, sk0, sc0, p0, step0 = _train_run()
    assert step0.captured_program is None        # plain jax.jit path
    assert sk1 == sk0 == 1                       # the inf step was skipped
    assert sc1 == sc0                            # same dynamic loss scale
    for a, b in zip(l1, l0):
        assert (np.isnan(a) and np.isnan(b)) or abs(a - b) < 1e-5
    for a, b in zip(p1, p0):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# decode-offset threading (models/llama.py) + decode-step capture
# ---------------------------------------------------------------------------

def _tiny_llama():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    P.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny(vocab=32, hidden=16, layers=1,
                                             heads=2, seq=32))


def test_decode_ops_share_one_cache_entry_across_offsets():
    """The rope/kv/mask ops take the offset as a traced i32 arg now, so
    eager decode at different token positions hits ONE per-op entry."""
    model = _tiny_llama()
    model.eval()
    caches = model.init_kv_caches(1, 8)
    ids = P.to_tensor(np.array([[3]], np.int64))
    with P.no_grad():
        for off in (0, 1, 2, 3):
            model.forward(ids, caches=caches,
                          position_offset=jnp.asarray(off, jnp.int32))
    s = dispatch.cache_info()["per_op"]["rope"]
    assert s["misses"] == 1, s      # one aval signature for every offset
    assert s["hits"] >= 1, s
    assert s["bypasses"] == 0, s    # no closure-capture bypasses left
    s = dispatch.cache_info()["per_op"]["kv_cache_upd"]
    assert s["misses"] == 1 and s["bypasses"] == 0, s


def test_generate_uses_capture_tier_and_matches_no_cache_oracle():
    model = _tiny_llama()
    model.eval()
    ids = P.to_tensor(np.array([[1, 5, 2]], np.int64))
    out_cached = model.generate(ids, max_new_tokens=4, use_cache=True)
    out_oracle = model.generate(ids, max_new_tokens=4, use_cache=False)
    np.testing.assert_array_equal(out_cached.numpy(), out_oracle.numpy())
    info = capture.capture_info()
    # prefill + decode signatures, decode executable re-served per token
    assert info["lowerings"] == 2, info
    assert info["hits"] >= 2, info
    # the step wrapper persists on the model: a second generate() with the
    # same shapes re-serves both executables instead of re-lowering
    out2 = model.generate(ids, max_new_tokens=4, use_cache=True)
    np.testing.assert_array_equal(out2.numpy(), out_cached.numpy())
    assert capture.capture_info()["lowerings"] == 2, capture.capture_info()


def test_trainstep_handles_changed_batch_shape():
    """drop_last=False epochs end with a smaller batch: the capture tier
    must route the new signature to the plain-jit fallback, not crash."""
    from paddle_tpu.parallel.trainer import compile_train_step
    P.seed(2)
    m = P.nn.Sequential(P.nn.Linear(8, 16), P.nn.ReLU(), P.nn.Linear(16, 4))
    opt = P.optimizer.AdamW(learning_rate=1e-2, parameters=m.parameters())
    step = compile_train_step(
        m, lambda mm, b: F.mse_loss(mm(b[0]), b[1]), opt)
    full = (_mk((4, 8)), _mk((4, 4)))
    part = (_mk((2, 8)), _mk((2, 4)))
    l1 = float(step(full).numpy())
    l2 = float(step(part).numpy())   # smaller final batch
    l3 = float(step(full).numpy())   # captured executable still serves
    assert all(np.isfinite(v) for v in (l1, l2, l3))
    assert step.captured_program is not None


def test_to_static_routes_through_pass_pipeline():
    lin = P.nn.Linear(4, 4)

    @P.jit.to_static
    def fn(x):
        return F.relu(lin(x)) + F.relu(lin(x))

    x = _mk((2, 4))
    out = fn(x)
    ref = 2 * np.maximum(
        x.numpy() @ np.asarray(lin.weight._value)
        + np.asarray(lin.bias._value), 0.0)
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-5, atol=1e-6)
    assert capture.capture_info()["lowerings"] == 1


def test_profiler_step_capture_summary():
    @capture_step
    def step(x):
        return P.tanh(x)

    x = _mk((2, 2))
    step(x)
    step(x)
    from paddle_tpu.profiler import step_capture_summary
    txt = step_capture_summary()
    assert "lowerings=1" in txt and "hits=1" in txt, txt
