"""Recompile-count guard tests for the compiled-op dispatch cache.

The contract under test (ops/_op_cache.py, README "Eager dispatch"):
- a repeated same-shape/dtype eager op compiles EXACTLY once, on both the
  no-grad and the vjp path (retrace counters prove it — the wrapper body
  only executes while jax traces);
- distinct shapes / dtypes / amp regimes get distinct entries;
- the LRU bound evicts; the disable switch restores the uncached path;
- results (fwd + grads) match the uncached path bitwise-comparable ranges
  for a multi-output namedtuple op (eigh);
- Tracer inputs, static mode, and array-bearing closures bypass.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.ops import dispatch


@pytest.fixture(autouse=True)
def _fresh_cache():
    dispatch.cache_clear()
    dispatch.set_op_cache_enabled(True)
    dispatch.set_op_cache_maxsize(512)
    dispatch.set_op_cache_compile_after(2)
    yield
    dispatch.cache_clear()
    dispatch.set_op_cache_enabled(True)
    dispatch.set_op_cache_maxsize(512)
    dispatch.set_op_cache_compile_after(2)


def _op_stats(name):
    return dispatch.cache_info()["per_op"].get(name, {})


def test_same_shape_nograd_compiles_exactly_once():
    x = P.to_tensor(np.random.randn(8, 16).astype(np.float32))
    outs = [P.nn.functional.softmax(x, axis=-1) for _ in range(8)]
    s = _op_stats("softmax")
    assert s["misses"] == 1, s        # first call ran eager, installed entry
    assert s["hits"] == 7, s          # every repeat served compiled
    assert s["retraces"] == 1, s      # ...from exactly ONE trace/compile
    ref = jax.nn.softmax(x._value, axis=-1)
    for o in outs:
        np.testing.assert_allclose(o.numpy(), np.asarray(ref), rtol=1e-6)


def test_vjp_path_compiles_exactly_once_fwd_and_bwd():
    x = P.to_tensor(np.random.randn(4, 8).astype(np.float32))
    w = P.to_tensor(np.random.randn(8, 4).astype(np.float32),
                    stop_gradient=False)
    grads = []
    for _ in range(5):
        (P.matmul(x, w)).sum().backward()
        grads.append(w.grad.numpy().copy())
        w.clear_grad()
    s = _op_stats("matmul")
    assert s["misses"] == 1, s
    assert s["hits"] == 4, s
    assert s["retraces"] == 1, s       # vjp-build wrapper traced once
    assert s["bwd_retraces"] == 1, s   # pullback wrapper traced once
    for g in grads[1:]:
        np.testing.assert_array_equal(g, grads[0])


def _softmax_fwd(x, w, b, tgt):
    return P.nn.functional.softmax(x, axis=-1)


def _gelu_fwd(x, w, b, tgt):
    return P.nn.functional.gelu(x, approximate=True)


def _linear_train(x, w, b, tgt):
    loss = P.nn.functional.mse_loss(P.nn.functional.linear(x, w, b), tgt)
    loss.backward()
    w.clear_grad()
    b.clear_grad()
    return loss


@pytest.mark.parametrize("workload", [_softmax_fwd, _gelu_fwd, _linear_train],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_repeated_eager_workload_is_served_from_the_cache(workload):
    """A same-shape eager loop (a forward op; a linear layer's forward,
    loss and backward) traces each of its ops once and serves every repeat
    compiled, with the uncached path's values."""
    rng = np.random.RandomState(0)
    x = P.to_tensor(rng.randn(64, 256).astype(np.float32))
    w = P.to_tensor(rng.randn(256, 64).astype(np.float32),
                    stop_gradient=False)
    b = P.to_tensor(np.zeros(64, np.float32), stop_gradient=False)
    tgt = P.to_tensor(rng.randn(64, 64).astype(np.float32))
    outs = [workload(x, w, b, tgt).numpy() for _ in range(6)]
    info = dispatch.cache_info()
    assert info["per_op"] and info["hits"] >= 5, info
    for name, per in info["per_op"].items():
        assert per["misses"] == 1 and per["retraces"] == 1, (name, per)
        assert per.get("bwd_retraces", 0) <= 1, (name, per)
    dispatch.set_op_cache_enabled(False)
    # a fused program rounds unlike op-by-op: close, not bitwise
    np.testing.assert_allclose(outs[-1], workload(x, w, b, tgt).numpy(),
                               rtol=1e-5, atol=1e-6)


def test_distinct_shapes_dtypes_amp_get_distinct_entries():
    base = dispatch.cache_info()["size"]
    a = P.to_tensor(np.random.randn(4, 4).astype(np.float32))
    b = P.to_tensor(np.random.randn(2, 4).astype(np.float32))   # new shape
    c = P.to_tensor(np.random.randn(4, 4).astype(np.float64))   # new dtype
    for t in (a, a, b, b, c, c):
        P.tanh(t)
    assert dispatch.cache_info()["size"] == base + 3
    with P.amp.auto_cast(custom_white_list=["tanh"]):            # amp regime
        P.tanh(a)
        P.tanh(a)
    assert dispatch.cache_info()["size"] == base + 4
    s = _op_stats("tanh")
    assert s["misses"] == 4 and s["hits"] == 4, s


def test_static_kwargs_key_by_value():
    x = P.to_tensor(np.random.randn(4, 6).astype(np.float32))
    for axis in (0, 1, 0, 1):
        P.nn.functional.softmax(x, axis=axis)
    s = _op_stats("softmax")
    assert s["misses"] == 2 and s["hits"] == 2, s


def test_lru_eviction_bounds_cache():
    dispatch.set_op_cache_maxsize(3)
    for n in (3, 4, 5, 6, 7):
        t = P.to_tensor(np.random.randn(n).astype(np.float32))
        P.tanh(t)
        P.tanh(t)
    info = dispatch.cache_info()
    assert info["size"] <= 3
    assert info["evictions"] >= 2


def test_disabled_flag_restores_uncached_path():
    dispatch.set_op_cache_enabled(False)
    x = P.to_tensor(np.random.randn(4, 4).astype(np.float32),
                    stop_gradient=False)
    for _ in range(3):
        P.tanh(x).sum().backward()
        x.clear_grad()
    info = dispatch.cache_info()
    assert info["enabled"] is False
    assert info["size"] == 0 and info["hits"] == 0 and info["misses"] == 0


def test_multi_output_namedtuple_fwd_and_grads_match_uncached():
    a = np.random.randn(5, 5)
    sym = (a + a.T).astype(np.float32)

    def run():
        x = P.to_tensor(sym, stop_gradient=False)
        w, v = P.linalg.eigh(x)
        (w.sum() + (v * v).sum()).backward()
        return w.numpy().copy(), v.numpy().copy(), x.grad.numpy().copy()

    run()                      # miss: eager
    w1, v1, g1 = run()         # hit: compiled vjp pair
    s = _op_stats("eigh")
    assert s["misses"] == 1 and s["hits"] == 1 and s["retraces"] == 1, s
    dispatch.set_op_cache_enabled(False)
    w0, v0, g0 = run()         # reference: plain jax.vjp path
    np.testing.assert_allclose(w1, w0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(v1, v0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(g1, g0, rtol=1e-5, atol=1e-5)


def test_tracer_inputs_bypass():
    x = np.random.randn(4, 4).astype(np.float32)

    def traced(a):
        return P.nn.functional.softmax(Tensor(a), axis=-1)._value

    out = jax.jit(traced)(jnp.asarray(x))
    info = dispatch.cache_info()
    assert info["size"] == 0, info       # nothing keyed on tracers
    assert _op_stats("softmax").get("bypasses", 0) >= 1
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jax.nn.softmax(x, axis=-1)),
                               rtol=1e-6)


def test_static_mode_bypasses():
    P.enable_static()
    try:
        x = P.static.data("cachex", [2, 3], "float32")
        y = P.tanh(x)
        assert dispatch.cache_info()["size"] == 0
    finally:
        P.disable_static()


def test_array_closure_bypasses():
    payload = jnp.ones((3,))
    x = P.to_tensor(np.random.randn(3).astype(np.float32))
    for _ in range(3):
        out = dispatch.apply(lambda v: v + payload, x, op_name="closure_op")
    s = _op_stats("closure_op")
    assert s.get("bypasses", 0) == 3 and s.get("hits", 0) == 0, s
    np.testing.assert_allclose(out.numpy(), x.numpy() + 1.0, rtol=1e-6)


def test_nonarray_output_poisons_entry():
    x = P.to_tensor(np.random.randn(3).astype(np.float32))
    for _ in range(3):
        out = dispatch.apply(lambda v: (v * 2, "tag"), x, op_name="mixed_out")
    assert isinstance(out, tuple) and out[1] == "tag"
    s = _op_stats("mixed_out")
    assert s["hits"] == 0, s  # jit would coerce "tag" — must stay eager


def test_eager_only_op_poisons_and_falls_back():
    # data-dependent output shape: traces fine never — first hit must poison
    x = P.to_tensor(np.array([1.0, 0.0, 2.0, 0.0], np.float32))
    m = P.to_tensor(np.array([True, False, True, False]))
    outs = [P.masked_select(x, m) for _ in range(3)]
    for o in outs:
        np.testing.assert_allclose(o.numpy(), [1.0, 2.0])


def test_nan_check_fires_on_cached_outputs():
    from paddle_tpu.utils import flags
    x = P.to_tensor(np.array([0.0, 1.0], np.float32))
    P.log(x)   # miss (eager) — -inf but flag off
    flags.set_flags({"FLAGS_check_nan_inf": True})
    try:
        with pytest.raises(FloatingPointError):
            P.log(x)  # served by the compiled executable — scan still runs
    finally:
        flags.set_flags({"FLAGS_check_nan_inf": False})


def test_cache_info_and_profiler_summary_shape():
    x = P.to_tensor(np.random.randn(2, 2).astype(np.float32))
    P.tanh(x)
    P.tanh(x)
    info = dispatch.cache_info()
    assert {"enabled", "size", "maxsize", "hits", "misses", "per_op"} <= \
        set(info)
    assert info["per_op"]["tanh"]["retraces"] == 1
    from paddle_tpu.profiler import op_cache_summary
    txt = op_cache_summary()
    assert "tanh" in txt and "Retrace" in txt


def test_compile_after_threshold_defers_compiles():
    dispatch.set_op_cache_compile_after(4)
    x = P.to_tensor(np.random.randn(3, 3).astype(np.float32))
    for _ in range(6):
        P.tanh(x)
    s = _op_stats("tanh")
    assert s["misses"] == 1 and s["deferred"] == 2, s   # calls 2 and 3
    assert s["hits"] == 3 and s["retraces"] == 1, s     # calls 4..6
