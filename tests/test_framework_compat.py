"""Top-level framework-compat surface (the last python/paddle/__init__.py
__all__ gaps): dtype info, RNG state, ParamAttr, LazyGuard, flops, places."""
import numpy as np

import paddle_tpu as P
import paddle_tpu.nn as nn


def test_reference_top_level_all_covered(reference_tree):
    """Line-by-line parity with the reference's public top-level namespace."""
    import ast
    src = open(reference_tree + "/__init__.py").read()
    names = []
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if getattr(t, "id", "") == "__all__":
                    names = [ast.literal_eval(e) for e in node.value.elts]
    missing = [n for n in names if not hasattr(P, n)]
    assert not missing, f"top-level API gaps: {missing}"


def test_iinfo_finfo():
    assert P.iinfo("int32").max == 2**31 - 1
    assert P.iinfo(P.int64).min == -(2**63)
    assert abs(P.finfo("float32").eps - np.finfo(np.float32).eps) < 1e-12
    assert P.finfo("bfloat16").bits == 16


def test_dtype_and_bool():
    assert P.dtype("float32") == np.float32
    t = P.to_tensor([True, False])
    assert t.dtype == P.bool


def test_rng_state_roundtrip():
    P.seed(7)
    st = P.get_rng_state()
    a = P.rand([4]).numpy()
    P.set_rng_state(st)
    b = P.rand([4]).numpy()
    np.testing.assert_allclose(a, b)
    st2 = P.get_cuda_rng_state()  # same logical state space
    P.set_cuda_rng_state(st2)


def test_param_attr_name_trainable_initializer():
    attr = P.ParamAttr(name="my_w", trainable=False,
                       initializer=nn.initializer.Constant(3.0))

    class M(nn.Layer):
        def __init__(self):
            super().__init__()
            self.w = self.create_parameter([2, 2], attr=attr)

    m = M()
    assert m.w.name == "my_w"
    assert m.w.stop_gradient
    np.testing.assert_allclose(m.w.numpy(), np.full((2, 2), 3.0))


def test_lazy_guard_defers_init():
    with P.LazyGuard():
        lin = nn.Linear(16, 16)
    assert float(np.abs(lin.weight.numpy()).sum()) == 0.0
    lin.lazy_init()
    assert float(np.abs(lin.weight.numpy()).sum()) > 0.0


def test_flops_counts_matmul():
    lin = nn.Linear(32, 64, bias_attr=False)
    got = P.flops(lin, (8, 32))
    assert got == 2 * 8 * 32 * 64  # one (8,32)x(32,64) matmul


def test_batch_reader():
    r = P.batch(lambda: iter(range(10)), 4)
    sizes = [len(b) for b in r()]
    assert sizes == [4, 4, 2]
    r2 = P.batch(lambda: iter(range(10)), 4, drop_last=True)
    assert [len(b) for b in r2()] == [4, 4]


def test_places_and_misc():
    assert P.CUDAPlace(0) == P.CUDAPlace(0)
    assert P.CPUPlace() != P.CUDAPlace(1)
    P.set_printoptions(precision=6)
    P.disable_signal_handler()
    P.check_shape([2, -1, 3])
    try:
        P.check_shape("bad")
        raise AssertionError("check_shape accepted a string")
    except TypeError:
        pass


def test_set_grad_enabled():
    x = P.to_tensor([2.0])
    x.stop_gradient = False
    with P.set_grad_enabled(False):
        y = x * 3
    assert y.stop_gradient
    with P.set_grad_enabled(True):
        z = x * 3
    assert not z.stop_gradient


# ---- paddle.device surface (device/__init__.py + device/cuda, L0 runtime) ----

def test_device_memory_stats_api():
    import paddle_tpu.device as D
    s = D.memory_stats()
    assert isinstance(s, dict)  # real counters on TPU; {} on plain CPU
    assert D.memory_allocated() >= 0
    assert D.max_memory_allocated() >= D.memory_allocated() or \
        D.max_memory_allocated() == 0
    D.synchronize()
    D.empty_cache()
    assert "cpu" in D.get_all_device_type()
    assert D.get_available_device()
    props = D.cuda.get_device_properties()
    assert hasattr(props, "total_memory")


def test_device_of_absent_platform_raises():
    """Naming a platform this process does not have is an error, as in
    set_device — never the first device of another platform."""
    import pytest

    import paddle_tpu.device as D
    for name in ("tpu", "tpu:0"):   # the test platform is CPU-only
        with pytest.raises(RuntimeError, match="no devices found for "
                                               "platform 'tpu'"):
            D.synchronize(name)
    assert D.memory_stats("cpu:0") == D.memory_stats("cpu")


def test_device_stream_event_api():
    import paddle_tpu.device as D
    s1, s2 = D.Stream(), D.Stream(priority=1)
    ev = s1.record_event()
    assert ev.query()
    s2.wait_event(ev)
    s2.wait_stream(s1)
    with D.stream_guard(s2) as cur:
        assert cur is s2
        assert D.current_stream() is s2
    assert D.current_stream() is not s2
