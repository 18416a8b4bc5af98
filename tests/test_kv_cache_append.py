"""The decode step's in-place K/V row write (ops/pallas/kv_cache_append.py).

The contract under test (ISSUE 28):
- the kernel writes bitwise what the vmapped `dynamic_update_slice` it
  replaces writes, offsets out of range included, and touches no other row;
- `models/llama.py` takes it where it can see that it applies (per-slot
  offsets, one token, a cache position of whole tiles) and nowhere else: the
  slot step at 8 KV heads x 128 holds no `scatter`, the verify step, the
  scalar-offset cached step and a `tiny` model keep their write;
- whichever write a step takes, the served tokens are bitwise the same, and
  the engine's `info()` says which one every layer took.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.jit import capture
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.steps import build_step
from paddle_tpu.ops.pallas import kv_cache_append as kva
from paddle_tpu.parallel import mesh as mesh_mod

S_MAX, H_KV, D = 32, 8, 128
LAYERS = 2


def _vmapped_update(kc, vc, kn, vn, off):
    """The write `kv_cache_append` replaces, as models/llama.py has it."""
    def one(c, n, o):
        z = jnp.asarray(0, jnp.int32)
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (o, z, z))
    return jax.vmap(one)(kc, kn, off), jax.vmap(one)(vc, vn, off)


def _operands(batch, dtype, seed=0):
    r = np.random.RandomState(seed)
    cache = lambda: jnp.asarray(r.randn(batch, S_MAX, H_KV, D), dtype)
    row = lambda: jnp.asarray(r.randn(batch, 1, H_KV, D), jnp.float32)
    return cache(), cache(), row(), row()


def _bits(x):
    return np.asarray(x).view(np.uint16 if x.dtype == jnp.bfloat16
                              else np.uint32)


# first, middle, last, beyond the end (clipped to the last), negative (counts
# from the end, as dynamic_update_slice reads it), far below (clipped to 0)
OFFSETS = [0, S_MAX // 2, S_MAX - 1, S_MAX + 7, -3, -S_MAX - 5]
LANDS = [0, S_MAX // 2, S_MAX - 1, S_MAX - 1, S_MAX - 3, 0]


@pytest.mark.parametrize("batch", [1, 3, 64])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_bitwise_the_vmapped_update(dtype, batch):
    kc, vc, kn, vn = _operands(batch, dtype)
    for shift in range(len(OFFSETS) if batch < len(OFFSETS) else 1):
        pick = [(b + shift) % len(OFFSETS) for b in range(batch)]
        off = jnp.asarray([OFFSETS[i] for i in pick], jnp.int32)
        got = kva.kv_cache_append(kc, vc, kn, vn, off)
        want = _vmapped_update(kc, vc, kn, vn, off)
        for g, w, old, new in zip(got, want, (kc, vc), (kn, vn)):
            assert g.dtype == w.dtype == dtype
            np.testing.assert_array_equal(_bits(g), _bits(w))
            # exactly one row a slot moved, and it holds the new row
            changed = np.any(_bits(g) != _bits(old), axis=(2, 3))
            for b, i in enumerate(pick):
                assert np.flatnonzero(changed[b]).tolist() == [LANDS[i]]
                np.testing.assert_array_equal(
                    _bits(g[b, LANDS[i]]), _bits(new[b, 0].astype(dtype)))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_donated_through_jit_equals_undonated(dtype):
    kc, vc, kn, vn = _operands(3, dtype, seed=1)
    off = jnp.asarray([5, 0, S_MAX - 1], jnp.int32)
    plain = kva.kv_cache_append(kc, vc, kn, vn, off)
    donated = jax.jit(kva.kv_cache_append, donate_argnums=(0, 1))(
        jnp.array(kc), jnp.array(vc), kn, vn, off)
    for a, b in zip(plain, donated):
        np.testing.assert_array_equal(_bits(a), _bits(b))


@pytest.mark.parametrize("h_kv,d,dtype,ok", [
    (8, 128, "bfloat16", True), (8, 128, "float32", True),
    (32, 128, "bfloat16", True), (16, 256, "float32", True),
    (1, 128, "bfloat16", False),     # Jamba's: half a packed tile
    (2, 16, "float32", False),       # the repo's `tiny` configs
    (12, 128, "float32", False),     # compiles, with a copy of the cache
    (8, 64, "bfloat16", False), (8, 128, "float16", False),
])
def test_whole_tiles(h_kv, d, dtype, ok):
    assert kva.whole_tiles(h_kv, d, dtype) is ok


# ---------------------------------------------------------------------------
# which write each step of the model takes
# ---------------------------------------------------------------------------

def _wide():
    """8 KV heads x 128: a cache position is whole tiles."""
    return LlamaConfig(vocab_size=128, hidden_size=H_KV * D,
                       intermediate_size=256, num_hidden_layers=LAYERS,
                       num_attention_heads=H_KV,
                       max_position_embeddings=64)


def _model(cfg, seed=11):
    P.seed(seed)
    return LlamaForCausalLM(cfg)


def _primitives(jaxpr, found):
    """Every equation of a jaxpr and of the jaxprs in its parameters, as
    (primitive name, pallas kernel name or None)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(("pallas_call", eqn.params["name"]))
            continue                 # the kernel's own body is not the step
        found.append((eqn.primitive.name, None))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


def _step_primitives(model, kind, tok, off, *rest):
    capture.set_step_capture_enabled(False)      # plain jit: traceable
    try:
        step = build_step(model, kind)
    finally:
        capture.set_step_capture_enabled(True)
    params = [p._value for p in model.parameters()]
    caches = [(k._value, v._value)
              for k, v in model.init_kv_caches(tok.shape[0], S_MAX)]
    closed = jax.make_jaxpr(step)(params, tok, caches, off, *rest)
    found = _primitives(closed.jaxpr, [])
    kernels = [k for p, k in found if p == "pallas_call"]
    return [p for p, _ in found], kernels


def test_slot_step_holds_no_scatter_and_one_append_a_layer():
    m = _model(_wide())
    b = 3
    prims, kernels = _step_primitives(
        m, "slot", jnp.zeros((b, 1), jnp.int32),
        jnp.asarray([4, 0, 9], jnp.int32), jnp.zeros((b,), jnp.int32))
    assert "scatter" not in prims
    assert kernels == ["kv_cache_append", "ragged_decode_attention"] * LAYERS


def test_verify_and_cached_steps_keep_their_write():
    m = _model(_wide())
    # a [B, k+1] window at per-slot offsets: the vmapped write, a scatter
    prims, kernels = _step_primitives(
        m, "verify", jnp.zeros((3, 3), jnp.int32),
        jnp.asarray([4, 0, 9], jnp.int32))
    assert prims.count("scatter") == 2 * LAYERS and kernels == []
    # generate()'s step: ONE scalar offset, a plain dynamic_update_slice
    prims, kernels = _step_primitives(
        m, "cached", jnp.zeros((3, 1), jnp.int32),
        jnp.asarray(4, jnp.int32))
    assert "scatter" not in prims and "kv_cache_append" not in kernels
    assert prims.count("dynamic_update_slice") == 2 * LAYERS


def test_slot_step_under_dp2_writes_what_one_device_writes():
    """Slots sharded over 'dp': the kernel runs once a shard
    (`mesh_mod.shard_kernel`) and the caches come back bitwise the same."""
    def run(mesh_axes):
        if mesh_axes:
            mesh_mod.init_mesh(mesh_axes, devices=jax.devices()[:2])
        try:
            m = _model(_wide())
            capture.set_step_capture_enabled(False)
            try:
                step = build_step(m, "slot")
            finally:
                capture.set_step_capture_enabled(True)
            r = np.random.RandomState(5)
            caches = [tuple(jnp.asarray(r.randn(*c.shape), c._value.dtype)
                            for c in kv) for kv in m.init_kv_caches(4, S_MAX)]
            nxt, out = step([p._value for p in m.parameters()],
                            jnp.asarray(r.randint(0, 128, (4, 1)), jnp.int32),
                            caches, jnp.asarray([3, 0, 9, S_MAX - 1], jnp.int32),
                            jnp.zeros((4,), jnp.int32))
            return [np.asarray(nxt)] + [np.asarray(c) for kv in out for c in kv]
        finally:
            mesh_mod.set_mesh(None)

    assert _same(run(None), run({"dp": 2}))


def _requests(vocab):
    r = np.random.RandomState(3)
    return [(r.randint(0, vocab, (int(r.randint(3, 20)),)),
             int(r.randint(4, 10))) for _ in range(5)]


def _serve(model, spec_k=0):
    """Mixed prompts, two joining mid-stream; -> (streams, engine info)."""
    kw = dict(spec_k=spec_k) if spec_k else {}
    eng = ServingEngine(model, max_batch=3, max_seq_len=64, **kw)
    reqs = []
    for i, (prompt, new) in enumerate(_requests(model.config.vocab_size)):
        reqs.append(eng.submit(prompt, max_new_tokens=new))
        if i == 2:
            eng.step()
            eng.step()
    eng.run()
    return [np.asarray(q.result()) for q in reqs], eng.info()


def _same(a, b):
    return len(a) == len(b) and all(np.array_equal(x, y)
                                    for x, y in zip(a, b))


def test_engine_takes_the_kernel_and_serves_the_scatters_tokens(monkeypatch):
    got, info = _serve(_model(_wide()))
    assert info["step"]["kv_write"] == {"kernel": LAYERS, "scatter": 0}
    # the same weights with the shape test refusing: the parent's write
    monkeypatch.setattr(kva, "whole_tiles", lambda *a: False)
    want, info = _serve(_model(_wide()))
    assert info["step"]["kv_write"] == {"kernel": 0, "scatter": LAYERS}
    assert _same(got, want)


def test_speculative_engine_verifies_through_the_scatter_bitwise():
    got, info = _serve(_model(_wide()), spec_k=2)
    assert info["spec"]["verify"]["kv_write"] == {"kernel": 0,
                                                  "scatter": LAYERS}
    want, _ = _serve(_model(_wide()))
    assert _same(got, want)


def test_tiny_shape_falls_back_and_serves_generates_tokens():
    """Head 16 x 2 KV heads is no whole tile: the scatter, by the shape test
    alone, and the stream is still the sequential oracle's."""
    m = _model(LlamaConfig.tiny(heads=4, layers=LAYERS))
    got, info = _serve(m)
    assert info["step"]["kv_write"] == {"kernel": 0, "scatter": LAYERS}
    for stream, (prompt, new) in zip(got[:2], _requests(m.config.vocab_size)):
        want = m.generate(P.to_tensor(prompt[None]), max_new_tokens=new)
        np.testing.assert_array_equal(stream, np.asarray(want.numpy())[0])
