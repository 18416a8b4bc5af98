"""Pallas flash-attention kernel vs the XLA reference attention.

Runs in interpret mode on the CPU test platform (conftest forces cpu), the
same discipline as the reference's fake-device testing (SURVEY §4).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.functional.attention import sdp_attention_ref
from paddle_tpu.ops.pallas.flash_attention import flash_attention


def _rand(rng, *shape):
    return jnp.asarray(rng.randn(*shape), jnp.float32)


@pytest.mark.parametrize(
    "B,S,H,D,Hkv,causal,Sk",
    [
        (2, 128, 2, 64, 2, False, 128),
        (2, 128, 2, 64, 2, True, 128),
        (1, 200, 4, 64, 4, True, 200),     # non-multiple seq (pad path)
        (2, 256, 4, 64, 2, True, 256),     # grouped-query attention
        (1, 128, 2, 64, 2, False, 256),    # cross-attention lengths
    ],
)
def test_flash_vs_ref(B, S, H, D, Hkv, causal, Sk):
    rng = np.random.RandomState(0)
    q = _rand(rng, B, S, H, D)
    k = _rand(rng, B, Sk, Hkv, D)
    v = _rand(rng, B, Sk, Hkv, D)

    out = flash_attention(q, k, v, causal, None)
    ref = sdp_attention_ref(q, k, v, None, 0.0, causal, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)

    f = lambda q, k, v: flash_attention(q, k, v, causal, None).sum()
    r = lambda q, k, v: sdp_attention_ref(q, k, v, None, 0.0, causal, None).sum()
    g1 = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


def test_flash_under_jit():
    rng = np.random.RandomState(1)
    q = _rand(rng, 1, 128, 2, 64)
    out = jax.jit(lambda q: flash_attention(q, q, q, True, None))(q)
    ref = sdp_attention_ref(q, q, q, None, 0.0, True, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_nn_functional_sdpa_matches():
    import paddle_tpu as P
    from paddle_tpu.nn.functional.attention import scaled_dot_product_attention

    rng = np.random.RandomState(2)
    q = P.to_tensor(rng.randn(2, 64, 4, 32).astype("float32"))
    out = scaled_dot_product_attention(q, q, q, is_causal=True)
    ref = sdp_attention_ref(q._value, q._value, q._value, None, 0.0, True, None)
    np.testing.assert_allclose(np.asarray(out._value), np.asarray(ref), atol=2e-4)


def test_sdpa_kernel_under_dp_mp_mesh(monkeypatch):
    """Batch on 'dp', heads on 'mp' (what the hybrid step produces): sdpa
    runs the kernel per shard through mesh.shard_kernel. Interpret mode
    stands in for Mosaic, which refuses the unwrapped call outright
    (tests/test_kernels_compile_tpu.py asks the real compiler)."""
    import paddle_tpu as P
    from jax.sharding import NamedSharding, PartitionSpec
    from paddle_tpu.autograd.grad_mode import no_grad
    from paddle_tpu.nn.functional import attention
    from paddle_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(attention, "_use_pallas", lambda q: True)
    mesh = mesh_mod.init_mesh({"dp": 2, "mp": 2}, devices=jax.devices()[:4])
    try:
        rng = np.random.RandomState(3)
        q, k, v = (jax.device_put(
            _rand(rng, 4, 128, 4, 64),
            NamedSharding(mesh, PartitionSpec("dp", None, "mp", None)))
            for _ in range(3))

        def loss(q, k, v):
            with no_grad():
                out = attention.scaled_dot_product_attention(
                    P.Tensor(q), P.Tensor(k), P.Tensor(v), is_causal=True)
            return out._value.sum()

        jaxpr = str(jax.make_jaxpr(loss)(q, k, v))
        assert "shard_map" in jaxpr and "pallas_call" in jaxpr
        got = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        want = jax.grad(lambda q, k, v: sdp_attention_ref(
            q, k, v, None, 0.0, True, None).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-3)
    finally:
        mesh_mod.set_mesh(None)
