"""Parity tests for the round-4 Pallas kernels (VERDICT r3 item 6):
fused linear+softmax-cross-entropy (incl. the TP-vocab-sharded variant) and
ragged KV-cache decode attention.  On the CPU mesh they run in Pallas
interpret mode — the same code path the TPU executes via Mosaic."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.ops.pallas.decode_attention import (
    _chunk_len, _ragged_ref, ragged_decode_attention)
from paddle_tpu.ops.pallas.fused_ce import (
    fused_linear_cross_entropy,
    fused_linear_cross_entropy_tp,
)
from paddle_tpu.parallel import mesh as mesh_mod


@pytest.fixture(autouse=True)
def _clean_mesh():
    yield
    mesh_mod.set_mesh(None)


def _ce_ref(h, w, lab):
    s = h @ w
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    return lse - s[jnp.arange(s.shape[0]), lab]


class TestFusedLinearCE:
    def _data(self, n=24, hd=64, v=1000, seed=0):
        r = np.random.RandomState(seed)
        h = jnp.asarray(r.randn(n, hd).astype(np.float32) * 0.3)
        w = jnp.asarray(r.randn(hd, v).astype(np.float32) * 0.1)
        lab = jnp.asarray(r.randint(0, v, (n,)), jnp.int32)
        return h, w, lab

    def test_forward_matches_reference(self):
        h, w, lab = self._data()
        np.testing.assert_allclose(
            np.asarray(fused_linear_cross_entropy(h, w, lab)),
            np.asarray(_ce_ref(h, w, lab)), rtol=1e-5, atol=1e-6)

    def test_forward_unaligned_shapes(self):
        # n, hd, v all off the tile multiples
        h, w, lab = self._data(n=13, hd=50, v=777, seed=3)
        np.testing.assert_allclose(
            np.asarray(fused_linear_cross_entropy(h, w, lab)),
            np.asarray(_ce_ref(h, w, lab)), rtol=1e-5, atol=1e-6)

    def test_grads_match_reference(self):
        h, w, lab = self._data(seed=1)
        g = jnp.asarray(np.random.RandomState(2).randn(h.shape[0])
                        .astype(np.float32))
        dh, dw = jax.grad(lambda a, b: jnp.sum(
            fused_linear_cross_entropy(a, b, lab) * g), argnums=(0, 1))(h, w)
        dh_r, dw_r = jax.grad(lambda a, b: jnp.sum(
            _ce_ref(a, b, lab) * g), argnums=(0, 1))(h, w)
        np.testing.assert_allclose(np.asarray(dh), np.asarray(dh_r),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_r),
                                   rtol=1e-4, atol=1e-6)

    def test_tensor_level_op(self):
        import paddle_tpu.incubate.nn.functional as IF
        h, w, lab = self._data(seed=4)
        th, tw = P.Tensor(h), P.Tensor(w)
        th.stop_gradient = False
        tw.stop_gradient = False
        loss = IF.fused_linear_cross_entropy(th, tw, P.Tensor(lab))
        loss.mean().backward()
        ref = jax.grad(lambda a: jnp.mean(_ce_ref(a, w, lab)))(h)
        np.testing.assert_allclose(np.asarray(th.grad.numpy()),
                                   np.asarray(ref), rtol=1e-4, atol=1e-6)

    def test_tp_sharded_matches_replicated(self):
        """shard_map over mp: vocab-sharded fused CE (fwd + grads) must match
        the single-device kernel on the full vocab."""
        import paddle_tpu.distributed as dist
        from jax.sharding import PartitionSpec as PS

        dist.init_parallel_env({"mp": 4})
        mesh = mesh_mod.get_mesh()
        n, hd, v = 16, 32, 512
        r = np.random.RandomState(7)
        h = jnp.asarray(r.randn(n, hd).astype(np.float32) * 0.3)
        w = jnp.asarray(r.randn(hd, v).astype(np.float32) * 0.1)
        lab = jnp.asarray(r.randint(0, v, (n,)), jnp.int32)
        g = jnp.asarray(r.randn(n).astype(np.float32))

        def tp_loss(h, w, lab):
            def inner(h, w_shard, lab):
                return fused_linear_cross_entropy_tp(h, w_shard, lab,
                                                     axis="mp")
            return jax.shard_map(
                inner, mesh=mesh,
                in_specs=(PS(), PS(None, "mp"), PS()),
                out_specs=PS(), axis_names={"mp"}, check_vma=False)(h, w, lab)

        loss = tp_loss(h, w, lab)
        np.testing.assert_allclose(np.asarray(loss),
                                   np.asarray(_ce_ref(h, w, lab)),
                                   rtol=1e-5, atol=1e-6)
        dh, dw = jax.grad(lambda a, b: jnp.sum(tp_loss(a, b, lab) * g),
                          argnums=(0, 1))(h, w)
        dh_r, dw_r = jax.grad(lambda a, b: jnp.sum(_ce_ref(a, b, lab) * g),
                              argnums=(0, 1))(h, w)
        np.testing.assert_allclose(np.asarray(dh), np.asarray(dh_r),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_r),
                                   rtol=1e-4, atol=1e-6)


class TestRaggedDecodeAttention:
    def _ref(self, q, k, v, lengths):
        B, Smax, Hkv, D = k.shape
        H = q.shape[2]
        group = H // Hkv
        kT = jnp.repeat(jnp.swapaxes(k, 1, 2), group, axis=1)
        vT = jnp.repeat(jnp.swapaxes(v, 1, 2), group, axis=1)
        qT = jnp.swapaxes(q, 1, 2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qT, kT) / np.sqrt(D)
        mask = (jnp.arange(Smax)[None, None, None, :]
                < lengths[:, None, None, None])
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vT), 1, 2)

    @pytest.mark.parametrize("hkv", [4, 8])   # GQA and MHA
    def test_matches_masked_reference(self, hkv):
        r = np.random.RandomState(0)
        B, Smax, H, D = 3, 384, 8, 128
        q = jnp.asarray(r.randn(B, 1, H, D).astype(np.float32) * 0.5)
        k = jnp.asarray(r.randn(B, Smax, hkv, D).astype(np.float32) * 0.5)
        v = jnp.asarray(r.randn(B, Smax, hkv, D).astype(np.float32) * 0.5)
        lengths = jnp.asarray([1, 200, 384], jnp.int32)
        out = ragged_decode_attention(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(self._ref(q, k, v, lengths)),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("heads,kv_heads",
                             [(8, 8), (16, 8), (32, 8), (6, 2)])
    @pytest.mark.parametrize("dtype,tol", [("float32", 2e-6),
                                           ("bfloat16", 2e-2)])
    def test_masks_scores_and_values_past_each_length(self, dtype, tol, heads,
                                                      kv_heads):
        """Lengths at 0, 1, either side of a chunk boundary and S_max, the
        cache NaN from each length on: a score or a value row let through
        where it should be masked turns the output NaN."""
        D, S = 128, 600
        bk = _chunk_len(S, kv_heads, D, jnp.dtype(dtype).itemsize)
        assert bk + 1 < S
        lens = [0, 1, bk - 1, bk, bk + 1, S]
        r = np.random.RandomState(0)
        q = jnp.asarray(r.randn(len(lens), 1, heads, D) * 0.5, dtype)
        k, v = (r.randn(len(lens), S, kv_heads, D).astype(np.float32) * 0.5
                for _ in range(2))
        want = _ragged_ref(q, jnp.asarray(k, dtype), jnp.asarray(v, dtype),
                           jnp.asarray(lens, jnp.int32), D ** -0.5)
        for i, n in enumerate(lens):
            k[i, n:] = v[i, n:] = np.nan
        out = ragged_decode_attention(q, jnp.asarray(k, dtype),
                                      jnp.asarray(v, dtype),
                                      jnp.asarray(lens, jnp.int32))
        assert out.shape == q.shape and out.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    def test_refuses_a_cache_it_would_have_to_copy(self):
        """64 lanes are no whole tile: the kernel used to pad, so copy, such
        a cache on every call; it refuses, typed, in interpret mode as on
        the chip, and a model asks `reads_in_place` before it calls."""
        from paddle_tpu.ops.pallas.decode_attention import (
            CacheLayoutUnsupported, reads_in_place)
        q, kv = jnp.ones((2, 1, 8, 64)), jnp.ones((2, 16, 4, 64))
        assert not reads_in_place(kv.shape, kv.shape)
        assert reads_in_place((2, 16, 4, 256), (2, 64, 128))
        with pytest.raises(CacheLayoutUnsupported, match="128 lanes") as e:
            ragged_decode_attention(q, kv, kv, jnp.asarray([1, 16]))
        assert (e.value.which, e.value.lanes) == ("key", 64)

    @pytest.mark.parametrize("heads,kernel", [(4, False), (1, True)],
                             ids=["head_8_masked", "head_128_kernel"])
    def test_generate_uses_ragged_kernel_and_matches_oracle(self, heads,
                                                            kernel):
        """End-to-end decode: cached generation (which routes single-token
        steps through the ragged kernel where the head is whole tiles of
        lanes, and through the masked attention where it is not) must equal
        the no-cache oracle."""
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.models.steps import compiled_step
        P.seed(0)
        cfg = LlamaConfig.tiny(vocab=64, hidden=32 if heads > 1 else 128,
                               layers=2, heads=heads, inter=64)
        model = LlamaForCausalLM(cfg)
        model.eval()
        ids = P.to_tensor(np.random.RandomState(1).randint(0, 64, (2, 5)))
        out_cached = model.generate(ids, max_new_tokens=6, use_cache=True)
        out_oracle = model.generate(ids, max_new_tokens=6, use_cache=False)
        np.testing.assert_array_equal(np.asarray(out_cached.numpy()),
                                      np.asarray(out_oracle.numpy()))
        # which attention the one-token program took, as it was traced
        decode = [p.op_counts() for p in compiled_step(
            model, "cached").programs()
            if (2, 1) in [a.shape for a in p.in_avals]]
        assert [(c.get("ragged_decode_attention", 0), c.get("decode_mask", 0))
                for c in decode] == [(2, 0) if kernel else (0, 2)]


class TestFusedLossTrainStep:
    def test_hybrid_step_fused_loss_parity(self):
        """build_hybrid_train_step(fused_loss=True) must produce the same
        loss trajectory as the unfused head."""
        from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                       build_hybrid_train_step)
        rng = np.random.RandomState(0)
        losses = {}
        for fused in (False, True):
            P.seed(0)
            cfg = LlamaConfig.tiny(vocab=128, hidden=32, layers=2, heads=4,
                                   inter=64)
            model = LlamaForCausalLM(cfg)
            opt = P.optimizer.AdamW(learning_rate=1e-3,
                                    parameters=model.parameters())
            step = build_hybrid_train_step(model, opt, mesh=None,
                                           fused_loss=fused)
            data = np.random.RandomState(5).randint(0, 128, (4, 17))
            batch = {"input_ids": P.to_tensor(data[:, :-1]),
                     "labels": P.to_tensor(data[:, 1:])}
            traj = [float(step(batch).numpy()) for _ in range(3)]
            losses[fused] = traj
        np.testing.assert_allclose(losses[True], losses[False],
                                   rtol=1e-5, atol=1e-6)

    def test_fused_loss_ignore_index_parity(self):
        """-100-padded labels (instruction tuning): the fused path must skip
        ignored rows AND divide by the valid count, like F.cross_entropy."""
        from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                       build_hybrid_train_step)
        losses = {}
        for fused in (False, True):
            P.seed(0)
            cfg = LlamaConfig.tiny(vocab=128, hidden=32, layers=2, heads=4,
                                   inter=64)
            model = LlamaForCausalLM(cfg)
            opt = P.optimizer.AdamW(learning_rate=1e-3,
                                    parameters=model.parameters())
            step = build_hybrid_train_step(model, opt, mesh=None,
                                           fused_loss=fused)
            data = np.random.RandomState(5).randint(0, 128, (4, 17))
            labels = data[:, 1:].copy()
            labels[:, :7] = -100     # mask a prefix, like SFT prompt tokens
            batch = {"input_ids": P.to_tensor(data[:, :-1]),
                     "labels": P.to_tensor(labels)}
            losses[fused] = [float(step(batch).numpy()) for _ in range(2)]
        np.testing.assert_allclose(losses[True], losses[False],
                                   rtol=1e-5, atol=1e-6)
