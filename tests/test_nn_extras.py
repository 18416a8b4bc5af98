"""nn/functional extras: the final python/paddle/nn(.functional) __all__ gaps
— losses, unpool, vision ops, RNN cell family, beam decode.  Numeric checks
against closed-form / numpy references (OpTest pattern, SURVEY §4)."""
import ast

import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F

t = P.to_tensor
rng = np.random.RandomState(7)


def _ref_all(path):
    names = []
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.Assign):
            for tg in node.targets:
                if getattr(tg, "id", "") == "__all__":
                    names += [ast.literal_eval(e) for e in node.value.elts
                              if isinstance(e, ast.Constant)]
    return names


def test_nn_all_parity(reference_tree):
    missing = [n for n in _ref_all(reference_tree + "/nn/__init__.py")
               if not hasattr(nn, n)]
    assert not missing, f"nn gaps: {missing}"


def test_functional_all_parity(reference_tree):
    missing = [n for n in
               _ref_all(reference_tree + "/nn/functional/__init__.py")
               if not hasattr(F, n)]
    assert not missing, f"functional gaps: {missing}"


def test_tensor_method_parity(reference_tree):
    from paddle_tpu.core.tensor import Tensor
    src = open(reference_tree + "/tensor/__init__.py").read()
    names = []
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Assign):
            for tg in node.targets:
                if getattr(tg, "id", "") == "tensor_method_func":
                    names = [ast.literal_eval(e) for e in node.value.elts
                             if isinstance(e, ast.Constant)]
    missing = [n for n in names if not hasattr(Tensor, n)]
    assert not missing, f"Tensor method gaps: {missing}"


# ---- losses ----

def test_soft_margin_loss_formula():
    x = rng.randn(8).astype("f")
    y = np.sign(rng.randn(8)).astype("f")
    got = float(F.soft_margin_loss(t(x), t(y)).numpy())
    np.testing.assert_allclose(got, np.log1p(np.exp(-y * x)).mean(), rtol=1e-5)


def test_poisson_nll_loss_formula():
    x, y = rng.rand(6).astype("f"), rng.poisson(2, 6).astype("f")
    got = float(F.poisson_nll_loss(t(x), t(y)).numpy())
    np.testing.assert_allclose(got, (np.exp(x) - y * x).mean(), rtol=1e-5)


def test_gaussian_nll_loss_formula():
    x, y, v = rng.randn(6).astype("f"), rng.randn(6).astype("f"), \
        rng.rand(6).astype("f") + 0.5
    got = float(F.gaussian_nll_loss(t(x), t(y), t(v)).numpy())
    ref = 0.5 * (np.log(v) + (x - y) ** 2 / v).mean()
    np.testing.assert_allclose(got, ref, rtol=1e-5)


def test_multi_margin_loss_formula():
    x = rng.randn(4, 5).astype("f")
    lab = np.array([0, 1, 2, 3])
    got = float(F.multi_margin_loss(t(x), t(lab)).numpy())
    ref = 0.0
    for i, l in enumerate(lab):
        m = np.maximum(0, 1.0 - x[i, l] + x[i])
        m[l] = 0
        ref += m.sum() / 5
    np.testing.assert_allclose(got, ref / 4, rtol=1e-5)


def test_rnnt_loss_matches_path_enumeration():
    # T=2, U=1: exactly two alignment paths; closed-form logsumexp reference
    acts = rng.randn(1, 2, 2, 3).astype("f")
    lp = acts - np.log(np.exp(acts).sum(-1, keepdims=True))
    lp = lp[0]
    pA = lp[0, 0, 1] + lp[0, 1, 0] + lp[1, 1, 0]
    pB = lp[0, 0, 0] + lp[1, 0, 1] + lp[1, 1, 0]
    ref = -np.logaddexp(pA, pB)
    got = float(np.asarray(F.rnnt_loss(t(acts), t([[1]]), t([2]), t([1]),
                                       reduction="none").numpy()).ravel()[0])
    np.testing.assert_allclose(got, ref, rtol=1e-4)


def test_hsigmoid_loss_binary_tree():
    # num_classes=2: single root decision, loss = -log sigmoid(+/- z)
    x = rng.randn(2, 4).astype("f")
    w = rng.randn(1, 4).astype("f")
    got = F.hsigmoid_loss(t(x), t([0, 1]), 2, t(w)).numpy()
    z = x @ w[0]
    # leaf l -> heap node l+2: branch bit 0 (leaf0) scores sigmoid(+z),
    # bit 1 (leaf1) scores sigmoid(-z)
    sig = lambda v: 1 / (1 + np.exp(-v))  # noqa: E731
    ref = np.array([-np.log(sig(z[0])), -np.log(sig(-z[1]))])
    np.testing.assert_allclose(got, ref, rtol=1e-4)


def test_npair_and_dice_and_mlsm_run():
    a, b = t(rng.randn(4, 8).astype("f")), t(rng.randn(4, 8).astype("f"))
    assert np.isfinite(float(F.npair_loss(a, b, t([0, 1, 0, 1])).numpy()))
    assert np.isfinite(float(F.dice_loss(
        t(rng.rand(2, 4, 3).astype("f")),
        t(rng.randint(0, 3, (2, 4, 1)))).numpy()))
    assert np.isfinite(float(F.multi_label_soft_margin_loss(
        t(rng.randn(3, 5).astype("f")),
        t((rng.rand(3, 5) > 0.5).astype("f"))).numpy()))


def test_margin_cross_entropy_reduces_to_ce_at_zero_margin():
    logits = np.clip(rng.randn(4, 6).astype("f") * 0.3, -1, 1)
    lab = np.array([0, 2, 4, 5])
    got = float(F.margin_cross_entropy(t(logits), t(lab), margin1=1.0,
                                       margin2=0.0, margin3=0.0,
                                       scale=1.0).numpy())
    z = logits
    ref = np.mean([-z[i, l] + np.log(np.exp(z[i]).sum())
                   for i, l in enumerate(lab)])
    np.testing.assert_allclose(got, ref, rtol=1e-4)


# ---- pooling mask + unpool ----

def test_max_pool_return_mask_and_unpool_roundtrip():
    x = t(rng.randn(2, 3, 4, 4).astype("f"))
    p, idx = F.max_pool2d(x, 2, 2, return_mask=True)
    xv = x.numpy().reshape(2, 3, -1)
    for n in range(2):
        for c in range(3):
            np.testing.assert_allclose(
                xv[n, c][idx.numpy()[n, c].ravel()], p.numpy()[n, c].ravel())
    u = F.max_unpool2d(p, idx, 2, 2)
    assert u.shape == [2, 3, 4, 4]
    nz = u.numpy()[u.numpy() != 0]
    np.testing.assert_allclose(np.sort(nz),
                               np.sort(p.numpy()[p.numpy() != 0].ravel()))


def test_max_pool_mask_with_padding_never_selects_pad():
    x = t(np.full((1, 1, 3, 3), -5.0, "f"))
    p, idx = F.max_pool2d(x, 2, 2, padding=1, return_mask=True)
    assert int(idx.numpy().max()) < 9  # all indices inside the real plane


def test_unpool_1d_3d():
    x1 = t(rng.randn(2, 3, 8).astype("f"))
    p1, i1 = F.max_pool1d(x1, 2, 2, return_mask=True)
    assert F.max_unpool1d(p1, i1, 2, 2).shape == [2, 3, 8]
    x3 = t(rng.randn(1, 2, 4, 4, 4).astype("f"))
    p3, i3 = F.max_pool3d(x3, 2, 2, return_mask=True)
    assert F.max_unpool3d(p3, i3, 2, 2).shape == [1, 2, 4, 4, 4]


# ---- vision ----

def test_affine_grid_sample_identity():
    theta = t(np.array([[[1, 0, 0], [0, 1, 0]]], "f"))
    img = t(rng.randn(1, 2, 5, 5).astype("f"))
    grid = F.affine_grid(theta, [1, 2, 5, 5])
    np.testing.assert_allclose(F.grid_sample(img, grid).numpy(), img.numpy(),
                               atol=1e-5)


def test_grid_sample_nearest_and_zeros_padding():
    img = t(np.arange(4, dtype="f").reshape(1, 1, 2, 2))
    # sample far outside: zeros padding
    grid = t(np.full((1, 1, 1, 2), 5.0, "f"))
    assert float(F.grid_sample(img, grid).numpy().ravel()[0]) == 0.0
    g2 = t(np.array([[[[-1.0, -1.0]]]], "f"))
    assert float(F.grid_sample(img, g2, mode="nearest").numpy().ravel()[0]) == 0.0


def test_temporal_shift_moves_segments():
    x = rng.randn(4, 4, 2, 2).astype("f")  # N*T=4 (T=2), C=4 -> fold=1
    out = F.temporal_shift(t(x), seg_num=2).numpy()
    v = x.reshape(2, 2, 4, 2, 2)
    o = out.reshape(2, 2, 4, 2, 2)
    np.testing.assert_allclose(o[:, 0, 0], v[:, 1, 0])   # chan 0 shifted back
    np.testing.assert_allclose(o[:, 1, 1], v[:, 0, 1])   # chan 1 shifted fwd
    np.testing.assert_allclose(o[:, :, 2:], v[:, :, 2:])  # rest untouched


def test_sparse_attention_full_pattern_equals_dense():
    B, H, L, D = 1, 2, 4, 8
    q, k, v = (rng.randn(B, H, L, D).astype("f") for _ in range(3))
    offs = np.broadcast_to(np.arange(0, (L + 1) * L, L), (B, H, L + 1)).copy()
    cols = np.broadcast_to(np.tile(np.arange(L), L), (B, H, L * L)).copy()
    got = F.sparse_attention(t(q), t(k), t(v), t(offs), t(cols)).numpy()
    s = (q @ k.transpose(0, 1, 3, 2)) / np.sqrt(D)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(got, p @ v, rtol=2e-5, atol=1e-5)


def test_gather_tree_backtrace():
    ids = t(np.array([[[2, 2]], [[3, 4]], [[5, 6]]]))
    parents = t(np.array([[[0, 0]], [[0, 1]], [[1, 0]]]))
    out = F.gather_tree(ids, parents).numpy()
    # beam0 final=5 came from parent beam1 at t1 (tok 4), whose parent beam0 (tok 2)
    assert out[:, 0, 0].tolist() == [2, 4, 5]
    assert out[:, 0, 1].tolist() == [2, 3, 6]


# ---- inplace activations ----

def test_inplace_activation_grad_flows():
    x = t(np.array([0.5, -0.5], "f"))
    x.stop_gradient = False
    y = x * 1.0
    y.tanh_()
    y.sum().backward()
    np.testing.assert_allclose(x.grad.numpy(),
                               1 - np.tanh([0.5, -0.5]) ** 2, rtol=1e-5)
    z = x * 1.0
    F.leaky_relu_(z, 0.1)
    assert np.allclose(z.numpy(), [0.5, -0.05])


# ---- RNN cell family + decode ----

def test_simple_rnn_cell_and_rnn_wrapper():
    cell = nn.SimpleRNNCell(4, 8)
    x = t(rng.randn(2, 5, 4).astype("f"))
    out, st = nn.RNN(cell)(x)
    assert out.shape == [2, 5, 8] and st.shape == [2, 8]
    # manual single-step parity
    h = np.zeros((2, 8), "f")
    wih, whh = cell.weight_ih.numpy(), cell.weight_hh.numpy()
    bih, bhh = cell.bias_ih.numpy(), cell.bias_hh.numpy()
    h1 = np.tanh(x.numpy()[:, 0] @ wih.T + bih + h @ whh.T + bhh)
    np.testing.assert_allclose(out.numpy()[:, 0], h1, rtol=1e-4)


def test_birnn_concats_directions():
    fw, bw = nn.SimpleRNNCell(4, 6), nn.SimpleRNNCell(4, 6)
    out, _ = nn.BiRNN(fw, bw)(t(rng.randn(2, 3, 4).astype("f")))
    assert out.shape == [2, 3, 12]


def test_dynamic_decode_beam_search():
    class ToyCell(nn.Layer):
        def forward(self, x, states):
            h = states[0] if isinstance(states, (list, tuple)) else states
            h2 = P.tanh(h + x * 0.0 + 0.1)
            return h2, h2

    emb = nn.Embedding(10, 8)
    outl = nn.Linear(8, 6)
    dec = nn.BeamSearchDecoder(ToyCell(), start_token=0, end_token=3,
                               beam_size=3, embedding_fn=emb, output_fn=outl)
    ids, lp = nn.dynamic_decode(dec, inits=t(np.zeros((2, 8), "f")),
                                max_step_num=5)
    assert ids.shape[0] == 2 and ids.shape[1] == 3
    # beams sorted by log-prob
    assert np.all(np.diff(lp.numpy(), axis=1) <= 1e-6)


def test_misc_layers():
    assert nn.Softmax2D()(t(rng.randn(1, 3, 2, 2).astype("f"))).shape == [1, 3, 2, 2]
    assert nn.Unflatten(1, [2, 3])(t(rng.randn(2, 6).astype("f"))).shape == [2, 2, 3]
    d = nn.PairwiseDistance()(t(rng.randn(3, 4).astype("f")),
                              t(rng.randn(3, 4).astype("f")))
    assert d.shape == [3]
    hl = nn.HSigmoidLoss(8, 7)
    assert hl(t(rng.randn(3, 8).astype("f")), t([0, 3, 6])).shape == [3]
    with pytest.raises(ValueError):
        nn.Softmax2D()(t(rng.randn(4).astype("f")))


def test_inplace_with_second_consumer_grad_correct():
    # regression: consumers recorded BEFORE an inplace op must keep the
    # pre-op tape linkage (consumer-registry rewiring in _inplace_assign)
    w = t(np.array([2.0], "f"))
    w.stop_gradient = False
    x = w * 1.0
    y = x * 3.0
    x.tanh_()
    (y + x).sum().backward()
    ref = 3 + 1 - np.tanh(2.0) ** 2
    np.testing.assert_allclose(w.grad.numpy(), [ref], rtol=1e-5)


def test_max_pool_ceil_mode_shapes_and_mask():
    x = t(rng.randn(1, 1, 8, 8).astype("f"))
    assert F.max_pool2d(x, 3, 2).shape == [1, 1, 3, 3]
    assert F.max_pool2d(x, 3, 2, ceil_mode=True).shape == [1, 1, 4, 4]
    p, idx = F.max_pool2d(x, 3, 2, ceil_mode=True, return_mask=True)
    np.testing.assert_allclose(
        p.numpy(), F.max_pool2d(x, 3, 2, ceil_mode=True).numpy())
    assert int(idx.numpy().max()) < 64  # never a ceil-pad slot


def test_rnnt_fastemit_scales_gradient_only():
    acts = rng.randn(1, 2, 2, 3).astype("f")
    args = (t([[1]]), t([2]), t([1]))
    l0 = F.rnnt_loss(t(acts), *args, fastemit_lambda=0.0, reduction="none")
    l1 = F.rnnt_loss(t(acts), *args, fastemit_lambda=0.5, reduction="none")
    np.testing.assert_allclose(np.ravel(l0.numpy()), np.ravel(l1.numpy()),
                               rtol=1e-6)
    a0 = t(acts); a0.stop_gradient = False
    F.rnnt_loss(a0, *args, fastemit_lambda=0.0).backward()
    a1 = t(acts); a1.stop_gradient = False
    F.rnnt_loss(a1, *args, fastemit_lambda=0.5).backward()
    assert not np.allclose(a0.grad.numpy(), a1.grad.numpy())


def test_sequence_mask_traced_needs_static_maxlen():
    fn = P.to_static(lambda v: F.sequence_mask(v))
    with pytest.raises(ValueError, match="maxlen"):
        fn(t([2, 3]))
    # static maxlen works under trace
    fn2 = P.to_static(lambda v: F.sequence_mask(v, maxlen=4))
    assert fn2(t([2, 3])).shape == [2, 4]


def test_dynamic_decode_lengths_align_with_beams():
    class ToyCell(nn.Layer):
        def forward(self, x, states):
            h = states[0] if isinstance(states, (list, tuple)) else states
            return P.tanh(h + x * 0.0 + 0.1), P.tanh(h + x * 0.0 + 0.1)

    dec = nn.BeamSearchDecoder(ToyCell(), 0, 3, 2, nn.Embedding(10, 8),
                               nn.Linear(8, 6))
    ids, lp, lens = nn.dynamic_decode(dec, inits=t(np.zeros((2, 8), "f")),
                                      max_step_num=5, return_length=True)
    for b in range(2):
        for w in range(2):
            seq, L = ids.numpy()[b, w], int(lens.numpy()[b, w])
            if 3 in seq.tolist():
                assert seq[L - 1] == 3
            else:
                assert L == len(seq)


# ---- numeric-gradient OpTests for the heavy new functionals ----

from op_test import OpTest  # noqa: E402


def test_grid_sample_grad_numeric():
    rng2 = np.random.RandomState(3)
    img = rng2.randn(1, 2, 5, 5).astype("f")
    # keep sample points interior so finite differences stay smooth
    grid = (rng2.rand(1, 3, 3, 2).astype("f") - 0.5) * 1.2
    OpTest.check_grad(F.grid_sample, [img, grid], wrt=(0, 1), eps=1e-4)


def test_max_unpool2d_grad_numeric():
    rng2 = np.random.RandomState(4)
    x = rng2.randn(1, 2, 4, 4).astype("f")
    p, idx = F.max_pool2d(t(x), 2, 2, return_mask=True)

    def op(pv):
        return F.max_unpool2d(pv, idx, 2, 2)
    OpTest.check_grad(op, [p.numpy()], wrt=(0,), eps=1e-4)


def test_rnnt_loss_grad_numeric():
    rng2 = np.random.RandomState(5)
    acts = rng2.randn(1, 3, 3, 4).astype("f") * 0.5

    def op(a):
        return F.rnnt_loss(a, t([[1, 2]]), t([3]), t([2]),
                           fastemit_lambda=0.0, reduction="sum")
    OpTest.check_grad(op, [acts], wrt=(0,), eps=1e-3, rtol=8e-2)


def test_deform_conv2d_grad_numeric():
    from paddle_tpu.vision.ops import deform_conv2d
    rng2 = np.random.RandomState(6)
    x = rng2.randn(1, 1, 5, 5).astype("f")
    w = rng2.randn(2, 1, 3, 3).astype("f")
    off = (rng2.rand(1, 18, 3, 3).astype("f") - 0.5) * 0.3
    OpTest.check_grad(deform_conv2d, [x, off, w], wrt=(0, 2), eps=1e-4)


def test_pairwise_and_losses_grad_numeric():
    rng2 = np.random.RandomState(7)
    a, b = rng2.randn(3, 4).astype("f"), rng2.randn(3, 4).astype("f")
    OpTest.check_grad(F.pairwise_distance, [a, b], wrt=(0, 1), eps=1e-4)
    x = rng2.randn(5).astype("f")
    y = np.sign(rng2.randn(5)).astype("f")
    OpTest.check_grad(F.soft_margin_loss, [x, y], wrt=(0,), eps=1e-4)
    v = rng2.rand(5).astype("f") + 0.5
    OpTest.check_grad(lambda p, l, vv: F.gaussian_nll_loss(p, l, vv),
                      [x, y, v], wrt=(0, 2), eps=1e-4)


def test_spectral_norm_forward_and_constant_uv_grad():
    """SpectralNorm divides by the power-iterated sigma, and its gradient
    treats the iterated u/v as CONSTANTS (reference spectral_norm_op: grad
    flows only through w in sigma = u^T w v, even unconverged iterations)."""
    import jax
    import jax.numpy as jnp

    w_np = rng.randn(4, 6).astype("f")
    layer = nn.SpectralNorm([4, 6], dim=0, power_iters=1)
    u0 = layer.weight_u.numpy().copy()
    v0 = layer.weight_v.numpy().copy()
    out = layer(t(w_np))
    # one manual power iteration from the SAME persistent u/v buffers
    def norm(a):
        return a / max(np.linalg.norm(a), 1e-12)
    v1 = norm(w_np.T @ u0)
    u1 = norm(w_np @ v1)
    sigma = float(u1 @ w_np @ v1)
    np.testing.assert_allclose(out.numpy(), w_np / sigma, rtol=1e-5)

    # grad semantics: d/dw sum(w/sigma) with d sigma/dw = u1 v1^T exactly
    layer2 = nn.SpectralNorm([4, 6], dim=0, power_iters=1)

    def f(wv):
        return jnp.sum(layer2(P.Tensor(wv))._value)

    g = jax.grad(f)(jnp.asarray(w_np))
    ones = np.ones_like(w_np)
    expected = ones / sigma - (np.sum(ones * w_np) / sigma ** 2) * np.outer(u1, v1)
    np.testing.assert_allclose(np.asarray(g), expected, rtol=1e-4, atol=1e-5)
