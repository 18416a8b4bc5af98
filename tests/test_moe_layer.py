"""The routed-expert layer (`models/experts.py`) and its grouped matmul
(`ops/pallas/grouped_expert_matmul.py`, interpreted) on the CPU, float32,
seeded random weights.

Tolerances, with their reasons.  Kernel and oracle are both float32 at full
matmul precision and differ only in the order of their sums: 1e-5 on values
of a few units.  The 16 shares of one layer add up to the uncut layer in
another order again: 2e-5.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as P
from benchmarks import reference_mimo
from paddle_tpu.models.experts import (
    COUNTER_NAMES, RoutedExperts, route, routed_experts)
from paddle_tpu.ops.pallas.grouped_expert_matmul import (
    group_row_starts, grouped_expert_matmul, grouped_matmul_ref, padded_rows)

TOL = 1e-5
HIGHEST = jax.lax.Precision.HIGHEST


def _laid_out(flat, sizes, rows, tile, fill=7.0):
    """`flat`'s rows, group after group, in the tile-aligned layout; the
    padding rows hold `fill`.  Returns the layout and each row's place."""
    starts = np.asarray(group_row_starts(jnp.asarray(sizes, jnp.int32), tile))
    lhs = np.full((rows, flat.shape[1]), fill, np.float32)
    where, at = [], 0
    for g, n in enumerate(sizes):
        lhs[starts[g]:starts[g] + n] = flat[at:at + n]
        where += list(range(starts[g], starts[g] + n))
        at += n
    return lhs, np.asarray(where, np.int64)


@pytest.mark.parametrize("sizes", [
    [3, 0, 9, 5],       # an empty group; live rows no multiple of the tile
    [0, 0, 17, 0],      # one group holds every row
    [8, 8, 8, 8],       # whole tiles
    [0, 0, 0, 0],       # nothing live: every tile is skipped
    [1, 1, 1, 30],      # the worst padding beside a long group
], ids=["empty_group", "one_group", "whole_tiles", "nothing", "skewed"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_against_ragged_dot(sizes, dtype):
    tile, k, n = 8, 32, 48
    rng = np.random.RandomState(sum(sizes))
    a = int(sum(sizes))
    rows = padded_rows(33, len(sizes), tile)
    flat = rng.randn(a, k).astype(np.float32)
    rhs = jnp.asarray(rng.randn(len(sizes), k, n), dtype)
    lhs, where = _laid_out(flat, sizes, rows, tile)
    gs = jnp.asarray(sizes, jnp.int32)
    got = grouped_expert_matmul(jnp.asarray(lhs, dtype), rhs, gs, tile)
    assert got.shape == (rows, n) and got.dtype == dtype
    if not a:
        return
    want = jax.lax.ragged_dot(jnp.asarray(flat, dtype), rhs, gs,
                              precision=HIGHEST,
                              preferred_element_type=jnp.float32)
    tol = TOL if dtype == jnp.float32 else 0.15    # 8 bits of ~6-unit sums
    np.testing.assert_allclose(np.asarray(got, np.float32)[where],
                               np.asarray(want), atol=tol, rtol=0)
    loop = grouped_matmul_ref(jnp.asarray(lhs, dtype), rhs, gs, tile)
    np.testing.assert_allclose(np.asarray(loop, np.float32)[where],
                               np.asarray(want), atol=tol, rtol=0)


def test_grouped_matmul_is_forward_only_and_checks_its_layout():
    lhs = jnp.ones((16, 8), jnp.float32)
    rhs = jnp.ones((2, 8, 4), jnp.float32)
    sizes = jnp.asarray([3, 4], jnp.int32)
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda x: grouped_expert_matmul(x, rhs, sizes, 8).sum())(lhs)
    with pytest.raises(ValueError, match="whole tiles"):
        grouped_expert_matmul(lhs[:15], rhs, sizes, 8)
    assert padded_rows(1024, 16, 128) == 3072       # a decode step's layout


# -- the layer ----------------------------------------------------------------

def _layer(experts=16, held=None, top_k=2, hidden=32, inter=16, seed=3,
           **over):
    P.seed(seed)
    layer = RoutedExperts(hidden, inter, experts, top_k, held=held,
                          tile_rows=8, **over)
    layer.eval()
    return layer


def _weights(layer):
    return {"mlp." + n: p._value for n, p in layer.named_parameters()}


def _cfg(layer):
    return {"num_experts_per_tok": layer.top_k, "norm_topk_prob": True,
            "routed_scaling_factor": None}


def _reference(layer, x, first=0, weights=None):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference_mimo.expert_ffn(
            weights or _weights(layer), jnp.asarray(x), _cfg(layer), first))


def _x(t, hidden=32, seed=0):
    return np.random.RandomState(seed).randn(t, hidden).astype(np.float32)


@pytest.mark.parametrize("held", [None, (4, 8)], ids=["all", "share"])
@pytest.mark.parametrize("tokens,chunk", [(21, 1024), (50, 16)],
                         ids=["one_chunk", "four_chunks"])
def test_layer_matches_the_reference_and_counts(held, tokens, chunk):
    layer = _layer(held=held, chunk_tokens=chunk)
    x = _x(tokens)
    with P.no_grad():
        y, stats = layer(P.to_tensor(x[None]))
    first, count = layer.held
    np.testing.assert_allclose(y.numpy()[0], _reference(layer, x, first),
                               atol=TOL, rtol=0)
    sel, _ = route(jnp.asarray(x), layer.router_weight._value,
                   layer.router_bias._value, top_k=2)
    sel = np.asarray(sel)
    tokens_of = [(sel == first + e).sum() for e in range(count)]
    stats = np.asarray(stats._value)
    assert len(COUNTER_NAMES) == 4 and stats.shape == (3 + count,)
    assert stats[0] == tokens * 2 and stats[1] == sum(tokens_of)
    assert list(stats[3:]) == tokens_of
    hit = sum(n > 0 for n in tokens_of)
    assert stats[2] == hit if chunk >= tokens else stats[2] >= hit


def test_total_imbalance_drops_nothing():
    """Every token to the same two held experts (the selection-only bias
    decides): no capacity, so every token's output is the reference's, and
    the two groups hold every assignment."""
    layer = _layer(held=(0, 8))
    bias = np.zeros(16, np.float32)
    bias[[3, 5]] = 10.0
    layer.router_bias._set_value(jnp.asarray(bias))
    x = _x(40, seed=1)
    with P.no_grad():
        y, stats = layer(P.to_tensor(x[None]))
    stats = np.asarray(stats._value)
    assert list(stats[3:]) == [0, 0, 0, 40, 0, 40, 0, 0]
    assert stats[1] == 80 == stats[0] and stats[2] == 2
    want = _reference(layer, x)
    assert np.abs(want).min(axis=1).max() > 0          # no row was zeroed
    np.testing.assert_allclose(y.numpy()[0], want, atol=TOL, rtol=0)
    # the bias selects and does not weigh: w are the raw scores' shares
    _, w = route(jnp.asarray(x), layer.router_weight._value,
                 jnp.asarray(bias), top_k=2)
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 1.0, atol=1e-6)


def test_nothing_local_gives_zeros_not_garbage():
    layer = _layer(held=(8, 4))
    bias = np.zeros(16, np.float32)
    bias[[0, 1]] = 10.0                                  # both held elsewhere
    layer.router_bias._set_value(jnp.asarray(bias))
    with P.no_grad():
        y, stats = layer(P.to_tensor(_x(9)[None]))
    assert not np.asarray(stats._value)[1:].any()
    assert not y.numpy().any()


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """256 experts, top-8, cut 16 ways as the benchmark's configuration is:
    share r holds experts [16 r, 16 r + 16).  The router's output is the
    same on every share, and the shares' outputs summed are the uncut
    reference's layer (256 experts, no share)."""
    whole = _layer(experts=256, top_k=8, seed=11)
    x = _x(24, seed=2)
    w = {n: p._value for n, p in whole.named_parameters()}
    want = _reference(whole, x)
    routed = route(jnp.asarray(x), w["router_weight"], w["router_bias"],
                   top_k=8)
    total = np.zeros_like(want)
    for r in range(16):
        share = _layer(experts=256, top_k=8, held=(16 * r, 16))
        share.set_state_dict({
            "router_weight": w["router_weight"],
            "router_bias": w["router_bias"],
            "gate_up_proj": w["gate_up_proj"][16 * r:16 * r + 16],
            "down_proj": w["down_proj"][16 * r:16 * r + 16]})
        with P.no_grad():
            y, stats = share(P.to_tensor(x[None]))
        total += y.numpy()[0]
        again = route(jnp.asarray(x), share.router_weight._value,
                      share.router_bias._value, top_k=8)
        for a, b in zip(routed, again):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        part = _reference(share, x, first=16 * r)
        np.testing.assert_allclose(y.numpy()[0], part, atol=TOL, rtol=0)
    assert np.abs(want).max() > 0.01
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=0)


def test_a_bfloat16_router_selects_differently():
    """What `router_dtype` guards: over 400 tokens a bfloat16 router (8 bits
    of score) picks another top-8 of 256 for some, a float32 one for none."""
    layer = _layer(experts=256, top_k=8, seed=5)
    x = jnp.asarray(_x(400, seed=3))
    w, b = layer.router_weight._value, layer.router_bias._value
    ref_sel, _, _ = reference_mimo.route(
        {"mlp.router_weight": w, "mlp.router_bias": b}, x,
        {"num_experts_per_tok": 8, "norm_topk_prob": True})
    same = lambda sel: (np.sort(np.asarray(sel), 1)
                        == np.sort(np.asarray(ref_sel), 1)).all(axis=1)
    assert same(route(x, w, b, top_k=8)[0]).all()
    assert not same(route(x, w, b, top_k=8,
                          router_dtype="bfloat16")[0]).all()


def test_options_are_checked():
    with pytest.raises(ValueError, match="held"):
        RoutedExperts(8, 4, 16, 2, held=(12, 8))
    with pytest.raises(ValueError, match="top_k"):
        RoutedExperts(8, 4, 2, 4)
    # sigmoid scores are all the layer has: another is refused where a
    # configuration could ask for it
    from paddle_tpu.models import MiMoConfig
    with pytest.raises(NotImplementedError, match="scoring_func"):
        MiMoConfig(scoring_func="softmax")
