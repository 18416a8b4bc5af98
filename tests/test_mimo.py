"""MiMo-V2-Flash (window and full attention mixed, routed experts) against
its plain reference, at a small size on the CPU with seeded random weights:
the model's forward, the serving engine's padded prefill and decode through
two kinds of cache, the widened kernels (interpreted), and the typed refusals
of what cannot work over a ring.

Tolerances, with their reasons.  Model and reference are both float32 here
and differ only in the order of their sums, so logits (range ~5) agree to
~5e-6; LOGIT_TOL is 1e-4, twenty times that.  Each variant the benchmark's
`correct` must catch reads far over it at this size (window 8, so a key is an
eighth of a head's weight): the sink left out 1.3, a window one position
short 1.1, V's scale left out 1.2, a bfloat16 router 7e-3 (the readings of
`test_engine_sees_every_variant`'s four cases, each asserted at ten times
the tolerance).
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as P
from benchmarks import reference_mimo
from paddle_tpu.inference.serving import (
    FixedSlotStateUnsupported, ServingEngine)
from paddle_tpu.inference.serving.gateway import protocol
from paddle_tpu.models import MiMoConfig, MiMoForCausalLM
from paddle_tpu.models.mimo import mimo_attention, partial_rope, ring_rows
from paddle_tpu.ops.pallas.decode_attention import (
    _ragged_ref, cache_lanes, ragged_decode_attention)
from paddle_tpu.ops.pallas.flash_attention import windowed_flash_attention
from paddle_tpu.ops.pallas.kv_cache_append import kv_cache_append

LOGIT_TOL = 1e-4
VOCAB = 128
WINDOW = 8


def _model(seed=5, **over):
    P.seed(seed)
    m = MiMoForCausalLM(dataclasses.replace(MiMoConfig.tiny(vocab=VOCAB),
                                            **over))
    m.eval()
    return m


def _reference_logits(m, ids, positions):
    ref = reference_mimo.make_reference(dataclasses.asdict(m.config))
    weights = {n: p._value for n, p in m.named_parameters()}
    return np.asarray(ref(weights, m.config.num_hidden_layers,
                          jnp.asarray(ids), jnp.asarray(positions)))


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, (n,))


# -- (a) the model's full forward --------------------------------------------

@pytest.mark.parametrize("pattern,held", [
    ((1, 1, 1), None), ((0, 0, 0), None), ((0, 1, 1, 0, 1), (4, 8))],
    ids=["window_only", "full_only", "mixed_with_a_share"])
def test_forward_matches_the_reference_on_logits(pattern, held):
    m = _model(num_hidden_layers=len(pattern),
               hybrid_layer_pattern=list(pattern),
               moe_layer_freq=[0] + [1] * (len(pattern) - 1),
               held_experts=held)
    assert [l.is_window for l in m.model.layers] == [bool(p) for p in pattern]
    ids = np.stack([_prompt(37, 1), _prompt(37, 2)])
    with P.no_grad():
        got = m(P.to_tensor(ids)).numpy()
    for row in range(2):
        want = _reference_logits(m, ids[row], np.arange(37))
        np.testing.assert_allclose(got[row], want, atol=LOGIT_TOL, rtol=0)


def test_the_model_is_forward_only_and_says_so():
    m = _model()
    with pytest.raises(NotImplementedError, match="forward only"):
        m(P.to_tensor(_prompt(12).reshape(1, -1))).sum().backward()


def test_config_refuses_what_is_not_built():
    with pytest.raises(NotImplementedError, match="group-limited"):
        MiMoConfig(n_group=8, topk_group=4)
    with pytest.raises(ValueError, match="entries"):
        MiMoConfig(num_hidden_layers=50)
    assert MiMoConfig().rotary_dim == 64                  # int(192 * 0.334)
    cfg = MiMoConfig()
    assert sum(cfg.hybrid_layer_pattern) == 39 and not cfg.is_moe(0)


# -- (b) the engine: padded prefill, then decode, on logits ------------------

def _engine_logits(m, prompt, n_new, buckets=(16, 32)):
    """The logits row behind every token of one request (the engine's
    `return_logits` step, which a sampled request takes), decoded greedily."""
    eng = ServingEngine(m, max_batch=2, max_seq_len=64,
                        prefill_buckets=list(buckets))
    rows = []

    def record(req, row):
        rows.append(np.asarray(row, np.float32))
        return int(np.argmax(row))

    eng._sample_row = record
    req = eng.submit(prompt, max_new_tokens=n_new, temperature=1.0)
    eng.run()
    return np.stack(rows), req.result(), eng


@pytest.mark.parametrize("plen", [5, WINDOW, 11, 16, 27], ids=[
    "shorter_than_the_window", "the_window", "longer_padded",
    "fills_a_bucket", "second_bucket"])
def test_engine_prefill_then_decode_against_the_reference(plen):
    m = _model(held_experts=(4, 8))
    rows, out, eng = _engine_logits(m, _prompt(plen, plen), 14)
    assert eng.info()["prefill_positions_padded"] == (16 if plen <= 16
                                                      else 32)
    want = _reference_logits(m, out[:-1], plen - 1 + np.arange(14))
    assert np.abs(rows - want).max() <= LOGIT_TOL


def _no_sink(m):
    for l in m.model.layers:
        l.self_attn.use_sink = False


def _short_window(m):
    for l in m.model.layers:
        if l.is_window:
            l.self_attn.window = WINDOW - 1


def _bf16_router(m):
    for l in m.model.layers:
        if l.is_moe:
            l.mlp.options["router_dtype"] = "bfloat16"


def _no_value_scale(m):
    for l in m.model.layers:
        l.self_attn.value_scale = 1.0


@pytest.mark.parametrize("variant", [_no_sink, _short_window, _bf16_router,
                                     _no_value_scale],
                         ids=lambda f: f.__name__.strip("_"))
def test_engine_sees_every_variant(variant):
    """What the benchmark's `correct` has to catch, each FAILING the
    tolerance the unaltered program passes above: the sink left out, the
    window one position short, the router in bfloat16, V's scale left out."""
    m = _model(held_experts=(4, 8))
    prompt = _prompt(11, 11)
    rows, out, _ = _engine_logits(m, prompt, 14)
    want = _reference_logits(m, out[:-1], 10 + np.arange(14))
    assert np.abs(rows - want).max() <= LOGIT_TOL
    m.__dict__.pop("_compiled_steps")          # the altered model's own steps
    variant(m)
    rows, out, _ = _engine_logits(m, prompt, 14)
    want = _reference_logits(m, out[:-1], 10 + np.arange(14))
    assert np.abs(rows - want).max() > 10 * LOGIT_TOL


# -- (c) right padding does not enter a ring ---------------------------------

def test_ring_after_a_padded_prefill_is_the_unpadded_one():
    m = _model()
    prompt = _prompt(11, 3)
    rings = []
    for buckets in ([16], [11]):
        eng = ServingEngine(m, max_batch=2, max_seq_len=64,
                            prefill_buckets=buckets)
        eng.submit(prompt, max_new_tokens=1)
        eng.step()
        assert eng.info()["prefill_positions_padded"] == buckets[0]
        rings.append([np.asarray(leaf[0]) for layer, pair in
                      zip(m.model.layers, eng._caches)
                      if layer.is_window for leaf in pair])
    assert len(rings[0]) == 6                     # 3 window layers x (K, V)
    for padded, exact in zip(*rings):
        assert padded.shape[0] == WINDOW * 2      # ring rows: 8 x 2 KV heads
        assert np.abs(exact).max() > 0
        np.testing.assert_allclose(padded, exact, atol=1e-6, rtol=0)


def test_ring_rows_holds_the_last_real_positions():
    new = jnp.arange(2 * 12, dtype=jnp.float32).reshape(2, 12, 1) + 1
    got = np.asarray(ring_rows(new, jnp.asarray([11, 3]), 4))[..., 0]
    # row r holds the position p in [len - 4, len) with p % 4 == r
    np.testing.assert_array_equal(got[0], [9, 10, 11, 8])    # p = 8, 9, 10, 7
    np.testing.assert_array_equal(got[1] - 12, [1, 2, 3, -12])  # p = 0, 1, 2


# -- (d) slots are independent: join, finish, reuse --------------------------

def test_join_finish_and_reuse_leave_other_slots_bitwise_unchanged():
    m = _model(held_experts=(0, 8))
    long_prompt = _prompt(13, 7)
    alone = ServingEngine(m, max_batch=3, max_seq_len=64)
    want = alone.submit(long_prompt, max_new_tokens=24)
    alone.run()

    eng = ServingEngine(m, max_batch=3, max_seq_len=64)
    watched = eng.submit(long_prompt, max_new_tokens=24)
    eng.submit(_prompt(9, 8), max_new_tokens=3)          # finishes early
    for _ in range(4):
        eng.step()
    late = eng.submit(_prompt(20, 9), max_new_tokens=5)  # joins mid-stream
    for _ in range(3):
        eng.step()
    reuse = eng.submit(_prompt(6, 10), max_new_tokens=4)  # takes a freed slot
    eng.run()
    np.testing.assert_array_equal(watched.result(), want.result())
    assert len(late.output_tokens) == 5 and len(reuse.output_tokens) == 4
    fresh = ServingEngine(m, max_batch=3, max_seq_len=64)
    same = fresh.submit(_prompt(6, 10), max_new_tokens=4)
    fresh.run()
    np.testing.assert_array_equal(reuse.result(), same.result())
    # one lowering a bucket used and one for the decode step, joins or not
    info = eng.info()
    assert info["step"]["lowerings"] == 4                 # 8, 16, 32 + decode
    assert info["step"]["bailouts"] == 0
    # the counters rode back with the tokens: one vector a step
    assert info["moe_steps"] == info["prefills"] + info["decode_steps"]


# -- (e) what a ring cannot do is refused, typed -----------------------------

@pytest.mark.parametrize("option", [
    {"prefix_sharing": True}, {"prefill_chunk": 16}, {"spec_k": 2}],
    ids=lambda o: next(iter(o)))
def test_engine_refuses_what_a_ring_cannot_do(option):
    with pytest.raises(FixedSlotStateUnsupported, match="sliding window") \
            as e:
        ServingEngine(_model(), max_batch=2, max_seq_len=64, **option)
    assert e.value.param == next(iter(option)) and e.value.kind == "window"
    assert isinstance(e.value, NotImplementedError)
    # and its wire status
    assert protocol.status_of(e.value) == protocol.STATUS_BAD_REQUEST


# -- (f) the cache by kind, the counters by name -----------------------------

def test_info_reports_the_cache_by_kind_and_the_experts_by_name():
    from paddle_tpu import profiler
    m = _model(held_experts=(4, 8))
    eng = ServingEngine(m, max_batch=2, max_seq_len=64, page_size=16)
    info = eng.info()
    lanes = cache_lanes(24) + cache_lanes(16)             # K and V: 128 each
    assert info["kv_bytes_per_position"] == 2 * 1 * lanes * 4   # 2 full, 1 KV
    assert info["window_bytes_per_slot"] == 3 * WINDOW * 2 * lanes * 4
    assert info["cache_bytes"] == {
        "kv": 2 * 64 * info["kv_bytes_per_position"], "state": 0,
        "window": 2 * info["window_bytes_per_slot"]}
    pool = info["pool"]
    assert pool["page_bytes"] == 16 * info["kv_bytes_per_position"]
    assert pool["window_bytes_per_slot"] == info["window_bytes_per_slot"]
    assert info["moe_steps"] == 0 and info["moe_expert_tokens"] == [0] * 8
    eng.generate([_prompt(5), _prompt(12, 1)], max_new_tokens=3)
    info = eng.info()
    # 4 expert layers, top-2: every computed position is two assignments a
    # layer, padding rows of a bucket and idle slots among them
    positions = 8 + 16 + 2 * 2                            # buckets + 2 steps
    assert info["moe_steps"] == 4
    assert info["moe_assignments"] == 4 * 2 * positions
    assert sum(info["moe_expert_tokens"]) == info["moe_assignments_local"]
    assert 0 < info["moe_assignments_local"] < info["moe_assignments"]
    assert "experts: steps=4" in profiler.serving_summary()
    assert "window=" in profiler.serving_summary()


# -- (g) the widened kernels, interpreted ------------------------------------

@pytest.mark.parametrize("layout", ["rows", "four_axes"])
@pytest.mark.parametrize("sink", [False, True], ids=["no_sink", "sink"])
def test_ragged_decode_attention_two_lane_counts_and_a_sink(layout, sink):
    rng = np.random.RandomState(0)
    # K of 24 lanes allocated as two tiles, V as one: whole tiles, as the
    # kernel asks of a cache on the chip and here alike
    b, s, h, hkv, d, dk, dv = 3, 40, 4, 2, 24, 256, 128
    q = jnp.asarray(rng.randn(b, 1, h, d), jnp.float32)
    k = np.zeros((b, s, hkv, dk), np.float32)             # zeros behind D
    k[..., :d] = rng.randn(b, s, hkv, d)
    v = rng.randn(b, s, hkv, dv).astype(np.float32)
    lengths = np.asarray([0, 17, s], np.int32)
    for i, n in enumerate(lengths):                       # never read
        k[i, n:], v[i, n:] = np.nan, np.nan
    sk = jnp.asarray(rng.randn(h), jnp.float32) if sink else None
    want = _ragged_ref(q, jnp.nan_to_num(k), jnp.nan_to_num(v),
                       jnp.asarray(lengths), d ** -0.5, sk)
    if layout == "rows":
        got = ragged_decode_attention(
            q, jnp.asarray(k).reshape(b, s * hkv, dk),
            jnp.asarray(v).reshape(b, s * hkv, dv), jnp.asarray(lengths),
            sink=sk, num_kv_heads=hkv)
    else:
        got = ragged_decode_attention(q, jnp.asarray(k), jnp.asarray(v),
                                      jnp.asarray(lengths), sink=sk)
    assert got.shape == (b, 1, h, dv) and not np.isnan(got).any()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    if sink:                                # the sink took part of the weight
        bare = _ragged_ref(q, jnp.nan_to_num(k), jnp.nan_to_num(v),
                           jnp.asarray(lengths), d ** -0.5)
        assert np.abs(np.asarray(bare) - np.asarray(want)).max() > 1e-2


def test_rows_need_their_head_count():
    with pytest.raises(ValueError, match="num_kv_heads"):
        ragged_decode_attention(jnp.ones((1, 1, 2, 8)), jnp.ones((1, 16, 8)),
                                jnp.ones((1, 16, 8)), jnp.ones((1,), jnp.int32))


def test_kv_cache_append_into_rows_of_two_lane_counts():
    rng = np.random.RandomState(1)
    b, positions, hkv = 3, 8, 2
    kc = jnp.asarray(rng.randn(b, positions * hkv, 32), jnp.float32)
    vc = jnp.asarray(rng.randn(b, positions * hkv, 16), jnp.float32)
    kn = jnp.asarray(rng.randn(b, hkv, 32), jnp.float32)
    vn = jnp.asarray(rng.randn(b, hkv, 16), jnp.float32)
    off = jnp.asarray([0, 5, 7], jnp.int32)
    k2, v2 = kv_cache_append(kc, vc, kn, vn, off)
    for c, c2, n in ((kc, k2, kn), (vc, v2, vn)):
        want = np.asarray(c).copy()
        for i, o in enumerate(np.asarray(off)):
            want[i, o * hkv:(o + 1) * hkv] = np.asarray(n)[i]
        np.testing.assert_array_equal(np.asarray(c2), want)


@pytest.mark.parametrize("seq,window,sink", [
    (300, 8, True), (300, None, False), (384, 128, True), (130, 200, True)],
    ids=["narrow_band", "causal", "band_of_a_block", "window_over_seq"])
def test_windowed_flash_attention_matches_the_masked_softmax(seq, window,
                                                             sink):
    rng = np.random.RandomState(seq)
    q = jnp.asarray(rng.randn(seq, 4, 24), jnp.float32)
    k = jnp.asarray(rng.randn(seq, 2, 24), jnp.float32)
    v = jnp.asarray(rng.randn(seq, 2, 16), jnp.float32)
    sk = jnp.asarray(rng.randn(4), jnp.float32) if sink else None
    got = windowed_flash_attention(q[None], k[None], v[None], sk, window,
                                   None)
    want = reference_mimo.masked_attention(q, k, v, window, sk)
    assert got.shape == (1, seq, 4, 16)
    np.testing.assert_allclose(got[0], want, atol=2e-6, rtol=0)
    with pytest.raises(ValueError, match="lanes"):
        windowed_flash_attention(q[None], v[None], v[None])


def test_partial_rope_rotates_the_leading_lanes_only():
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(2, 5, 4, 24), jnp.float32)
    k = jnp.asarray(rng.randn(2, 5, 2, 24), jnp.float32)
    v = jnp.asarray(rng.randn(2, 5, 2, 16), jnp.float32)
    off = jnp.asarray([0, 3], jnp.int32)
    q2, k2, v2 = partial_rope(q, k, v, off, theta=1e4, rotary_dim=8,
                              value_scale=0.707)
    np.testing.assert_array_equal(q2[..., 8:], q[..., 8:])
    np.testing.assert_allclose(v2, 0.707 * v, rtol=1e-6)
    np.testing.assert_allclose(q2[0, 0], q[0, 0], atol=1e-6)   # position 0
    # row 1 starts at position 3: its first position is the reference's 3rd
    want = reference_mimo.partial_rope(
        jnp.concatenate([jnp.zeros((3, 2, 24)), k[1]]), 1e4, 8)[3:]
    np.testing.assert_allclose(k2[1], want, atol=1e-5)


def test_mimo_attention_decodes_through_a_ring_as_through_a_full_cache():
    """One layer's attention, a prefill then six decode steps: through a
    ring of 8 positions with the window 8, and through a full cache with the
    window's mask: the same outputs."""
    rng = np.random.RandomState(4)
    h, hkv, d, dv, w, s0 = 4, 2, 24, 16, 8, 11
    mk = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    sink = mk(h)
    qs, ks, vs = mk(1, 20, h, d), mk(1, 20, hkv, d), mk(1, 20, hkv, dv)
    want = reference_mimo.masked_attention(qs[0], ks[0], vs[0], w, sink)
    ring = (jnp.zeros((1, w * hkv, 128), jnp.float32),
            jnp.zeros((1, w * hkv, 128), jnp.float32))
    zero, lens = jnp.zeros((1,), jnp.int32), jnp.asarray([s0], jnp.int32)
    # a bucket of 16 around 11 real positions
    out, kc, vc = mimo_attention(qs[:, :16], ks[:, :16], vs[:, :16], *ring,
                                 zero, lens, sink, window=w)
    np.testing.assert_allclose(out[0, :s0], want[:s0], atol=1e-5)
    for t in range(s0, 17):
        out, kc, vc = mimo_attention(
            qs[:, t:t + 1], ks[:, t:t + 1], vs[:, t:t + 1], kc, vc,
            jnp.asarray([t], jnp.int32), jnp.ones((1,), jnp.int32), sink,
            window=w)
        np.testing.assert_allclose(out[0, 0], want[t], atol=1e-5)
