"""Jamba (Mamba layers beside attention) against its plain reference, at a
small size on the CPU with seeded random weights: the model's forward, the
serving engine's padded prefill and decode through a state of two kinds, the
selective-scan and one-KV-head decode kernels (interpreted), and the typed
refusals of what cannot work over a recurrent state.

Tolerances, with their reasons.  Model and reference are both float32 here
and differ only in the order of their sums, so logits (range ~4) agree to
~5e-6; LOGIT_TOL is 1e-4, twenty times that.  An SSM state kept in bfloat16
rounds at 2**-9 a step and reads 6e-3 to 2e-2 after a dozen decode steps,
sixty times over the tolerance: the comparison sees a lower precision.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as P
from benchmarks import reference_jamba
from paddle_tpu.inference.serving import (
    FixedSlotStateUnsupported, ServingEngine)
from paddle_tpu.models import JambaConfig, JambaForCausalLM
from paddle_tpu.models.jamba import (
    jamba_attention, mamba_conv1d, tied_lm_head)
from paddle_tpu.ops.pallas.decode_attention import (
    _ragged_ref, mqa_decode_attention)
from paddle_tpu.ops.pallas.selective_scan import (
    selective_scan, selective_scan_ref)

LOGIT_TOL = 1e-4
VOCAB = 128


def _model(seed=5, **over):
    P.seed(seed)
    m = JambaForCausalLM(dataclasses.replace(JambaConfig.tiny(vocab=VOCAB),
                                             **over))
    m.eval()
    return m


def _reference_logits(m, ids, positions):
    ref = reference_jamba.make_reference(dataclasses.asdict(m.config))
    weights = {n: p._value for n, p in m.named_parameters()}
    return np.asarray(ref(weights, m.config.num_hidden_layers,
                          jnp.asarray(ids), jnp.asarray(positions)))


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, (n,))


# -- (a) the model's full forward --------------------------------------------

@pytest.mark.parametrize("kv_heads", [1, 2], ids=["mqa", "gqa"])
def test_forward_matches_the_reference_on_logits(kv_heads):
    m = _model(num_key_value_heads=kv_heads)
    assert [l.is_attention for l in m.model.layers] == [
        False, False, True, False] * 2            # period 4, offset 2
    ids = np.stack([_prompt(37, 1), _prompt(37, 2)])
    with P.no_grad():
        got = m(P.to_tensor(ids)).numpy()
    for row in range(2):
        want = _reference_logits(m, ids[row], np.arange(37))
        np.testing.assert_allclose(got[row], want, atol=LOGIT_TOL, rtol=0)


def test_the_scan_has_no_backward_pass_and_says_so():
    m = _model()
    with pytest.raises(NotImplementedError, match="forward only"):
        m(P.to_tensor(_prompt(12).reshape(1, -1))).sum().backward()


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


@pytest.mark.parametrize("plen", [16, 11], ids=["fills_bucket", "padded"])
@pytest.mark.parametrize("state", ["float32", "bfloat16"])
def test_engine_prefill_then_decode_against_the_reference(plen, state):
    m = _model(ssm_state_dtype=state)
    rows, out, eng = _engine_logits(m, _prompt(plen, plen), 12)
    assert eng.info()["prefill_positions_padded"] == 16
    want = _reference_logits(m, out[:-1], plen - 1 + np.arange(12))
    err = np.abs(rows - want).max(axis=1)
    assert err[0] <= LOGIT_TOL      # the prefill's own row: no state was read
    if state == "float32":
        assert err.max() <= LOGIT_TOL
    else:
        assert err.max() > 10 * LOGIT_TOL, "a bfloat16 state must be seen"


# -- (c) right padding does not run the recurrence on ------------------------

def test_state_after_a_padded_prefill_is_the_unpadded_one():
    m = _model()
    prompt = _prompt(11, 3)
    states = []
    for buckets in ([16], [11]):
        eng = ServingEngine(m, max_batch=2, max_seq_len=64,
                            prefill_buckets=buckets)
        eng.submit(prompt, max_new_tokens=1)
        eng.step()
        assert eng.info()["prefill_positions_padded"] == buckets[0]
        states.append([np.asarray(leaf[0]) for layer, pair in
                       zip(m.model.layers, eng._caches)
                       if not layer.is_attention for leaf in pair])
    assert len(states[0]) == 12                   # 6 Mamba layers x 2 leaves
    for padded, exact in zip(*states):
        assert np.abs(exact).max() > 0
        np.testing.assert_allclose(padded, exact, atol=1e-6, rtol=0)


# -- (d) slots are independent: join, finish, reuse --------------------------

def test_join_finish_and_reuse_leave_other_slots_bitwise_unchanged():
    m = _model()
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
    # the reused slot's request reads what it would read alone
    fresh = ServingEngine(m, max_batch=3, max_seq_len=64)
    same = fresh.submit(_prompt(6, 10), max_new_tokens=4)
    fresh.run()
    np.testing.assert_array_equal(reuse.result(), same.result())
    # one lowering a bucket used and one for the decode step, joins or not
    assert eng.info()["step"]["lowerings"] == 4           # 8, 16, 32 + decode


# -- (e) the kernels, interpreted ---------------------------------------------

def _scan_inputs(b, s, di, n, dtype, seed=0):
    k = jax.random.split(jax.random.key(seed), 8)
    norm = lambda i, shape: jax.random.normal(k[i], shape, jnp.float32)
    return (norm(0, (b, s, di)).astype(dtype),
            jax.nn.softplus(norm(1, (b, s, di)) - 2.0),
            -jnp.exp(0.5 * norm(2, (di, n))), norm(3, (b, s, n)),
            norm(4, (b, s, n)), norm(5, (di,)),
            norm(6, (b, s, di)).astype(dtype), norm(7, (b, n, di)))


@pytest.mark.parametrize("shape,lengths", [
    ((2, 13, 128, 8), [13, 5]),             # one short chunk, a padded row
    ((4, 150, 256, 16), [150, 64, 70, 1]),  # chunk boundaries at 64 and 128
    ((1, 64, 1024, 16), [33]),              # a whole 8 x 128 channel block
    ((1, 40, 96, 4), [40]),                 # channels padded to 128
], ids=["short", "ragged_across_chunks", "block_of_rows", "padded_channels"])
def test_selective_scan_matches_the_recurrence(shape, lengths):
    args = _scan_inputs(*shape, jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    y, h = selective_scan(*args, lens)
    y_ref, h_ref = selective_scan_ref(*args, lens)
    # float32 both; exp and the sums associate differently: ~5e-6 on O(1)
    np.testing.assert_allclose(h, h_ref, atol=2e-5, rtol=0)
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(y[row, :n], y_ref[row, :n], atol=5e-5,
                                   rtol=0)


def test_selective_scan_stops_the_state_at_the_length():
    """What comes after the length moves neither the state nor the outputs
    before it."""
    args = list(_scan_inputs(1, 96, 128, 8, jnp.float32))
    lens = jnp.asarray([70], jnp.int32)
    _, h = selective_scan(*args, lens)
    cut = [a[:, :70] if a.ndim == 3 and a.shape[1] == 96 else a for a in args]
    _, h_cut = selective_scan(*cut, lens)
    np.testing.assert_allclose(h, h_cut, atol=1e-6, rtol=0)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_mqa_decode_attention_matches_the_masked_softmax(dtype, tol):
    """One KV head, its axis folded out of the cache.  bfloat16: p rounds to
    8 bits before p.V, as in the flash kernels."""
    k = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(k[0], (3, 1, 20, 128)).astype(dtype)
    kc = jax.random.normal(k[1], (3, 300, 128)).astype(dtype)
    vc = jax.random.normal(k[2], (3, 300, 128)).astype(dtype)
    lens = jnp.asarray([300, 17, 0], jnp.int32)
    got = mqa_decode_attention(q, kc, vc, lens)
    want = _ragged_ref(q, kc[:, :, None], vc[:, :, None], lens, 128 ** -0.5)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=0)
    assert not np.asarray(got[2], np.float32).any()


# -- (f) what a recurrent state cannot do is refused, typed ------------------

@pytest.mark.parametrize("option", [
    {"prefix_sharing": True}, {"prefill_chunk": 16}, {"spec_k": 2}],
    ids=lambda o: next(iter(o)))
def test_engine_refuses_what_a_recurrent_state_cannot_do(option):
    with pytest.raises(FixedSlotStateUnsupported) as e:
        ServingEngine(_model(), max_batch=2, max_seq_len=64, **option)
    assert e.value.param == next(iter(option))
    assert isinstance(e.value, NotImplementedError)


def test_engine_refuses_the_environment_knobs_too(monkeypatch):
    monkeypatch.setenv("PT_SERVE_PREFILL_CHUNK", "8")
    with pytest.raises(FixedSlotStateUnsupported, match="prefill_chunk"):
        ServingEngine(_model(), max_batch=2, max_seq_len=64)


# -- the cache by kind ---------------------------------------------------------

def test_info_reports_the_cache_by_kind():
    m = _model()
    eng = ServingEngine(m, max_batch=2, max_seq_len=64, page_size=16)
    info = eng.info()
    di, n, k = m.config.d_inner, 8, 4
    assert info["state_bytes_per_slot"] == 6 * (4 * n * di + 4 * (k - 1) * di)
    assert info["kv_bytes_per_position"] == 2 * 2 * 16 * 4    # 2 layers, K V
    assert info["cache_bytes"] == {
        "kv": 2 * 64 * info["kv_bytes_per_position"],
        "state": 2 * info["state_bytes_per_slot"], "window": 0}
    pool = info["pool"]
    assert pool["page_bytes"] == 16 * info["kv_bytes_per_position"]
    assert pool["slot_state_bytes"] == info["state_bytes_per_slot"]
    eng.generate([_prompt(5), _prompt(12, 1)], max_new_tokens=2)
    info = eng.info()
    assert info["prefill_positions"] == 17
    assert info["prefill_positions_padded"] == 8 + 16
    # the decode step updates every Mamba layer's float32 state of d_inner
    # 128 in place (`ssm_decode_update`), none through the jnp fusion
    assert info["step"]["ssm_update"] == {"kernel": 6, "fusion": 0}


def test_llama_engine_reports_the_same_counters_and_the_pad_attribute():
    """The Llama family through the pytree cache: all K/V, no fixed state,
    `pad` on the prefill span beside `bucket`, and the summary's line."""
    from paddle_tpu import profiler
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import trace
    P.seed(11)
    m = LlamaForCausalLM(LlamaConfig.tiny(vocab=VOCAB, hidden=32, layers=2,
                                          heads=4, inter=64, seq=64))
    eng = ServingEngine(m, max_batch=2, max_seq_len=64)
    was_on = trace.enabled()
    trace.enable(True)
    try:
        trace.trace_clear()
        eng.generate([_prompt(5)], max_new_tokens=2)
        spans = [r for r in trace.trace_records()
                 if r["name"] == "engine.prefill"]
    finally:
        trace.enable(was_on)
    assert [(s["args"]["bucket"], s["args"]["prompt_len"], s["args"]["pad"])
            for s in spans] == [(8, 5, 3)]
    info = eng.info()
    assert info["state_bytes_per_slot"] == 0
    assert info["kv_bytes_per_position"] == 2 * 2 * 4 * 8 * 4   # L, K V, H, D
    assert info["cache_bytes"] == {"kv": 2 * 64 * 512, "state": 0, "window": 0}
    assert (info["prefill_positions"], info["prefill_positions_padded"]) \
        == (5, 8)
    assert "cache: kv=0.1 MB (512 B a position) state=0.0 MB (0 B a slot)" \
        in profiler.serving_summary()


# -- the three ops the model dispatches under names of its own ----------------

def test_mamba_conv1d_is_causal_and_keeps_the_last_real_inputs():
    """Against a plain loop in float64: out[t] = silu(bias + sum_j w[:, j] *
    x[t + j - 3]) with the carried window in front; the new window is the 3
    inputs before each row's length, whatever its right padding holds."""
    rs = np.random.RandomState(3)
    b, s, di, k = 2, 7, 6, 4
    xz, win = rs.randn(b, s, 2 * di), rs.randn(b, (k - 1) * di)
    w, bias, lens = rs.randn(di, k), rs.randn(di), np.array([7, 4])
    u, z, new_win = mamba_conv1d(*(jnp.asarray(a, jnp.float32) for a in
                                   (xz, win, w, bias)), jnp.asarray(lens))
    ext = np.concatenate([win.reshape(b, k - 1, di), xz[..., :di]], axis=1)
    pre = np.stack([bias + sum(w[:, j] * ext[:, t + j] for j in range(k))
                    for t in range(s)], axis=1)
    np.testing.assert_allclose(u, pre / (1 + np.exp(-pre)), atol=1e-5)
    np.testing.assert_array_equal(z, xz[..., di:].astype(np.float32))
    for row, n in enumerate(lens):
        np.testing.assert_array_equal(
            np.asarray(new_win[row]).reshape(k - 1, di),
            ext[row, n:n + k - 1].astype(np.float32))


@pytest.mark.parametrize("kv_heads", [1, 2], ids=["folded_cache", "gqa"])
def test_jamba_attention_window_over_a_cache(kv_heads):
    """A 5-position window at offsets 0 and 3 over a cache whose prefix
    holds earlier keys: the masked softmax over positions <= each query's,
    and the window written into the cache at the offset."""
    rs = np.random.RandomState(4)
    b, s, nh, d, s_max = 2, 5, 4, 8, 16
    shape = (b, s_max) + ((d,) if kv_heads == 1 else (kv_heads, d))
    f = lambda *sh: jnp.asarray(rs.randn(*sh), jnp.float32)
    q, kn, vn = f(b, s, nh, d), f(b, s, kv_heads * d), f(b, s, kv_heads * d)
    kc, vc, off = f(*shape), f(*shape), jnp.asarray([0, 3], jnp.int32)
    out, kc2, vc2 = jamba_attention(q, kn, vn, kc, vc, off,
                                    num_kv_heads=kv_heads)
    for row, o in enumerate([0, 3]):
        k4 = np.array(kc[row]).reshape(s_max, kv_heads, d)
        v4 = np.array(vc[row]).reshape(s_max, kv_heads, d)
        k4[o:o + s] = np.asarray(kn[row]).reshape(s, kv_heads, d)
        v4[o:o + s] = np.asarray(vn[row]).reshape(s, kv_heads, d)
        np.testing.assert_array_equal(
            np.asarray(kc2[row]).reshape(s_max, kv_heads, d), k4)
        for t in range(s):
            for h in range(nh):
                g = h // (nh // kv_heads)
                sc = k4[:o + t + 1, g] @ np.asarray(q[row, t, h]) / d ** 0.5
                p = np.exp(sc - sc.max())
                np.testing.assert_allclose(
                    out[row, t, h], (p / p.sum()) @ v4[:o + t + 1, g],
                    atol=1e-5)


def test_tied_lm_head_is_float32_from_the_accumulator():
    """bfloat16 hidden states on a bfloat16 embedding give float32 logits
    that carry more than bfloat16's 8 bits: a near-tie is not decided by a
    rounding of the logits."""
    rs = np.random.RandomState(6)
    h = jnp.asarray(rs.randn(3, 64), jnp.bfloat16)
    w = jnp.asarray(rs.randn(VOCAB, 64), jnp.bfloat16)
    got = tied_lm_head(h, w)
    assert got.dtype == jnp.float32 and got.shape == (3, VOCAB)
    want = np.asarray(h, np.float64) @ np.asarray(w, np.float64).T
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert np.abs(np.asarray(got.astype(jnp.bfloat16), np.float64)
                  - want).max() > 1e-3

