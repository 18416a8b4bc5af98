"""Phi-4-mini-flash-reasoning (SambaY: Mamba, window and full attention, a
cross-decoder of Gated Memory Units and cross-attention over one shared
cache, differential attention) against its plain reference, at a small size
on the CPU with seeded random weights: the serving step's padded prefill
(the cross-decoder on the last position only) and decode through three
kinds of cache, differential attention through the kernels (interpreted),
the GMU's memory, and the typed refusals of what state and rings cannot
do.

Tolerances, with their reasons.  Model and reference are both float32 here
and differ only in the order of their sums, so logits (range ~8) agree to
~1e-5; LOGIT_TOL is 1e-4, ten times that.  Each variant the benchmark's
`correct` must catch reads far over it at this size (window 8, so a key is
an eighth of a head's weight): `test_engine_sees_every_variant` asserts each
at ten times the tolerance.
"""
import dataclasses
import types

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as P
from benchmarks import reference_phi4flash
from paddle_tpu.inference.serving import (
    FixedSlotStateUnsupported, ServingEngine)
from paddle_tpu.models import (
    JambaConfig, JambaMambaMixer, Phi4FlashConfig, Phi4FlashForCausalLM)
from paddle_tpu.models import phi4flash
from paddle_tpu.models.phi4flash import (
    cross_attention, diff_combine, diff_lambda, diff_query_rows, last_rows,
    layer_norm)
from paddle_tpu.models.steps import build_step
from paddle_tpu.observability import trace
from paddle_tpu.ops.pallas.decode_attention import cache_lanes

LOGIT_TOL = 1e-4
LAYER_TOL = 1e-4
VOCAB = 128
WINDOW = 8


def _model(seed=5, **over):
    """The tiny model with its LayerNorm biases and the differential
    RMSNorm's weight drawn too, so that leaving one out would show."""
    P.seed(seed)
    m = Phi4FlashForCausalLM(dataclasses.replace(
        Phi4FlashConfig.tiny(vocab=VOCAB, window=WINDOW), **over))
    m.eval()
    rng = np.random.RandomState(seed)
    for n, p in m.named_parameters():
        if n.endswith(("norm.bias", "norm.weight", "subln.weight")):
            p._set_value(jnp.asarray(
                np.asarray(p._value) + 0.1 * rng.randn(*p.shape),
                p._value.dtype))
    return m


def _weights(m):
    return {n: p._value for n, p in m.named_parameters()}


def _reference_logits(m, ids, positions):
    ref = reference_phi4flash.make_reference(dataclasses.asdict(m.config))
    return np.asarray(ref(_weights(m), m.config.num_hidden_layers,
                          jnp.asarray(ids), jnp.asarray(positions)))


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, (n,))


# -- (a) the layer map and the full forward ---------------------------------

def test_the_layer_map_and_what_the_config_refuses():
    cfg = Phi4FlashConfig()
    kinds = [cfg.layer_kind(i) for i in range(32)]
    assert kinds[:16] == ["mamba", "window"] * 8
    assert kinds[16:18] == ["mamba", "full"]
    assert kinds[18:] == ["gmu", "cross"] * 7
    assert (cfg.memory_layer, cfg.full_layer, cfg.head_dim,
            cfg.d_inner) == (16, 17, 64, 5120)
    assert cfg.lambda_init(0) == pytest.approx(0.2)
    assert cfg.lambda_init(31) == pytest.approx(0.8 - 0.6 * np.exp(-9.3))
    assert [Phi4FlashConfig.tiny().layer_kind(i) for i in range(8)] == [
        "mamba", "window", "mamba", "window", "mamba", "full", "gmu", "cross"]
    with pytest.raises(ValueError, match="multiple of 4"):
        Phi4FlashConfig(num_hidden_layers=30)
    with pytest.raises(NotImplementedError, match="mb_per_layer"):
        Phi4FlashConfig(mb_per_layer=3)
    with pytest.raises(NotImplementedError, match="tied head"):
        Phi4FlashConfig(tie_word_embeddings=False)


def _slot_step(m, tok, last_pos, kind="slot_logits"):
    """The serving step over fresh caches: a padded prefill of `tok`
    [B, S] up to each row's `last_pos`."""
    step = build_step(m, kind)
    params = [p._value for p in m.parameters()]
    b = tok.shape[0]
    caches = [(a._value, c._value) for a, c in m.init_kv_caches(b, 64)]
    return step(params, jnp.asarray(tok), caches, jnp.zeros((b,), jnp.int32),
                jnp.asarray(last_pos, jnp.int32))


def test_forward_matches_the_reference_on_logits():
    """The served prefill, two rows of different lengths in one batch (the
    cross-decoder on each row's last position), against the reference,
    which runs the cross-decoder over every position."""
    m = _model()
    prompts = [_prompt(29, 1), _prompt(17, 2)]
    tok = np.zeros((2, 32), np.int64)
    for row, p in enumerate(prompts):
        tok[row, :p.size] = p
    nxt, logits, counted, _ = _slot_step(m, tok, [28, 16])
    assert np.asarray(counted).tolist() == [2 * 32, 2]
    for row, p in enumerate(prompts):
        want = _reference_logits(m, p, [p.size - 1])[0]
        np.testing.assert_allclose(np.asarray(logits)[row], want,
                                   atol=LOGIT_TOL, rtol=0)
        assert int(np.asarray(nxt)[row]) == int(np.argmax(want))


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


@pytest.mark.parametrize("plen", [5, 11, 16, 27], ids=[
    "shorter_than_the_window", "longer_padded", "fills_a_bucket",
    "second_bucket"])
def test_engine_prefill_then_decode_against_the_reference(plen):
    m = _model()
    rows, out, eng = _engine_logits(m, _prompt(plen, plen), 12)
    info = eng.info()
    bucket = 16 if plen <= 16 else 32
    assert info["prefill_positions_padded"] == bucket
    # the prefill ran the self-decoder over its bucket and the cross-decoder
    # on one position; each decode step both on one position a slot
    assert info["self_decoder_positions"] == bucket + 2 * info["decode_steps"]
    assert info["cross_decoder_positions"] == 1 + 2 * info["decode_steps"]
    want = _reference_logits(m, out[:-1], plen - 1 + np.arange(12))
    assert np.abs(rows - want).max() <= LOGIT_TOL


def test_the_prefill_runs_the_cross_decoder_on_the_last_position_only():
    """The slot step at a bucket of 16 with the prompt's last real token at
    10: its two halves as traced (the ring's spans), its counters, and the
    last position's logits against the reference, which runs the
    cross-decoder on every position."""
    m = _model()
    prompt = _prompt(11, 4)
    tok = np.zeros((1, 16), np.int64)
    tok[0, :11] = prompt
    was = trace.enabled()
    trace.enable(True)
    trace.trace_clear()
    try:
        nxt, logits, counted, _ = _slot_step(m, tok, [10])
        spans = {r["name"]: r["args"]["positions"]
                 for r in trace.trace_records()
                 if r["name"].startswith("phi4flash.")}
    finally:
        trace.enable(was)
    assert spans == {"phi4flash.self_decoder": 16,
                     "phi4flash.cross_decoder": 1}
    assert np.asarray(counted).tolist() == [16, 1]
    want = _reference_logits(m, prompt, [10])[0]
    np.testing.assert_allclose(np.asarray(logits)[0], want, atol=LOGIT_TOL,
                               rtol=0)
    assert int(np.asarray(nxt)[0]) == int(np.argmax(want))


def _skips_nothing(monkeypatch):
    """A prefill that runs the cross-decoder over every position of its
    window: no last rows taken, and a cross layer that hands back its query
    rows (the decode kernel takes one position a row)."""
    monkeypatch.setattr(phi4flash, "last_rows", lambda x, last: x)
    monkeypatch.setattr(phi4flash, "cross_attention",
                        lambda q, kc, vc, lengths, **kw: q)


@pytest.mark.parametrize("skips", [True, False],
                         ids=["skips", "skips_nothing"])
def test_the_cross_decoder_share_reads_what_the_prefill_ran(skips,
                                                            monkeypatch):
    """The benchmark's `cross_decoder_prefill_share` over an engine's
    counters: one position a prefill when the prefill skips, every one of
    the bucket's when it does not (the counters are the shapes each half
    ran at, not a count the step assumes)."""
    from benchmarks.readers import cross_decoder_prefill_share
    m = _model()
    if not skips:
        _skips_nothing(monkeypatch)
    eng = ServingEngine(m, max_batch=2, max_seq_len=64,
                        prefill_buckets=[16, 32])
    before = eng.info()
    eng.submit(_prompt(11, 11), max_new_tokens=3)
    eng.run()
    got = cross_decoder_prefill_share.read({
        "cell": types.SimpleNamespace(traffic={"slots": 2}),
        "engine_info": {"before": before, "after": eng.info()}})
    assert got["detail"]["self_decoder"] == 16
    assert got["value"] == pytest.approx(100 / 16 if skips else 100.0)


def test_a_decode_step_reads_each_cache_once_a_layer():
    """One decode kernel call a reading layer: the two window rings, the
    full layer and the cross layer each once (a two-call differential
    attention would read every cache twice)."""
    import jax
    from paddle_tpu.jit import capture
    m = _model()
    capture.set_step_capture_enabled(False)
    try:
        step = build_step(m, "slot")
    finally:
        capture.set_step_capture_enabled(True)
    params = [p._value for p in m.parameters()]
    caches = [(a._value, b._value) for a, b in m.init_kv_caches(3, 64)]
    text = str(jax.make_jaxpr(step)(
        params, jnp.zeros((3, 1), jnp.int32), caches,
        jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.int32)))
    # the kernel's one jitted entry (`_ragged`), called once a layer
    assert text.count("jit[name=_ragged") == 4


# -- (c) differential attention through the kernels, three forms -------------

def _slots_through_layer(m, i, normed, reals, steps, shared=None):
    """Layer i's mixer over len(reals) slots of one sequence's normed
    input [1, n, hidden]: a padded prefill a slot (the cross layer at its
    last real position over `shared`), the slots' caches side by side, then
    `steps` decode steps of all of them together.  Returns the prefill
    outputs a slot, the decode outputs a step and the caches after them."""
    layer = m.model.layers[i]
    n, i32 = normed.shape[1], lambda a: jnp.asarray(a, jnp.int32)
    at = lambda pos: P.to_tensor(normed._value[0, pos][:, None])
    if layer.kind == "cross":
        got, _, _ = layer.mixer(at(reals - 1), None, i32(reals - 1), None,
                                shared=shared)
        pre, cache = [np.asarray(got._value[:, 0])], None
    else:
        slots, pre = [], []
        for real in reals:
            got, c, _ = layer.mixer(normed, m.init_kv_caches(1, n)[i],
                                    i32([0]), i32([real]))
            pre.append(np.asarray(got._value[0, :real]))
            slots.append(c)
        cache = tuple(P.to_tensor(jnp.concatenate([c[j]._value for c in slots]))
                      for j in (0, 1))
    dec = []
    for t in range(steps):
        pos = reals + t
        got, cache, _ = layer.mixer(at(pos), cache, i32(pos),
                                    i32(np.ones(len(reals))), shared=shared)
        dec.append(np.asarray(got._value[:, 0]))
    return pre, dec, cache


@pytest.mark.parametrize("head_dim", [32, 64], ids=["lanes_padded",
                                                    "whole_tiles"])
def test_differential_attention_in_three_forms_against_two_softmaxes(
        head_dim):
    """The window layer over its ring, the full layer over its cache and the
    cross layer over the full layer's, in the kernels' interpret mode, at
    ragged lengths either side of the window, against the reference's two
    softmaxes on each slot's own sequence."""
    m = _model(hidden_size=4 * head_dim)
    n = 24
    layers_of = reference_phi4flash.make_layer_reference(
        dataclasses.asdict(m.config))
    refs = layers_of(_weights(m), 8, jnp.asarray(_prompt(n, 9)))
    reals = np.asarray([5, 11, 16])           # < window, > window, padded
    steps = 3
    cfg = m.config
    normed = {i: m.model.layers[i].input_layernorm(
        P.to_tensor(refs[i]["x_in"][None])) for i in (1, 5, 7)}
    with P.no_grad():
        _, _, shared = _slots_through_layer(m, cfg.full_layer,
                                            normed[5], reals, steps)
        for i in (1, 5, 7):
            pre, dec, _ = _slots_through_layer(m, i, normed[i], reals, steps,
                                               shared)
            want = np.asarray(refs[i]["mix"])
            if m.model.layers[i].kind == "cross":
                np.testing.assert_allclose(pre[0], want[reals - 1],
                                           atol=LAYER_TOL, rtol=0)
            else:
                for got, real in zip(pre, reals):
                    np.testing.assert_allclose(got, want[:real],
                                               atol=LAYER_TOL, rtol=0)
            for t, got in enumerate(dec):
                np.testing.assert_allclose(got, want[reals + t],
                                           atol=LAYER_TOL, rtol=0)
    # the cache rows: K as k1_g || k2_g, V as one head of 2D lanes, the
    # lanes padded to whole tiles behind
    lanes = cache_lanes(2 * head_dim)
    k_rows = np.asarray(shared[0]._value).reshape(3, n, 2, lanes)
    np.testing.assert_allclose(k_rows[0, :5, :, :2 * head_dim],
                               np.asarray(refs[5]["k"])[:5], atol=1e-5,
                               rtol=0)
    assert not np.asarray(k_rows[..., 2 * head_dim:]).any()


def test_the_kernel_query_rows_carry_one_half_each():
    q = jnp.arange(16, dtype=jnp.float32).reshape(1, 1, 16) + 1
    rows = np.asarray(diff_query_rows(q, head_dim=4))[0, 0]
    assert rows.shape == (4, 8)
    # head pair i: [q1_i || 0] then [0 || q2_i]
    np.testing.assert_array_equal(rows[0], [1, 2, 3, 4, 0, 0, 0, 0])
    np.testing.assert_array_equal(rows[1], [0, 0, 0, 0, 5, 6, 7, 8])
    np.testing.assert_array_equal(rows[2], [9, 10, 11, 12, 0, 0, 0, 0])


def test_the_differential_pieces_against_their_equations():
    """lambda, the subtraction with its RMSNorm and scale, the last rows of
    a prefill, the float32 LayerNorm, and cross-attention on one position
    against the softmax written out."""
    rng = np.random.RandomState(0)
    lq1, lk1, lq2, lk2 = (rng.randn(8).astype(np.float32) * 0.1
                          for _ in range(4))
    lam = float(diff_lambda(lq1, lk1, lq2, lk2, lambda_init=0.3))
    assert lam == pytest.approx(np.exp(lq1 @ lk1) - np.exp(lq2 @ lk2) + 0.3,
                                rel=1e-6)
    out = rng.randn(1, 2, 4, 16).astype(np.float32)      # 2 head pairs
    w = rng.rand(16).astype(np.float32) + 0.5
    got = np.asarray(diff_combine(out, lam, w, lambda_init=0.3, eps=1e-5))
    d = out.reshape(1, 2, 2, 2, 16)
    d = d[..., 0, :] - lam * d[..., 1, :]
    want = d / np.sqrt((d ** 2).mean(-1, keepdims=True) + 1e-5) * w * 0.7
    np.testing.assert_allclose(got, want.reshape(1, 2, 32), rtol=1e-5)
    x = rng.randn(2, 5, 3).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(last_rows(x, jnp.asarray([4, 1]))),
                                  np.stack([x[0, 4:5], x[1, 1:2]]))
    b = rng.randn(3).astype(np.float32)
    ln = np.asarray(layer_norm(x, np.ones(3, np.float32), b, eps=1e-5))
    mu, var = x.mean(-1, keepdims=True), x.var(-1, keepdims=True)
    np.testing.assert_allclose(ln, (x - mu) / np.sqrt(var + 1e-5) + b,
                               rtol=1e-4, atol=1e-5)
    # one query over the first 4 of a cache's 6 positions (K/V row g of
    # position p at p * 2 + g; query head h reads row h // 2)
    q = rng.randn(1, 1, 4, 128).astype(np.float32)
    k = rng.randn(6, 2, 128).astype(np.float32)
    v = rng.randn(6, 2, 128).astype(np.float32)
    one = np.asarray(cross_attention(
        q, k.reshape(1, 12, 128), v.reshape(1, 12, 128), jnp.asarray([4]),
        num_kv_heads=2, scale=0.125))
    for h in range(4):
        sc = k[:4, h // 2] @ q[0, 0, h] * 0.125
        a = np.exp(sc - sc.max())
        np.testing.assert_allclose(one[0, 0, h], a @ v[:4, h // 2] / a.sum(),
                                   atol=1e-5, rtol=0)


# -- (d) the GMU reads the memory layer's gated inner output ----------------

def test_the_gmu_reads_the_memory_layer_and_no_other():
    m = _model()
    cfg = m.config
    n = 20
    refs = reference_phi4flash.make_layer_reference(
        dataclasses.asdict(cfg))(_weights(m), 8, jnp.asarray(_prompt(n, 3)))
    layers = m.model.layers
    i32 = lambda a: jnp.asarray(a, jnp.int32)

    def memory_of(i):
        y = layers[i].input_layernorm(P.to_tensor(refs[i]["x_in"][None]))
        _, _, inner = layers[i].mixer(y, m.init_kv_caches(1, n)[i],
                                      i32([0]), i32([n]))
        return inner

    gmu = cfg.memory_layer + 2
    assert layers[gmu].kind == "gmu"
    y = layers[gmu].input_layernorm(P.to_tensor(refs[gmu]["x_in"][None]))
    want = np.asarray(refs[gmu]["mix"])
    with P.no_grad():
        mem = memory_of(cfg.memory_layer)
        np.testing.assert_allclose(np.asarray(mem._value[0]),
                                   np.asarray(refs[cfg.memory_layer]["m"]),
                                   atol=1e-5, rtol=0)
        got, _, _ = layers[gmu].mixer(y, None, None, None, memory=mem)
        np.testing.assert_allclose(np.asarray(got._value[0]), want,
                                   atol=LAYER_TOL, rtol=0)
        other, _, _ = layers[gmu].mixer(y, None, None, None,
                                        memory=memory_of(0))
        assert np.abs(np.asarray(other._value[0]) - want).max() \
            > 100 * LAYER_TOL


# -- (e) what the checks must catch ------------------------------------------

def _lambda_zero(m, monkeypatch):
    monkeypatch.setattr(phi4flash, "diff_lambda",
                        lambda *a, lambda_init: jnp.float32(0.0))


def _no_subln(m, monkeypatch):
    def unnormed(out, lam, w, *, lambda_init, eps):
        # the same subtraction and scale, without the RMSNorm
        b, s, nh, dv = out.shape
        o = out.astype(jnp.float32).reshape(b, s, nh // 2, 2, dv)
        d = (o[..., 0, :] - lam * o[..., 1, :]) * (1.0 - lambda_init)
        return d.reshape(b, s, -1).astype(out.dtype)
    monkeypatch.setattr(phi4flash, "diff_combine", unnormed)


def _short_window(m, monkeypatch):
    for layer in m.model.layers:
        if layer.kind == "window":
            layer.self_attn.window = WINDOW - 1


def _wrong_memory(m, monkeypatch):
    monkeypatch.setattr(Phi4FlashConfig, "memory_layer", property(
        lambda self: self.num_hidden_layers // 2 - 2))


@pytest.mark.parametrize("variant", [_lambda_zero, _no_subln, _short_window,
                                     _wrong_memory],
                         ids=lambda f: f.__name__.strip("_"))
def test_engine_sees_every_variant(variant, monkeypatch):
    """What the benchmark's `correct` has to catch, each FAILING the
    tolerance the unaltered program passes
    (`test_engine_prefill_then_decode_against_the_reference`): lambda 0,
    the differential RMSNorm left out, the window one position short, the
    memory taken from another Mamba layer."""
    m = _model()
    prompt = _prompt(13, 13)
    variant(m, monkeypatch)
    rows, out, _ = _engine_logits(m, prompt, 10)
    monkeypatch.undo()                         # the reference's own config
    want = _reference_logits(m, out[:-1], 12 + np.arange(10))
    assert np.abs(rows - want).max() > 10 * LOGIT_TOL


# -- (f) slots are independent: join, finish, reuse --------------------------

def test_join_finish_and_reuse_leave_other_slots_bitwise_unchanged():
    m = _model()
    long_prompt = _prompt(13, 7)
    alone = ServingEngine(m, max_batch=3, max_seq_len=64)
    want = alone.submit(long_prompt, max_new_tokens=20)
    alone.run()

    eng = ServingEngine(m, max_batch=3, max_seq_len=64)
    watched = eng.submit(long_prompt, max_new_tokens=20)
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
    info = eng.info()
    assert info["step"]["lowerings"] == 4                 # 8, 16, 32 + decode
    assert info["step"]["bailouts"] == 0
    # the 3 Mamba layers (d_inner 256) update their state in place
    assert info["step"]["ssm_update"] == {"kernel": 3, "fusion": 0}


# -- (g) what state and rings cannot do is refused, typed --------------------

@pytest.mark.parametrize("option", [
    {"prefix_sharing": True}, {"prefill_chunk": 16}, {"spec_k": 2}],
    ids=lambda o: next(iter(o)))
def test_engine_refuses_what_state_and_rings_cannot_do(option):
    with pytest.raises(FixedSlotStateUnsupported) as e:
        ServingEngine(_model(), max_batch=2, max_seq_len=64, **option)
    assert e.value.param == next(iter(option))
    assert e.value.kind in ("state", "window")
    assert isinstance(e.value, NotImplementedError)


def test_info_reports_the_cache_by_kind_and_bytes_a_slot():
    m = _model()
    cfg = m.config
    eng = ServingEngine(m, max_batch=2, max_seq_len=64, page_size=16)
    info = eng.info()
    rows, lanes = cfg.num_key_value_heads // 2, cache_lanes(2 * cfg.head_dim)
    # ONE full cache (K and V) whatever the number of layers reading it
    assert info["kv_bytes_per_position"] == 2 * rows * lanes * 4
    assert info["cache_bytes"]["kv"] == 2 * 64 * info["kv_bytes_per_position"]
    # two window layers' rings and three Mamba layers' state
    assert info["window_bytes_per_slot"] == 2 * 2 * WINDOW * rows * lanes * 4
    assert info["state_bytes_per_slot"] == 3 * (
        (cfg.mamba_d_conv - 1) * cfg.d_inner + cfg.mamba_d_state
        * cfg.d_inner) * 4
    assert info["self_decoder_positions"] == 0
    assert info["cross_decoder_positions"] == 0
    kinds = m.cache_kinds()
    assert [k for k, _ in kinds] == ["state", "window", "state", "window",
                                     "state", "kv"]


# -- (h) the Mamba mixer's flag: Jamba keeps its norms -----------------------

def test_the_mamba_mixer_without_jambas_norms():
    P.seed(1)
    jamba = JambaMambaMixer(JambaConfig.tiny())
    assert {"dt_layernorm.weight", "b_layernorm.weight",
            "c_layernorm.weight"} <= set(dict(jamba.named_parameters()))
    plain = JambaMambaMixer(dataclasses.replace(JambaConfig.tiny(),
                                                mamba_inner_norms=False))
    assert not any("layernorm" in n for n, _ in plain.named_parameters())
    cfg = JambaConfig.tiny()
    x = P.to_tensor(np.random.RandomState(0).randn(2, 6, 64).astype("float32"))
    state = (P.to_tensor(np.zeros((2, 3 * cfg.d_inner), np.float32)),
             P.to_tensor(np.zeros((2, cfg.mamba_d_state, cfg.d_inner),
                                  np.float32)))
    lens = jnp.asarray([6, 4], jnp.int32)
    with P.no_grad():
        out, st = jamba(x, state, lens)
        out2, st2, inner = jamba(x, state, lens, inner=True)
        np.testing.assert_allclose(out2.numpy(), out.numpy(), atol=1e-6,
                                   rtol=0)
        np.testing.assert_allclose(np.asarray(st2[1]._value),
                                   np.asarray(st[1]._value), atol=1e-6,
                                   rtol=0)
        # the inner output is what out_proj reads
        np.testing.assert_allclose(jamba.out_proj(inner).numpy(),
                                   out.numpy(), atol=1e-6, rtol=0)
        y, _, m_inner = plain(x, state, lens, inner=True)
    # the plain mixer against the reference's (no norm on dt, B, C)
    p = {f"mamba.{n}": v._value for n, v in plain.named_parameters()}
    ref_cfg = {"mamba_expand": 2, "hidden_size": 64, "mamba_d_state": 8,
               "mamba_d_conv": 4, "mamba_dt_rank": 8}
    want, want_m = reference_phi4flash.mamba_mixer(p, x._value[0], ref_cfg)
    np.testing.assert_allclose(y.numpy()[0], np.asarray(want), atol=1e-5,
                               rtol=0)
    np.testing.assert_allclose(np.asarray(m_inner._value[0]),
                               np.asarray(want_m), atol=1e-5, rtol=0)
