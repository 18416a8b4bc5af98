"""Serving engine: continuous batching over the captured ragged decode path.

The contract under test (ISSUE 7 acceptance):
- engine output token-identical to the sequential generate() oracle on
  mixed prompt lengths (bucketed prefill + batch-slot decode correctness);
- a late-joining request changes NEITHER the tokens NOR the number of
  step-capture lowerings of an in-flight request (join/evict strictly
  between decode steps, fixed decode signature);
- per-request deadlines: an expired queued request is rejected with the
  typed RequestTimeout and its reserved KV pages return to the pool
  (asserted via the pool introspection counters);
- concurrent entry points: Predictor.clone()/PredictorPool from multiple
  threads sharing one loaded program; engine.submit() from many threads.
"""
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.nn as nn
from paddle_tpu.inference.serving import (
    KVPagePool, PoolExhausted, RequestState, ServingEngine)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.utils.deadline import DeadlineExceeded, RequestTimeout


def _model(seed=7, vocab=64, hidden=32, layers=2, heads=4, seq=64):
    P.seed(seed)
    cfg = LlamaConfig.tiny(vocab=vocab, hidden=hidden, layers=layers,
                           heads=heads, inter=hidden * 2, seq=seq)
    return LlamaForCausalLM(cfg)


def _prompt(n, seed=0, vocab=64):
    return np.random.RandomState(seed).randint(0, vocab, (n,))


# ---------------------------------------------------------------------------
# KV page pool
# ---------------------------------------------------------------------------

def test_kv_pool_alloc_release_freelist():
    pool = KVPagePool(total_pages=4, page_size=16)
    assert pool.pages_for(1) == 1 and pool.pages_for(16) == 1 \
        and pool.pages_for(17) == 2
    a = pool.alloc(3)
    assert pool.free_pages == 1
    info = pool.info()
    assert info["active_pages"] == 3 and info["peak_active"] == 3
    # all-or-nothing: failed alloc takes nothing
    with pytest.raises(PoolExhausted):
        pool.alloc(2)
    assert pool.free_pages == 1
    pool.release(a)
    assert pool.free_pages == 4
    assert pool.info()["releases"] == 3


def test_kv_pool_refcount():
    pool = KVPagePool(total_pages=2, page_size=8)
    pages = pool.alloc(2)
    pool.retain(pages)           # second holder (prefix-sharing substrate)
    pool.release(pages)
    assert pool.free_pages == 0  # still held once
    pool.release(pages)
    assert pool.free_pages == 2
    with pytest.raises(ValueError):
        pool.release(pages)      # double release is a bug, not a no-op
    with pytest.raises(ValueError):
        pool.retain(pages)       # retaining a free page likewise


# ---------------------------------------------------------------------------
# engine vs the sequential generate() oracle
# ---------------------------------------------------------------------------

def test_engine_matches_sequential_generate():
    """Mixed prompt lengths — bucket-exact (8) and padded (5, 11) — must
    emit exactly the oracle's tokens (greedy, same weights, same math)."""
    m = _model()
    prompts = [_prompt(5, seed=1), _prompt(8, seed=2), _prompt(11, seed=3)]
    oracle = [np.asarray(
        m.generate(P.to_tensor(p.reshape(1, -1)), max_new_tokens=7).numpy())[0]
        for p in prompts]
    eng = ServingEngine(m, max_batch=4, max_seq_len=64, page_size=8)
    outs = eng.generate(prompts, max_new_tokens=7)
    for o, e in zip(oracle, outs):
        np.testing.assert_array_equal(o, e)
    info = eng.info()
    assert info["finished"] == 3 and info["timed_out"] == 0
    assert info["pool"]["active_pages"] == 0  # everything returned


def test_engine_eos_stops_request():
    """EOS emitted mid-stream finishes the request and frees its slot."""
    m = _model(seed=11)
    p = _prompt(6, seed=4)
    base = np.asarray(
        m.generate(P.to_tensor(p.reshape(1, -1)), max_new_tokens=8).numpy())[0]
    eos = int(base[6 + 2])  # the 3rd generated token, forced to be "EOS"
    eng = ServingEngine(m, max_batch=2, max_seq_len=64, eos_token_id=eos)
    req = eng.submit(p, max_new_tokens=8)
    eng.run()
    out = req.result()
    assert req.finish_reason == "eos"
    assert out.size == 6 + 3 and out[-1] == eos
    np.testing.assert_array_equal(out, base[:9])


# ---------------------------------------------------------------------------
# the continuous-batching contract itself
# ---------------------------------------------------------------------------

def test_join_mid_stream_is_invisible_to_inflight_request():
    """Request B joins while A is mid-decode: A's tokens are bitwise those
    of a solo run, and the join adds ZERO step-capture lowerings (B's
    prompt shares A's prefill bucket; the decode signature is fixed)."""
    m = _model(seed=13)
    pa, pb = _prompt(5, seed=5), _prompt(7, seed=6)  # same bucket (8)

    solo = ServingEngine(m, max_batch=4, max_seq_len=64)
    ra_solo = solo.submit(pa, max_new_tokens=12)
    solo.run()
    solo_tokens = list(ra_solo.output_tokens)

    eng = ServingEngine(m, max_batch=4, max_seq_len=64)
    ra = eng.submit(pa, max_new_tokens=12)
    eng.step()
    eng.step()
    assert 1 < len(ra.output_tokens) < 12  # genuinely mid-stream
    lowerings_before = eng.info()["step"]["lowerings"]
    rb = eng.submit(pb, max_new_tokens=6)
    eng.run()
    assert eng.info()["step"]["lowerings"] == lowerings_before, \
        "a join must reuse bucketed signatures only — no new lowering"
    assert list(ra.output_tokens) == solo_tokens, \
        "a late joiner perturbed an in-flight request's tokens"
    assert rb.state is RequestState.FINISHED and len(rb.output_tokens) == 6


def test_capacity_queueing_drains_fifo():
    """More requests than slots/pages: the tail waits, joins as capacity
    frees, and everyone finishes with correct outputs (continuous
    batching, not rejection)."""
    m = _model(seed=17)
    prompts = [_prompt(4 + i, seed=20 + i) for i in range(5)]
    oracle = [np.asarray(
        m.generate(P.to_tensor(p.reshape(1, -1)), max_new_tokens=6).numpy())[0]
        for p in prompts]
    eng = ServingEngine(m, max_batch=2, max_seq_len=32, page_size=16)
    outs = eng.generate(prompts, max_new_tokens=6)
    for o, e in zip(oracle, outs):
        np.testing.assert_array_equal(o, e)
    info = eng.info()
    assert info["admitted"] == 5 and info["finished"] == 5
    assert info["avg_occupancy"] > 0.5


def test_oversized_request_rejected_typed():
    m = _model(seed=19)
    eng = ServingEngine(m, max_batch=2, max_seq_len=32)
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.submit(_prompt(30), max_new_tokens=16)
    assert eng.info()["rejected"] == 1


def test_unsupported_sampling_params_rejected_typed():
    """Asks the engine cannot honor stay TYPED rejections (never silently
    greedy): top_p without a positive temperature has no distribution to
    draw from, and a SPECULATIVE engine is greedy-only by construction
    (greedy acceptance is the exactness argument). Greedy-equivalent
    spellings stay accepted everywhere."""
    from paddle_tpu.inference.serving import SamplingUnsupported

    m = _model(seed=23)
    eng = ServingEngine(m, max_batch=2, max_seq_len=32)
    with pytest.raises(NotImplementedError, match="top_p"):
        eng.submit(_prompt(4), max_new_tokens=2, top_p=0.9)
    assert eng.info()["rejected"] == 1
    # invalid VALUES are typed rejections too, not silently-served nonsense:
    # a negative temperature would invert the distribution, top_p outside
    # (0, 1] has no nucleus, non-finite values poison the softmax
    with pytest.raises(SamplingUnsupported, match="finite"):
        eng.submit(_prompt(4), max_new_tokens=2, temperature=-1.0)
    with pytest.raises(SamplingUnsupported, match="top_p"):
        eng.submit(_prompt(4), max_new_tokens=2, temperature=0.5, top_p=0.0)
    with pytest.raises(SamplingUnsupported, match="top_p"):
        eng.submit(_prompt(4), max_new_tokens=2, temperature=0.5, top_p=1.5)
    with pytest.raises(SamplingUnsupported, match="finite"):
        eng.submit(_prompt(4), max_new_tokens=2,
                   temperature=float("nan"))
    assert eng.info()["rejected"] == 5
    # temperature=0 / top_p=1 ARE greedy: accepted and served
    r = eng.submit(_prompt(4), max_new_tokens=2, temperature=0.0, top_p=1.0)
    eng.run()
    assert r.result().size == 6
    # a rejected request never touched the pool
    assert eng.pool.info()["active_pages"] == 0

    spec = ServingEngine(m, max_batch=2, max_seq_len=32, spec_k=2)
    with pytest.raises(SamplingUnsupported, match="SPECULATIVELY"):
        spec.submit(_prompt(4), max_new_tokens=2, temperature=0.8)
    with pytest.raises(SamplingUnsupported, match="top_p"):
        spec.submit(_prompt(4), max_new_tokens=2, top_p=0.9)
    assert spec.info()["rejected"] == 2
    rg = spec.submit(_prompt(4), max_new_tokens=2, temperature=0.0, top_p=1.0)
    spec.run()
    assert rg.result().size == 6


def test_per_slot_sampling_greedy_rows_bitwise():
    """Per-slot temperature/top-p sampling (the retired blanket
    SamplingUnsupported): a sampled slot decodes host-side off its logits
    row while greedy neighbors in the SAME batch stay bitwise the
    sequential oracle — and a sampled stream is reproducible per seed."""
    m = _model(seed=47)
    pg, ps = _prompt(5, seed=70), _prompt(7, seed=71)
    oracle = np.asarray(
        m.generate(P.to_tensor(pg.reshape(1, -1)), max_new_tokens=8).numpy())[0]
    greedy_s = np.asarray(
        m.generate(P.to_tensor(ps.reshape(1, -1)), max_new_tokens=8).numpy())[0]

    eng = ServingEngine(m, max_batch=3, max_seq_len=64)
    rg = eng.submit(pg, max_new_tokens=8)
    r1 = eng.submit(ps, max_new_tokens=8, temperature=0.8, top_p=0.9,
                    seed=123)
    r2 = eng.submit(ps, max_new_tokens=8, temperature=0.8, top_p=0.9,
                    seed=123)
    eng.run()
    np.testing.assert_array_equal(rg.result(), oracle)  # bitwise, mixed batch
    np.testing.assert_array_equal(r1.result(), r2.result())  # same seed
    assert not np.array_equal(r1.result(), greedy_s), \
        "temperature=0.8 stream should not be the greedy stream"
    info = eng.info()
    assert info["sampled_tokens"] == 16
    assert info["finished"] == 3 and info["pool"]["active_pages"] == 0


def test_behind_head_reservation_cannot_wedge_fifo():
    """Review regression: a small request behind a BLOCKED head must not
    pin the pages the head is waiting for — reservations stay FIFO-prefix-
    ordered, so the queue always drains once running requests finish."""
    from paddle_tpu.inference.serving import (
        ContinuousBatchingScheduler, Request)
    pool = KVPagePool(total_pages=10, page_size=1)
    sched = ContinuousBatchingScheduler(pool, max_batch=2)
    c = Request(np.arange(3), max_new_tokens=3)   # 6 pages, runs first
    sched.submit(c)
    assert sched.schedule()[0] == [c]
    a = Request(np.arange(4), max_new_tokens=4)   # 8 pages: blocked head
    sched.submit(a)
    assert not a.pages                            # 4 free < 8
    b = Request(np.arange(2), max_new_tokens=2)   # 4 pages: fits the gap
    sched.submit(b)
    assert not b.pages, "behind a blocked head B must NOT reserve"
    sched.schedule()
    assert sched.active == 1 and sched.queue_depth == 2
    c.finish_reason = "length"                    # C completes
    joined, _ = sched.schedule()
    assert joined == [a], "head joins the moment capacity returns"
    a.finish_reason = "length"
    joined, _ = sched.schedule()
    assert joined == [b]
    b.finish_reason = "length"
    sched.schedule()
    assert sched.idle and pool.free_pages == 10


def test_explicit_prefill_buckets_clamped_to_cache():
    """Review regression: an explicit bucket past max_seq_len must not
    trace a KV write larger than the cache — it is clamped up front."""
    m = _model(seed=43)
    eng = ServingEngine(m, max_batch=2, max_seq_len=32, prefill_buckets=[64])
    assert eng.buckets == [32]
    req = eng.submit(_prompt(5, seed=60), max_new_tokens=4)
    eng.run()
    assert req.state is RequestState.FINISHED
    with pytest.raises(ValueError, match="prefill_buckets"):
        ServingEngine(m, max_batch=2, max_seq_len=32, prefill_buckets=[0])


# ---------------------------------------------------------------------------
# per-call prep: no eager jnp.zeros (a prefill's scratch caches come from
# one compiled program, the constant operands are made once)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layers", [2, 4])
def test_prep_makes_no_eager_zeros_whatever_the_depth(layers, monkeypatch):
    """Once warm, a `_prefill` and a `_decode` call `jnp.zeros` not at all,
    at 2 layers as at 4: each eager one is a dispatch of its own on the
    chip, and a prefill has 2L scratch caches to make."""
    from paddle_tpu.inference.serving import engine as engine_mod
    m = _model(seed=83, layers=layers)
    eng = ServingEngine(m, max_batch=2, max_seq_len=64)
    eng.generate([_prompt(5, seed=1)], max_new_tokens=3)  # compiles all
    calls = []
    real_zeros = engine_mod.jnp.zeros

    def counting_zeros(*a, **k):
        calls.append(a)
        return real_zeros(*a, **k)

    monkeypatch.setattr(engine_mod.jnp, "zeros", counting_zeros)
    made = {"_prefill": [], "_decode": []}
    for name, counts in made.items():
        def counted(*a, _fn=getattr(eng, name), _counts=counts, **k):
            before = len(calls)
            out = _fn(*a, **k)
            _counts.append(len(calls) - before)
            return out
        monkeypatch.setattr(eng, name, counted)
    req = eng.submit(_prompt(6, seed=2), max_new_tokens=4)  # same bucket
    eng.run()
    assert len(req.output_tokens) == 4
    assert made["_prefill"] == [0]
    assert made["_decode"] and set(made["_decode"]) == {0}


def test_zero_cache_maker_compiles_once_per_layout():
    """Two engines over one cache layout, prefills in every bucket: the
    maker holds ONE compiled entry (its signature knows neither the engine
    nor the bucket) and is not a captured step, so the slot step's
    lowerings are the buckets used + the decode signature, as before."""
    from paddle_tpu.inference.serving import engine as engine_mod
    engine_mod._zero_caches.clear_cache()
    for seed in (85, 86):
        eng = ServingEngine(_model(seed=seed), max_batch=2, max_seq_len=64)
        assert eng.buckets == [8, 16, 32, 64]
        eng.generate([_prompt(n, seed=n) for n in (5, 12, 20, 40)],
                     max_new_tokens=2)
        assert eng.info()["prefills"] == 4
        assert eng.info()["step"]["lowerings"] == len(eng.buckets) + 1
    made = engine_mod._zero_caches(
        len(eng._caches), (1,) + eng._cache_shape, eng._cache_dtype)
    bufs = [a for pair in made for a in pair]
    assert len({a.unsafe_buffer_pointer() for a in bufs}) == len(bufs) == 4
    assert not any(np.asarray(a).any() for a in bufs)
    assert engine_mod._zero_caches._cache_size() == 1


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_slot_reuse_after_long_request_starts_from_zero_rows(sampled):
    """A long request leaves its K/V in the slot; the shorter one that
    takes the slot next prefills over fresh zero caches, so the rows past
    ITS bucket are zero again and its tokens are the oracle's. Every
    scratch buffer is its own: the step's donation takes them all."""
    import warnings
    m = _model(seed=87)
    kw = dict(temperature=0.8, top_p=0.9, seed=321) if sampled else {}
    short = _prompt(5, seed=3)                       # bucket 8, rows 0..7
    if sampled:   # the same stream from an engine whose slot was never used
        fresh = ServingEngine(m, max_batch=1, max_seq_len=64)
        ro = fresh.submit(short, max_new_tokens=3, **kw)
        fresh.run()
        oracle = ro.result()
    else:
        oracle = np.asarray(m.generate(
            P.to_tensor(short.reshape(1, -1)), max_new_tokens=3).numpy())[0]
    eng = ServingEngine(m, max_batch=1, max_seq_len=64)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        long_req = eng.submit(_prompt(40, seed=4), max_new_tokens=4, **kw)
        eng.run()
        assert long_req.state is RequestState.FINISHED   # and evicted
        assert all(np.asarray(kc[0, 8:40]).any() for kc, _ in eng._caches)
        req = eng.submit(short, max_new_tokens=3, **kw)
        eng.run()
    np.testing.assert_array_equal(req.result(), oracle)
    for kc, vc in eng._caches:
        assert not np.asarray(kc[0, 8:]).any()
        assert not np.asarray(vc[0, 8:]).any()
    assert not [w for w in caught if "donated buffers" in str(w.message)]


# ---------------------------------------------------------------------------
# deadlines: typed rejection/eviction with pages returned
# ---------------------------------------------------------------------------

def test_expired_queued_request_rejected_and_pages_returned():
    m = _model(seed=23)
    # pool: 1 slot x 4 pages of 16. A (4+20 tokens) holds 2 pages, leaving
    # spare capacity for B (4+10 -> 1 page) to RESERVE while queued on the
    # busy slot — the reservation an expiring queued request must give back
    eng = ServingEngine(m, max_batch=1, max_seq_len=64, page_size=16)
    ra = eng.submit(_prompt(4, seed=7), max_new_tokens=20)   # occupies slot
    eng.step()
    assert eng.info()["active"] == 1
    pages_a = eng.pool.info()["active_pages"]
    rb = eng.submit(_prompt(4, seed=8), max_new_tokens=10, ttl=0.02)
    assert eng.pool.info()["active_pages"] > pages_a  # B reserved while queued
    time.sleep(0.05)
    eng.step()  # the between-steps scheduler pass expires B
    assert rb.state is RequestState.TIMED_OUT
    assert isinstance(rb.error, RequestTimeout)
    assert isinstance(rb.error, DeadlineExceeded)  # typed hierarchy intact
    with pytest.raises(RequestTimeout):
        rb.result()
    assert eng.pool.info()["active_pages"] == pages_a, \
        "expired queued request must return its reserved KV pages"
    assert eng.info()["timed_out"] == 1
    eng.run()
    assert ra.state is RequestState.FINISHED  # A undisturbed


def test_expired_running_request_evicted_and_slot_reused():
    m = _model(seed=29)
    eng = ServingEngine(m, max_batch=1, max_seq_len=64)
    ra = eng.submit(_prompt(4, seed=9), max_new_tokens=50, ttl=0.05)
    eng.step()
    assert ra.state is RequestState.DECODING
    time.sleep(0.08)
    eng.step()
    assert ra.state is RequestState.TIMED_OUT
    assert ra.finish_reason == "ttl"
    assert len(ra.output_tokens) > 0          # partial output preserved
    with pytest.raises(RequestTimeout):
        ra.result()
    assert eng.pool.info()["active_pages"] == 0
    # the freed slot serves the next request normally
    rc = eng.submit(_prompt(5, seed=10), max_new_tokens=4)
    eng.run()
    assert rc.state is RequestState.FINISHED and len(rc.output_tokens) == 4


# ---------------------------------------------------------------------------
# concurrent entry points
# ---------------------------------------------------------------------------

def test_engine_submit_from_many_threads():
    m = _model(seed=31)
    prompts = [_prompt(4 + (i % 5), seed=40 + i) for i in range(6)]
    oracle = [np.asarray(
        m.generate(P.to_tensor(p.reshape(1, -1)), max_new_tokens=5).numpy())[0]
        for p in prompts]
    eng = ServingEngine(m, max_batch=3, max_seq_len=64)
    reqs = [None] * len(prompts)

    def worker(i):
        reqs[i] = eng.submit(prompts[i], max_new_tokens=5)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    eng.run()
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(r.result(), oracle[i])


def test_predictor_clone_and_pool_multithreaded(tmp_path):
    """Predictor.clone()/PredictorPool: many threads share ONE loaded
    program (weights shared), outputs stay isolated per thread."""
    import jax

    from paddle_tpu import inference
    from paddle_tpu.static import InputSpec

    P.seed(0)
    mlp = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))
    prefix = None
    if hasattr(jax, "export"):  # jit.save needs jax.export (absent on the
        prefix = str(tmp_path / "served")   # CI jax — run the shared path)
        P.jit.save(mlp, prefix,
                   input_spec=[InputSpec([None, 16], "float32",
                                         name="feats")])
        base = inference.create_predictor(inference.Config(prefix))
    else:
        base = inference.Predictor(inference.Config(), _shared=mlp)
    preds = [base] + [base.clone() for _ in range(3)]
    assert all(p._layer is base._layer for p in preds)  # one shared program

    feeds = [np.random.RandomState(i).rand(2, 16).astype(np.float32)
             for i in range(4)]
    expect = [np.asarray(mlp(P.to_tensor(f)).numpy()) for f in feeds]
    got = [None] * 4
    errs = []

    def worker(i):
        try:
            for _ in range(5):  # hammer to surface cross-thread bleed
                h = preds[i].get_input_handle(preds[i].get_input_names()[0])
                h.copy_from_cpu(feeds[i])
                preds[i].run()
                out = preds[i].get_output_handle(
                    preds[i].get_output_names()[0]).copy_to_cpu()
                got[i] = out
        except BaseException as e:  # noqa: BLE001 — surfaced in main thread
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    for g, e in zip(got, expect):
        np.testing.assert_allclose(g, e, rtol=1e-5, atol=1e-6)

    if prefix is not None:  # PredictorPool loads from disk: needs jit.save
        pool = inference.PredictorPool(inference.Config(prefix), size=3)
        p2 = pool.retrieve(2)
        p2.get_input_handle(p2.get_input_names()[0]).copy_from_cpu(feeds[0])
        p2.run()
        out = p2.get_output_handle(p2.get_output_names()[0]).copy_to_cpu()
        np.testing.assert_allclose(out, expect[0], rtol=1e-5, atol=1e-6)
    else:  # same contract via clone-shared predictors
        pool_preds = [base.clone() for _ in range(3)]
        assert all(p._layer is base._layer for p in pool_preds)


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def test_serving_summary_renders_counters():
    from paddle_tpu import profiler
    m = _model(seed=37)
    eng = ServingEngine(m, max_batch=2, max_seq_len=32)
    eng.generate([_prompt(4, seed=50), _prompt(6, seed=51)],
                 max_new_tokens=4)
    text = profiler.serving_summary()
    assert "submitted=2" in text and "finished=2" in text
    assert "kv pool" in text and "occupancy=" in text
    info = eng.info()
    assert info["tokens_generated"] == 8
    assert info["step"]["lowerings"] >= 2  # prefill bucket(s) + decode
    del eng  # engines are weakly registered; drop for other tests


# ---------------------------------------------------------------------------
# speculative decoding: propose-k draft, single-call batch-slot verify
# ---------------------------------------------------------------------------

def _draft_model(seed=99, vocab=64):
    P.seed(seed)
    cfg = LlamaConfig.tiny(vocab=vocab, hidden=16, layers=1, heads=2,
                           inter=32, seq=64)
    return LlamaForCausalLM(cfg)


@pytest.mark.parametrize("drafter", ["ngram", "model"])
def test_speculative_output_bitwise_identical(drafter):
    """THE speculative contract: greedy output is bitwise the
    non-speculative engine's (itself pinned to sequential generate()) on
    mixed prompt lengths, for BOTH drafter backends — the drafter is pure
    opportunity, never correctness. The verify executable lowers exactly
    once for the fixed [max_batch, k+1] signature."""
    m = _model(seed=53)
    prompts = [_prompt(5, seed=80), _prompt(8, seed=81), _prompt(11, seed=82)]
    oracle = [np.asarray(
        m.generate(P.to_tensor(p.reshape(1, -1)), max_new_tokens=9).numpy())[0]
        for p in prompts]
    base = ServingEngine(m, max_batch=4, max_seq_len=64, page_size=8)
    base_outs = base.generate(prompts, max_new_tokens=9)
    for o, e in zip(oracle, base_outs):
        np.testing.assert_array_equal(o, e)

    kw = {"draft_model": _draft_model()} if drafter == "model" else {}
    spec = ServingEngine(m, max_batch=4, max_seq_len=64, page_size=8,
                         spec_k=3, drafter=drafter, **kw)
    spec_outs = spec.generate(prompts, max_new_tokens=9)
    for o, e in zip(base_outs, spec_outs):
        np.testing.assert_array_equal(o, e)
    info = spec.info()
    assert info["spec"]["k"] == 3
    assert info["spec"]["drafter"]["kind"] == drafter
    assert info["spec"]["verify"]["lowerings"] == 1, \
        "one verify lowering per (max_batch, k+1) signature"
    assert info["spec"]["verify_steps"] > 0
    # every verify emits >= 1 token per served slot (the bonus token)
    assert info["spec"]["tokens_per_verify"] >= 1.0
    assert info["pool"]["active_pages"] == 0


def test_speculative_eos_matches_oracle():
    """EOS inside an accepted window must stop the request exactly where
    the sequential path stops (the EOS is kept, later accepted tokens are
    discarded by the emission cap)."""
    m = _model(seed=11)
    p = _prompt(6, seed=4)
    base = np.asarray(
        m.generate(P.to_tensor(p.reshape(1, -1)), max_new_tokens=8).numpy())[0]
    eos = int(base[6 + 2])  # the 3rd generated token, forced to be "EOS"
    eng = ServingEngine(m, max_batch=2, max_seq_len=64, eos_token_id=eos,
                        spec_k=4)
    req = eng.submit(p, max_new_tokens=8)
    eng.run()
    out = req.result()
    assert req.finish_reason == "eos"
    assert out.size == 6 + 3 and out[-1] == eos
    np.testing.assert_array_equal(out, base[:9])


def test_spec_late_join_changes_nothing_inflight():
    """The PR 7 join contract survives speculation: a request joining while
    A speculates mid-stream changes NEITHER A's tokens (bitwise) NOR any
    lowering count — the verify signature is pinned at [max_batch, k+1]."""
    m = _model(seed=59)
    pa, pb = _prompt(5, seed=85), _prompt(7, seed=86)  # same bucket (8)

    solo = ServingEngine(m, max_batch=4, max_seq_len=64, spec_k=2)
    ra_solo = solo.submit(pa, max_new_tokens=12)
    solo.run()
    solo_tokens = list(ra_solo.output_tokens)

    eng = ServingEngine(m, max_batch=4, max_seq_len=64, spec_k=2)
    ra = eng.submit(pa, max_new_tokens=12)
    eng.step()
    eng.step()
    assert 1 < len(ra.output_tokens) < 12  # genuinely mid-stream
    step_before = eng.info()["step"]["lowerings"]
    verify_before = eng.info()["spec"]["verify"]["lowerings"]
    rb = eng.submit(pb, max_new_tokens=6)
    eng.run()
    assert eng.info()["step"]["lowerings"] == step_before
    assert eng.info()["spec"]["verify"]["lowerings"] == verify_before, \
        "a join must not add a verify lowering"
    assert list(ra.output_tokens) == solo_tokens, \
        "a late joiner perturbed an in-flight speculative request"
    assert rb.state is RequestState.FINISHED and len(rb.output_tokens) == 6


def test_spec_eviction_with_inflight_drafts_returns_pages():
    """Regression (ISSUE 9 satellite): a queued request expiring
    (RequestTimeout) and a mid-decode TTL eviction while the slot holds
    in-flight draft state must return every page, drop the drafter's
    per-request state, and leave the verify signature's lowering count
    unchanged — rejection really is cursor arithmetic, no pool churn."""
    m = _model(seed=61)
    eng = ServingEngine(m, max_batch=1, max_seq_len=64, page_size=16,
                        spec_k=3)
    ra = eng.submit(_prompt(4, seed=90), max_new_tokens=30)  # holds the slot
    eng.step()
    assert ra.state is RequestState.DECODING
    assert eng.drafter._idx, "drafter holds in-flight state for A"
    pages_a = eng.pool.info()["active_pages"]
    verify_before = eng.info()["spec"]["verify"]["lowerings"]

    # 1. queued request expires -> typed RequestTimeout, reservation back
    rb = eng.submit(_prompt(4, seed=91), max_new_tokens=8, ttl=0.02)
    assert eng.pool.info()["active_pages"] > pages_a  # B reserved queued
    time.sleep(0.05)
    eng.step()
    assert rb.state is RequestState.TIMED_OUT
    with pytest.raises(RequestTimeout):
        rb.result()
    assert eng.pool.info()["active_pages"] == pages_a

    # 2. A itself expires MID-DECODE with draft state in flight
    ra.deadline = type(ra.deadline)(0.0, what="expired now")
    time.sleep(0.01)
    eng.step()   # eviction pass sees the expired deadline
    assert ra.state is RequestState.TIMED_OUT
    assert len(ra.output_tokens) > 0          # partial output preserved
    assert eng.pool.info()["active_pages"] == 0
    assert not eng.drafter._idx, "evicted request's drafter state leaked"

    # 3. the slot serves the next request; no signature ever re-lowered
    rc = eng.submit(_prompt(5, seed=92), max_new_tokens=4)
    eng.run()
    assert rc.state is RequestState.FINISHED and len(rc.output_tokens) == 4
    assert eng.info()["spec"]["verify"]["lowerings"] == verify_before


def test_spec_capacity_guard_includes_verify_scratch():
    """A request whose prompt+max_new+k cannot fit the static layout is a
    typed sizing error up front (the verify window may write k positions
    past the accepted cursor, so those are part of the ask)."""
    m = _model(seed=67)
    eng = ServingEngine(m, max_batch=2, max_seq_len=32, spec_k=4)
    with pytest.raises(ValueError, match="verify scratch"):
        eng.submit(_prompt(20), max_new_tokens=10)   # 20+10+4 > 32
    # the same ask fits a non-speculative engine
    eng2 = ServingEngine(m, max_batch=2, max_seq_len=32)
    r = eng2.submit(_prompt(20), max_new_tokens=10)
    eng2.run()
    assert r.result().size == 30


def test_ngram_drafter_unit():
    """Prompt-lookup mechanics: longest-suffix match replays its
    continuation, the self-match falls back to the previous occurrence,
    no-match falls back to repeat-last, proposals are exactly k."""
    from paddle_tpu.inference.serving import NGramDrafter

    class R:  # minimal request stand-in
        rid, prompt, output_tokens = 7, np.asarray([1, 2, 3, 1, 2]), []

    d = NGramDrafter(max_n=3)
    d.on_join(R)
    # suffix (1, 2) last occurred at the start -> continuation is 3, 1, 2
    assert d.propose({0: R}, 3) == {0: [3, 1, 2]}
    # observe new tokens; suffix (9,) has no earlier occurrence -> repeat
    R.output_tokens = [9]
    d.observe(R, 1)
    assert d.propose({0: R}, 2) == {0: [9, 9]}
    d.on_evict(R)
    assert not d._idx


def test_spec_summary_renders_acceptance():
    from paddle_tpu import profiler
    m = _model(seed=71)
    eng = ServingEngine(m, max_batch=2, max_seq_len=32, spec_k=2)
    eng.generate([_prompt(4, seed=95), _prompt(6, seed=96)],
                 max_new_tokens=6)
    text = profiler.serving_summary()
    assert "spec: drafter=ngram k=2" in text
    assert "acceptance=" in text and "tokens/verify=" in text
    info = eng.info()["spec"]
    assert info["draft_tokens_proposed"] > 0
    # the default n-gram drafter counts propose() calls so the advertised
    # draft-vs-verify diagnostic is live, not a hard-wired 0
    assert info["draft_steps"] > 0
    assert 0.0 <= info["acceptance_rate"] <= 1.0
    hist = info["tokens_per_verify_hist"]
    assert len(hist) == 4 and sum(hist) > 0   # emitted 1..k+1 per slot
    del eng
