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
import contextlib
import functools
import itertools
import os
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
import paddle_tpu.nn as nn
from paddle_tpu.core.tensor import Parameter, Tensor
from paddle_tpu.inference.serving import (
    KVPagePool, PoolExhausted, FixedSlotStateUnsupported, RequestState,
    ServingEngine)
from paddle_tpu.inference.serving import engine as engine_mod
from paddle_tpu.jit import capture
from paddle_tpu.models import (
    JambaConfig, JambaForCausalLM, LlamaConfig, LlamaForCausalLM, MiMoConfig,
    MiMoForCausalLM)
from paddle_tpu.models.steps import build_step, cache_kinds, compiled_step
from paddle_tpu.observability import trace
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

@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
def test_engine_matches_sequential_generate(traced):
    """Mixed prompt lengths — bucket-exact (8) and padded (5, 11) — must
    emit exactly the oracle's tokens (greedy, same weights, same math),
    with the tracer recording every span of the loop or none."""
    m = _model()
    prompts = [_prompt(5, seed=1), _prompt(8, seed=2), _prompt(11, seed=3)]
    oracle = [np.asarray(
        m.generate(P.to_tensor(p.reshape(1, -1)), max_new_tokens=7).numpy())[0]
        for p in prompts]
    eng = ServingEngine(m, max_batch=4, max_seq_len=64, page_size=8)
    trace.enable(traced)
    try:
        outs = eng.generate(prompts, max_new_tokens=7)
        assert bool(trace.trace_records()) == traced
    finally:
        trace.enable(False)
        trace.trace_clear()
    for o, e in zip(oracle, outs):
        np.testing.assert_array_equal(o, e)
    info = eng.info()
    assert info["finished"] == 3 and info["timed_out"] == 0
    assert 0 < info["avg_occupancy"] <= 1.0
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
    engine_mod._zero_caches.clear_cache()
    for seed in (85, 86):
        eng = ServingEngine(_model(seed=seed), max_batch=2, max_seq_len=64)
        assert eng.buckets == [8, 16, 32, 64]
        eng.generate([_prompt(n, seed=n) for n in (5, 12, 20, 40)],
                     max_new_tokens=2)
        assert eng.info()["prefills"] == 4
        assert eng.info()["step"]["lowerings"] == len(eng.buckets) + 1
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


# ---------------------------------------------------------------------------
# the seam between a model and the engine (models/steps.py)
# ---------------------------------------------------------------------------

def _tiny(family):
    P.seed(3)
    if family == "llama":
        return LlamaForCausalLM(LlamaConfig.tiny())
    m = JambaForCausalLM(JambaConfig.tiny())
    m.eval()
    return m


@pytest.mark.parametrize("family,kind,name", [
    ("llama", "cached", "llama_cached_step"),
    ("llama", "slot", "llama_slot_step"),
    ("llama", "slot_logits", "llama_slot_step"),
    ("llama", "verify", "llama_verify_step"),
    ("jamba", "slot", "jamba_slot_step"),
    ("jamba", "slot_logits", "jamba_slot_step")])
def test_compiled_step_is_one_object_a_kind_under_its_recorded_name(
        family, kind, name):
    """Asking twice gives the SAME captured step (so its lowerings are
    shared), named as `profiler.lint_summary()` and the staticcheck record
    it; `build_step` is the fresh one a tool takes apart."""
    m = _tiny(family)
    step = compiled_step(m, kind)
    assert compiled_step(m, kind) is step
    assert step.__name__ == name
    assert isinstance(step, capture.CapturedStep)
    assert build_step(m, kind) is not step
    assert compiled_step(_tiny(family), kind) is not step   # a model its own


@pytest.mark.parametrize("family", ["llama", "jamba"])
def test_engines_and_drafters_over_one_model_share_its_lowerings(family):
    """A second engine over the same weights, and a draft-model drafter
    whose draft IS that model, run on the first engine's lowerings."""
    m = _tiny(family)
    vocab = m.config.vocab_size
    prompts = [_prompt(5, seed=1, vocab=vocab),
               _prompt(11, seed=2, vocab=vocab)]
    first = ServingEngine(m, max_batch=2, max_seq_len=64)
    outs = first.generate(prompts, max_new_tokens=4)
    assert first._step_fn is compiled_step(m, "slot")
    before = first._step_fn.cache_info()["lowerings"]
    assert before == 3                       # buckets 8 and 16, and decode
    second = ServingEngine(m, max_batch=2, max_seq_len=64)
    assert second._step_fn is first._step_fn
    for a, b in zip(outs, second.generate(prompts, max_new_tokens=4)):
        np.testing.assert_array_equal(a, b)
    if family == "llama":                    # a recurrent state refuses spec_k
        spec = ServingEngine(m, max_batch=2, max_seq_len=64, spec_k=2,
                             drafter="model", draft_model=m)
        assert spec.drafter._step_fn is first._step_fn
        assert spec._verify_fn is compiled_step(m, "verify")
        for a, b in zip(outs, spec.generate(prompts, max_new_tokens=4)):
            np.testing.assert_array_equal(a, b)
        assert spec.drafter.draft_calls > 0
    assert first._step_fn.cache_info()["lowerings"] == before


@pytest.mark.parametrize("family", ["llama", "jamba"])
def test_cache_contract_and_the_one_form_of_the_zero_maker(family,
                                                           monkeypatch):
    """What `models/steps.py` writes down of a model's state: the slot axis
    first in every leaf, a kind a leaf in the same structure; and the
    engine's zero-maker takes that pytree's structure for every model and
    returns buffers no two of which alias (the step donates each)."""
    m = _tiny(family)
    b, s = 3, 64
    caches = m.init_kv_caches(b, s)
    leaves = jax.tree_util.tree_leaves(caches)
    assert leaves and all(t._value.shape[0] == b for t in leaves)
    kinds = cache_kinds(m, caches)
    assert jax.tree_util.tree_structure(kinds) \
        == jax.tree_util.tree_structure(caches)
    want = {"kv"} if family == "llama" else {"kv", "state"}
    assert set(jax.tree_util.tree_leaves(kinds)) == want

    calls = []
    real = engine_mod._zero_caches
    monkeypatch.setattr(engine_mod, "_zero_caches",
                        lambda *a: calls.append(a) or real(*a))
    eng = ServingEngine(m, max_batch=b, max_seq_len=s)
    eng.generate([_prompt(5, seed=4, vocab=m.config.vocab_size)],
                 max_new_tokens=2)
    treedef = jax.tree_util.tree_structure(eng._caches)
    assert calls == [eng._zero_args] and calls[0][0] == treedef
    bufs = jax.tree_util.tree_leaves(real(*eng._zero_args))
    assert len({a.unsafe_buffer_pointer() for a in bufs}) == len(bufs) \
        == len(leaves)
    assert [(a.shape, a.dtype) for a in bufs] == [
        ((1,) + t._value.shape[1:], t._value.dtype) for t in leaves]
    assert not any(np.asarray(a).any() for a in bufs)


class _ToyModel:
    """All that `models/steps.py` asks of a model and nothing else, over a
    state of its own shape: an embedding, ONE K/V pair (a position's row is
    its token's embedding, and twice it), ONE fixed-state leaf (the sum of
    the real tokens' embeddings so far) and a head.  With q the last real
    token's embedding: h = sum_j V[j] (K[j] . q) + state, logits = h @ head.
    Small integer weights, so float32 is exact and an argmax has no noise."""

    step_name = "toy"

    class config:
        max_position_embeddings = 32
        vocab_size = 16

    def __init__(self, seed=0, d=4):
        r = np.random.RandomState(seed)
        self.embed = Parameter(jnp.asarray(
            r.randint(-2, 3, (self.config.vocab_size, d)), jnp.float32))
        self.head = Parameter(jnp.asarray(
            r.randint(-3, 4, (d, self.config.vocab_size)), jnp.float32))

    def parameters(self):
        return [self.embed, self.head]

    def init_kv_caches(self, batch_size, max_len):
        d = self.embed.shape[1]
        zeros = lambda *shape: Tensor(jnp.zeros(shape, jnp.float32))
        return {"kv": (zeros(batch_size, max_len, d),
                       zeros(batch_size, max_len, d)),
                "sum": zeros(batch_size, d)}

    def cache_kinds(self):
        return {"kv": ("kv", "kv"), "sum": "state"}

    def slot_step_body(self, tok, caches, off, last_pos, return_logits=False):
        e = self.embed._value[tok._value]                       # [B, S, D]
        put = jax.vmap(lambda c, n, o: jax.lax.dynamic_update_slice(
            c, n, (o, jnp.zeros_like(o))))
        k = put(caches["kv"][0]._value, e, off)
        v = put(caches["kv"][1]._value, 2 * e, off)
        real = jnp.arange(e.shape[1])[None] <= last_pos[:, None]
        state = caches["sum"]._value + jnp.sum(e * real[..., None], axis=1)
        q = e[jnp.arange(e.shape[0]), last_pos]                 # [B, D]
        seen = jnp.arange(k.shape[1])[None] <= (off + last_pos)[:, None]
        scores = jnp.einsum("bsd,bd->bs", k, q) * seen
        logits = (jnp.einsum("bs,bsd->bd", scores, v) + state) \
            @ self.head._value
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        new = {"kv": (Tensor(k), Tensor(v)), "sum": Tensor(state)}
        return ((nxt, logits) if return_logits else (nxt,)), new

    def reference(self, prompt, n_new):
        """The same model in numpy, a token at a time over the whole
        sequence, with no cache."""
        emb, head = np.asarray(self.embed._value), np.asarray(self.head._value)
        toks = [int(t) for t in prompt]
        for _ in range(n_new):
            e = emb[toks]
            h = (2 * e * (e @ e[-1])[:, None]).sum(0) + e.sum(0)
            toks.append(int(np.argmax(h @ head)))
        return np.asarray(toks)


def test_a_model_that_keeps_the_written_contract_is_served_as_it_is():
    """Adding a model costs one file: this one lives here, shares no code
    with `models/`, keeps a state of its own structure (a dict, one leaf of
    it recurrent), and the engine serves it to completion (padded prefills,
    joins mid-stream, slot reuse) with the numpy reference's tokens."""
    m = _ToyModel()
    eng = ServingEngine(m, max_batch=2, max_seq_len=32)
    work = [(_prompt(n, seed=n, vocab=16), new)
            for n, new in ((5, 6), (8, 3), (11, 9), (3, 12), (6, 4))]
    reqs = [eng.submit(p, max_new_tokens=new) for p, new in work[:3]]
    eng.step()
    eng.step()
    reqs += [eng.submit(p, max_new_tokens=new) for p, new in work[3:]]
    eng.run()
    for req, (p, new) in zip(reqs, work):
        np.testing.assert_array_equal(req.result(), m.reference(p, new))
    info = eng.info()
    assert info["finished"] == 5 and info["pool"]["active_pages"] == 0
    assert info["cache_bytes"] == {"kv": 2 * 2 * 32 * 4 * 4,
                                   "state": 2 * 4 * 4, "window": 0}
    assert eng._step_fn.__name__ == "toy_slot_step"
    assert info["step"]["lowerings"] == 3        # buckets 8 and 16, decode
    sampled = eng.submit(work[0][0], max_new_tokens=4, temperature=0.8,
                         seed=1)
    eng.run()
    assert sampled.result().size == 5 + 4        # the logits row's step
    # one leaf is recurrent and the model has no window body
    with pytest.raises(FixedSlotStateUnsupported):
        ServingEngine(m, max_batch=2, max_seq_len=32, spec_k=2)


# ---------------------------------------------------------------------------
# a decode step launched one ahead: step i+1 is called before step i's
# tokens are read, and every read from outside sees the engine settled
# ---------------------------------------------------------------------------

FAMILIES = ["llama", "jamba", "mimo"]


@functools.lru_cache(maxsize=None)
def _served(family):
    """One tiny model a family for the cases below: the compiled steps are
    kept on the model, so the cases share their lowerings."""
    P.seed(3)
    if family == "llama":
        return LlamaForCausalLM(LlamaConfig.tiny())
    m = JambaForCausalLM(JambaConfig.tiny()) if family == "jamba" \
        else MiMoForCausalLM(MiMoConfig.tiny(held=(4, 8)))
    m.eval()
    return m


def _settled_streams(m, work, **engine_kw):
    """Each request's tokens read alone from an engine that is settled after
    every step: never a step in flight, the order of launch and read the
    engine had before it ran ahead."""
    eng = ServingEngine(m, max_batch=1, max_seq_len=64, **engine_kw)
    outs = []
    for p, new in work:
        req = eng.submit(p, max_new_tokens=new)
        while not req.done:
            eng.step()
            eng.settle()
        outs.append(list(req.output_tokens))
    ahead = eng.info()["decode_ahead"]
    assert ahead["launched_ahead"] == 0 and ahead["rows_dropped"] == 0
    return outs


def _ahead_adds_up(eng):
    info = eng.info()
    ahead = info["decode_ahead"]
    assert ahead["decode_steps"] == info["decode_steps"]
    assert set(ahead["settled"]) == set(engine_mod.SETTLE_CAUSES)
    assert ahead["launched_ahead"] + sum(ahead["settled"].values()) \
        == ahead["decode_steps"]
    return ahead


@contextlib.contextmanager
def _ring():
    trace.trace_clear()
    trace.enable(True)
    try:
        yield
    finally:
        trace.enable(False)
        trace.trace_clear()


@pytest.mark.parametrize("family", FAMILIES)
def test_streams_with_a_step_in_flight_are_the_settled_engines(family):
    """Joins and finishes at different steps, a step in flight throughout:
    every stream is token for token the one read alone with no step ever in
    flight (and `generate()`'s, where the family has one); the slot step
    keeps one lowering a signature and the merge one executable."""
    m = _served(family)
    vocab = m.config.vocab_size
    work = [(_prompt(n, seed=n, vocab=vocab), new) for n, new in
            ((5, 9), (12, 3), (7, 14), (20, 6), (3, 11), (9, 2), (15, 8))]
    want = _settled_streams(m, work)
    eng = ServingEngine(m, max_batch=3, max_seq_len=64)
    eng.generate([work[0][0]], max_new_tokens=2)    # three slots' decode step
    lowerings = eng.info()["step"]["lowerings"]
    reqs = [eng.submit(p, max_new_tokens=new) for p, new in work[:4]]
    for _ in range(4):
        eng.step()
    reqs += [eng.submit(p, max_new_tokens=new) for p, new in work[4:]]
    eng.run()
    for req, (p, new), stream in zip(reqs, work, want):
        assert req.state is RequestState.FINISHED
        assert list(req.output_tokens) == stream
        if family == "llama":
            np.testing.assert_array_equal(req.result(), np.asarray(m.generate(
                P.to_tensor(p.reshape(1, -1)), max_new_tokens=new).numpy())[0])
    ahead = _ahead_adds_up(eng)
    assert ahead["launched_ahead"] > ahead["decode_steps"] // 2
    assert ahead["rows_dropped"] == 0
    assert eng.info()["pool"]["active_pages"] == 0
    # the buckets these prompts use (8, 16, 32), which the settled engine
    # has served, and the decode signature: a step in flight adds none
    assert eng.info()["step"]["lowerings"] == lowerings
    assert engine_mod._merge_tokens.cache_info().currsize >= 1
    assert eng._merge is ServingEngine(m, max_batch=3, max_seq_len=64)._merge


@pytest.mark.parametrize("family", FAMILIES)
def test_eos_with_a_step_in_flight_drops_one_row_and_frees_the_slot(family):
    """An EOS is met when its token is read, one step after the next was
    launched with the request still in it: that row is dropped, nothing is
    appended after the EOS, and the request that takes the slot next decodes
    what it decodes alone."""
    m = _served(family)
    vocab = m.config.vocab_size
    pa, pb = _prompt(6, seed=4, vocab=vocab), _prompt(10, seed=5, vocab=vocab)
    (solo,) = _settled_streams(m, [(pa, 12)])
    # a token the stream first shows a few steps in
    k = next(i for i in range(2, 12) if solo[i] not in solo[:i])
    eos = solo[k]
    want_a, want_b = _settled_streams(m, [(pa, 12), (pb, 7)],
                                      eos_token_id=eos)
    assert want_a == solo[:k + 1]
    eng = ServingEngine(m, max_batch=1, max_seq_len=64, eos_token_id=eos)
    ra = eng.submit(pa, max_new_tokens=12)
    rb = eng.submit(pb, max_new_tokens=7)          # waits for the slot
    eng.run()
    assert ra.finish_reason == "eos" and list(ra.output_tokens) == want_a
    assert rb.slot == ra.slot and list(rb.output_tokens) == want_b
    ahead = _ahead_adds_up(eng)
    assert ahead["rows_dropped"] == 1 + (rb.finish_reason == "eos")
    assert eng.info()["pool"]["active_pages"] == 0


@pytest.mark.parametrize("family", FAMILIES)
def test_ttl_eviction_with_a_step_in_flight_appends_nothing_after(family):
    m = _served(family)
    vocab = m.config.vocab_size
    pc = _prompt(9, seed=8, vocab=vocab)
    (want_c,) = _settled_streams(m, [(pc, 6)])      # and every shape is warm
    eng = ServingEngine(m, max_batch=1, max_seq_len=64)
    ra = eng.submit(_prompt(4, seed=9, vocab=vocab), max_new_tokens=50,
                    ttl=0.5)
    while len(ra.output_tokens) < 3 and not ra.deadline.expired:
        eng.step()
    assert ra.state is RequestState.DECODING and eng._flight is not None
    assert eng._flight.holds(ra.slot, ra)
    while not ra.deadline.expired:
        time.sleep(0.01)
    emitted = list(ra.output_tokens)
    rc = eng.submit(pc, max_new_tokens=6)
    eng.step()              # evicts A, whose row is in flight; C takes the slot
    assert ra.state is RequestState.TIMED_OUT and ra.finish_reason == "ttl"
    eng.run()
    assert list(ra.output_tokens) == emitted, "a token after the finish"
    with pytest.raises(RequestTimeout):
        ra.result()
    assert rc.slot == ra.slot and list(rc.output_tokens) == want_c
    assert _ahead_adds_up(eng)["rows_dropped"] == 1
    assert eng.info()["pool"]["active_pages"] == 0


@pytest.mark.parametrize("why", ["sampled", "spec_k"])
def test_a_sampled_slot_or_a_drafter_keeps_every_step_behind(why):
    """The host draws a sampled slot's token from the logits row and a
    drafter proposes from the emitted tokens: neither step's input is known
    before the step before it is read, so `launched_ahead` stands still and
    every `engine.decode_step` says `ahead=False`; the greedy neighbour's
    stream is the one it has alone."""
    m = _served("llama")
    vocab = m.config.vocab_size
    pa, pb = _prompt(6, seed=1, vocab=vocab), _prompt(9, seed=2, vocab=vocab)
    (want_a,) = _settled_streams(m, [(pa, 10)])
    eng = ServingEngine(m, max_batch=2, max_seq_len=64,
                        spec_k=2 if why == "spec_k" else 0)
    with _ring():
        ra = eng.submit(pa, max_new_tokens=10)
        rb = eng.submit(pb, max_new_tokens=8, **(
            dict(temperature=0.8, seed=11) if why == "sampled" else {}))
        eng.run()
        steps = [r for r in trace.trace_records()
                 if r["name"] == "engine.decode_step"]
    assert list(ra.output_tokens) == want_a and rb.done
    ahead = _ahead_adds_up(eng)
    behind = [r for r in steps if rb.rid in r["args"]["rids"]]
    assert len(behind) == 7 and not any(r["args"]["ahead"] for r in behind)
    if why == "spec_k":
        assert ahead["launched_ahead"] == 0
        assert ahead["settled"]["speculative"] == ahead["decode_steps"]
    else:
        # A's last two steps, alone and greedy, run ahead again
        assert ahead["settled"]["sampling"] == 7
        assert ahead["launched_ahead"] == sum(
            r["args"]["ahead"] for r in steps) <= 2


def _consumed(m, eng_caches, slot, fresh_caches, n, max_seq_len=64):
    """The largest distance, over the cache's leaves, between what `slot`
    holds and what a fresh prefill of the same `n` tokens left in slot 0:
    K/V rows up to `n`, a recurrent state and a window's ring whole."""
    kinds = jax.tree_util.tree_leaves(cache_kinds(m, eng_caches))
    worst = 0.0
    for kind, got, want in zip(kinds, jax.tree_util.tree_leaves(eng_caches),
                               jax.tree_util.tree_leaves(fresh_caches)):
        got, want = np.asarray(got[slot], np.float32), \
            np.asarray(want[0], np.float32)
        if kind == "kv":
            rows = n * (got.shape[0] // max_seq_len)
            got, want = got[:rows], want[:rows]
        worst = max(worst, float(np.abs(got - want).max()))
    return worst


@pytest.mark.parametrize("family", FAMILIES)
def test_a_read_from_outside_sees_all_but_the_newest_token_consumed(family):
    """After any number of `step()` calls, `eng.scheduler.running()` and
    then `eng._caches` (the benchmark's readers, in their order) agree: the
    cache of every running request is the one a fresh prefill of prompt +
    `output_tokens[:-1]` leaves. The tokens as they stood with the step in
    flight not read are one short of what the cache has consumed."""
    m = _served(family)
    vocab = m.config.vocab_size
    eng = ServingEngine(m, max_batch=2, max_seq_len=64)
    eng.submit(_prompt(7, seed=21, vocab=vocab), max_new_tokens=40)
    eng.submit(_prompt(11, seed=22, vocab=vocab), max_new_tokens=40)
    for steps in (1, 2, 5, 3):
        for _ in range(steps):
            eng.step()
        assert eng._flight is not None
        unread = {slot: list(req.output_tokens)
                  for slot, req in eng._scheduler.running().items()}
        running = eng.scheduler.running()          # settles
        assert eng._flight is None and len(running) == 2
        caches = eng._caches
        for slot, req in running.items():
            assert req.output_tokens[:-1] == unread[slot]
            for out, close in ((req.output_tokens, True), (unread[slot], False)):
                ids = np.concatenate([req.prompt, out[:-1]])
                fresh = ServingEngine(m, max_batch=1, max_seq_len=64)
                fresh.submit(ids, max_new_tokens=1)
                fresh.step()
                err = _consumed(m, caches, slot, fresh._caches, ids.size)
                # K/V rows past the tokens are not compared, so a Llama's
                # cache cannot tell a token too few
                assert err < 1e-4 if close else \
                    (err > 1e-3 or family == "llama"), (steps, slot, err)
    # counted when the next step is launched with nothing in flight
    assert _ahead_adds_up(eng)["settled"]["outside_read"] == 3


def test_a_saturated_closed_loop_runs_ahead_and_launch_closes_before_wait():
    """Four clients on four slots, each sending its next request when its
    last completes (the benchmark's closed loop, which reads only its
    requests): at least 85% of the decode steps are launched before the one
    before was read, joins and finishes included; and in the ring step
    i+1's `engine.decode.launch` closes before step i's
    `engine.decode.wait` does."""
    m = _served("llama")
    vocab = m.config.vocab_size
    pool = [(_prompt(4 + 3 * (i % 5), seed=i, vocab=vocab), 12 + 5 * (i % 4))
            for i in range(16)]
    eng = ServingEngine(m, max_batch=4, max_seq_len=64)
    eng.generate([p for p, _ in pool[:3]], max_new_tokens=2)    # warm
    before = eng.info()["decode_ahead"]
    with _ring():
        todo = iter(pool)
        live = [eng.submit(p, max_new_tokens=n)
                for p, n in itertools.islice(todo, 4)]
        done = []
        while live:
            for i, req in reversed(list(enumerate(live))):
                if req.done:
                    done.append(live.pop(i))
                    nxt = next(todo, None)
                    if nxt is not None:
                        live.append(eng.submit(nxt[0], max_new_tokens=nxt[1]))
            eng.step()
        recs = trace.trace_records()
    assert len(done) == len(pool)
    after = _ahead_adds_up(eng)
    steps = after["decode_steps"] - before["decode_steps"]
    assert steps > 60
    assert (after["launched_ahead"] - before["launched_ahead"]) / steps >= 0.85
    assert after["rows_dropped"] == 0
    # steps are launched and read in one order, so the k-th launch and the
    # k-th wait of the ring are one step's
    launches = [r for r in recs if r["name"] == "engine.decode.launch"]
    waits = [r for r in recs if r["name"] == "engine.decode.wait"]
    assert len(launches) == len(waits) == steps
    by_id = {r["id"]: r for r in recs}
    end = lambda r: r["ts"] + r["dur"]
    checked = 0
    for k, launch in enumerate(launches):
        span = by_id[launch["parent"]]
        assert span["name"] == "engine.decode_step"
        if span["args"]["ahead"]:
            assert end(launch) <= waits[k - 1]["ts"] < end(waits[k - 1])
            assert by_id[waits[k - 1]["parent"]] is span
            checked += 1
        elif k:
            assert end(waits[k - 1]) <= launch["ts"]
    assert checked >= 0.85 * steps


def test_readers_on_other_threads_settle_under_the_engines_lock():
    """One driver thread steps the engine while more threads than cores read
    it from outside (`info()`, `scheduler.running()`, `settle()`) and another
    submits: a read settles the step in flight under the lock `step()` holds,
    so no token is emitted twice or lost, every stream is the settled
    engine's, and the counters add up."""
    m = _served("llama")
    vocab = m.config.vocab_size
    work = [(_prompt(4 + i, seed=30 + i, vocab=vocab), 18 + 3 * (i % 4))
            for i in range(8)]
    want = _settled_streams(m, work)
    eng = ServingEngine(m, max_batch=3, max_seq_len=64)
    stop, errors, reads = threading.Event(), [], [0]

    def drive():
        while not stop.is_set():
            if eng.idle:
                time.sleep(0.0005)
            else:
                eng.step()

    def read(k):
        try:
            while not stop.is_set():
                if k % 3 == 0:
                    eng.settle()
                elif k % 3 == 1:
                    info = eng.info()
                    ahead = info["decode_ahead"]
                    assert ahead["launched_ahead"] + sum(
                        ahead["settled"].values()) == info["decode_steps"]
                else:
                    for req in eng.scheduler.running().values():
                        assert len(req.output_tokens) <= req.max_new_tokens
                reads[0] += 1
        except Exception as e:  # noqa: BLE001 - reported by the assert below
            errors.append(e)

    threads = [threading.Thread(target=drive, daemon=True)] + [
        threading.Thread(target=read, args=(k,), daemon=True)
        for k in range(min((os.cpu_count() or 4) + 1, 12))]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        reqs = []
        for p, new in work:
            reqs.append(eng.submit(p, max_new_tokens=new))
            time.sleep(0.002)
        for req in reqs:
            assert req.wait(120.0), "the engine stopped serving"
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30.0)
        sys.setswitchinterval(old)
    assert not errors, errors[:1]
    assert not any(t.is_alive() for t in threads) and reads[0] > 0
    for req, stream in zip(reqs, want):
        assert list(req.output_tokens) == stream
    info = eng.info()
    assert info["tokens_generated"] == sum(len(s) for s in want)
    assert _ahead_adds_up(eng)["rows_dropped"] == 0
    assert info["pool"]["active_pages"] == 0


# ---------------------------------------------------------------------------
# the prefill budget: one prefill call a step while slots decode, long
# prompts cut into pieces of the slot step (the engine's module docstring)
# ---------------------------------------------------------------------------

LONG = 1280     # default buckets 8 .. 1024 and 1280: C is the bucket 512


@functools.lru_cache(maxsize=None)
def _long_model():
    """A tiny Llama whose cache holds prompts past the 512 bucket; the cases
    share it, so they count lowerings as differences."""
    return _model(seed=17, seq=LONG)


def _cut_schedule(eng, short, long_, new_long, **kw):
    """A short request decoding, then a long one joining: the prefill
    calls each step ran (read from outside, so every step is settled) until
    the long one decodes, then the rest of both streams."""
    rs = eng.submit(short, max_new_tokens=40)
    eng.step()
    eng.step()
    rl = eng.submit(long_, max_new_tokens=new_long, **kw)
    calls, emitted = [], []
    while rl.state is not RequestState.DECODING:
        info, made = eng.info(), len(rs.output_tokens)
        eng.step()
        after = eng.info()
        calls.append(after["prefill_chunks"] - info["prefill_chunks"])
        emitted.append(len(rs.output_tokens) - made)
    eng.run()
    return rs, rl, calls, emitted


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_a_long_prompt_joining_while_a_slot_decodes_is_cut_one_call_a_step(
        sampled):
    """(a) 1,100 positions joining while a slot decodes: three calls of the
    slot step (512, 512, then the bucket 128 for the 76 left), one a
    step, each step a token of the decoding slot; both streams bitwise an
    engine's that does not cut (no bucket of 512 or more below S_max, so C
    is S_max) and `generate()`'s."""
    m = _long_model()
    short, long_ = _prompt(20, seed=71), _prompt(1100, seed=72)
    kw = {"temperature": 0.8, "seed": 5} if sampled else {}
    eng = ServingEngine(m, max_batch=3, max_seq_len=LONG)
    whole = ServingEngine(m, max_batch=3, max_seq_len=LONG,
                          prefill_buckets=[8, 16, 32, 64, 128, 256])
    assert eng.info()["prefill_cut"] == 512
    assert whole.info()["prefill_cut"] == LONG
    rs, rl, calls, emitted = _cut_schedule(eng, short, long_, 6, **kw)
    ws, wl, whole_calls, _ = _cut_schedule(whole, short, long_, 6, **kw)
    assert calls == [1, 1, 1] and emitted == [1, 1, 1]
    assert whole_calls == [0]
    info = eng.info()
    assert info["chunked_prefills"] == 1 and info["prefill_chunks"] == 3
    assert info["prefill_deferred"] == 0
    assert info["prefill_positions"] - whole.info()["prefill_positions"] == 0
    assert list(rs.output_tokens) == list(ws.output_tokens)
    assert list(rl.output_tokens) == list(wl.output_tokens)
    for req, p, new in ((rs, short, 40), (rl, long_, 6)):
        if sampled and req is rl:
            continue
        np.testing.assert_array_equal(req.result(), np.asarray(m.generate(
            P.to_tensor(p.reshape(1, -1)), max_new_tokens=new).numpy())[0])
    assert info["pool"]["active_pages"] == 0


def test_a_second_joiner_waits_one_step_and_is_counted():
    """(b) Two joiners in one pass while a slot decodes: the first prefills
    in that step, the second in the next (`prefill_deferred` 1), and every
    stream is the settled engine's."""
    m = _served("llama")
    vocab = m.config.vocab_size
    work = [(_prompt(5, seed=81, vocab=vocab), 12),
            (_prompt(9, seed=82, vocab=vocab), 5),
            (_prompt(14, seed=83, vocab=vocab), 5)]
    want = _settled_streams(m, work)
    eng = ServingEngine(m, max_batch=3, max_seq_len=64)
    r0 = eng.submit(*work[0][:1], max_new_tokens=work[0][1])
    eng.step()
    ra = eng.submit(work[1][0], max_new_tokens=work[1][1])
    rb = eng.submit(work[2][0], max_new_tokens=work[2][1])
    eng.step()
    assert ra.state is RequestState.DECODING
    assert rb.state is RequestState.PREFILL
    assert eng.info()["prefill_deferred"] == 1
    eng.step()
    assert rb.state is RequestState.DECODING
    eng.run()
    assert eng.info()["prefill_deferred"] == 1
    for req, stream in zip((r0, ra, rb), want):
        assert list(req.output_tokens) == stream


def test_with_no_slot_decoding_every_joiner_prefills_whole_in_its_step():
    """(c) Nothing decoding: a prompt past C prefills whole and every
    joiner of the pass prefills in the step it joins, as before."""
    m = _long_model()
    eng = ServingEngine(m, max_batch=3, max_seq_len=LONG)
    reqs = [eng.submit(_prompt(n, seed=n), max_new_tokens=3)
            for n in (1100, 30, 700)]
    eng.step()
    assert all(r.state is RequestState.DECODING for r in reqs)
    eng.run()
    info = eng.info()
    assert info["chunked_prefills"] == 0 and info["prefill_chunks"] == 0
    assert info["prefill_deferred"] == 0 and info["prefills"] == 3


def test_cutting_adds_no_lowering_when_c_is_a_bucket():
    """(d) Every bucket and the decode step warm, as the benchmark's
    set-up leaves them: the pieces and the last piece reuse the buckets'
    signatures, so cutting lowers nothing, like the engine that does not
    cut."""
    m = _long_model()
    eng = ServingEngine(m, max_batch=3, max_seq_len=LONG)
    for b in eng.buckets:
        if b + 2 <= LONG:
            eng.submit(_prompt(b, seed=b), max_new_tokens=2)
    eng.run()
    before = eng.info()["step"]["lowerings"]
    _cut_schedule(eng, _prompt(12, seed=91), _prompt(1300 - 240, seed=92), 4)
    _cut_schedule(eng, _prompt(7, seed=93), _prompt(600, seed=94), 4)
    info = eng.info()
    assert info["chunked_prefills"] == 2
    assert info["step"]["lowerings"] == before


@pytest.mark.parametrize("family", ["jamba", "mimo"])
def test_a_model_with_fixed_slot_state_is_never_cut(family):
    """(e) A model that keeps "state" or "window" leaves has no C by
    default (a piece would have to carry them): a prompt past 512 joining
    while a slot decodes prefills whole, one call; `prefill_chunk` given is
    refused, typed, as before."""
    m = _served(family)
    vocab = m.config.vocab_size
    eng = ServingEngine(m, max_batch=2, max_seq_len=1024)
    assert eng.info()["prefill_cut"] == 0
    r0 = eng.submit(_prompt(6, seed=95, vocab=vocab), max_new_tokens=8)
    eng.step()
    r1 = eng.submit(_prompt(600, seed=96, vocab=vocab), max_new_tokens=2)
    eng.step()
    assert r1.state is RequestState.DECODING
    eng.run()
    info = eng.info()
    assert info["chunked_prefills"] == 0 and info["prefill_chunks"] == 0
    assert r0.state is r1.state is RequestState.FINISHED
    with pytest.raises(FixedSlotStateUnsupported, match="prefill_chunk"):
        ServingEngine(m, max_batch=2, max_seq_len=64, prefill_chunk=16)


# ---------------------------------------------------------------------------
# the device's own time: one `device.*` span a call the engine launches
# (observability/trace.py's docstring)
# ---------------------------------------------------------------------------

def _device_run(path):
    """(engine, requests) after a traced run down one path of the engine:
    a cut prompt joining a decoding slot (whole prefills, pieces, a last
    piece, decode steps), a shared prefix's tail (scratch windows) or
    speculative decoding (verify steps)."""
    if path == "cut":
        eng = ServingEngine(_long_model(), max_batch=3, max_seq_len=LONG)
        rs, rl, calls, _ = _cut_schedule(eng, _prompt(20, seed=71),
                                         _prompt(1100, seed=72), 6)
        assert calls == [1, 1, 1]
        return eng, [rs, rl]
    m = _served("llama")
    vocab = m.config.vocab_size
    if path == "prefix":
        eng = ServingEngine(m, max_batch=2, max_seq_len=64, page_size=16,
                            prefix_sharing=True, prefill_chunk=16)
        head = _prompt(40, seed=31, vocab=vocab)
        ra = eng.submit(head, max_new_tokens=5)
        eng.run()
        rb = eng.submit(np.concatenate([head[:32], _prompt(
            20, seed=32, vocab=vocab)]), max_new_tokens=5)
        eng.run()
        assert rb.shared_len == 32
        return eng, [ra, rb]
    eng = ServingEngine(m, max_batch=2, max_seq_len=64, spec_k=2)
    reqs = [eng.submit(_prompt(n, seed=n, vocab=vocab), max_new_tokens=6)
            for n in (6, 11)]
    eng.run()
    return eng, reqs


@pytest.mark.parametrize("path", ["cut", "prefix", "speculative"])
def test_every_call_the_engine_launches_is_one_device_span(path):
    """Each decode step, whole prefill, piece of a cut prompt, scratch
    window and verify step is ONE `device.*` record carrying its `rid` or
    `step`; the records follow one another on the device's lane without
    overlap, and no launched call is left unseen."""
    with _ring():
        eng, reqs = _device_run(path)
        info = eng.info()
        recs = [r for r in trace.trace_records()
                if r["name"].startswith("device.")]
        fifo = trace.trace_info()["device"]
    assert fifo == {"depth": 0, "seen": len(recs), "undone": 0}
    kind = lambda k: [r for r in recs if r["name"] == "device." + k]
    rids = {r.rid for r in reqs}
    verify = info["spec"]["verify_steps"] if path == "speculative" else 0
    steps = [r["args"]["step"] for r in kind("decode_step")
             + kind("verify_step")]
    assert sorted(steps) == list(range(info["decode_steps"]))
    assert len(kind("verify_step")) == verify
    assert all(set(r["args"]["rids"]) <= rids for r in kind("decode_step"))
    whole = info["prefills"] - info["shared_prefix_joins"]
    assert len(kind("prefill")) == whole
    assert len(kind("prefill_chunk")) == info["prefill_chunks"] \
        - info["chunked_prefills"]
    assert {r["args"]["rid"] for r in kind("prefill") + kind("prefill_chunk")
            + kind("window")} <= rids
    if path == "cut":
        assert [(r["args"]["pos"], r["args"]["tokens"])
                for r in kind("prefill_chunk")] == [(0, 512), (512, 512)]
        assert [r["args"]["pos"] for r in kind("prefill")] == [0, 1024]
    if path == "prefix":
        assert [(r["args"]["pos"], r["args"]["tokens"])
                for r in kind("window")] == [(32, 16), (48, 4)]
        assert [(r["args"]["pos"], r["args"]["tokens"])
                for r in kind("prefill_chunk")] == [(0, 16), (16, 16)]
    else:
        assert kind("window") == []
    on_lane = sorted(recs, key=lambda r: r["ts"])
    assert on_lane == sorted(recs, key=lambda r: r["id"])     # launch order
    for a, b in zip(on_lane, on_lane[1:]):
        assert a["ts"] + a["dur"] <= b["ts"]
    assert all(r["args"]["late_ns"] >= 0 for r in recs)


def test_a_blocking_prefill_reads_the_step_in_flight_first():
    """A call whose first token the host reads (a whole prefill, a cut
    prompt's last piece) reads the decode step in flight before it: that
    step's tokens do not wait out the call, so no gap holds two calls (the
    piece launched the step before lies under that step already). The
    pieces before the last read nothing; the streams stay bitwise."""
    m = _long_model()
    short, long_, mid = (_prompt(20, seed=101), _prompt(1100, seed=102),
                         _prompt(300, seed=103))
    eng = ServingEngine(m, max_batch=3, max_seq_len=LONG)
    rs = eng.submit(short, max_new_tokens=40)
    eng.step()
    eng.step()
    rl = eng.submit(long_, max_new_tokens=4)
    rm = eng.submit(mid, max_new_tokens=4)
    while rm.state is not RequestState.DECODING:
        eng.step()          # no read from outside: a step stays in flight
    eng.run()
    ahead = _ahead_adds_up(eng)
    # the long one's last piece and the mid one's whole prefill
    assert ahead["settled"]["prefill"] == 2
    info = eng.info()
    assert info["chunked_prefills"] == 1 and info["prefill_chunks"] == 3
    assert info["prefill_deferred"] == 3     # the mid one, behind 3 calls
    for req, p, new in ((rs, short, 40), (rl, long_, 4), (rm, mid, 4)):
        np.testing.assert_array_equal(req.result(), np.asarray(m.generate(
            P.to_tensor(p.reshape(1, -1)), max_new_tokens=new).numpy())[0])
