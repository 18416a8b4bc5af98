"""Serving gateway: socketed front-end + prefix sharing + chunked prefill.

The contract under test (ISSUE 14 acceptance):
- END TO END OVER A REAL SOCKET: tokens received through the gateway are
  bitwise the in-process engine's for the same requests; typed errors
  (RequestTimeout from a TTL, sizing ValueError, SamplingUnsupported)
  re-raise client-side; graceful drain finishes in-flight requests;
- PREFIX SHARING: a shared-prefix workload (8 requests over one common
  prompt) saves >= 2x prefill pages vs unshared with bitwise-unchanged
  tokens; the radix tree's pages obey the refcount law (evicted only when
  refcounts release; reclaim unwedges admission);
- CHUNKED PREFILL: a mega-prompt prefills in fixed [1, chunk] windows
  interleaved with decode steps — every inter-decode-step gap stays under
  the single-chunk bound, tokens stay bitwise, and chunking adds AT MOST
  one prefill signature (the frozen-lowering proof);
- the fork-during-prefill race: KVPagePool.share() typed-rejects a page
  still being written by an in-flight prefill (PageUncommitted).
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.inference.serving import (
    KVPagePool, PageUncommitted, PrefixCache, RequestState, ServingEngine)
from paddle_tpu.inference.serving.gateway import (
    GatewayClient, GatewayDraining, ServingGateway)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.utils.deadline import DeadlineExceeded, RequestTimeout


def _model(seed=7, vocab=64, hidden=32, layers=2, heads=4, seq=64):
    P.seed(seed)
    cfg = LlamaConfig.tiny(vocab=vocab, hidden=hidden, layers=layers,
                           heads=heads, inter=hidden * 2, seq=seq)
    return LlamaForCausalLM(cfg)


def _prompt(n, seed=0, vocab=64):
    return np.random.RandomState(seed).randint(0, vocab, (n,))


@pytest.fixture(scope="module")
def model():
    return _model()


def _oracle(model, prompts, new=8, **kw):
    eng = ServingEngine(model, max_batch=4, max_seq_len=64, **kw)
    return eng.generate(prompts, max_new_tokens=new)


# ---------------------------------------------------------------------------
# the socket transport
# ---------------------------------------------------------------------------

def test_gateway_tokens_bitwise_the_inprocess_engines(model):
    """THE transport contract: a round-trip over a real TCP socket returns
    exactly the bytes the in-process engine computes — the gateway adds
    transport, never math."""
    prompts = [_prompt(5, seed=1), _prompt(9, seed=2), _prompt(14, seed=3)]
    oracle = _oracle(model, prompts)
    eng = ServingEngine(model, max_batch=4, max_seq_len=64)
    gw = ServingGateway(eng)
    try:
        cli = GatewayClient("127.0.0.1", gw.port)
        outs = [cli.generate(p, max_new_tokens=8) for p in prompts]
        for a, b in zip(oracle, outs):
            np.testing.assert_array_equal(a, b)
        # seeded sampling is reproducible over the wire too
        s1 = cli.generate(prompts[0], max_new_tokens=6, temperature=0.8,
                          seed=42)
        s2 = cli.generate(prompts[0], max_new_tokens=6, temperature=0.8,
                          seed=42)
        np.testing.assert_array_equal(s1, s2)
        info = gw.info()
        assert info["responses"] >= 5 and info["errors"] == 0
        cli.close()
    finally:
        gw.stop(drain=True, timeout=10.0)


def test_the_gateways_driver_and_health_poll_leave_the_step_in_flight(model):
    """The driver asks the engine `idle`, the HEALTH verb `queue_depth` and
    `active`: bookkeeping that does not read the decode step in flight. So
    through the socket the steps are launched ahead as in process, however
    often a load balancer polls; `info()` is the read that settles."""
    eng = ServingEngine(model, max_batch=4, max_seq_len=64)
    gw = ServingGateway(eng)
    try:
        cli = GatewayClient("127.0.0.1", gw.port)
        cli.generate(_prompt(5, seed=1), max_new_tokens=4)       # warm
        polls, stop = [], threading.Event()

        def poll():
            probe = GatewayClient("127.0.0.1", gw.port)
            while not stop.is_set():
                polls.append(probe.health()["active"])
            probe.close()

        prober = threading.Thread(target=poll, daemon=True)
        prober.start()
        out = cli.generate(_prompt(9, seed=2), max_new_tokens=40)
        stop.set()
        prober.join(timeout=10.0)
        assert out.size == 9 + 40 and 1 in polls
        cli.close()
    finally:
        gw.stop(drain=True, timeout=10.0)
    ahead = eng.info()["decode_ahead"]
    assert ahead["settled"]["outside_read"] == 0
    assert ahead["launched_ahead"] >= 0.85 * ahead["decode_steps"] > 30


def test_gateway_ttl_travels_as_typed_request_timeout(model):
    """A request whose TTL runs out engine-side answers a 408 frame; the
    client re-raises the typed RequestTimeout (hierarchy intact) — the
    deadline layer is visible THROUGH the socket."""
    eng = ServingEngine(model, max_batch=2, max_seq_len=64)
    gw = ServingGateway(eng)
    try:
        cli = GatewayClient("127.0.0.1", gw.port)
        with pytest.raises(RequestTimeout) as ei:
            cli.generate(_prompt(4, seed=9), max_new_tokens=40, ttl=1e-4)
        assert isinstance(ei.value, DeadlineExceeded)
        # the engine stays healthy for the next request on the SAME conn
        out = cli.generate(_prompt(4, seed=9), max_new_tokens=3)
        assert out.size == 7
        # typed sizing + sampling rejections cross the wire as themselves
        from paddle_tpu.inference.serving import SamplingUnsupported
        with pytest.raises(ValueError, match="max_seq_len"):
            cli.generate(_prompt(60, seed=10), max_new_tokens=30)
        with pytest.raises(SamplingUnsupported):
            cli.generate(_prompt(4, seed=9), max_new_tokens=2, top_p=0.5)
        cli.close()
    finally:
        gw.stop(drain=True, timeout=10.0)


def test_gateway_graceful_drain_finishes_inflight(model):
    """stop(drain=True): the listener closes and new GENERATEs get the
    typed 503, but a request already accepted finishes and its caller
    gets full tokens — the gateway never abandons its own work."""
    eng = ServingEngine(model, max_batch=2, max_seq_len=64)
    gw = ServingGateway(eng)
    cli = GatewayClient("127.0.0.1", gw.port)
    got = {}

    def worker():
        got["out"] = cli.generate(_prompt(6, seed=11), max_new_tokens=12)

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    # wait for the request to be genuinely in flight engine-side; `idle`
    # and not `scheduler.idle`, which settles under the engine's lock and
    # so waits behind the driver's steps until the request is done
    deadline = time.monotonic() + 5.0
    while eng.idle and time.monotonic() < deadline:
        time.sleep(0.005)
    assert not eng.idle, "request never reached the engine"
    drained = gw.stop(drain=True, timeout=15.0)
    t.join(10.0)
    assert not t.is_alive()
    assert drained, "drain did not reach idle"
    assert got["out"].size == 6 + 12
    oracle = _oracle(model, [_prompt(6, seed=11)], new=12)[0]
    np.testing.assert_array_equal(got["out"], oracle)
    # a fresh submit against the draining gateway is the typed 503
    eng2 = ServingEngine(model, max_batch=2, max_seq_len=64)
    gw2 = ServingGateway(eng2)
    cli2 = GatewayClient("127.0.0.1", gw2.port)
    gw2._draining = True  # drain() also closes the listener; keep the conn
    with pytest.raises(GatewayDraining):
        cli2.generate(_prompt(4, seed=12), max_new_tokens=2)
    cli2.close()
    gw2.stop(drain=False)
    cli.close()


# ---------------------------------------------------------------------------
# prefix sharing
# ---------------------------------------------------------------------------

def test_shared_prefix_saves_pages_bitwise(model):
    """ISSUE acceptance: 8 requests over one common long prompt — the
    shared engine prefills the suffixes only (>= 2x prefill-pages-saved
    vs unshared page demand for the prompts) and every token stream is
    bitwise the unshared engine's."""
    rng = np.random.RandomState(5)
    common = rng.randint(0, 64, (32,))   # 2 full pages of 16
    prompts = [np.concatenate([common, rng.randint(0, 64, (3 + i,))])
               for i in range(8)]
    base = ServingEngine(model, max_batch=4, max_seq_len=64, page_size=16)
    oracle = base.generate(prompts, max_new_tokens=6)

    eng = ServingEngine(model, max_batch=4, max_seq_len=64, page_size=16,
                        prefix_sharing=True)
    outs = []
    for p in prompts:   # arrival order: donor commits, then borrowers
        r = eng.submit(p, max_new_tokens=6)
        eng.run()
        outs.append(r.result())
    for a, b in zip(oracle, outs):
        np.testing.assert_array_equal(a, b)
    info = eng.info()
    # 7 borrowers x 2 shared pages = 14 of the 16 prompt-prefix pages the
    # unshared engine would prefill — comfortably over the 2x floor
    prompt_pages = sum(p.size // 16 for p in prompts)
    assert info["prefill_pages_saved"] >= prompt_pages / 2, info
    assert info["shared_prefix_joins"] == 7, info
    assert info["prefix"]["pages_evicted"] == 0
    # refcount law: only the tree's own pages stay active at idle
    assert info["pool"]["active_pages"] == info["prefix"]["pages_held"]


def test_prefix_tree_eviction_respects_refcounts(model):
    """A cached chain a live request decodes against is NOT evictable;
    once refcounts release, admission pressure reclaims tree-only pages
    through the scheduler hook instead of wedging the queue."""
    eng = ServingEngine(model, max_batch=2, max_seq_len=64, page_size=16,
                        prefix_sharing=True)
    donor = _prompt(33, seed=21)            # 2 full pages cached
    ra = eng.submit(donor, max_new_tokens=20)
    eng.step()                               # prefill + commit to tree
    assert eng.prefix_cache.info()["pages_held"] == 2
    held = eng.prefix_cache.info()["pages_held"]
    # a live borrower pins the chain: evict() must not free it
    rb = eng.submit(donor, max_new_tokens=4)
    eng.step()
    assert rb.shared_len == 32
    assert eng.prefix_cache.evict(99) == 0, \
        "evicted a page a live request shares"
    eng.run()
    assert rb.state is RequestState.FINISHED
    # everyone done: the tree's pages are reclaimable, and demand for the
    # whole pool (2 x 4-page requests against 8 pages, 2 tree-held) gets
    # them back via the reclaim hook instead of wedging the queue
    assert ra.state is RequestState.FINISHED
    big1 = eng.submit(_prompt(40, seed=22), max_new_tokens=24)
    big2 = eng.submit(_prompt(40, seed=23), max_new_tokens=24)
    eng.run()
    assert big1.state is RequestState.FINISHED
    assert big2.state is RequestState.FINISHED
    assert eng.prefix_cache.info()["pages_evicted"] >= 1
    del held


def test_share_of_uncommitted_page_typed_rejected():
    """Regression (ISSUE satellite): the fork-during-prefill race. A page
    still being written by an in-flight chunked prefill is NOT shareable —
    share() raises the typed PageUncommitted and takes no refs."""
    pool = KVPagePool(total_pages=4, page_size=8)
    pages = pool.alloc(2)
    with pytest.raises(PageUncommitted):
        pool.share(pages)
    assert all(p.refs == 1 for p in pages), "failed share must take no refs"
    pool.commit(pages)
    pool.share(pages)
    assert all(p.refs == 2 for p in pages)
    pool.release(pages)
    pool.release(pages)
    assert pool.free_pages == 4
    # released pages lose the committed mark: recycled pages from the free
    # list can never be shared before their NEW prefill commits them
    fresh = pool.alloc(2)
    with pytest.raises(PageUncommitted):
        pool.share(fresh)


def test_fork_during_chunked_prefill_misses_tree(model):
    """Engine-level race: B (same prompt) submitted while A is mid-chunked
    prefill must NOT share (A's pages are uncommitted, nothing of A's is
    in the tree yet) — and both streams stay bitwise the oracle."""
    prompt = _prompt(40, seed=31)
    oracle = _oracle(model, [prompt, prompt], new=5,
                     page_size=16)
    eng = ServingEngine(model, max_batch=2, max_seq_len=64, page_size=16,
                        prefix_sharing=True, prefill_chunk=16)
    ra = eng.submit(prompt, max_new_tokens=5)
    eng.step()                      # A joined, first chunk only
    assert ra.state is RequestState.PREFILL
    rb = eng.submit(prompt, max_new_tokens=5)
    eng.step()                      # B joins while A is mid-prefill
    assert rb.shared_len == 0, "B shared pages of an in-flight prefill"
    eng.run()
    np.testing.assert_array_equal(ra.result(), oracle[0])
    np.testing.assert_array_equal(rb.result(), oracle[1])
    # A committed once done: a THIRD request does share
    rc = eng.submit(prompt, max_new_tokens=5)
    eng.run()
    assert rc.shared_len == 32
    np.testing.assert_array_equal(rc.result(), oracle[0])


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------

def test_chunked_prefill_bitwise_and_one_signature(model):
    """Chunked mega-prompt output is bitwise the whole-prompt engine's,
    and the pieces add at most ONE lowering (the slot step's [1, chunk]
    signature) however many pieces run — the frozen-lowering proof. The
    pieces go through the slot step, so no window step is built."""
    prompts = [_prompt(45, seed=41), _prompt(37, seed=42),
               _prompt(6, seed=43)]
    oracle = _oracle(model, prompts, new=6)
    eng = ServingEngine(model, max_batch=4, max_seq_len=64,
                        prefill_chunk=16)
    before = eng._step_fn.cache_info()["lowerings"]
    outs = eng.generate(prompts, max_new_tokens=6)
    for a, b in zip(oracle, outs):
        np.testing.assert_array_equal(a, b)
    info = eng.info()
    assert info["chunked_prefills"] == 2          # the 6-token prompt: bucket
    assert info["prefill_chunks"] == 3 + 3
    assert "window" not in info
    assert info["step"]["lowerings"] - before <= 1, \
        "chunking must add at most ONE prefill signature"
    assert info["pool"]["active_pages"] == 0


def test_chunked_prefill_never_stalls_decode(model):
    """THE chunked-prefill contract: while a mega-prompt prefills, an
    in-flight request keeps emitting a token EVERY engine step (the
    decode batch is never stalled behind the mega-prompt), and its tokens
    are bitwise its solo stream."""
    solo_eng = ServingEngine(model, max_batch=4, max_seq_len=64)
    rs = solo_eng.submit(_prompt(5, seed=51), max_new_tokens=20)
    solo_eng.run()
    solo = list(rs.output_tokens)

    eng = ServingEngine(model, max_batch=4, max_seq_len=64,
                        prefill_chunk=8)
    ra = eng.submit(_prompt(5, seed=51), max_new_tokens=20)
    eng.step()
    eng.step()
    n_before = len(ra.output_tokens)
    assert ra.state is RequestState.DECODING
    # the mega-prompt: 6 chunks of 8 — joins now
    rb = eng.submit(_prompt(45, seed=52), max_new_tokens=4)
    gaps = []
    while rb.state is not RequestState.DECODING and not rb.done:
        # info() reads the decode step in flight, so between two of them
        # lies exactly the one step() launches
        positions = eng.info()["prefill_positions_padded"]
        before = len(ra.output_tokens)
        eng.step()
        gaps.append(eng.info()["prefill_positions_padded"] - positions)
        assert len(ra.output_tokens) == before + 1, \
            "a decode step was stalled behind the mega-prompt's prefill"
    assert len(ra.output_tokens) > n_before
    eng.run()
    assert list(ra.output_tokens) == solo, \
        "the mega-prompt's chunked prefill perturbed an in-flight stream"
    # the gap between two tokens of the in-flight stream, in the prefill
    # positions computed inside it (its time is the chip's to give): one
    # chunk, against the whole bucket an unchunked engine runs at the join
    assert max(gaps) == 8
    whole = ServingEngine(model, max_batch=4, max_seq_len=64)
    whole.submit(_prompt(5, seed=51), max_new_tokens=20)
    whole.step()
    before = whole.info()["prefill_positions_padded"]
    whole.submit(_prompt(45, seed=52), max_new_tokens=4)
    whole.step()
    assert whole.info()["prefill_positions_padded"] - before == 64 > max(gaps)
    whole.run()
    oracle_b = _oracle(model, [_prompt(45, seed=52)], new=4)[0]
    np.testing.assert_array_equal(rb.result(), oracle_b)


def test_chunked_prefill_ttl_eviction_returns_everything(model):
    """A mega-prompt whose TTL lapses MID-chunked-prefill is evicted with
    its pages returned and its scratch dropped; the engine keeps serving."""
    eng = ServingEngine(model, max_batch=2, max_seq_len=64,
                        prefill_chunk=8)
    ra = eng.submit(_prompt(45, seed=61), max_new_tokens=8, ttl=0.01)
    eng.step()
    assert ra.state is RequestState.PREFILL and ra.scratch is not None
    time.sleep(0.03)
    eng.step()   # eviction pass sees the expired deadline
    assert ra.state is RequestState.TIMED_OUT
    assert ra.scratch is None, "evicted mid-prefill scratch leaked"
    assert eng.pool.info()["active_pages"] == 0
    with pytest.raises(RequestTimeout):
        ra.result()
    rb = eng.submit(_prompt(5, seed=62), max_new_tokens=4)
    eng.run()
    assert rb.state is RequestState.FINISHED


# ---------------------------------------------------------------------------
# gateway + tentpole features through one socket
# ---------------------------------------------------------------------------

def test_gateway_shared_and_chunked_end_to_end(model):
    """The full stack at once: engine with prefix sharing AND chunked
    prefill behind a gateway — socket tokens bitwise the plain engine's,
    pages actually saved, chunks actually run."""
    rng = np.random.RandomState(8)
    common = rng.randint(0, 64, (32,))
    prompts = [np.concatenate([common, rng.randint(0, 64, (2 + i,))])
               for i in range(4)]
    oracle = _oracle(model, prompts, new=5, page_size=16)
    eng = ServingEngine(model, max_batch=4, max_seq_len=64, page_size=16,
                        prefix_sharing=True, prefill_chunk=16)
    gw = ServingGateway(eng)
    try:
        cli = GatewayClient("127.0.0.1", gw.port)
        outs = [cli.generate(p, max_new_tokens=5) for p in prompts]
        for a, b in zip(oracle, outs):
            np.testing.assert_array_equal(a, b)
        info = eng.info()
        assert info["shared_prefix_joins"] >= 3
        assert info["prefill_pages_saved"] >= 6
        assert info["prefill_chunks"] >= 1
        cli.close()
    finally:
        gw.stop(drain=True, timeout=10.0)


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def test_summaries_render_gateway_and_prefix_counters(model):
    from paddle_tpu import profiler
    eng = ServingEngine(model, max_batch=2, max_seq_len=64, page_size=16,
                        prefix_sharing=True, prefill_chunk=16)
    gw = ServingGateway(eng)
    try:
        cli = GatewayClient("127.0.0.1", gw.port)
        p = _prompt(20, seed=71)
        cli.generate(p, max_new_tokens=4)
        cli.generate(p, max_new_tokens=4)
        text = profiler.serving_summary()
        assert "prefix:" in text and "pages_saved=" in text
        assert "chunks=" in text
        gtext = profiler.gateway_summary()
        assert f"port={gw.port}" in gtext
        assert "requests=2" in gtext
        cli.close()
    finally:
        gw.stop(drain=True, timeout=10.0)
    del eng
