"""Overload control and graceful degradation (ISSUE 18).

The contract under test, layer by layer:

- **bounded admission**: a submit() past `max_queue` — or, with
  deadline-aware shedding enabled, one whose TTL cannot cover the
  projected queue wait at the engine's measured token rate — raises the
  typed `EngineOverloaded` (terminal, carries `retry_after_ms`) instead
  of queueing it into a guaranteed RequestTimeout;
- **the degradation ladder**: sustained queue pressure sheds optional
  work in order (prefix tree -> speculative scratch -> chunked-prefill
  interleave), enters/exits with hysteresis, stamps every transition on
  the trace ring, and exports level + occupancy through info()/metrics;
- **the flight recorder**: every shed's EngineOverloaded construction
  snapshots the ring, so `last_incident()` carries the shed event with
  the pressure level stamped on it;
- **the wire**: the shed travels as a 429 frame with `retry-after-ms`,
  the client re-raises the typed `EngineOverloaded`, backs off with the
  server's advice, trips its circuit breaker (`CircuitOpen`) after
  consecutive typed failures, and recovers through the half-open probe;
- **HEALTH**: a load balancer reads readiness + pressure without ever
  touching the generate path, draining or not.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.observability import trace
from paddle_tpu.utils.deadline import EngineOverloaded, RequestTimeout
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.inference.serving.gateway import (CircuitOpen, GatewayClient,
                                                  ServingGateway)


def _model(seed=7, vocab=64, hidden=32, layers=2, heads=4, seq=64):
    P.seed(seed)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig.tiny(vocab=vocab, hidden=hidden, layers=layers,
                           heads=heads, inter=hidden * 2, seq=seq)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def model():
    # ONE model per suite: engines over the same weights share lowerings
    return _model()


def _prompt(n, seed=0, vocab=64):
    return np.random.RandomState(seed).randint(1, vocab, (n,))


@pytest.fixture
def tracing():
    trace.trace_clear()
    trace.clear_incidents()
    trace.enable(True)
    yield
    trace.enable(False)
    trace.trace_clear()
    trace.clear_incidents()


# ---------------------------------------------------------------------------
# bounded admission (engine level)
# ---------------------------------------------------------------------------

def test_queue_cap_sheds_typed_with_retry_after(model):
    eng = ServingEngine(model, max_batch=1, max_seq_len=64, max_queue=2)
    r1 = eng.submit(_prompt(4, seed=1), max_new_tokens=3)
    r2 = eng.submit(_prompt(4, seed=2), max_new_tokens=3)
    with pytest.raises(EngineOverloaded) as ei:
        eng.submit(_prompt(4, seed=3), max_new_tokens=3)
    # terminal + typed: carries the retry advice, counts as a shed
    assert ei.value.retry_after_ms >= 1
    assert "max_queue" in str(ei.value)
    info = eng.info()
    assert info["pressure"]["shed"] == 1
    assert info["rejected"] >= 1
    # the accepted requests are untouched by the shed
    eng.run()
    assert r1.result().size == 7 and r2.result().size == 7
    assert eng.info()["pressure"]["shed"] == 1  # no double count


def test_cold_engine_never_deadline_sheds(model):
    # deadline-aware shedding enabled, but NO measured rate yet: the
    # estimate would be fiction, so the first burst always queues
    eng = ServingEngine(model, max_batch=1, max_seq_len=64, shed_ttl=5.0)
    req = eng.submit(_prompt(4, seed=4), max_new_tokens=2, ttl=1e-6)
    assert req is not None  # queued, not shed (it will expire, typed)


def test_deadline_aware_shed_on_projected_wait(model):
    eng = ServingEngine(model, max_batch=1, max_seq_len=64, shed_ttl=30.0)
    # warm: one full request gives the engine a measured token rate
    eng.generate([_prompt(4, seed=5)], max_new_tokens=4)
    assert eng._measured_rate() is not None
    # backlog ~40 tokens on one slot; a microscopic TTL cannot cover it
    eng.submit(_prompt(4, seed=6), max_new_tokens=40)
    with pytest.raises(EngineOverloaded) as ei:
        eng.submit(_prompt(4, seed=7), max_new_tokens=40, ttl=1e-6)
    assert "projected queue wait" in str(ei.value)
    assert ei.value.retry_after_ms >= 1
    # a TTL-less request is judged against shed_ttl=30s: plenty, queued
    r = eng.submit(_prompt(4, seed=8), max_new_tokens=4)
    eng.run()
    assert r.result().size == 8


def test_deadline_shed_off_by_default(model):
    # without the knob, a doomed-TTL request queues and expires TYPED
    # (the pre-existing contract tier-1 pins in test_serving.py)
    eng = ServingEngine(model, max_batch=1, max_seq_len=64)
    eng.generate([_prompt(4, seed=9)], max_new_tokens=4)  # warm rate
    eng.submit(_prompt(4, seed=10), max_new_tokens=40)
    rb = eng.submit(_prompt(4, seed=11), max_new_tokens=4, ttl=0.001)
    eng.run()
    with pytest.raises(RequestTimeout):
        rb.result()


def test_burst_over_capacity_is_served_or_shed_typed_never_lost(model):
    """Twice what slots and queue hold, offered in two waves: every request
    is either accepted and served with a never-overloaded engine's tokens or
    shed with the typed EngineOverloaded (anything else fails the test), the
    engine's shed counter agrees, and every engine step is accounted to
    exactly one pressure level, more than one of which was entered."""
    work = [(_prompt(4 + i % 5, seed=70 + i), 6 + i % 4) for i in range(12)]
    calm = ServingEngine(model, max_batch=2, max_seq_len=64, max_queue=64)
    oracle = [calm.submit(p, max_new_tokens=n) for p, n in work]
    calm.run()
    eng = ServingEngine(model, max_batch=2, max_seq_len=64, max_queue=4)
    accepted, shed, steps = {}, 0, 0
    for wave in (range(0, 6), range(6, 12)):
        for i in wave:
            try:
                accepted[i] = eng.submit(work[i][0],
                                         max_new_tokens=work[i][1])
            except EngineOverloaded as e:
                assert e.retry_after_ms >= 1
                shed += 1
        for _ in range(2):
            eng.step()
            steps += 1
    while not eng.scheduler.idle:
        eng.step()
        steps += 1
    assert accepted and shed and len(accepted) + shed == len(work)
    for i, req in accepted.items():
        np.testing.assert_array_equal(req.result(), oracle[i].result())
    pressure = eng.info()["pressure"]
    assert pressure["shed"] == shed
    by_level = [pressure[f"level{i}_steps"] for i in range(4)]
    assert sum(by_level) == steps
    assert sum(1 for n in by_level if n) >= 2, by_level
    assert eng.pressure_level == 0 and eng.pool.info()["active_pages"] == 0


# ---------------------------------------------------------------------------
# the degradation ladder
# ---------------------------------------------------------------------------

def test_ladder_enters_and_exits_with_hysteresis(model, tracing):
    eng = ServingEngine(model, max_batch=1, max_seq_len=64, max_queue=8)
    for i in range(7):  # depth 7/8 = 0.875 -> level 2 at the first step
        eng.submit(_prompt(4, seed=20 + i), max_new_tokens=2)
    eng.step()
    assert eng.pressure_level == 2
    assert eng.info()["pressure"]["level"] == 2
    eng.run()
    # drained: the ladder walked back down to healthy
    assert eng.pressure_level == 0
    lvl = eng.info()["pressure"]
    assert lvl["level0_steps"] > 0 and lvl["level2_steps"] > 0
    # every transition was stamped on the ring, with hysteresis: the
    # ladder never flapped (each level entered at most once on the way
    # up, exited at most once on the way down)
    trans = [r for r in trace.trace_records()
             if r["name"] == "engine.pressure"]
    assert trans, "no ladder transition reached the trace ring"
    seen = [(r["args"]["prev"], r["args"]["level"]) for r in trans]
    assert seen[0][1] == 2                       # straight to level 2
    assert seen[-1][1] == 0                      # back to healthy
    assert len(seen) == len(set(seen)), f"ladder flapped: {seen}"


def test_ladder_level1_trims_prefix_tree_and_pauses_commits(model):
    eng = ServingEngine(model, max_batch=1, max_seq_len=64, max_queue=4,
                        page_size=16, prefix_sharing=True)
    # commit a prefix chain into the tree (1/4 queued stays level 0)
    eng.generate([_prompt(32, seed=30)], max_new_tokens=2)
    assert eng.info()["prefix"]["pages_held"] > 0
    # two queued requests at depth 2/4 = 0.5 -> level 1
    eng.submit(_prompt(4, seed=31), max_new_tokens=2)
    eng.submit(_prompt(4, seed=32), max_new_tokens=2)
    eng.step()
    assert eng.pressure_level >= 1
    info = eng.info()
    assert info["prefix"]["pages_held"] == 0, "tree not trimmed at level 1"
    assert info["pressure"]["prefix_paused"] == 1
    assert info["pressure"]["pressure_trims"] >= 1
    eng.run()
    # healthy again: sharing resumes (pause flag dropped)
    assert eng.pressure_level == 0
    assert eng.info()["pressure"]["prefix_paused"] == 0
    # and the tree regrows from fresh traffic after the exit
    eng.generate([_prompt(32, seed=30)], max_new_tokens=2)
    assert eng.info()["prefix"]["pages_held"] > 0


def test_ladder_level2_pauses_spec_and_returns_scratch(model):
    eng = ServingEngine(model, max_batch=1, max_seq_len=64, max_queue=4,
                        spec_k=2)
    assert eng.scheduler.reserve_extra == 2
    for i in range(3):  # depth 3/4 = 0.75 -> level 2
        eng.submit(_prompt(4, seed=40 + i), max_new_tokens=3)
    reqs = [eng.submit(_prompt(4, seed=43), max_new_tokens=3)]
    with pytest.raises(EngineOverloaded):
        eng.submit(_prompt(4, seed=44), max_new_tokens=3)  # cap at 4
    eng.step()
    assert eng.pressure_level >= 2
    info = eng.info()["pressure"]
    assert info["spec_paused"] == 1 and info["spec_pauses"] == 1
    # the verify scratch went back: future reservations are spec-free
    assert eng.scheduler.reserve_extra == 0
    eng.run()
    assert eng.pressure_level == 0
    # exit restored the scratch reservation for future admissions
    assert eng.scheduler.reserve_extra == 2
    assert reqs[0].result().size == 7
    # the greedy stream is bitwise the non-speculative engine's: the
    # ladder degraded throughput, never tokens
    plain = ServingEngine(model, max_batch=1, max_seq_len=64)
    ref = plain.generate([_prompt(4, seed=43)], max_new_tokens=3)
    assert np.array_equal(reqs[0].result(), ref[0])


def test_shed_lands_in_last_incident_with_pressure_level(model, tracing):
    eng = ServingEngine(model, max_batch=1, max_seq_len=64, max_queue=1)
    eng.submit(_prompt(4, seed=50), max_new_tokens=2)
    with pytest.raises(EngineOverloaded):
        eng.submit(_prompt(4, seed=51), max_new_tokens=2)
    inc = trace.last_incident()
    assert inc is not None and inc["error"] == "EngineOverloaded"
    assert inc["spans"], "shed incident carries no timeline"
    last = inc["spans"][-1]
    assert last["name"] == "engine.shed"
    assert "level" in last["args"]          # pressure level stamped
    assert last["args"]["retry_after_ms"] >= 1
    eng.run()


# ---------------------------------------------------------------------------
# the wire: 429 + retry-after-ms, backoff, breaker, HEALTH
# ---------------------------------------------------------------------------

def _saturate(eng, cli_a, prompt, max_new):
    """Occupy the single slot with a long request via a background client
    and wait until it is actually decoding."""
    done = {}

    def run_a():
        done["tokens"] = cli_a.generate(prompt, max_new_tokens=max_new,
                                        timeout=60.0)

    t = threading.Thread(target=run_a, daemon=True)
    t.start()
    deadline = time.monotonic() + 30.0
    # `active`, not `scheduler.active`: the latter settles under the
    # engine's lock, so it waits behind the driver's steps
    while eng.active == 0:
        if time.monotonic() > deadline:
            pytest.fail("saturating request never started decoding")
        time.sleep(0.002)
    return t, done


def test_wire_429_retry_after_and_breaker(monkeypatch):
    monkeypatch.setenv("PT_GATEWAY_BREAKER_THRESHOLD", "2")
    monkeypatch.setenv("PT_GATEWAY_BREAKER_COOLDOWN", "0.3")
    # a model of its own with room for a LONG hold: the slot must stay taken
    # while three calls cross the wire, and since PR 32 a head that is not
    # whole tiles of lanes decodes through the masked attention, a fraction
    # of a millisecond a step here where the interpreted kernel took several
    eng = ServingEngine(_model(seq=1024), max_batch=1, max_seq_len=1024,
                        max_queue=1)
    gw = ServingGateway(eng)
    cli_a = cli_b = cli = None
    try:
        cli_a = GatewayClient("127.0.0.1", gw.port)
        cli_b = GatewayClient("127.0.0.1", gw.port)
        cli = GatewayClient("127.0.0.1", gw.port)
        ta, da = _saturate(eng, cli_a, _prompt(4, seed=60), 1000)
        # fill the queue (depth 1 == max_queue) through a second client
        db = {}

        def run_b():
            db["tokens"] = cli_b.generate(_prompt(4, seed=61),
                                          max_new_tokens=8, timeout=60.0)

        tb = threading.Thread(target=run_b, daemon=True)
        tb.start()
        deadline = time.monotonic() + 30.0
        while eng.queue_depth == 0:
            if time.monotonic() > deadline:
                pytest.fail("queue never filled")
            time.sleep(0.002)
        # 1st + 2nd shed: typed EngineOverloaded over the wire, with the
        # server's retry-after-ms on the reconstructed exception
        for _ in range(2):
            with pytest.raises(EngineOverloaded) as ei:
                cli.generate(_prompt(4, seed=62), max_new_tokens=4,
                             retries=0, timeout=10.0)
            assert ei.value.retry_after_ms >= 1
        # threshold reached: the breaker fails the NEXT call locally
        with pytest.raises(CircuitOpen) as ci:
            cli.generate(_prompt(4, seed=62), max_new_tokens=4,
                         retries=0, timeout=10.0)
        assert ci.value.retry_after_ms >= 1
        assert cli.breaker_open
        # HEALTH is breaker-exempt and never touches the generate path
        h = cli.health()
        assert h["ready"] is True and h["draining"] is False
        assert h["queued"] >= 0 and h["pressure"] >= 0
        # let the saturating traffic drain, ride out the cooldown: the
        # half-open probe succeeds and closes the breaker
        ta.join(60.0)
        tb.join(60.0)
        assert da["tokens"].size == 1004 and db["tokens"].size == 12
        time.sleep(0.35)
        out = cli.generate(_prompt(4, seed=63), max_new_tokens=4,
                           retries=0, timeout=30.0)
        assert out.size == 8
        assert not cli.breaker_open
        # metrics: the ladder exports through the wire scrape
        text = cli.metrics()
        assert "pt_serving_pressure_level" in text
        assert "pt_serving_pressure_shed" in text
    finally:
        for c in (cli_a, cli_b, cli):
            if c is not None:
                c.close()
        gw.stop(drain=True, timeout=10.0)


def test_client_backoff_retries_past_transient_overload(model):
    eng = ServingEngine(model, max_batch=1, max_seq_len=64, max_queue=1)
    gw = ServingGateway(eng)
    cli_a = cli = None
    try:
        cli_a = GatewayClient("127.0.0.1", gw.port)
        cli = GatewayClient("127.0.0.1", gw.port)
        ta, da = _saturate(eng, cli_a, _prompt(4, seed=70), 24)
        eng.submit(_prompt(4, seed=71), max_new_tokens=2)  # fill the queue
        # the overload is transient (the slot drains in ~24 steps): the
        # jittered retry-after backoff rides it out and succeeds
        out = cli.generate(_prompt(4, seed=72), max_new_tokens=4,
                           retries=50, timeout=60.0)
        assert out.size == 8
        ta.join(60.0)
        assert da["tokens"].size == 28
    finally:
        for c in (cli_a, cli):
            if c is not None:
                c.close()
        gw.stop(drain=True, timeout=10.0)


def test_health_verb_reports_drain(model):
    eng = ServingEngine(model, max_batch=2, max_seq_len=64)
    gw = ServingGateway(eng)
    cli = None
    try:
        cli = GatewayClient("127.0.0.1", gw.port)
        h = cli.health()
        assert h == {"ready": True, "draining": False, "pressure": 0,
                     "queued": 0, "active": 0}
        gw.drain(timeout=5.0)
        h = cli.health()
        assert h["ready"] is False and h["draining"] is True
    finally:
        if cli is not None:
            cli.close()
        gw.stop(drain=False)
