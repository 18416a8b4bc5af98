"""Unified trace spans, flight recorder, and the metrics wire (ISSUE 15).

The contract under test:
- span()/event() record into a thread-safe bounded ring with parent
  linkage and correlation attrs; disabled tracing is a no-op (the ring
  stays empty and every span() is the one shared no-op: the near-zero-
  cost law's observable half; the measured half is PERF.md's, on the chip);
- with tracing on, one engine run yields the serving loop's phases under
  the names of trace.py's docstring, each nested under its cause, and
  under an open jax.profiler session the same spans lie on the host plane
  of the profiler's trace (PR 25);
- a launched call becomes ONE `device.*` record once it is seen done, from
  the later of the previous call's completion and its own dispatch, on
  the device's own lane of an export; a collection of Python's collector is
  a `gc.collect` span and a count; with tracing off neither runs at all;
- A GATEWAY-DRIVEN serving run exports a Chrome-trace JSON in which ONE
  request id links the gateway request span to the engine's prefill /
  decode-step / verify-step spans and the scheduler's join/evict events
  (the acceptance timeline);
- a chaos delay at an armed fault site yields a typed deadline error
  whose flight-recorder incident timeline is non-empty and ENDS at the
  faulted site;
- the metrics registry (Counter/Gauge/Histogram + pull collectors)
  renders deterministic Prometheus text; the gateway's PTSG/1 METRICS
  verb round-trips the engine's counters byte-for-byte vs the in-process
  snapshot, and answers the typed 503 while draining;
- every profiler summary renders cleanly in a fresh process whose
  subsystem was never imported (the shared no-data idiom), without
  importing it.
"""
import gc
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.distributed import chaos
from paddle_tpu.observability import metrics, trace
from paddle_tpu.utils.deadline import DeadlineExceeded, RequestTimeout

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _model(seed=7, vocab=64, hidden=32, layers=2, heads=4, seq=64):
    P.seed(seed)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig.tiny(vocab=vocab, hidden=hidden, layers=layers,
                           heads=heads, inter=hidden * 2, seq=seq)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def model():
    # ONE model for every engine test in this file: engines over the same
    # weights share step lowerings (the model-stash idiom), so the suite
    # pays the prefill/decode/verify compiles once
    return _model()


def _prompt(n, seed=0, vocab=64):
    return np.random.RandomState(seed).randint(1, vocab, (n,))


@pytest.fixture
def tracing():
    """Enable tracing around one test; restore the disabled default and
    drain ring + incidents so tests stay order-independent."""
    trace.trace_clear()
    trace.clear_incidents()
    gc.collect()     # a test's few records then meet no collection
    trace.enable(True)
    yield
    trace.enable(False)
    trace.trace_clear()
    trace.clear_incidents()


# ---------------------------------------------------------------------------
# the trace ring
# ---------------------------------------------------------------------------

def test_span_nesting_ring_and_export(tracing, tmp_path):
    with trace.span("outer", rid=7) as sp:
        sp.set(late="attr")
        with trace.span("inner", rid=7):
            trace.event("tick", rid=7)
    recs = trace.trace_records()
    assert [r["name"] for r in recs] == ["tick", "inner", "outer"]
    outer = recs[2]
    inner = recs[1]
    assert inner["parent"] == outer["id"]       # nesting -> parent linkage
    assert recs[0]["parent"] == inner["id"]     # events parent too
    assert outer["args"] == {"rid": 7, "late": "attr"}
    assert inner["dur"] >= 0 and recs[0]["dur"] is None
    path = trace.export_trace(str(tmp_path / "t.json"))
    evs = json.load(open(path))["traceEvents"]
    assert [e["name"] for e in evs] == ["tick", "inner", "outer"]
    assert evs[2]["ph"] == "X" and evs[2]["dur"] >= 0
    assert evs[0]["ph"] == "i"
    assert evs[1]["args"]["parent_id"] == evs[2]["args"]["span_id"]


def test_ring_bound_and_dropped_counter(tracing):
    trace.set_ring_size(4)
    try:
        for i in range(10):
            trace.event(f"e{i}")
        recs = trace.trace_records()
        assert len(recs) == 4
        assert [r["name"] for r in recs] == ["e6", "e7", "e8", "e9"]
        assert trace.trace_info()["dropped"] == 6
    finally:
        trace.set_ring_size(4096)


def test_disabled_tracing_is_a_noop():
    trace.enable(False)
    trace.trace_clear()
    with trace.span("x", rid=1) as sp:
        assert sp.set(a=1) is sp    # the null span keeps the API
        trace.event("y")
    assert trace.trace_records() == []
    assert trace.trace_info()["enabled"] is False


class _Late:
    """A launched call's output: done once `ready` is set, or once the
    host blocks on it."""

    def __init__(self):
        self.ready = False
        self.checks = self.blocks = 0

    def is_ready(self):
        self.checks += 1
        return self.ready

    def block_until_ready(self):
        self.blocks += 1
        self.ready = True
        return self


def _device(recs):
    return [r for r in recs if r["name"].startswith("device.")]


@pytest.mark.parametrize("seen_by", ["poll", "wait"])
def test_a_launched_call_is_one_device_span_once_seen_done(tracing, seen_by):
    """Two calls launched back to back: the first spans from its dispatch
    to its completion, the second from the first's completion (it was
    dispatched earlier) to its own, in launch order.  A poll at a span's
    enter or exit stamps what is done (`late_ns` the time since the check
    that found it not done); `done()`
    blocks on every call up to the one named, in device order (`late_ns`
    0: the wait wakes at completion)."""
    a, b = _Late(), _Late()
    t0 = time.monotonic_ns()
    trace.launched("device.prefill", a, rid=4, bucket=8, pos=0)
    t1 = time.monotonic_ns()
    trace.launched("device.decode_step", b, step=0, rids=[4])
    with trace.span("engine.step"):          # a check: neither is done
        pass
    t_miss = time.monotonic_ns()
    assert _device(trace.trace_records()) == [] and a.checks >= 1
    if seen_by == "poll":
        a.ready = True
        with trace.span("engine.decode.prep"):
            pass
        t_done = time.monotonic_ns()
        assert [r["name"] for r in _device(trace.trace_records())] == [
            "device.prefill"]
        b.ready = True
        with trace.span("engine.decode.emit"):
            pass
    else:
        trace.done(b)
        t_done = time.monotonic_ns()
        assert a.blocks == 1 and b.blocks == 1
    pre, dec = _device(trace.trace_records())
    assert (pre["name"], dec["name"]) == ("device.prefill",
                                          "device.decode_step")
    assert t0 <= pre["ts"] <= t1                   # its dispatch
    assert pre["args"] == dict(rid=4, bucket=8, pos=0,
                               late_ns=pre["args"]["late_ns"])
    assert dec["args"] == dict(step=0, rids=[4],
                               late_ns=dec["args"]["late_ns"])
    assert dec["ts"] == pre["ts"] + pre["dur"]     # the previous completion
    assert pre["ts"] + pre["dur"] <= t_done
    assert pre["tid"] == dec["tid"] == trace.DEVICE_TID
    assert pre["cat"] == "device" and pre["parent"] is None
    if seen_by == "poll":
        assert 0 < pre["args"]["late_ns"] <= pre["ts"] + pre["dur"] - t_miss \
            + (t_miss - t1)
    else:
        assert pre["args"]["late_ns"] == dec["args"]["late_ns"] == 0
    info = trace.trace_info()["device"]
    assert info == {"depth": 0, "seen": 2, "undone": 0}


def test_calls_never_seen_done_are_counted_and_the_lane_is_named(tracing,
                                                                  tmp_path):
    a, b = _Late(), _Late()
    trace.launched("device.window", a, rid=1, pos=32, tokens=16)
    trace.launched("device.verify_step", b, step=3, rids=[1])
    trace.done(a)
    assert b.blocks == 0 and trace.trace_info()["device"]["depth"] == 1
    with trace.span("engine.step"):
        pass
    path = trace.export_trace(str(tmp_path / "t.json"))
    evs = json.load(open(path))["traceEvents"]
    lane = [e for e in evs if e["tid"] == trace.DEVICE_TID]
    assert {"ph": "M", "name": "thread_name", "pid": os.getpid(),
            "tid": trace.DEVICE_TID, "args": {"name": "device"}} in lane
    (win,) = [e for e in lane if e["ph"] == "X"]
    assert win["name"] == "device.window" and win["args"]["pos"] == 32
    assert all(e["tid"] != trace.DEVICE_TID for e in evs
               if e["name"] == "engine.step")
    trace.enable(False)       # the call in flight is never seen done
    assert trace.trace_info()["device"] == {"depth": 0, "seen": 1,
                                            "undone": 1}


@pytest.mark.parametrize("generation", [0, 1, 2])
def test_a_collection_is_counted_and_a_gc_collect_span(tracing, generation):
    """Every collection is counted by generation; one of generation 1 or 2
    is a `gc.collect` span too."""
    recorded = generation > 0
    before = trace.trace_info()["gc"].get(generation, {"count": 0})["count"]
    with trace.span("engine.step") as outer:
        gc.collect(generation)
    got = trace.trace_info()["gc"][generation]
    assert got["count"] >= before + 1 and got["ns"] > 0
    spans = [r for r in trace.trace_records() if r["name"] == "gc.collect"
             and r["args"]["generation"] == generation]
    assert bool(spans) is recorded
    if recorded:
        (r,) = spans
        assert set(r["args"]) == {"generation", "collected", "uncollectable"}
        assert r["parent"] == outer.sid and r["dur"] > 0


def test_a_collection_under_the_rings_lock_waits_on_no_lock(tracing):
    """A collection strikes at any allocation, also one made while the
    ring's lock is held (a snapshot builds a list under it): the hook
    takes no lock, and its record reaches the ring at the next read."""
    finished = threading.Event()

    def work():
        with trace._RING._lock:
            gc.collect(1)
        finished.set()

    t = threading.Thread(target=work, daemon=True)
    t.start()
    t.join(30)
    assert finished.is_set()
    assert [r["args"]["generation"] for r in trace.trace_records()
            if r["name"] == "gc.collect"] == [1]


def test_calls_are_stamped_once_in_order_under_threads_polling(tracing):
    """Threads that record spans poll the FIFO's head while the launching
    thread blocks on its calls: every call is ONE record, in launch order,
    none lost or doubled (the FIFO's lock; a blocking wait holds none)."""
    import random
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    stop = threading.Event()

    def spans():
        for _ in range(4000):
            if stop.is_set():
                return
            with trace.span("engine.decode.emit"):
                pass

    workers = [threading.Thread(target=spans, daemon=True) for _ in range(6)]
    rng = random.Random(5)
    outs = [_Late() for _ in range(300)]
    trace.set_ring_size(1 << 16)       # holds every worker's spans too
    try:
        for w in workers:
            w.start()
        for i, out in enumerate(outs):
            trace.launched("device.decode_step", out, step=i)
            for o in rng.sample(outs[:i + 1], min(3, i + 1)):
                o.ready = True
            if i % 10 == 9:
                trace.done(out)
        trace.done(outs[-1])
    finally:
        stop.set()
        for w in workers:
            w.join(30)
        sys.setswitchinterval(old)
    assert not any(w.is_alive() for w in workers)
    assert trace.trace_info()["dropped"] == 0
    got = [r["args"]["step"] for r in _device(trace.trace_records())]
    trace.set_ring_size(4096)
    assert got == list(range(len(outs)))
    assert trace.trace_info()["device"] == {"depth": 0, "seen": len(outs),
                                            "undone": 0}


def test_tracing_off_runs_nothing_new(model):
    """Off: no collector hook, no FIFO entry, no record and no thread,
    through the calls themselves and a whole engine run; on, the hook is
    there, and off again it is gone."""
    from paddle_tpu.inference.serving import ServingEngine
    trace.enable(False)
    trace.trace_clear()
    hooks, threads = list(gc.callbacks), set(threading.enumerate())
    out = _Late()
    trace.launched("device.decode_step", out, step=0, rids=[1])
    trace.done(out)
    assert out.checks == out.blocks == 0
    eng = ServingEngine(model, max_batch=2, max_seq_len=64)
    eng.generate([_prompt(5, seed=1)], max_new_tokens=4)
    gc.collect()
    assert trace.trace_records() == []
    info = trace.trace_info()
    assert info["device"] == {"depth": 0, "seen": 0, "undone": 0}
    assert info["gc"] == {}
    assert gc.callbacks == hooks
    assert set(threading.enumerate()) == threads
    trace.enable(True)
    try:
        assert len(gc.callbacks) == len(hooks) + 1
    finally:
        trace.enable(False)
    assert gc.callbacks == hooks


def test_trace_summary_renders(tracing):
    import paddle_tpu.profiler as prof
    with trace.span("site.a"):
        trace.event("site.b")
    out = prof.trace_summary()
    assert "site.a" in out and "records=" in out


# ---------------------------------------------------------------------------
# the acceptance timeline: one rid across gateway -> engine -> verify
# ---------------------------------------------------------------------------

def test_gateway_run_exports_rid_linked_chrome_trace(tracing, tmp_path, model):
    """A gateway-driven serving run on a SPECULATIVE engine: the exported
    Chrome trace holds one request id linking gateway.request ->
    engine.submit/prefill -> engine.decode_step -> engine.verify_step ->
    scheduler join/evict — the cross-layer correlation the ISSUE names."""
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.inference.serving.gateway import (GatewayClient,
                                                      ServingGateway)
    eng = ServingEngine(model, max_batch=4, max_seq_len=64, spec_k=2,
                        drafter="ngram")
    gw = ServingGateway(eng)
    try:
        cli = GatewayClient("127.0.0.1", gw.port)
        out = cli.generate(_prompt(8, seed=3), max_new_tokens=8)
        assert out.size == 16
        cli.close()
    finally:
        gw.stop(drain=True, timeout=10.0)
    path = trace.export_trace(str(tmp_path / "serve.json"))
    evs = json.load(open(path))["traceEvents"]
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    # the wire-side span carries the engine's request id
    gw_spans = by_name["gateway.request"]
    assert len(gw_spans) == 1 and gw_spans[0]["ph"] == "X"
    rid = gw_spans[0]["args"]["rid"]
    # ... and that SAME id links every engine-side span of the request
    assert any(e["args"].get("rid") == rid
               for e in by_name["engine.submit"])
    assert any(e["args"].get("rid") == rid
               for e in by_name["engine.prefill"])
    assert any(rid in e["args"].get("rids", ())
               for e in by_name["engine.decode_step"])
    assert any(rid in e["args"].get("rids", ())
               for e in by_name["engine.verify_step"])
    assert any(e["args"].get("rid") == rid
               for e in by_name["scheduler.join"])
    assert any(e["args"].get("rid") == rid
               for e in by_name["scheduler.evict"])
    # the verify span nests inside its decode step
    verify = by_name["engine.verify_step"][0]
    decode_ids = {e["args"]["span_id"] for e in by_name["engine.decode_step"]}
    assert verify["args"]["parent_id"] in decode_ids
    # gateway read spans exist on the wire side of the same timeline
    assert by_name["gateway.read"]


def test_engine_trace_off_records_nothing(model):
    """The PT_TRACE=0 default: a full engine run leaves the ring empty
    (no hidden recording on the serving hot path), and every span() of it
    was the ONE shared no-op object."""
    from paddle_tpu.inference.serving import ServingEngine
    trace.enable(False)
    trace.trace_clear()
    eng = ServingEngine(model, max_batch=2, max_seq_len=64)
    eng.generate([_prompt(5, seed=1)], max_new_tokens=4)
    assert trace.trace_records() == []
    assert trace.span("engine.decode.prep") is trace._NULL
    assert trace.span("engine.prefill", rid=1) is trace._NULL


# ---------------------------------------------------------------------------
# the serving loop's phases: the span names the benchmark reads
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def loop_records(model):
    """One engine run with tracing on: two prompts, one joining mid-stream."""
    from paddle_tpu.inference.serving import ServingEngine
    trace.trace_clear()
    trace.enable(True)
    try:
        eng = ServingEngine(model, max_batch=2, max_seq_len=64)
        first = eng.submit(_prompt(5, seed=1), max_new_tokens=6)
        eng.step()
        eng.step()
        second = eng.submit(_prompt(9, seed=2), max_new_tokens=4)
        eng.run()
        assert first.done and second.done
        recs = trace.trace_records()
    finally:
        trace.enable(False)
        trace.trace_clear()
    return recs, {first.rid, second.rid}


def _named(recs, name):
    return [r for r in recs if r["name"] == name]


# a step's wait and emit sit under the decode step span that reads it, or,
# where a prefill joins while a step is in flight, under the `engine.settle`
# (cause "prefill") inside that prefill's wait: the step in flight is read
# before the prefill's first token, so its tokens do not wait out the call
READS = ("engine.decode_step", "engine.settle")
LOOP_TREE = [
    ("engine.prefill", ("engine.step",)),
    ("engine.decode_step", ("engine.step",)),
    ("scheduler.join", ("engine.step",)),
    ("engine.prefill.prep", ("engine.prefill",)),
    ("engine.prefill.launch", ("engine.prefill",)),
    ("engine.prefill.wait", ("engine.prefill",)),
    ("engine.prefill.commit", ("engine.prefill",)),
    ("engine.settle", ("engine.prefill.wait",)),
    ("engine.decode.prep", ("engine.decode_step",)),
    ("engine.decode.launch", ("engine.decode_step",)),
    ("engine.decode.wait", READS),
    ("engine.decode.emit", READS),
    ("capture.execute", ("capture.call",)),
]


@pytest.mark.parametrize("child,parents", LOOP_TREE,
                         ids=[c for c, _ in LOOP_TREE])
def test_serving_loop_span_nests_under_its_cause(loop_records, child,
                                                 parents):
    recs, _ = loop_records
    by_id = {r["id"]: r for r in recs}
    mine = _named(recs, child)
    assert mine, f"the engine run recorded no {child}"
    for r in mine:
        assert r["parent"] in by_id, (child, "has no recorded parent")
        assert by_id[r["parent"]]["name"] in parents
    # one child of a kind a parent span. A decode step is launched one
    # ahead: a span holds step i+1's prep and launch and step i's wait and
    # emit, so the first of a run has no wait or emit, the span that reads
    # the last step no prep or launch, and every step is read once
    if parents == ("engine.prefill",):
        assert len(mine) == len(_named(recs, parents[0]))
    if "engine.decode_step" in parents:
        assert len({r["parent"] for r in mine}) == len(mine)
        launched = len(_named(recs, "engine.decode.launch"))
        assert len(mine) == launched < len(_named(recs, "engine.decode_step"))
    if child == "engine.settle":
        assert {r["args"]["cause"] for r in mine} == {"prefill"}


def test_capture_call_sits_under_the_launch_spans(loop_records):
    recs, _ = loop_records
    by_id = {r["id"]: r for r in recs}
    calls = _named(recs, "capture.call")
    assert calls
    assert {by_id[r["parent"]]["name"] for r in calls} == {
        "engine.prefill.launch", "engine.decode.launch"}
    # a NEW signature shows by name: its trace and lowering are children
    for name in ("capture.trace", "capture.lower"):
        for r in _named(recs, name):
            assert by_id[r["parent"]]["name"] == "capture.call"


def test_children_fit_inside_their_parent(loop_records):
    recs, _ = loop_records
    kids = {}
    for r in recs:
        if r["dur"] is not None and r["parent"] is not None:
            kids.setdefault(r["parent"], []).append(r)
    checked = 0
    for r in recs:
        if r["dur"] is None or r["id"] not in kids:
            continue
        assert sum(k["dur"] for k in kids[r["id"]]) <= r["dur"], r["name"]
        for k in kids[r["id"]]:
            assert r["ts"] <= k["ts"] and \
                k["ts"] + k["dur"] <= r["ts"] + r["dur"]
        checked += 1
    assert checked >= 10
    # at most ~10 records a decode step: no span in the per-slot loops (a
    # step read by a prefill's settle has its wait and emit there)
    steps = _named(recs, "engine.decode_step")
    reads = steps + _named(recs, "engine.settle")
    # (a collection of Python's collector nests where it struck)
    under = [r for r in recs if r["parent"] in {s["id"] for s in reads}
             and r["name"] != "gc.collect"]
    launched = len(_named(recs, "engine.decode.launch"))
    assert len(under) == 4 * launched <= 4 * len(steps)


def test_join_carries_the_queue_wait_and_prefill_spans_the_rid(loop_records):
    recs, rids = loop_records
    joins = _named(recs, "scheduler.join")
    assert {j["args"]["rid"] for j in joins} == rids
    assert all(isinstance(j["args"]["waited_ns"], int)
               and j["args"]["waited_ns"] >= 0 for j in joins)
    for name in ("engine.prefill", "engine.prefill.prep",
                 "engine.prefill.launch", "engine.prefill.wait",
                 "engine.prefill.commit"):
        assert {r["args"]["rid"] for r in _named(recs, name)} == rids, name


def test_speculative_step_keeps_verify_between_decode_and_launch(tracing,
                                                                 model):
    from paddle_tpu.inference.serving import ServingEngine
    eng = ServingEngine(model, max_batch=2, max_seq_len=64, spec_k=2,
                        drafter="ngram")
    eng.generate([_prompt(6, seed=4)], max_new_tokens=5)
    recs = trace.trace_records()
    by_id = {r["id"]: r for r in recs}
    up = lambda r: by_id[r["parent"]]["name"]
    assert all(up(r) == "engine.decode_step"
               for r in _named(recs, "engine.verify_step"))
    for name in ("engine.decode.launch", "engine.decode.wait"):
        assert _named(recs, name)
        assert all(up(r) == "engine.verify_step" for r in _named(recs, name))
    for name in ("engine.decode.prep", "engine.decode.emit"):
        assert all(up(r) == "engine.decode_step" for r in _named(recs, name))


def test_an_idle_engine_step_records_nothing(tracing, model):
    from paddle_tpu.inference.serving import ServingEngine
    eng = ServingEngine(model, max_batch=2, max_seq_len=64)
    for _ in range(3):
        assert eng.step() == 0
    assert [r for r in trace.trace_records()
            if r["name"] != "gc.collect"] == []


def test_spans_lie_on_the_profilers_host_plane(tracing, tmp_path, model):
    """One clock: with a jax.profiler session open, the program's spans
    are TraceAnnotations on /host:CPU beside the device's operations, read
    back the way the benchmark's reduction reads a trace."""
    import jax
    from jax.profiler import ProfileData

    from paddle_tpu.inference.serving import ServingEngine
    eng = ServingEngine(model, max_batch=2, max_seq_len=64)
    eng.generate([_prompt(5, seed=1)], max_new_tokens=2)      # warm
    trace.trace_clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.generate([_prompt(5, seed=1)], max_new_tokens=3)
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    hits = sorted((tmp_path / "plugins" / "profile").glob("*/*.xplane.pb"))
    assert hits
    host = {}
    for plane in ProfileData.from_file(str(hits[-1])).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    host.setdefault(e.name, []).append(
                        (e.start_ns, e.duration_ns))
    ring = trace.trace_records()
    for name in ("engine.step", "engine.prefill", "engine.prefill.prep",
                 "engine.decode_step", "engine.decode.prep",
                 "engine.decode.launch", "engine.decode.wait",
                 "engine.decode.emit", "capture.call", "capture.execute"):
        assert len(host.get(name, ())) == len(_named(ring, name)) > 0, name
    # a collection too: the forced one (the ring also keeps any that struck
    # while the session opened or closed, which the profiler did not see)
    assert 0 < len(host.get("gc.collect", ())) <= len(
        _named(ring, "gc.collect"))
    # the same two points: the ring's stamps enclose the annotation's, so
    # a record lasts what its annotation lasts plus the stamps' own cost
    ring_step = sorted(r["dur"] for r in _named(ring, "engine.step"))
    prof_step = sorted(d for _, d in host["engine.step"])
    for a, b in zip(ring_step, prof_step):
        assert -1_000 < a - b < 5_000_000, (a, b)


def test_the_bridge_is_one_site_and_trace_py_imports_no_jax_at_import():
    import ast
    import re
    pkg = os.path.join(REPO, "paddle_tpu")
    sites = []
    for root, _, files in os.walk(pkg):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path, encoding="utf-8") as fh:
                    n = len(re.findall(r"TraceAnnotation\(", fh.read()))
                if n:
                    sites.append((os.path.relpath(path, pkg), n))
    assert sites == [(os.path.join("observability", "trace.py"), 1)]
    with open(os.path.join(pkg, "observability", "trace.py")) as fh:
        tree = ast.parse(fh.read())
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    names = [a.name for n in top if isinstance(n, ast.Import)
             for a in n.names] + [n.module or "" for n in top
                                  if isinstance(n, ast.ImportFrom)]
    assert not any(n.split(".")[0] == "jax" for n in names), names


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_typed_deadline_captures_incident(tracing):
    with trace.span("some.site", step=3):
        pass
    try:
        raise DeadlineExceeded("unit-test wait", 2.5)
    except DeadlineExceeded:
        pass
    inc = trace.last_incident()
    assert inc is not None
    assert inc["error"] == "DeadlineExceeded"
    assert inc["what"] == "unit-test wait" and inc["timeout"] == 2.5
    assert inc["spans"][-1]["name"] == "some.site"


def test_chaos_delay_incident_ends_at_faulted_site(tracing, monkeypatch, model):
    """The postmortem law: a delay chaos case at gateway.read stalls the
    exchange into the client's typed RequestTimeout, and last_incident()
    holds a non-empty timeline ENDING at the faulted site (the chaos
    event records before the stall, inside the read span)."""
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.inference.serving.gateway import (GatewayClient,
                                                      ServingGateway)
    eng = ServingEngine(model, max_batch=2, max_seq_len=64)
    gw = ServingGateway(eng)
    try:
        cli = GatewayClient("127.0.0.1", gw.port)
        chaos.reset_hits()
        monkeypatch.setenv("PT_FAULTPOINT", "gateway.read")
        monkeypatch.setenv("PT_FAULTPOINT_MODE", "delay:1.5")
        monkeypatch.setenv("PT_FAULTPOINT_HITS", "inf")
        trace.clear_incidents()
        with pytest.raises(RequestTimeout):
            cli.generate(_prompt(4, seed=2), max_new_tokens=4, timeout=0.4)
        inc = trace.last_incident()
        assert inc is not None and inc["error"] == "RequestTimeout"
        assert inc["spans"], "incident carries no timeline"
        last = inc["spans"][-1]
        assert last["name"] == "gateway.read"      # ends at the faulted site
        assert last["cat"] == "chaos.fault"
        assert last["args"]["mode"].startswith("delay")
        cli.close()
    finally:
        monkeypatch.delenv("PT_FAULTPOINT")
        chaos.reset_hits()
        gw.stop(drain=False)


# ---------------------------------------------------------------------------
# metrics registry + the wire
# ---------------------------------------------------------------------------

def test_metric_instruments_and_render():
    c = metrics.Counter("pt_unittest_total", "a test counter")
    c.inc()
    c.inc(4, kind="x")
    g = metrics.Gauge("pt_unittest_gauge", "a gauge")
    g.set(2.5)
    g.inc(0.5)
    h = metrics.Histogram("pt_unittest_seconds", "a histogram",
                          buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(9.0)
    with pytest.raises(ValueError):
        c.inc(-1)
    snap = metrics.metrics_snapshot()
    assert snap["pt_unittest_total"]["values"]["kind=x"] == 4.0
    assert snap["pt_unittest_gauge"]["values"][""] == 3.0
    text = metrics.render_prometheus()
    assert "# TYPE pt_unittest_total counter" in text
    assert 'pt_unittest_total{kind="x"} 4' in text
    assert 'pt_unittest_seconds_bucket{le="0.1"} 1' in text
    assert 'pt_unittest_seconds_bucket{le="+Inf"} 3' in text
    assert "pt_unittest_seconds_count 3" in text
    # deterministic: two renders over unchanged instruments are identical
    assert metrics.render_prometheus() == text


def test_registry_rejects_kind_conflict_and_custom_collector():
    metrics.Counter("pt_unittest_conflict", "first")
    with pytest.raises(ValueError):
        metrics.Gauge("pt_unittest_conflict", "second")
    metrics.register_collector(
        "unittest", lambda: [("pt_unittest_pull", "gauge", "pulled", {}, 7)])
    try:
        assert "pt_unittest_pull 7" in metrics.render_prometheus()
    finally:
        metrics.unregister_collector("unittest")


def test_gateway_metrics_verb_roundtrips_engine_counters(model):
    """The wire scrape equals the in-process snapshot byte-for-byte on
    the engine's counter lines, taken over a quiet engine — the gateway
    adds transport, never resampling."""
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.inference.serving.gateway import (GatewayClient,
                                                      ServingGateway)
    eng = ServingEngine(model, max_batch=2, max_seq_len=64)
    gw = ServingGateway(eng)
    try:
        cli = GatewayClient("127.0.0.1", gw.port)
        cli.generate(_prompt(5, seed=1), max_new_tokens=6)
        cli.generate(_prompt(9, seed=2), max_new_tokens=4)
        wire = cli.metrics()
        local = metrics.render_prometheus()

        def engine_lines(text):
            return [ln for ln in text.splitlines()
                    if ln.startswith("pt_serving_")]

        assert engine_lines(wire) == engine_lines(local)
        assert any(ln.startswith("pt_serving_tokens_generated")
                   for ln in engine_lines(wire))
        # the scrape itself is visible in the gateway funnel
        assert gw.info()["metrics_scrapes"] == 1
        cli.close()
    finally:
        gw.stop(drain=True, timeout=10.0)


def test_gateway_metrics_scrape_while_draining_is_typed_503(model):
    """Drain-awareness: a scraper hitting a draining gateway gets the
    typed GatewayDraining (503 frame), never a healthy-looking sample."""
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.inference.serving.gateway import (GatewayClient,
                                                      GatewayDraining,
                                                      ServingGateway)
    eng = ServingEngine(model, max_batch=2, max_seq_len=64)
    gw = ServingGateway(eng)
    cli = None
    try:
        cli = GatewayClient("127.0.0.1", gw.port)
        assert "pt_gateway_requests" in cli.metrics()  # live scrape works
        # park one slow request so drain() has something in flight, then
        # drain in the background and scrape on the EXISTING connection
        req = eng.submit(_prompt(4, seed=5), max_new_tokens=48)
        stopper = threading.Thread(target=gw.stop,
                                   kwargs={"drain": True, "timeout": 15.0},
                                   daemon=True)
        stopper.start()
        deadline = time.monotonic() + 5.0
        while not gw.info()["draining"] and time.monotonic() < deadline:
            time.sleep(0.005)
        assert gw.info()["draining"]
        with pytest.raises(GatewayDraining):
            cli.metrics()
        req.wait(timeout=10.0)
        stopper.join(timeout=15.0)
        assert not stopper.is_alive()
    finally:
        if cli is not None:
            cli.close()
        gw.stop(drain=False)


# ---------------------------------------------------------------------------
# profiler empty-state sweep (fresh process, subsystems never imported)
# ---------------------------------------------------------------------------

SUMMARIES = ("op_cache_summary", "step_capture_summary", "lint_summary",
             "serving_summary", "gateway_summary", "comm_summary",
             "reshard_summary", "supervisor_summary", "trace_summary")

_SWEEP = """
import sys
import paddle_tpu.profiler as prof
for name in {names!r}:
    out = getattr(prof, name)()
    assert isinstance(out, str) and out, name
    print(name, "::", out.splitlines()[0])
# rendering a summary must never import its subsystem
assert "paddle_tpu.inference.serving" not in sys.modules
assert "paddle_tpu.inference.serving.gateway" not in sys.modules
"""


def test_every_summary_renders_in_fresh_process():
    """All nine profiler summaries render in a process that never
    exercised their subsystems — empty-state guards + the one shared
    no-data idiom, and the render itself imports nothing heavy."""
    r = subprocess.run(
        [sys.executable, "-c", _SWEEP.format(names=SUMMARIES)],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = dict(ln.split(" :: ", 1) for ln in r.stdout.splitlines())
    assert set(lines) == set(SUMMARIES)
    # the unloaded subsystems all use the ONE shared idiom
    assert lines["serving_summary"] == "serving: no data (subsystem not loaded)"
    assert lines["gateway_summary"] == "gateway: no data (subsystem not loaded)"
