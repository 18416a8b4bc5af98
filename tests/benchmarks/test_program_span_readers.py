"""The readers of the program's span ring (PR 25) on hand-made span lists
whose answers are known, and the trace reduction with program spans on the
host's line: an idle gap goes to the engine phase that covers it."""
import copy
import json
import os

import pytest

from benchmarks import reduce_trace as RT
from benchmarks.readers import (event_attr_ms, span_self_ms,
                                span_time_share)
from paddle_tpu.observability import trace as ptrace

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
MS = 1_000_000
SKEW = 7_000 * MS            # the ring's clock runs 7 s ahead of the window's


def _span(sid, name, ts_ms, dur_ms, parent=None, **args):
    return {"name": name, "cat": "span", "ts": SKEW + ts_ms * MS,
            "dur": None if dur_ms is None else dur_ms * MS, "tid": 1,
            "id": sid, "parent": parent, "args": args}


def _decode_step(sid, ts_ms, prep=1.0, launch=2.0, wait=10.0, emit=0.5,
                 step_parent=None):
    """One engine.decode_step with its four children and the capture
    tier's two spans under the launch; returns (records, duration)."""
    dur = prep + launch + wait + emit + 0.5      # 0.5 ms of its own
    t = ts_ms
    recs = [_span(sid + 1, "engine.decode.prep", t, prep, sid)]
    t += prep
    recs += [_span(sid + 6, "capture.execute", t + 0.5, launch - 1.0, sid + 5),
             _span(sid + 5, "capture.call", t + 0.25, launch - 0.5, sid + 2),
             _span(sid + 2, "engine.decode.launch", t, launch, sid)]
    t += launch
    recs.append(_span(sid + 3, "engine.decode.wait", t, wait, sid))
    t += wait
    recs.append(_span(sid + 4, "engine.decode.emit", t, emit, sid))
    recs.append(_span(sid, "engine.decode_step", ts_ms, dur, step_parent))
    return recs, dur


@pytest.fixture
def ring(monkeypatch):
    """Puts a hand-made list where the readers look for the ring."""
    state = {"records": [], "dropped": 0}
    monkeypatch.setattr(ptrace, "trace_records", lambda: list(state["records"]))
    monkeypatch.setattr(ptrace, "trace_info",
                        lambda: {"dropped": state["dropped"]})
    return state


def _ev(t0_s, t1_s):
    return {"t0": t0_s, "t1": t1_s, "clock_skew_ns": SKEW}


def test_self_time_is_the_span_less_its_named_children_by_parent_id(ring):
    recs = []
    for i, (ts, wait) in enumerate([(1000, 10.0), (1100, 14.0), (1200, 12.0)]):
        got, _ = _decode_step(100 * (i + 1), ts, wait=wait)
        recs += got
    # a wait span of ANOTHER parent is nobody's here
    recs.append(_span(900, "engine.decode.wait", 1050, 500.0, None))
    ring["records"] = recs
    got = span_self_ms.read(_ev(1.0, 2.0), span="engine.decode_step",
                            less=["engine.decode.wait"], q=50)
    assert got["value"] == pytest.approx(4.0)          # 1 + 2 + 0.5 + 0.5
    assert got["detail"]["spans"] == 3
    assert got["detail"]["whole_p50_ms"] == pytest.approx(16.0)
    # a grandchild is taken from its ancestor of that name, through the launch
    got = span_self_ms.read(_ev(1.0, 2.0), span="capture.call",
                            less=["capture.execute"], q=50)
    assert got["value"] == pytest.approx(0.5)
    got = span_self_ms.read(_ev(1.0, 2.0), span="engine.decode_step",
                            less=["capture.execute"], q=50)
    assert got["value"] == pytest.approx(16.0 - 1.0)


def test_the_window_is_clipped_on_the_rings_clock(ring):
    recs = []
    for i, ts in enumerate([500, 1500, 2500]):       # before, inside, after
        got, _ = _decode_step(100 * (i + 1), ts, prep=1.0 + i)
        recs += got
    ring["records"] = recs
    got = span_self_ms.read(_ev(1.0, 2.0), span="engine.decode_step",
                            less=["engine.decode.wait"], q=50)
    assert got["detail"]["spans"] == 1
    assert got["value"] == pytest.approx(2.0 + 2.0 + 0.5 + 0.5)
    # without the skew every span lies 7 s after the window
    ev = dict(_ev(1.0, 2.0), clock_skew_ns=0)
    assert span_self_ms.read(ev, span="engine.decode_step",
                             less=["engine.decode.wait"], q=50)["value"] is None


def test_a_share_clips_spans_to_the_window_and_takes_every_wait_below(ring):
    # engine.step 900..1100 ms holds one decode step 950..1050 whose wait
    # is 960..1040; the window opens at 1000 ms
    ring["records"] = [
        _span(3, "engine.decode.wait", 960, 80, 2),
        _span(2, "engine.decode_step", 950, 100, 1),
        _span(5, "engine.prefill.wait", 1060, 20, 4),
        _span(4, "engine.prefill", 1055, 30, 1),
        _span(1, "engine.step", 900, 200, None),
        _span(6, "engine.step", 1900, 200, None),     # half in the window
    ]
    ev = _ev(1.0, 2.0)
    got = span_time_share.read(ev, spans=["engine.step"], less=["*.wait"])
    # inside: 100 + 100 ms; waits inside the window: 40 + 20 ms
    assert got["detail"]["inside_s"] == pytest.approx(0.200)
    assert got["detail"]["less_s"] == pytest.approx(0.060)
    assert got["value"] == pytest.approx(14.0)
    got = span_time_share.read(ev, spans=["engine.prefill",
                                          "engine.prefill_chunk"], less=[])
    assert got["value"] == pytest.approx(3.0)


def test_an_events_attribute_percentile(ring):
    ring["records"] = [
        dict(_span(i, "scheduler.join", 1000 + i, None, None,
                   rid=i, waited_ns=i * MS)) for i in range(1, 101)
    ] + [_span(500, "scheduler.join", 100, None, None, rid=0,
               waited_ns=10_000 * MS),                 # before the window
         _span(501, "scheduler.join", 1500, None, None, rid=7)]   # no attr
    got = event_attr_ms.read(_ev(1.0, 2.0), event="scheduler.join",
                             attr="waited_ns", q=95)
    assert got["detail"]["events"] == 100
    assert got["value"] == pytest.approx(95.05)


READS = [
    (span_self_ms, dict(span="engine.decode_step",
                        less=["engine.decode.wait"], q=50)),
    (span_time_share, dict(spans=["engine.step"], less=["*.wait"])),
    (event_attr_ms, dict(event="scheduler.join", attr="waited_ns", q=95)),
]


@pytest.mark.parametrize("reader,params", READS,
                         ids=[r.__name__.split(".")[-1] for r, _ in READS])
def test_none_when_the_ring_lost_the_windows_start(ring, reader, params):
    recs, _ = _decode_step(100, 1500)
    recs.append(_span(1, "engine.step", 1400, 300, None))
    recs.append(_span(2, "scheduler.join", 1450, None, None, waited_ns=5))
    ring["records"] = sorted(recs, key=lambda r: r["ts"] + (r["dur"] or 0))
    assert reader.read(_ev(1.0, 2.0), **params)["value"] is not None
    ring["dropped"] = 12        # the oldest record left ends in the window
    got = reader.read(_ev(1.0, 2.0), **params)
    assert got["value"] is None and "dropped 12" in got["detail"]
    # what was dropped ended before the window: nothing of it is missing
    ring["records"].insert(0, _span(9, "engine.step", 200, 100, None))
    assert reader.read(_ev(1.0, 2.0), **params)["value"] is not None


@pytest.mark.parametrize("reader,params", READS,
                         ids=[r.__name__.split(".")[-1] for r, _ in READS])
def test_none_when_the_program_has_no_such_span(ring, reader, params):
    """The parent of the PR that adds a span runs the same reader: it
    reads nothing and does not raise."""
    ring["records"] = [_span(1, "engine.decode_step", 1500, 20, None,
                             step=3),
                       _span(2, "scheduler.join", 1400, None, None, rid=1)]
    got = reader.read(_ev(1.0, 2.0), **params)
    assert got["value"] is None and isinstance(got["detail"], str)
    ring["records"] = []
    assert reader.read(_ev(1.0, 2.0), **params)["value"] is None


def test_the_readers_take_the_real_ring():
    """No hand-made list: spans recorded by the program itself."""
    import time
    ptrace.trace_clear()
    ptrace.enable(True)
    try:
        t0 = time.perf_counter()
        with ptrace.span("engine.step"):
            with ptrace.span("engine.decode_step"):
                with ptrace.span("engine.decode.wait"):
                    time.sleep(0.02)
                time.sleep(0.005)
        t1 = time.perf_counter()
    finally:
        ptrace.enable(False)
    ev = {"t0": t0, "t1": t1, "clock_skew_ns": time.monotonic_ns()
          - int(time.perf_counter() * 1e9)}
    try:
        got = span_self_ms.read(ev, span="engine.decode_step",
                                less=["engine.decode.wait"], q=50)
        assert 4.0 < got["value"] < 20.0
        share = span_time_share.read(ev, spans=["engine.step"],
                                     less=["*.wait"])
        assert 0.0 < share["value"] < 60.0
    finally:
        ptrace.trace_clear()


# ---- the reduction with program spans on the host's line ------------------------

def test_a_gap_goes_to_the_engine_phase_that_covers_it():
    with open(os.path.join(FIXTURES, "trace_events.json")) as fh:
        recorded = json.load(fh)
    (old_name, idle_s), = RT.reduce(recorded)["idle_gaps"]
    assert old_name == "_array.py_631__value"
    # the one idle gap of the recorded step, found again from the events
    t0, t1 = RT.window_of(recorded)
    busy = RT.merged(RT.clipped([(s, s + d) for _, s, d in
                                 recorded["devices"]["0"] if d > 0], t0, t1))
    (g0, g1), = [g for g in RT.gaps_of(busy, t0, t1)
                 if g[1] - g[0] >= RT.MIN_GAP_NS]
    assert (g1 - g0) / 1e9 == pytest.approx(idle_s)
    line = next(k for k, evs in recorded["host"].items()
                if any(e[0] == RT.WINDOW_MARK for e in evs))

    # the Python tracer's event of the function round the gap, alone
    with_py = copy.deepcopy(recorded)
    with_py["host"][line].append(
        ["$engine.py:899 _decode", g0 - 20_000, (g1 - g0) + 40_000])
    assert RT.reduce(with_py)["idle_gaps"][0][0] == "_engine.py_899__decode"

    # the program's span inside it (observability/trace.py opens a
    # TraceAnnotation of the span's name): innermost, so the gap is its
    with_span = copy.deepcopy(with_py)
    with_span["host"][line].append(
        ["engine.decode.prep", g0 - 5_000, (g1 - g0) + 10_000])
    (name, s), = RT.reduce(with_span)["idle_gaps"]
    assert name == "engine.decode.prep" and s == pytest.approx(idle_s)

    # a span too short to explain the gap leaves it to the one round it
    short = copy.deepcopy(with_py)
    short["host"][line].append(
        ["engine.decode.emit", (g0 + g1) / 2 - 1_000, 2_000])
    assert RT.reduce(short)["idle_gaps"][0][0] == "_engine.py_899__decode"
