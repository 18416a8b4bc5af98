"""The readers of the device's calls and the collector's pauses on the
program's span ring (`device.*`, `gc.collect`) on hand-made span lists whose
answers are known, their `None` where a program keeps no such records, and
the records the program itself makes."""
from types import SimpleNamespace

import pytest

from benchmarks.readers import (device_idle_window, device_span_ms,
                                device_span_share, gc_pause_share)
from paddle_tpu.observability import trace as ptrace

MS = 1_000_000
SKEW = 7_000 * MS            # the ring's clock runs 7 s ahead of the window's


def _span(sid, name, ts_ms, dur_ms, parent=None, **args):
    return {"name": name, "cat": "span", "ts": SKEW + ts_ms * MS,
            "dur": None if dur_ms is None else dur_ms * MS, "tid": 1,
            "id": sid, "parent": parent, "args": args}


def _decode_step(sid, ts_ms, prep=1.0, launch=2.0, wait=10.0, emit=0.5):
    """One host-side engine.decode_step with its four children, as a
    program without device records leaves it."""
    t = ts_ms
    recs = [_span(sid + 1, "engine.decode.prep", t, prep, sid)]
    t += prep
    recs.append(_span(sid + 2, "engine.decode.launch", t, launch, sid))
    t += launch
    recs.append(_span(sid + 3, "engine.decode.wait", t, wait, sid))
    t += wait
    recs.append(_span(sid + 4, "engine.decode.emit", t, emit, sid))
    recs.append(_span(sid, "engine.decode_step", ts_ms,
                      prep + launch + wait + emit + 0.5, None))
    return recs


@pytest.fixture
def ring(monkeypatch):
    """Puts a hand-made list, and trace_info()'s counters, where the
    readers look for the ring."""
    state = {"records": [], "dropped": 0, "info": {}}
    monkeypatch.setattr(ptrace, "trace_records", lambda: list(state["records"]))
    monkeypatch.setattr(ptrace, "trace_info",
                        lambda: {"dropped": state["dropped"], **state["info"]})
    return state


def _ev(t0_s, t1_s):
    return {"t0": t0_s, "t1": t1_s, "clock_skew_ns": SKEW}


def _device(sid, name, ts_ms, dur_ms, **args):
    return dict(_span(sid, name, ts_ms, dur_ms, None,
                      late_ns=args.pop("late_ms", 0) * MS, **args),
                cat="device", tid=0)


def _made_ring():
    """One second of window (1000..2000 ms): the device runs a prefill, a
    piece of a cut prompt, decode steps of 10, 20 and 30 ms and a scratch
    window half past the window's end; the host's engine.step holds a prep
    over the device's idle 1260..1350 ms, a collection inside it, and
    nothing over the idle 1900..1950 ms."""
    return [
        _device(1, "device.prefill", 990, 110, rid=1, bucket=128, pos=0),
        _device(2, "device.prefill_chunk", 1100, 100, rid=2, pos=0,
                tokens=512, late_ms=2),
        _device(3, "device.decode_step", 1200, 10, step=0, rids=[1]),
        _device(4, "device.decode_step", 1210, 20, step=1, rids=[1]),
        _device(5, "device.decode_step", 1230, 30, step=2, rids=[1]),
        _device(6, "device.decode_step", 2000, 10, step=3, rids=[1]),
        _span(11, "engine.decode.prep", 1295, 60, 10),
        dict(_span(12, "gc.collect", 1300, 45, 11, generation=1,
                   collected=3, uncollectable=0), cat="gc"),
        _span(10, "engine.step", 1290, 100, None),
        _device(7, "device.window", 1350, 550, rid=3, pos=32, tokens=16),
        _device(8, "device.window", 1950, 100, rid=3, pos=48, tokens=4),
        dict(_span(13, "gc.collect", 1990, 20, None, generation=2,
                   collected=0, uncollectable=0), cat="gc"),
    ]


def test_the_device_readers_on_a_made_ring(ring):
    ring["records"] = _made_ring()
    ring["info"] = {"gc": {1: {"count": 1, "ns": 45 * MS}}}
    ev = dict(_ev(1.0, 2.0), trace=SimpleNamespace(t_start=1.5, t_stop=2.0),
              reduced={"idle_share": 0.125})
    got = device_idle_window.read(ev)
    # busy 1000..1260 and 1350..1900 and 1950..2000: 860 ms of 1000
    assert got["value"] == pytest.approx(14.0)
    d = got["detail"]
    # 1260..1350 is the collection's (the innermost span open at its
    # midpoint, inside the prep, and half the gap long), 1900..1950 nobody's
    assert d["idle_gaps"] == [["gc.collect", pytest.approx(0.09)],
                              [device_idle_window.NO_SPAN, pytest.approx(0.05)]]
    assert d["traced"] == {"window_s": pytest.approx(0.5),
                           "idle_share": pytest.approx(10.0),
                           "late_share": pytest.approx(0.0),
                           "profiler_idle_share": pytest.approx(12.5)}
    # the piece's completion was seen 2 ms late: 2 ms of the window in doubt
    assert d["late_ms_p50"] == 0.0 and d["spans"] == 7
    assert d["late_share"] == pytest.approx(0.2)
    # every piece of a prompt on the device: 100 + 100 + 550 + 50 ms
    got = device_span_share.read(ev, spans=["device.prefill",
                                            "device.prefill_chunk",
                                            "device.window"])
    assert got["value"] == pytest.approx(80.0)
    assert got["detail"]["calls"] == 4
    got = device_span_ms.read(ev, span="device.decode_step", q=50)
    assert got["value"] == pytest.approx(20.0)       # 10, 20, 30 and not 3's
    assert got["detail"]["spans"] == 3
    got = gc_pause_share.read(ev)
    assert got["value"] == pytest.approx(5.5)        # 45 + 10 of 20 ms
    assert got["detail"]["by_generation"] == {
        "1": {"count": 1, "longest_ms": pytest.approx(45.0)},
        "2": {"count": 1, "longest_ms": pytest.approx(20.0)}}
    # a program with the hook that paused in nothing of the window reads 0
    ring["records"] = [r for r in _made_ring() if r["name"] != "gc.collect"]
    assert gc_pause_share.read(ev)["value"] == 0.0


@pytest.mark.parametrize("late_ms,end_ms", [(50, 1230), (10, 1260), (0, 1260)],
                         ids=["pause-after-the-check", "pause-before-the-check",
                              "seen-by-a-wait"])
def test_a_call_seen_after_a_pause_ends_where_the_pause_began(ring, late_ms,
                                                              end_ms):
    """A decode step stamped at 1260 ms, `late_ms` after the last check that
    found it running, and a collection from 1230 ms: a pause that began
    after that check held the host while the device finished, so the step
    ends at 1230; one before it, or a stamp a blocking wait took, stands."""
    ring["records"] = [
        _device(1, "device.decode_step", 1200, 60, step=0, rids=[1],
                late_ms=late_ms),
        dict(_span(2, "gc.collect", 1230, 25, None, generation=2,
                   collected=0, uncollectable=0), cat="gc"),
        _device(3, "device.decode_step", 1260, 10, step=1, rids=[1]),
    ]
    got = device_idle_window.read(_ev(1.0, 2.0))
    busy = (end_ms - 1200) + 10
    assert got["value"] == pytest.approx(100.0 - busy / 10.0)
    paused = end_ms < 1260
    assert got["detail"]["paused_calls"] == int(paused)
    assert got["detail"]["paused_share"] == pytest.approx(
        (1260 - end_ms) / 10.0)
    # the doubt left is the part before the pause
    assert got["detail"]["late_share"] == pytest.approx(
        (20 if paused else late_ms) / 10.0)
    got = device_span_ms.read(_ev(1.0, 2.0), span="device.decode_step", q=50)
    assert got["value"] == pytest.approx((end_ms - 1200 + 10) / 2.0)
    assert ring["records"][0]["dur"] == 60 * MS      # the ring is left as is


DEVICE_READS = [
    (device_idle_window, {}),
    (device_span_share, dict(spans=["device.prefill", "device.prefill_chunk",
                                    "device.window"])),
    (device_span_ms, dict(span="device.decode_step", q=50)),
    (gc_pause_share, {}),
]


@pytest.mark.parametrize("reader,params", DEVICE_READS,
                         ids=[r.__name__.split(".")[-1] for r, _ in DEVICE_READS])
def test_the_device_readers_read_none_where_nothing_is_there(ring, reader,
                                                             params):
    """The parent of the PR that added the records (no `device.*` span, no
    collector's hook in trace_info) reads None with its reason, and so does
    a ring that lost the window's start; nothing raises."""
    ev = dict(_ev(1.0, 2.0), trace=SimpleNamespace(t_start=1.5, t_stop=2.0))
    parent = _decode_step(100, 1500)
    ring["records"] = parent
    got = reader.read(ev, **params)
    assert got["value"] is None and isinstance(got["detail"], str)
    ring["records"] = _made_ring()
    ring["info"] = {"gc": {}}
    assert reader.read(ev, **params)["value"] is not None
    ring["records"] = sorted(_made_ring()[2:],
                             key=lambda r: r["ts"] + (r["dur"] or 0))
    ring["dropped"] = 5
    got = reader.read(ev, **params)
    assert got["value"] is None and "dropped 5" in got["detail"]


def test_the_device_readers_take_the_real_ring():
    """Records made by the program: a launched call seen done and a
    forced collection."""
    import gc
    import time

    class Out:
        def is_ready(self):
            return True

    ptrace.trace_clear()
    ptrace.enable(True)
    try:
        t0 = time.perf_counter()
        with ptrace.span("engine.step"):
            ptrace.launched("device.decode_step", Out(), step=0, rids=[1])
            time.sleep(0.005)
        gc.collect()
        time.sleep(0.005)
        t1 = time.perf_counter()
    finally:
        ptrace.enable(False)
    ev = {"t0": t0, "t1": t1, "clock_skew_ns": time.monotonic_ns()
          - int(time.perf_counter() * 1e9)}
    try:
        assert 0.0 < device_idle_window.read(ev)["value"] < 100.0
        assert device_span_ms.read(ev, span="device.decode_step",
                                   q=50)["value"] < 20.0
        assert 0.0 < gc_pause_share.read(ev)["value"] < 100.0
    finally:
        ptrace.trace_clear()
