"""Tests for the native C++ runtime core (csrc/runtime.cc via ctypes).

Covers the native-equivalents demanded by SURVEY.md §2.1/§2.5: flags registry,
blocking queue (LoDTensorBlockingQueue analog), TCPStore rendezvous, host
tracer. The TCPStore test exercises a real multi-client rendezvous the way
ProcessGroup bootstrap uses it (collective.py:153 in the reference).
"""
import json
import threading
import time

import pytest

from paddle_tpu.utils import flags, native


def test_native_builds():
    assert native.available(), f"native runtime failed to build: {native.load_error()}"


def test_native_library_keyed_on_source_content(tmp_path, monkeypatch):
    """A binary built from another runtime.cc is never the one loaded: the
    library's name carries a digest of its source, mtimes play no part."""
    import os
    real = native._so_path()
    assert native.available() and os.path.exists(real)
    other = tmp_path / "runtime.cc"
    with open(native._SRC, "rb") as f:
        other.write_bytes(f.read() + b"\n// another source\n")
    os.utime(other, (0, 0))                 # older than every build product
    monkeypatch.setattr(native, "_SRC", str(other))
    assert native._so_path() != real
    assert not os.path.exists(native._so_path())


def test_flags_roundtrip():
    flags.define_flag("FLAGS_test_int", 7)
    assert flags.flag("FLAGS_test_int") == 7
    flags.set_flags({"FLAGS_test_int": 13})
    assert flags.get_flags("FLAGS_test_int") == {"FLAGS_test_int": 13}
    flags.define_flag("FLAGS_test_bool", True)
    flags.set_flags({"FLAGS_test_bool": False})
    assert flags.flag("FLAGS_test_bool") is False
    with pytest.raises(KeyError):
        flags.set_flags({"FLAGS_does_not_exist": 1})


def test_blocking_queue_producer_consumer():
    q = native.BlockingQueue(capacity=4)
    items = [bytes([i]) * (i + 1) for i in range(50)]
    got = []

    def producer():
        for it in items:
            q.push(it)
        q.close()

    t = threading.Thread(target=producer)
    t.start()
    while True:
        try:
            b = q.pop()
        except RuntimeError:  # closed + drained
            break
        got.append(b)
    t.join()
    assert got == items


def test_blocking_queue_timeout_and_capacity():
    q = native.BlockingQueue(capacity=1)
    assert q.push(b"a", timeout=1.0)
    t0 = time.monotonic()
    assert not q.push(b"b", timeout=0.1)  # full -> timeout
    assert time.monotonic() - t0 >= 0.09
    assert q.pop() == b"a"
    assert q.pop(timeout=0.05) is None  # empty -> timeout
    q.close()


def test_tcp_store_rendezvous():
    from paddle_tpu.distributed.store import TCPStore

    master = TCPStore("127.0.0.1", 0, is_master=True, world_size=3)
    port = master.port
    master.set("nccl_uid", b"\x01\x02\x03deadbeef")

    results = {}

    def rank(i):
        c = TCPStore("127.0.0.1", port, is_master=False)
        results[i] = c.get("nccl_uid")
        c.add("arrived", 1)
        c.wait("go")
        results[f"go{i}"] = c.get("go")
        c.stop()

    threads = [threading.Thread(target=rank, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    # barrier master side: wait until all ranks arrived, then release
    deadline = time.monotonic() + 10
    while int(master.get("arrived") or b"0") < 3:
        assert time.monotonic() < deadline, "ranks never arrived"
        time.sleep(0.01)
    master.set("go", b"now")
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    for i in range(3):
        assert results[i] == b"\x01\x02\x03deadbeef"
        assert results[f"go{i}"] == b"now"
    assert master.add("counter", 5) == 5
    assert master.add("counter", -2) == 3
    assert master.delete_key("counter")
    master.stop()


def test_host_tracer_chrome_dump():
    lib = native.get_lib()
    assert lib is not None
    lib.pt_trace_clear()
    lib.pt_trace_enable(1)
    t0 = lib.pt_now_ns()
    lib.pt_trace_record(b"matmul", b"op", t0, 1500, 1)
    lib.pt_trace_record(b'with"quote', b"py", t0 + 2000, 300, 2)
    lib.pt_trace_enable(0)
    lib.pt_trace_record(b"dropped", b"op", t0, 1, 1)  # disabled -> not recorded
    assert lib.pt_trace_count() == 2

    import ctypes
    out = ctypes.c_void_p()
    n = lib.pt_trace_dump(ctypes.byref(out))
    raw = native._take_bytes(lib, out, n)
    events = json.loads(raw)
    assert len(events) == 2
    assert events[0]["name"] == "matmul"
    assert events[0]["ph"] == "X"
    assert events[0]["dur"] == pytest.approx(1.5)
    assert events[1]["name"] == 'with"quote'
    lib.pt_trace_clear()
    assert lib.pt_trace_count() == 0
