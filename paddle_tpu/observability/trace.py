"""Unified trace spans: one event spine under every subsystem.

Before this module the repo's observability was eight disconnected text
tables (`profiler.*_summary()`), each scraping its own ad-hoc counters —
nothing correlated a gateway request with the engine steps that served it,
or a supervisor scale event with the reshard bytes it moved, and nothing a
dashboard or a postmortem could consume. This module is the shared spine:

- :func:`span` / :func:`event` — record one timed span (context manager)
  or one instant event into a **thread-safe bounded ring buffer** of
  monotonic-ns records. Sites carry free-form ``attrs`` — the correlation
  ids (`rid` for a serving request, `epoch` for a supervision epoch,
  `step` for a captured-step signature) that link records ACROSS layers:
  gateway request id → engine submit/prefill-chunk/decode-step/verify
  spans → scheduler events; supervisor epoch id → detect/rendezvous/
  swap/resume spans; step name → capture call/trace/lower/execute spans.
- **near-zero cost when off**: tracing defaults to disabled (``PT_TRACE=0``)
  and a disabled ``span()`` returns a shared no-op context manager after
  one module-global bool check; ``event()`` returns immediately. The ON
  cost of record is the one measured on the chip (PERF.md, Findings).
- **one clock with the device trace**: a recorded span also opens a
  ``jax.profiler.TraceAnnotation`` of the same name, entered and left at
  the ring's own two stamps. Whenever a ``jax.profiler`` session is open
  the program's spans lie on ``/host:CPU`` beside the device's operations
  (one Perfetto timeline), and a reduction of that trace lays a device
  idle gap to the engine phase that covers it. ``jax`` is imported by the
  first recorded span, not by this module.
- :func:`export_trace` — dump the ring as Chrome trace-event JSON
  (loadable in Perfetto / chrome://tracing): spans as ``ph:"X"`` complete
  events, instants as ``ph:"i"``, correlation attrs under ``args``.
- the **flight recorder** — every typed :class:`DeadlineExceeded`
  construction snapshots the last-K ring records into
  :func:`last_incident` (hooked via ``utils.deadline.set_incident_hook``,
  installed when ``paddle_tpu.observability`` imports), so a chaos-matrix
  timeout produces a postmortem timeline ending at the faulted site, not
  just a typed error.

The names are a contract: the benchmark's per-layer readers
(``benchmarks/readers/``), the names in a traced run's ``idle_gaps`` and an
operator's use of the ring (a flight-recorder incident, a Chrome export, the
``METRICS`` verb) read these and nothing else.  Each nests under the span
that caused it; the ones that serve a single request carry ``rid``.  The
serving loop (``inference/serving``)::

    engine.step                      one ServingEngine.step() with work to do
      scheduler.join (event)         rid, slot, pages, waited_ns (since submit)
      engine.prefill                 rid, bucket, prompt_len, pad (bucket less
                                     the positions it computes), pos (a cut
                                     prompt's last piece starts there)
        engine.prefill.prep          padded prompt, uploads, one zero-cache call
        engine.prefill.launch        the step call (parent of capture.call)
        engine.prefill.wait          the first token's download, after
                                     the step in flight is read (an
                                     engine.settle, cause prefill)
        engine.prefill.commit        slot write, prefix commit, drafter join
      engine.prefill_chunk           rid, pos, tokens: a piece of a cut prompt
                                     but its last, or a shared-prefix tail's
                                     scratch window
      engine.decode_step             step, rids, ahead (spec=True when speculative)
        engine.decode.prep           tok / off arrays, the token merge, drafts, uploads
        engine.decode.launch         the step call (parent of capture.call)
        engine.decode.wait           the host blocked on the device's answer
        engine.decode.emit           append_token, host sampling, counters
    engine.settle                    cause, step, rids: a read from outside (wait, emit)
    capture.call                     CapturedStep.__call__, captured path
      capture.trace, capture.lower   only when the signature is new
      capture.execute                the executable's own call

A greedy decode step is launched one ahead (``ServingEngine``'s docstring), so
the four children of one ``engine.decode_step`` belong to TWO steps: ``prep``
and ``launch`` are step i+1's (``step`` and ``rids`` name it), ``wait`` is the
block on step i's tokens, whose download began when step i was launched, and
``emit`` appends step i's.  ``ahead=True`` says so; with ``ahead=False``
(a sampled slot, a drafter, nothing in flight) the step in flight, if any, is
read first (a ``wait`` and an ``emit`` before the ``prep``), and a step the
host needs at once is read again after its ``launch``.  The span's duration is
still one turn of the decode loop, and ``decode_step`` less ``wait`` the
host's own work in it, which the device now overlaps: the device's idle share
says whether the host holds the chip back, not this.  A prefill's ``wait``
holds the tail of the step in flight before it.  ``engine.settle`` reads the
step in flight for a reader from outside the loop (``cause``: one of
``engine.SETTLE_CAUSES``) or before a scratch window.
A speculative step keeps ``engine.verify_step`` between ``engine.decode_step``
and its ``launch`` / ``wait``.  ``pad`` sums, over a window's prefills, to
what ``ServingEngine.info()`` counts as ``prefill_positions_padded`` less
``prefill_positions`` (``benchmarks/readers/prefill_padding_share.py`` reads
either).  A model with recurrent state beside K/V runs under the same names.

Who reads each (``idle_gaps`` names any host span that covers a device gap):

    engine.step                      host_serial_share.serve (less *.wait)
    engine.prefill, .prefill_chunk   prefill_time_share.serve; engine.prefill
                                     alone prefill_ms_p50, prefill_host_ms_p50
                                     (less engine.prefill.wait),
                                     prefill_padding_share.serve (pad, bucket)
    engine.prefill.{prep,launch,     idle_gaps (PERF.md cites engine.prefill.wait,
      wait,commit}                   engine.prefill.commit)
    engine.decode_step               engine_decode_step_ms_p50, decode_host_ms_p50
                                     (less engine.decode.wait)
    engine.decode.{prep,launch,      idle_gaps (PERF.md cites engine.decode.prep);
      wait,emit}                     *.wait by host_serial_share.serve
    engine.settle, engine.verify_step idle_gaps; the rid timeline of an export
    capture.call less capture.execute capture_call_overhead_ms_p50; capture.call
                                     in idle_gaps (PERF.md)
    capture.trace, capture.lower     a new signature by name in an export
    scheduler.join (event)           queue_wait_ms_p95 (waited_ns)
    scheduler.evict, engine.submit,  the flight recorder and the rid timeline
      engine.shed, engine.pressure   of an export (events)
      (events)
    gateway.request, gateway.read    the same, on the wire's side
    supervisor.* (its sites)         the flight recorder; the incident export
    <site> (cat chaos.fault, event)  the flight recorder: an incident ENDS at
                                     the faulted site
    device.*                         below
    gc.collect                       gc_pause_share.serve; idle_gaps; the
                                     device readers (a call stamped late
                                     over a pause ends where it began)
    trace_info()["gc"]               collections and their nanoseconds by
                                     generation, for an operator (counters)

The device's own time (``launched`` / ``done``).  A call the engine
launches returns before the device has run it, so no host span says how
long the device worked.  ``launched(name, out, **attrs)`` puts the call on a
small FIFO in launch order (the order the device runs them) with the stamp
of its dispatch, taken when the call returns to the host; once the call is
seen done it becomes ONE record ``device.<kind>`` on a lane of its own
(``tid`` ``DEVICE_TID``), from the later of the previous call's completion
and this call's dispatch to its completion.  The small programs the host
dispatches between two calls (a zero-cache maker, the token merge, a slot
write; a fraction of a millisecond each on the device) lie in no span: a
span that began at their dispatch held the host's own work between them and
the call, the prep and the capture tier's call, as device time, and read the
idle share 3-4 points under the profiler's in the saturated cells.  The
watched output is one no later call donates (a step's next tokens), never a
cache.  A completion is stamped where the main thread observes it: ``done(out)``, called where the host is about to
block on ``out``, blocks first on every earlier call, in device order (free:
the device runs them first), and every recorded span's enter and exit polls
``is_ready()`` on the FIFO's head.  ``late_ns`` bounds the stamp's error:
for a completion a poll found, the time since the last check that found the
call not done (or since its dispatch); 0 for one a blocking wait returned on
(the wait wakes at completion).  Kinds and attributes, from the engine::

    device.decode_step               step, rids: a [max_batch, 1] decode step
    device.prefill                   rid, bucket, pos: a whole prefill, or a
                                     cut prompt's last piece (pos > 0)
    device.prefill_chunk             rid, pos, tokens: a cut prompt's piece
    device.window                    rid, pos, tokens: a shared-prefix tail's
                                     scratch window (``_advance_one``)
    device.verify_step               step, rids: a speculative verify call
      (every one)                    late_ns

Read by ``device_idle_share.window`` (their union over the whole window),
``prefill_device_share.serve`` (device.prefill + .prefill_chunk + .window)
and ``decode_device_ms_p50`` (device.decode_step).  ``trace_info()``
reports the FIFO's ``depth``, the calls ``seen`` done and the ones never
seen done (``undone``: dropped from a full FIFO or left when tracing turned
off).

Collector pauses.  While tracing is on, one ``gc.callbacks`` hook turns a
collection of generation 1 or 2 into a ``gc.collect`` span (``generation``,
``collected``, ``uncollectable``), entered and left as a profiler
annotation too, so a device gap it causes is named ``gc.collect`` in
``idle_gaps``; the many short generation-0 collections would fill the ring,
so they are only counted: ``trace_info()["gc"]`` gives every collection and
its nanoseconds by generation.  ``enable(False)`` removes the hook.

Env knobs:
- ``PT_TRACE``                (default 0)    1 enables span recording
- ``PT_TRACE_RING``           (default 4096) ring capacity (records)
- ``PT_TRACE_INCIDENT_SPANS`` (default 64)   last-K records per incident
"""
from __future__ import annotations

import gc
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Optional

__all__ = ["span", "event", "enabled", "enable", "trace_clear",
           "trace_records", "trace_info", "export_trace", "set_ring_size",
           "launched", "done", "DEVICE_TID",
           "record_incident", "last_incident", "incidents",
           "clear_incidents"]

from ..utils.deadline import env_int as _env_pos_int

_enabled = os.environ.get("PT_TRACE", "0").strip().lower() \
    not in ("0", "", "false", "off")
_ids = itertools.count(1)
_tls = threading.local()   # per-thread open-span stack (parent linkage)


class _LockedRing:
    """Bounded ring of records under its own lock — the audited-container
    idiom (utils/memo) for module state: every write goes through a method
    on this instance, so the thread-safety story is in one place."""

    def __init__(self, maxlen: int):
        self._d: deque = deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self.dropped = 0
        self.pushed = 0   # monotone: total records EVER pushed (the ring
                          # bounds retention, not the count — a metric on
                          # incidents must keep climbing past the bound)

    def push(self, rec) -> None:
        with self._lock:
            if len(self._d) == self._d.maxlen:
                self.dropped += 1
            self.pushed += 1
            self._d.append(rec)

    def snapshot(self) -> list:
        with self._lock:
            return list(self._d)

    def tail(self, k: int) -> list:
        with self._lock:
            return list(self._d)[-k:]

    def last(self):
        with self._lock:
            return self._d[-1] if self._d else None

    def clear(self) -> None:
        with self._lock:
            self._d.clear()
            self.dropped = 0
            self.pushed = 0

    def resize(self, maxlen: int) -> None:
        with self._lock:
            self._d = deque(maxlen=max(1, int(maxlen)))
            self.dropped = 0
            self.pushed = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)

    @property
    def maxlen(self) -> int:
        with self._lock:
            return self._d.maxlen


_RING = _LockedRing(_env_pos_int("PT_TRACE_RING", 4096))
_INCIDENT_K = _env_pos_int("PT_TRACE_INCIDENT_SPANS", 64)
_INCIDENTS = _LockedRing(8)

# the export's lane of the device's calls (a Chrome trace-event `tid` no
# host thread has)
DEVICE_TID = 0
# calls launched and not yet seen done that the FIFO keeps; the engine has
# at most a few (a decode step in flight, a piece, a prefill)
_FIFO_MAX = 64


class _Call:
    """One launched call: its record's name and attributes, the output
    whose readiness says it is done, and the stamp of its dispatch."""

    __slots__ = ("name", "out", "attrs", "launch")

    def __init__(self, name, out, attrs, launch):
        self.name, self.out, self.attrs, self.launch = name, out, attrs, launch


class _DeviceCalls:
    """The calls launched and not yet seen done, in launch order (the
    order the device runs them), under one lock; a blocking wait holds no
    lock (module docstring: the device's own time)."""

    def __init__(self):
        self._q: deque = deque()
        self._lock = threading.Lock()
        self._end = 0          # completion stamp of the last call seen done
        self._miss = 0         # the last check that found the head not done
        self.seen = 0
        self.undone = 0

    def launched(self, name: str, out, attrs: dict) -> None:
        now = time.monotonic_ns()
        with self._lock:
            if len(self._q) == _FIFO_MAX:
                self._q.popleft()
                self.undone += 1
            self._q.append(_Call(name, out, attrs, now))

    def _pop(self, now: int, late: int) -> None:
        """The head, seen done at `now`, onto the ring as its record (under
        the lock, so that the ring holds the calls in launch order)."""
        c = self._q.popleft()
        start = min(max(c.launch, self._end), now)
        self._end = now
        self.seen += 1
        _RING.push({"name": c.name, "cat": "device", "ts": start,
                    "dur": now - start, "tid": DEVICE_TID, "id": next(_ids),
                    "parent": None,
                    "args": dict(c.attrs, late_ns=late)})

    def poll(self) -> None:
        """Stamp every call at the head that is done, oldest first."""
        with self._lock:
            while self._q:
                now = time.monotonic_ns()
                head = self._q[0]
                if not head.out.is_ready():
                    self._miss = now
                    return
                self._pop(now, now - max(self._miss, head.launch))

    def wait(self, out) -> None:
        """Block on every call up to the one that made `out`, in device
        order, stamping each as it is seen done."""
        while True:
            with self._lock:
                if not any(c.out is out for c in self._q):
                    return
                head = self._q[0]
                now = time.monotonic_ns()
                if head.out.is_ready():
                    self._pop(now, now - max(self._miss, head.launch))
                    continue
                self._miss = now
            head.out.block_until_ready()
            now = time.monotonic_ns()
            with self._lock:
                if self._q and self._q[0] is head:   # else a poll stamped it
                    self._pop(now, 0)

    def drain(self) -> None:
        """Tracing turns off: stamp what is done, count the rest undone."""
        self.poll()
        with self._lock:
            self.undone += len(self._q)
            self._q.clear()

    def clear(self) -> None:
        with self._lock:
            self._q.clear()
            self._end = self._miss = self.seen = self.undone = 0

    def info(self) -> dict:
        with self._lock:
            return {"depth": len(self._q), "seen": self.seen,
                    "undone": self.undone}


_CALLS = _DeviceCalls()


class _GcPauses:
    """Python's collector on the ring: the one ``gc.callbacks`` hook of the
    module docstring, installed while tracing is on.  A collection strikes
    at any allocation, also one made under a lock of this module, so the
    hook takes no lock: it counts into fixed slots and leaves its records
    on a deque that the next recorded span or a read of the ring moves
    to the ring."""

    def __init__(self):
        self._open = None       # (start, annotation, parent) of a collection
        self._counts = [[0, 0] for _ in range(3)]   # a generation: n, ns
        self.pending: deque = deque()

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            # no jax import inside a collection: the annotation only once a
            # recorded span has imported it, and none for generation 0
            ann = _annotation("gc.collect") if info["generation"] \
                and "jax.profiler" in sys.modules else None
            stack = getattr(_tls, "stack", ())
            self._open = (time.monotonic_ns(), ann,
                          stack[-1] if stack else None)
            if ann is not None:
                ann.__enter__()
            return
        opened, self._open = self._open, None
        if opened is None:
            return
        t0, ann, parent = opened
        if ann is not None:
            ann.__exit__(None, None, None)
        end = time.monotonic_ns()
        gen = info["generation"]
        n = self._counts[gen]
        n[0] += 1
        n[1] += end - t0
        if gen:
            self.pending.append({
                "name": "gc.collect", "cat": "gc", "ts": t0, "dur": end - t0,
                "tid": threading.get_ident(), "id": next(_ids),
                "parent": parent,
                "args": {"generation": gen, "collected": info["collected"],
                         "uncollectable": info["uncollectable"]}})

    def flush(self) -> None:
        """Move the records the hook left to the ring."""
        while self.pending:
            try:
                rec = self.pending.popleft()
            except IndexError:      # another thread took the last one
                return
            _RING.push(rec)

    def hook(self, on: bool) -> None:
        if on and self not in gc.callbacks:
            gc.callbacks.append(self)
        elif not on and self in gc.callbacks:
            gc.callbacks.remove(self)
            self._open = None
            self.flush()

    def clear(self) -> None:
        self.pending.clear()
        for n in self._counts:
            n[0] = n[1] = 0

    def info(self) -> dict:
        return {g: {"count": n[0], "ns": n[1]}
                for g, n in enumerate(self._counts) if n[0]}


_GC = _GcPauses()


def enabled() -> bool:
    return _enabled


def enable(on: bool = True) -> None:
    """Turn span recording on/off at runtime (the PT_TRACE override for
    tests and benches; the ring and incidents are kept either way). Off,
    the collector's hook goes and the calls not yet seen done are counted
    as such."""
    global _enabled
    _enabled = bool(on)
    _GC.hook(_enabled)
    if not _enabled:
        _CALLS.drain()


def set_ring_size(n: int) -> None:
    """Re-arm the ring at a new bound (drops current contents)."""
    _RING.resize(n)


def trace_clear() -> None:
    _RING.clear()
    _CALLS.clear()
    _GC.clear()


def launched(name: str, out, **attrs) -> None:
    """An executable call just launched, whose completion `out` (a device
    array no later call donates) shows: recorded as ONE span ``name``
    (``device.<kind>``) on the device's lane once it is seen done."""
    if not _enabled:
        return
    _CALLS.launched(name, out, attrs)


def done(out) -> None:
    """The host is about to block on `out`: block first on every launched
    call up to the one that made it, in device order, stamping each."""
    if not _enabled or not _CALLS._q:
        return
    _CALLS.wait(out)  # staticcheck: ok[unbounded-blocking] — blocks on the device's own calls, launched by this process, which the caller's read of `out` waits for next anyway; there is no peer to time out on


def _annotation(name: str):
    """The span's twin on the profiler's clock (module docstring); with no
    profiler session open it is a no-op of its own."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


class _Span:
    """One open span; ``with span(...) as sp: sp.set(rid=...)`` attaches
    correlation attrs discovered mid-span (a request id that only exists
    after submit)."""

    __slots__ = ("name", "cat", "attrs", "sid", "parent", "_t0", "_ann")

    def __init__(self, name: str, cat: str, attrs: dict):
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self.sid = next(_ids)
        self.parent: Optional[int] = None
        self._t0 = 0
        self._ann = None

    def set(self, **attrs) -> "_Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "_Span":
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        if stack:
            self.parent = stack[-1]
        if _GC.pending:
            _GC.flush()
        if _CALLS._q:
            _CALLS.poll()
        # stamped before the annotation is made: a TraceAnnotation takes its
        # start when it is constructed, and the ring's stamps enclose it
        self._t0 = time.monotonic_ns()
        self._ann = _annotation(self.name)
        # on the stack only now: a collection that strikes while the
        # annotation is made nests in the enclosing span, which holds it
        stack.append(self.sid)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        end = time.monotonic_ns()
        stack = getattr(_tls, "stack", ())
        if stack and stack[-1] == self.sid:
            stack.pop()
        if _GC.pending:
            _GC.flush()
        if _CALLS._q:
            _CALLS.poll()
        _RING.push({"name": self.name, "cat": self.cat, "ts": self._t0,
                    "dur": end - self._t0, "tid": threading.get_ident(),
                    "id": self.sid, "parent": self.parent,
                    "args": self.attrs})
        return False


class _NullSpan:
    """The disabled path: one shared, reusable no-op context manager —
    a disabled call site pays one bool check and this singleton."""

    __slots__ = ()

    def set(self, **attrs) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


def span(name: str, cat: str = "span", **attrs):
    """Context manager recording one timed span named after its site
    (``engine.decode_step``, ``supervisor.swap``, ...). ``attrs`` are the
    correlation ids; when tracing is off this is a no-op singleton."""
    if not _enabled:
        return _NULL
    return _Span(name, cat, attrs)


def event(name: str, cat: str = "event", **attrs) -> None:
    """Record one instant event (a scheduler join, a CommOp issue, an
    armed chaos fault) — the zero-duration sibling of span()."""
    if not _enabled:
        return
    stack = getattr(_tls, "stack", ())
    _RING.push({"name": name, "cat": cat, "ts": time.monotonic_ns(),
                "dur": None, "tid": threading.get_ident(), "id": next(_ids),
                "parent": stack[-1] if stack else None, "args": attrs})


def trace_records() -> list:
    """Snapshot of the ring, oldest first."""
    _GC.flush()
    return _RING.snapshot()


def trace_info() -> dict:
    """Counters for profiler.trace_summary(); `device` is the FIFO of
    launched calls (its depth, the calls seen done and those never seen
    done), `gc` every collection since the ring was cleared, by generation
    (count and nanoseconds)."""
    return {"enabled": _enabled, "records": len(_RING),
            "capacity": _RING.maxlen, "dropped": _RING.dropped,
            # CUMULATIVE: the incident deque keeps only the last 8, but
            # the count keeps climbing (an alert on its increase must see
            # every incident, not plateau at the retention bound)
            "incidents": _INCIDENTS.pushed,
            "device": _CALLS.info(), "gc": _GC.info()}


# ---------------------------------------------------------------------------
# Chrome trace-event export (Perfetto / chrome://tracing)
# ---------------------------------------------------------------------------

def _jsonable(x):
    """Span attrs come from live code (np ints, tuples); the export must
    never fail on them."""
    try:
        json.dumps(x)
        return x
    except (TypeError, ValueError):
        if isinstance(x, (list, tuple)):
            return [_jsonable(v) for v in x]
        if isinstance(x, dict):
            return {str(k): _jsonable(v) for k, v in x.items()}
        try:
            return int(x)
        except (TypeError, ValueError):
            return str(x)


def _chrome_events(records: list) -> list:
    pid = os.getpid()
    out = []
    if any(r["tid"] == DEVICE_TID for r in records):
        # the device's calls on a lane of their own, below the host's
        out += [{"ph": "M", "name": "thread_name", "pid": pid,
                 "tid": DEVICE_TID, "args": {"name": "device"}},
                {"ph": "M", "name": "thread_sort_index", "pid": pid,
                 "tid": DEVICE_TID, "args": {"sort_index": 1 << 30}}]
    for r in records:
        args = {str(k): _jsonable(v) for k, v in r["args"].items()}
        args["span_id"] = r["id"]
        if r["parent"] is not None:
            args["parent_id"] = r["parent"]
        ev = {"name": r["name"], "cat": r["cat"], "pid": pid,
              "tid": r["tid"], "ts": r["ts"] / 1000.0, "args": args}
        if r["dur"] is None:
            ev["ph"] = "i"
            ev["s"] = "t"
        else:
            ev["ph"] = "X"
            ev["dur"] = r["dur"] / 1000.0
        out.append(ev)
    return out


def export_trace(path: str) -> str:
    """Write the ring as Chrome trace-event JSON; returns ``path``.  The
    ``device.*`` records lie on a lane of their own, ``DEVICE_TID``, named
    ``device``.
    ``ts`` is monotonic-ns converted to the format's microseconds, so
    relative timing (the part a timeline reader uses) is exact."""
    events = _chrome_events(trace_records())
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return path


# ---------------------------------------------------------------------------
# flight recorder: last-K spans per typed deadline error
# ---------------------------------------------------------------------------

def record_incident(exc: BaseException) -> None:
    """Snapshot the last-K ring records against one typed error. Installed
    as the utils.deadline incident hook — every DeadlineExceeded
    construction lands here, so a chaos-matrix timeout carries its own
    postmortem timeline. Never raises (a recorder crash inside an error
    path would mask the real error)."""
    try:
        _INCIDENTS.push({
            "error": type(exc).__name__,
            "what": getattr(exc, "what", None) or str(exc),
            "timeout": getattr(exc, "timeout", None),
            "ts": time.monotonic_ns(),
            "spans": _RING.tail(_INCIDENT_K),
        })
    except Exception:  # noqa: BLE001 — never mask the raising error
        pass


def last_incident() -> Optional[dict]:
    """The most recent incident (typed-deadline raise) with its span
    timeline, or None when no typed deadline error has been raised."""
    return _INCIDENTS.last()


def incidents() -> list:
    return _INCIDENTS.snapshot()


def clear_incidents() -> None:
    _INCIDENTS.clear()


# PT_TRACE=1 at start-up: the collector's hook, as enable() installs it
_GC.hook(_enabled)
