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

The serving loop's span names are a contract: the benchmark's per-layer
readers (``benchmarks/readers/``) and the names in a traced run's
``idle_gaps`` are these. Each nests under the span that caused it; the ones
that serve a single request carry ``rid``::

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

Env knobs:
- ``PT_TRACE``                (default 0)    1 enables span recording
- ``PT_TRACE_RING``           (default 4096) ring capacity (records)
- ``PT_TRACE_INCIDENT_SPANS`` (default 64)   last-K records per incident
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Optional

__all__ = ["span", "event", "enabled", "enable", "trace_clear",
           "trace_records", "trace_info", "export_trace", "set_ring_size",
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


def enabled() -> bool:
    return _enabled


def enable(on: bool = True) -> None:
    """Turn span recording on/off at runtime (the PT_TRACE override for
    tests and benches; the ring and incidents are kept either way)."""
    global _enabled
    _enabled = bool(on)


def set_ring_size(n: int) -> None:
    """Re-arm the ring at a new bound (drops current contents)."""
    _RING.resize(n)


def trace_clear() -> None:
    _RING.clear()


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
        stack.append(self.sid)
        # the same span on the profiler's clock (module docstring); with no
        # profiler session open the annotation is a no-op of its own
        from jax.profiler import TraceAnnotation
        self._ann = TraceAnnotation(self.name)
        self._t0 = time.monotonic_ns()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        end = time.monotonic_ns()
        stack = getattr(_tls, "stack", ())
        if stack and stack[-1] == self.sid:
            stack.pop()
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
    return _RING.snapshot()


def trace_info() -> dict:
    """Counters for profiler.trace_summary()."""
    return {"enabled": _enabled, "records": len(_RING),
            "capacity": _RING.maxlen, "dropped": _RING.dropped,
            # CUMULATIVE: the incident deque keeps only the last 8, but
            # the count keeps climbing (an alert on its increase must see
            # every incident, not plateau at the retention bound)
            "incidents": _INCIDENTS.pushed}


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
    """Write the ring as Chrome trace-event JSON; returns ``path``.
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
