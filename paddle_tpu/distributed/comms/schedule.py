"""Comm schedules: every collective is a first-class, accounted operation.

GC3 (arxiv 2201.11840) treats collectives as scheduled program objects —
with owners, explicit cost, and slots that can overlap compute — instead
of opaque calls sprinkled through the step.  This module is the bookkeeping
half of that idea for the comms subsystem:

- :class:`CommOp` — one issued collective: owner (which subsystem asked),
  site (stable name for aggregation), kind/axis/shape, bytes **logical**
  (what the full-precision collective would move) vs bytes **wire** (what
  actually moves — smaller when the quantized context is on), the wire
  dtype, the deadline budget it ran under, and the overlap ``slot`` the
  capture-tier pass assigned (None until scheduled).
- :class:`CommSchedule` — the per-step record.  ``step_schedule()`` scopes
  one; without an active scope, ops land on the process-global schedule.
- a process-global per-site aggregate that survives step boundaries —
  ``comm_info()`` feeds ``profiler.comm_summary()`` from it.

Collectives register at TRACE time (the python call site), so a captured
step records its CommOps once per lowering, not once per invocation —
the recompile-count guard in tests/test_comms.py pins that.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import List, Optional


@dataclass
class CommOp:
    """One issued collective, in schedule order."""
    owner: str                 # who asked: "trainer.grad_sync", "collective.api", ...
    site: str                  # stable aggregation key, usually owner/kind/axis
    kind: str                  # all_reduce | all_gather | reduce_scatter | ...
    axis: Optional[str]        # mesh axis (None: no mesh — round-trip only)
    shape: tuple
    dtype: str                 # logical dtype on the math side
    bytes_logical: int
    bytes_wire: int
    quantized: Optional[str] = None   # wire dtype ("int8"/"fp8") or None
    deadline_s: Optional[float] = None
    slot: Optional[int] = None        # overlap slot (comm_schedule pass)
    seq: int = 0

    @property
    def compression(self) -> float:
        return self.bytes_logical / max(self.bytes_wire, 1)


@dataclass
class CommSchedule:
    """The ordered CommOps of one step (or of the process, for the global
    default schedule).  ``maxlen`` bounds the retained ops (the GLOBAL
    schedule uses it: an eager training loop records one op per collective
    per step forever, and only the per-site aggregate needs to be
    complete — the op list is a recent-history window there).  ``seq`` is
    a monotone issue counter, not a list index, so trimming never
    renumbers."""
    label: str = "global"
    ops: List[CommOp] = field(default_factory=list)
    maxlen: Optional[int] = None
    _seq: int = 0

    def add(self, op: CommOp) -> CommOp:
        op.seq = self._seq
        self._seq += 1
        self.ops.append(op)
        if self.maxlen is not None and len(self.ops) > self.maxlen:
            del self.ops[:len(self.ops) - self.maxlen]
        return op

    def bytes_logical(self) -> int:
        return sum(o.bytes_logical for o in self.ops)

    def bytes_wire(self) -> int:
        return sum(o.bytes_wire for o in self.ops)


_LOCK = threading.Lock()
_tls = threading.local()

# site -> {"count", "bytes_logical", "bytes_wire", "kind", "owner",
#          "quantized", "slots": set of assigned slots}
_SITES: dict = {}
_GLOBAL = CommSchedule("global", maxlen=4096)


def current_schedule() -> CommSchedule:
    sched = getattr(_tls, "schedule", None)
    return sched if sched is not None else _GLOBAL


@contextmanager
def step_schedule(label: str = "step"):
    """Scope a fresh CommSchedule: collectives issued (traced) inside land
    on it.  Yields the schedule so the caller can inspect per-step ops;
    the per-site aggregate is updated either way."""
    prev = getattr(_tls, "schedule", None)
    sched = CommSchedule(label)
    _tls.schedule = sched
    try:
        yield sched
    finally:
        _tls.schedule = prev


def record(op: CommOp) -> CommOp:
    """Register one issued collective on the current schedule + the
    per-site aggregate.  The schedule append shares the aggregate's lock:
    concurrent tracing threads (serving engines, parallel step builds)
    must not race the seq counter or the trim."""
    with _LOCK:
        current_schedule().add(op)
        s = _SITES.setdefault(op.site, {  # staticcheck: ok[mutable-global] — lock-guarded per-site aggregate IS the feature (comm_summary reads it)
            "count": 0, "bytes_logical": 0, "bytes_wire": 0,
            "kind": op.kind, "owner": op.owner, "quantized": None,
            "slots": set()})
        s["count"] += 1
        s["bytes_logical"] += op.bytes_logical
        s["bytes_wire"] += op.bytes_wire
        if op.quantized:
            s["quantized"] = op.quantized
        if op.slot is not None:
            s["slots"].add(op.slot)
    return op


def comm_info() -> dict:
    """Per-site aggregate for profiler.comm_summary(): count, logical vs
    wire bytes, compression ratio, wire dtype, overlap slots."""
    with _LOCK:
        sites = {
            site: {
                "count": s["count"],
                "bytes_logical": s["bytes_logical"],
                "bytes_wire": s["bytes_wire"],
                "compression": round(
                    s["bytes_logical"] / max(s["bytes_wire"], 1), 3),
                "kind": s["kind"],
                "owner": s["owner"],
                "quantized": s["quantized"],
                "slots": sorted(s["slots"]),
            }
            for site, s in sorted(_SITES.items())
        }
    return {
        "sites": sites,
        "total_logical": sum(s["bytes_logical"] for s in sites.values()),
        "total_wire": sum(s["bytes_wire"] for s in sites.values()),
        "collectives": sum(s["count"] for s in sites.values()),
    }


def comm_clear() -> None:
    """Reset the aggregate + the global schedule (tests/benches)."""
    with _LOCK:
        _SITES.clear()  # staticcheck: ok[mutable-global] — lock-guarded reset of the audited aggregate (tests/benches)
        _GLOBAL.ops.clear()
