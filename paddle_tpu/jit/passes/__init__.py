"""Graft-level pass pipeline over captured whole-step programs.

The analog of the reference's ProgramDesc/PIR pass managers
(paddle/fluid/framework/ir/ graph fuse passes, paddle/ir/ PIR passes) and of
CINN's graph-level optimizations — rebuilt on the jaxpr, the TPU-native
program form a captured step canonicalizes into (jit/capture.py).  Each pass
is jaxpr -> jaxpr, value-semantics preserving:

- ``fusion``   — collapses nested compiled regions (`jit` call equations:
  to_static subprograms, jitted helpers, chains of per-op executables that
  entered the trace as calls) into the parent program so XLA sees ONE
  region to schedule and fuse across.
- ``cse``      — common-subexpression elimination + duplicate-constant
  folding (value-identical constvars collapse to one buffer).
- ``dve``      — dead-value elimination: drops equations (and constants)
  whose results never reach an output; effectful equations are kept.
- ``comm``     — comm-schedule pass (passes/comm_schedule.py): tags every
  collective equation (any nesting level) with an overlap slot, registers
  the tally with distributed/comms, and hoists independent collectives to
  their earliest dependency-legal position so XLA can overlap wire time
  with compute (GC3-style, arxiv 2201.11840).

Donation inference (passes/donation.py) runs beside the pipeline: it maps
(input avals, output avals) to the argument positions that can safely alias
their output buffers (params/opt-state style updates).

The analyze-only lint pass (passes/lint.py) also runs beside the pipeline,
per lowering: semantic hazards of the captured program (recompile-hazard,
donation-miss, unscheduled-collective, dead-compute, host-callback) —
read-only, recorded for ``profiler.lint_summary()`` and wrapped into the
ratcheted CI gate by the staticcheck jaxpr tier
(tools/staticcheck/jaxpr/).

Every pass records what it did into a :class:`PassReport`; the capture layer
surfaces the totals through ``profiler.step_capture_summary()``.

Env: ``PT_STEP_CAPTURE_PASSES`` — comma-separated subset of
``fusion,cse,dve,comm`` (default ``all``; ``0``/``none`` disables the
pipeline while keeping capture itself on).
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import List, Tuple

__all__ = ["PassReport", "run_pipeline", "default_passes"]

_ALL = ("fusion", "cse", "dve", "comm")


@dataclass
class PassReport:
    """What the pipeline did to one captured program."""
    inlined_calls: int = 0      # jit/call regions spliced into the parent
    cse_folded: int = 0         # equations replaced by an earlier duplicate
    consts_deduped: int = 0     # value-identical constants collapsed
    dve_removed: int = 0        # dead equations dropped
    dve_consts_dropped: int = 0  # constants orphaned by DVE
    comm_tagged: int = 0        # collective eqns tagged (all nesting levels)
    comm_hoisted: int = 0       # collectives moved to their earliest slot
    comm_slots: int = 0         # max overlap slots at any one level
    donated_args: Tuple[int, ...] = ()   # flat arg positions inferred donatable
    eqns_before: int = 0
    eqns_after: int = 0
    passes_run: List[str] = field(default_factory=list)

    def as_dict(self) -> dict:
        return {
            "inlined_calls": self.inlined_calls,
            "cse_folded": self.cse_folded,
            "consts_deduped": self.consts_deduped,
            "dve_removed": self.dve_removed,
            "dve_consts_dropped": self.dve_consts_dropped,
            "comm_tagged": self.comm_tagged,
            "comm_hoisted": self.comm_hoisted,
            "comm_slots": self.comm_slots,
            "donated_args": list(self.donated_args),
            "eqns_before": self.eqns_before,
            "eqns_after": self.eqns_after,
            "passes_run": list(self.passes_run),
        }


def default_passes() -> Tuple[str, ...]:
    """Pipeline selection from PT_STEP_CAPTURE_PASSES (default: all)."""
    raw = os.environ.get("PT_STEP_CAPTURE_PASSES", "all").strip().lower()
    if raw in ("0", "none", "off", ""):
        return ()
    if raw in ("all", "1"):
        return _ALL
    return tuple(p for p in (s.strip() for s in raw.split(",")) if p in _ALL)


def run_pipeline(closed, passes=None, report: PassReport | None = None):
    """Run the selected passes over a ClosedJaxpr.

    Returns ``(closed_jaxpr, report)``. A pass that raises is a bug, not a
    lost optimization: the exception propagates into the capture layer's
    bailout net (jit/capture.py), which counts and names it — so
    ``capture_info()["bailouts"]`` sees every pass failure.
    """
    from . import comm_schedule as _comm
    from . import cse as _cse
    from . import dve as _dve
    from . import fusion as _fusion

    if report is None:
        report = PassReport()
    if passes is None:
        passes = default_passes()
    report.eqns_before = len(closed.jaxpr.eqns)
    table = {"fusion": _fusion.inline_calls, "cse": _cse.fold,
             "dve": _dve.eliminate, "comm": _comm.schedule}
    for name in passes:
        fn = table.get(name)
        if fn is None:
            continue
        closed = fn(closed, report)
        report.passes_run.append(name)
    report.eqns_after = len(closed.jaxpr.eqns)
    return closed, report
