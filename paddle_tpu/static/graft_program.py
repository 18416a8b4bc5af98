"""GraftProgram: the canonical form of a captured whole step.

The bridge between the two program worlds this codebase already has:

- the *op-level* record dispatch produces (one entry per `apply()` site —
  the ProgramDesc-shaped view `static.framework.Program` models), and
- the *jaxpr-level* form the pass pipeline (jit/passes/) transforms and XLA
  lowers (the PIR/CINN-shaped view).

jit/capture.py canonicalizes every captured step into one of these. The
op-level record is what a human debugs against ("which ops made it into
the step, in what order"); the jaxpr is what actually runs. `as_program()`
re-materializes the op record as a `static.framework.Program` so the whole
static-world tooling (repr, op listing) applies to captured steps too.
"""
from __future__ import annotations

from collections import Counter
from typing import List, Optional, Tuple

__all__ = ["GraftProgram"]


class GraftProgram:
    """One captured step: transformed jaxpr + op record + pass report."""

    def __init__(self, closed_jaxpr, op_names: List[str], pass_report,
                 in_avals: Tuple = (), out_avals: Tuple = (),
                 donate: Tuple[int, ...] = ()):
        self.closed_jaxpr = closed_jaxpr
        self.op_names = list(op_names)
        self.pass_report = pass_report
        self.in_avals = tuple(in_avals)
        self.out_avals = tuple(out_avals)
        self.donate = tuple(donate)

    # ---- jaxpr-level views -------------------------------------------------
    @property
    def num_eqns(self) -> int:
        return len(self.closed_jaxpr.jaxpr.eqns)

    def primitive_counts(self) -> dict:
        return dict(Counter(e.primitive.name
                            for e in self.closed_jaxpr.jaxpr.eqns))

    def lower_text(self) -> str:
        """StableHLO of the program as captured (kernel-provenance checks:
        ops/pallas/_common.kernel_names finds the Pallas kernels in it)."""
        import jax
        from jax.extend.core import jaxpr_as_fun
        return jax.jit(jaxpr_as_fun(self.closed_jaxpr)).lower(
            *(jax.ShapeDtypeStruct(a.shape, a.dtype)
              for a in self.in_avals)).as_text()

    # ---- op-level views ----------------------------------------------------
    def op_counts(self) -> dict:
        return dict(Counter(self.op_names))

    def as_program(self):
        """The op record as a `static.framework.Program` (inspection only:
        the Operators carry names, not replayable callables — execution
        belongs to the lowered jaxpr)."""
        from .framework import Operator, Program
        prog = Program()
        block = prog.global_block()
        for i, name in enumerate(self.op_names):
            block.append_op(Operator(None, (), {}, [f"{name}_{i}"], name))
        return prog

    def describe(self, max_lines: Optional[int] = 40) -> str:
        rep = self.pass_report
        head = (f"GraftProgram: {len(self.op_names)} dispatched ops -> "
                f"{self.num_eqns} equations, donate={list(self.donate)}")
        lines = [head]
        if rep is not None:
            lines.append(
                f"passes: inlined={rep.inlined_calls} cse={rep.cse_folded} "
                f"consts_deduped={rep.consts_deduped} dve={rep.dve_removed} "
                f"({rep.eqns_before}->{rep.eqns_after} eqns)")
        txt = str(self.closed_jaxpr.jaxpr).splitlines()
        if max_lines is not None and len(txt) > max_lines:
            txt = txt[:max_lines] + [f"  ... ({len(txt) - max_lines} more)"]
        return "\n".join(lines + txt)

    def __repr__(self):
        return (f"<GraftProgram ops={len(self.op_names)} "
                f"eqns={self.num_eqns} donate={list(self.donate)}>")
