"""Analyze-only lint pass: semantic hazards of a captured step program.

The AST tier (tools/staticcheck) sees Python source; this pass sees what
actually runs — the closed jaxpr a captured step lowers to — and reports
the hazards that only exist at that level (GC3, arxiv 2201.11840, makes
the case for compiler-level collective visibility; EQuARX, arxiv
2506.17615, for verifying at the IR that a quantized path *replaces* the
fp32 collective it shadows instead of running beside it).

Rules (shared verbatim by the staticcheck jaxpr tier, which wraps them
into ratcheted `Finding`s — see tools/staticcheck/jaxpr/):

- ``recompile-hazard``     weak_type avals on program inputs: a python
  scalar leaked into the traced signature, so value-equal calls can land
  on different lowerings (and x64 promotion flips under it).
- ``donation-miss``        donation is engaged but an input aval that
  matches a so-far-unclaimed output was not donated (a silently doubled
  live buffer), or a donated input matches NO output (the buffer is
  deleted with nothing aliasing it — referencing it after the call is
  the PR-10 write_back-before-rebuild class of bug).
- ``unscheduled-collective`` collective equations present in the program
  that the comm-schedule pass never tagged (the semantic complement of
  the AST naked-collective rule), including the fp32-beside-quantized
  duplication: a full-precision reduce on the same axis as an int8/fp8
  wire leg.
- ``dead-compute``         pure equation subgraphs reaching no output at
  any nesting level — what remains beyond the top-level DVE pass (which
  deliberately does not rewrite sub-jaxprs).
- ``host-callback``        callback/ordered-IO equations inside the step:
  every invocation round-trips to host, serializing the device stream.

Like comm_schedule.analyze(), everything here is read-only: analyze()
never mutates the program, and the capture-layer hook (jit/capture.py)
treats a raising lint as an observability loss, never a lowering failure.
Per-step results land in an audited registry that
``profiler.lint_summary()`` renders.

Env: ``PT_STEP_CAPTURE_LINT`` (default 1) — 0 disables the per-lowering
hook (analyze() itself keeps working for explicit callers).
"""
from __future__ import annotations

import os
from typing import List, Optional

import jax.core as jcore
from jax.extend import core as jex

from ...utils.memo import LockedLRU
from .comm_schedule import COLLECTIVE_PRIMS, _eqn_axes, _iter_subjaxprs, _open
from .donation import infer_donation

__all__ = ["RULES", "analyze", "lint_records", "record_lint",
           "clear_lint_records", "lint_enabled"]

RULES = ("recompile-hazard", "donation-miss", "unscheduled-collective",
         "dead-compute", "host-callback")

# callback primitive names on this jax line (pure_callback carries no
# effect object, so match by name; the effects check below catches the
# ordered/IO forms any future jax renames these into)
_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
})
_WIRE_DTYPES = ("int8", "uint8", "float8_e4m3fn", "float8_e5m2")


def lint_enabled() -> bool:
    return os.environ.get("PT_STEP_CAPTURE_LINT", "1").lower() \
        not in ("0", "false")


def comm_tagged_of(report) -> int:
    """Tagged-collective count of one lowering's PassReport, with a
    skipped/absent comm pass counting as ZERO — collectives in the
    program are then 'unscheduled' by definition. The ONE place this
    semantics lives; both the capture hook and the staticcheck jaxpr
    tier call it."""
    if report is not None and "comm" in report.passes_run:
        return report.comm_tagged
    return 0


def _finding(rule: str, detail: str, message: str) -> dict:
    return {"rule": rule, "detail": detail, "message": message}


# ---------------------------------------------------------------------------
# recursive walks (the comm_schedule nesting idiom: params may hold
# sub-jaxprs under jaxpr/call_jaxpr/branches/..., raw or closed)
# ---------------------------------------------------------------------------

def _walk_eqns(jaxpr: jex.Jaxpr, depth: int = 0):
    """Yield (eqn, depth) for every equation at every nesting level."""
    for eqn in jaxpr.eqns:
        yield eqn, depth
        for _k, _i, sub in _iter_subjaxprs(eqn.params):
            yield from _walk_eqns(_open(sub), depth + 1)


def _dead_eqns(jaxpr: jex.Jaxpr) -> List:
    """Pure equations whose results reach no output of their level."""
    live = {v for v in jaxpr.outvars if isinstance(v, jex.Var)}
    dead = []
    for eqn in reversed(jaxpr.eqns):
        outs = [v for v in eqn.outvars if not isinstance(v, jcore.DropVar)]
        if eqn.effects or any(v in live for v in outs):
            for v in eqn.invars:
                if isinstance(v, jex.Var):
                    live.add(v)
        else:
            dead.append(eqn)
    return dead


def _dead_compute(jaxpr: jex.Jaxpr, depth: int = 0):
    """-> [(primitive_name, depth)] dead at this level or below."""
    out = [(e.primitive.name, depth) for e in _dead_eqns(jaxpr)]
    for eqn in jaxpr.eqns:
        for _k, _i, sub in _iter_subjaxprs(eqn.params):
            out.extend(_dead_compute(_open(sub), depth + 1))
    return out


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------

def _check_recompile(closed) -> List[dict]:
    weak = [i for i, v in enumerate(closed.jaxpr.invars)
            if getattr(v.aval, "weak_type", False)]
    if not weak:
        return []
    return [_finding(
        "recompile-hazard", f"weak_type_invars={tuple(weak)}",
        f"input positions {tuple(weak)} carry weak_type avals — a python "
        f"scalar leaked into the traced signature; pass jnp.asarray(x, "
        f"dtype) so value-equal calls share one lowering and x64 "
        f"promotion cannot flip the program")]


def _check_donation(closed, donated) -> List[dict]:
    findings = []
    in_avals = [v.aval for v in closed.jaxpr.invars]
    out_avals = [getattr(v, "aval", None) for v in closed.jaxpr.outvars]
    out_avals = [a for a in out_avals if a is not None]
    donated = tuple(donated or ())
    if not donated:
        return []  # donation off is a caller choice, not a program hazard

    def key(a):
        return (tuple(a.shape), str(a.dtype))

    # claim outputs for the donated positions first; a donated input that
    # finds no output to alias is the write_back-before-rebuild shape
    budget: dict = {}
    for a in out_avals:
        budget[key(a)] = budget.get(key(a), 0) + 1
    unmatched = []
    out_of_range = tuple(i for i in donated if i >= len(in_avals))
    if out_of_range:
        # the donation accounting itself is wrong — exactly when this
        # rule matters most, so report instead of silently skipping
        findings.append(_finding(
            "donation-miss", f"donated_out_of_range={out_of_range}",
            f"donated positions {out_of_range} exceed the program's "
            f"{len(in_avals)} inputs — the flat-position accounting "
            f"disagrees with the lowered program's invars"))
    for i in donated:
        if i >= len(in_avals):
            continue
        k = key(in_avals[i])
        if budget.get(k, 0) > 0:
            budget[k] -= 1
        else:
            unmatched.append(i)
    if unmatched:
        findings.append(_finding(
            "donation-miss", f"donated_unmatched={tuple(unmatched)}",
            f"donated input positions {tuple(unmatched)} match no output "
            f"aval — XLA deletes the buffer with nothing aliasing it; any "
            f"host reference after the call hits a deleted array (the "
            f"MULTICHIP write_back-before-rebuild donation bug class)"))

    # with donation engaged, inputs the inference would also donate are
    # misses: the step is silently holding two copies of those buffers.
    # Inference runs against the outputs REMAINING after the actual
    # donations claimed theirs (and never re-considers donated
    # positions), so a correctly-donated program can't be flagged.
    remaining = []
    claimed = dict(budget)  # post-donation leftovers, multiset by aval key
    for a in out_avals:
        k = key(a)
        if claimed.get(k, 0) > 0:
            claimed[k] -= 1
            remaining.append(a)
    missed = tuple(sorted(
        infer_donation(in_avals, remaining, reserved=donated)))
    if missed:
        findings.append(_finding(
            "donation-miss", f"missed={missed}",
            f"input positions {missed} are donatable (an unclaimed output "
            f"matches their aval) but were not donated — the step holds "
            f"two live copies of those buffers"))
    return findings


def _collect_collectives(closed) -> List[dict]:
    out = []
    for eqn, depth in _walk_eqns(_open(closed)):
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            # ALL operand dtypes: one psum over a pytree is a single eqn
            # with one invar per leaf, and a wire leg riding beside an
            # fp32 leg in the same call is still the duplication
            dtypes = []
            for v in eqn.invars:
                aval = getattr(v, "aval", None)
                if aval is not None and hasattr(aval, "dtype"):
                    dtypes.append(str(aval.dtype))
            out.append({"kind": eqn.primitive.name,
                        "axes": _eqn_axes(eqn), "dtypes": dtypes,
                        "depth": depth})
    return out


def _check_collectives(closed, comm_tagged: Optional[int]) -> List[dict]:
    colls = _collect_collectives(closed)
    findings = []
    if colls and comm_tagged is not None and comm_tagged < len(colls):
        kinds = sorted({c["kind"] for c in colls})
        findings.append(_finding(
            "unscheduled-collective",
            f"untagged={len(colls) - comm_tagged}",
            f"{len(colls)} collective equation(s) ({', '.join(kinds)}) in "
            f"the program but the comm-schedule pass tagged {comm_tagged} "
            f"— collectives are bypassing the comms schedule (no CommOp "
            f"record, no overlap slot, invisible to comm_summary)"))
    # fp32-beside-quantized: a full-precision reduction on the same axes
    # as a wire-dtype leg duplicates the collective the quantized path
    # was supposed to replace (EQuARX's replace-not-shadow contract)
    by_axes: dict = {}
    for c in colls:
        by_axes.setdefault(c["axes"], []).append(c)
    for axes, group in by_axes.items():
        if not axes:
            continue
        wire = [(c, d) for c in group for d in c["dtypes"]
                if d in _WIRE_DTYPES]
        # full-precision leg: f32, or f64 on the x64-enabled proxy
        fp32 = [c for c in group
                if {"float32", "float64"} & set(c["dtypes"])]
        if wire and fp32:
            findings.append(_finding(
                "unscheduled-collective",
                f"fp32_beside_quantized_axes={'+'.join(axes)}",
                f"axis {'+'.join(axes)} carries both a quantized wire leg "
                f"({wire[0][0]['kind']}@{wire[0][1]}) and a float32 "
                f"{fp32[0]['kind']} — the full-precision collective runs "
                f"beside the quantized one instead of being replaced by "
                f"it"))
    return findings


def _check_dead(closed) -> List[dict]:
    # top level is DVE's job; anything at depth>=1 (and anything DVE left
    # behind when the pipeline was trimmed) is real residue
    dead = _dead_compute(_open(closed))
    if not dead:
        return []
    prims = sorted({p for p, _ in dead})
    return [_finding(
        "dead-compute", f"dead={len(dead)}",
        f"{len(dead)} pure equation(s) reach no program output "
        f"({', '.join(prims[:6])}{'...' if len(prims) > 6 else ''}; "
        f"max nesting depth {max(d for _, d in dead)}) — compute the "
        f"DVE pass cannot see because it lives inside sub-jaxprs")]


def _check_callbacks(closed) -> List[dict]:
    hits: dict = {}
    for eqn, _depth in _walk_eqns(_open(closed)):
        name = eqn.primitive.name
        io_eff = any("IO" in type(e).__name__ or "Ordered" in type(e).__name__
                     or "Debug" in type(e).__name__ for e in eqn.effects)
        if name in _CALLBACK_PRIMS or "callback" in name or io_eff:
            hits[name] = hits.get(name, 0) + 1
    if not hits:
        return []
    what = ", ".join(f"{k}x{v}" for k, v in sorted(hits.items()))
    return [_finding(
        "host-callback", f"callbacks={'+'.join(sorted(hits))}",
        f"host callback(s) inside the captured step ({what}) — every "
        f"invocation round-trips to the host and serializes the device "
        f"stream; hoist the callback out of the step or accept the sync "
        f"explicitly")]


def analyze(closed, *, donated=(), comm_tagged: Optional[int] = None,
            name: str = "step") -> List[dict]:
    """Run every rule over one (Closed)Jaxpr; returns finding dicts
    (rule/detail/message). ``donated``: flat input positions the lowering
    donates. ``comm_tagged``: the comm pass's tagged-collective count for
    THIS program (None = pass didn't run in a comparable way — the
    untagged check is skipped, duplication detection still runs)."""
    del name  # part of the stable signature; rules are program-local
    findings: List[dict] = []
    findings += _check_recompile(closed)
    findings += _check_donation(closed, donated)
    findings += _check_collectives(closed, comm_tagged)
    findings += _check_dead(closed)
    findings += _check_callbacks(closed)
    return findings


# ---------------------------------------------------------------------------
# per-step records (profiler.lint_summary reads these)
# ---------------------------------------------------------------------------

# audited registry (memo idiom): one entry per step name, newest lowering
# wins; bounded so a signature-churning workload cannot grow it unbounded
_RECORDS = LockedLRU(maxsize=64)


def record_lint(name: str, closed, *, donated=(),
                comm_tagged: Optional[int] = None) -> List[dict]:
    """The capture-layer hook: analyze one lowering and file the result
    under the step's name. Never raises (observability must not break
    lowering); returns the findings for the caller's own use."""
    try:
        findings = analyze(closed, donated=donated, comm_tagged=comm_tagged,
                           name=name)
        _RECORDS.put(name, {
            "eqns": len(closed.jaxpr.eqns),
            "findings": findings,
            "rules_hit": sorted({f["rule"] for f in findings}),
        })
        return findings
    except Exception:  # noqa: BLE001 — lint may never break a lowering
        return []


def lint_records() -> dict:
    """{step_name: {eqns, findings, rules_hit}} for recent lowerings."""
    return dict(_RECORDS.items())


def clear_lint_records():
    _RECORDS.clear()
