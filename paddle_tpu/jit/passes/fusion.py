"""Region fusion: inline nested compiled-call equations.

A step traced through the op library is mostly flat primitives (eager ops
bypass the per-op executable cache under a trace and emit inline), but
anything that was ALREADY a compiled region re-enters the capture as one
opaque `jit` call equation: a `to_static` subprogram invoked inside the
step, a jitted helper, a cached per-op executable called directly. Left
opaque, each is a separate XLA computation — a fusion barrier with its own
call overhead.

This pass splices such call regions into the parent program (fresh
variables per site, constants hoisted, recursively until flat), so the
whole step lowers as ONE region and XLA fuses across the former
boundaries — the role BuildCinnPass/graph-fuse passes play for the
reference's subgraphs, inverted: they group ops INTO regions, we erase
region edges because XLA wants maximal scope.

Only plain calls are inlined: an equation carrying sharding/layout
constraints or internal donation keeps its boundary (those annotations
have no parent-level equivalent after splicing).
"""
from __future__ import annotations

import jax.core as jcore
from jax.extend import core as jex

from ._util import rebuild, subst_fn

_CALL_PRIMS = ("jit", "closed_call", "core_call")
_MAX_ROUNDS = 8   # nested-call depth bound; real steps are depth 1-2


def _unspecified(s) -> bool:
    return type(s).__name__ == "UnspecifiedValue"


def _plain_call(eqn) -> bool:
    if eqn.primitive.name not in _CALL_PRIMS:
        return False
    p = eqn.params
    if not isinstance(p.get("jaxpr"), jex.ClosedJaxpr):
        return False
    for key in ("in_shardings", "out_shardings"):
        if not all(_unspecified(s) for s in (p.get(key) or ())):
            return False
    for key in ("in_layouts", "out_layouts"):
        if not all(l is None for l in (p.get(key) or ())):
            return False
    if any(p.get("donated_invars") or ()):
        return False
    if p.get("compiler_options_kvs"):
        return False
    return True


def _splice(eqn, subst, constvars, consts, out_eqns, env):
    """Append the call's body to out_eqns with per-site fresh variables."""
    inner = eqn.params["jaxpr"]
    ij = inner.jaxpr
    vmap = {}
    for iv, outer_atom in zip(ij.invars, [subst(v) for v in eqn.invars]):
        vmap[iv] = outer_atom
    for cv, c in zip(ij.constvars, inner.consts):
        fresh = jex.Var(cv.aval)
        vmap[cv] = fresh
        constvars.append(fresh)
        consts.append(c)

    def in_atom(a):
        if isinstance(a, jex.Var):
            return vmap[a]
        return a

    for ieqn in ij.eqns:
        new_outs = []
        for o in ieqn.outvars:
            if isinstance(o, jcore.DropVar):
                new_outs.append(jcore.DropVar(o.aval))
            else:
                fresh = jex.Var(o.aval)
                vmap[o] = fresh
                new_outs.append(fresh)
        out_eqns.append(ieqn.replace(
            invars=[in_atom(v) for v in ieqn.invars], outvars=new_outs))

    for o, io in zip(eqn.outvars, ij.outvars):
        if isinstance(o, jcore.DropVar):
            continue
        env[o] = vmap[io] if isinstance(io, jex.Var) else io


def inline_calls(closed, report):
    for _ in range(_MAX_ROUNDS):
        jaxpr = closed.jaxpr
        if not any(_plain_call(e) for e in jaxpr.eqns):
            return closed
        env: dict = {}
        subst = subst_fn(env)
        constvars = list(jaxpr.constvars)
        consts = list(closed.consts)
        kept = []
        for eqn in jaxpr.eqns:
            if _plain_call(eqn):
                _splice(eqn, subst, constvars, consts, kept, env)
                report.inlined_calls += 1
            else:
                kept.append(eqn.replace(
                    invars=[subst(v) for v in eqn.invars]))
        outvars = [subst(v) if isinstance(v, jex.Var) else v
                   for v in jaxpr.outvars]
        closed = rebuild(jaxpr, constvars, consts, kept, outvars)
    return closed
