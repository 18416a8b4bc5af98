"""Dead-value elimination.

Backward liveness walk from the program outputs: an equation whose results
never reach an output (directly or through later equations) is dropped,
along with any constants only it referenced. Equations carrying effects
(io_callback, ordered side effects) are always kept — the captured-step
contract forbids host effects anyway (they bail capture out), but the pass
must stay sound on any jaxpr it is handed.

The walk recurses into sub-jaxprs (pjit/call regions, scan/cond bodies,
shard_map bodies — the comm_schedule nesting idiom) with each sub-level's
OWN outvars as the live roots: the calling convention of the enclosing
equation never changes, only dead interior equations go. This is where
AD recompute residue lives — a vjp'd shard_map re-traces forward gathers
whose primal outputs the backward never reads, and this jax line has no
shard_map DCE rule of its own — and it is exactly the residue the lint's
``dead-compute`` rule (passes/lint.py) reports when left behind.

The eager tape has no analog of this: every dispatched op executes. Whole-
step capture is what makes "computed but never used" a statically decidable
property — the reference gets the same from its ProgramDesc-level
`eliminate_dead_code` style passes.
"""
from __future__ import annotations

import jax.core as jcore
from jax.extend import core as jex

from .comm_schedule import _iter_subjaxprs, _open


def _sweep(jaxpr: jex.Jaxpr, report) -> jex.Jaxpr:
    """Drop dead pure equations at this level, recursing into sub-jaxprs
    first. Returns the original object when nothing changed. Constvars
    are left in place below the top level (an orphaned constvar is legal
    and the enclosing ClosedJaxpr's consts list must stay aligned)."""
    changed = False
    eqns = []
    for eqn in jaxpr.eqns:
        subs = _iter_subjaxprs(eqn.params)
        if subs:
            new_params = dict(eqn.params)
            sub_changed = False
            for k, i, sub in subs:
                inner = _sweep(_open(sub), report)
                if inner is _open(sub):
                    continue
                sub_changed = True
                new_sub = jex.ClosedJaxpr(inner, sub.consts) \
                    if isinstance(sub, jex.ClosedJaxpr) else inner
                if i is None:
                    new_params[k] = new_sub
                else:
                    seq = list(new_params[k])
                    seq[i] = new_sub
                    new_params[k] = tuple(seq) \
                        if isinstance(new_params[k], tuple) else seq
            if sub_changed:
                eqn = eqn.replace(params=new_params)
                changed = True
        eqns.append(eqn)

    live = {v for v in jaxpr.outvars if isinstance(v, jex.Var)}
    kept = []
    removed = 0
    for eqn in reversed(eqns):
        outs = [v for v in eqn.outvars if not isinstance(v, jcore.DropVar)]
        # an equation is dead when nothing live reads it — including the
        # all-outputs-dropped form jax leaves behind for unused bindings
        if eqn.effects or any(v in live for v in outs):
            kept.append(eqn)
            for v in eqn.invars:
                if isinstance(v, jex.Var):
                    live.add(v)
        else:
            removed += 1
    if not removed and not changed:
        return jaxpr
    report.dve_removed += removed
    kept.reverse()
    return jaxpr.replace(eqns=kept)


def eliminate(closed, report):
    jaxpr = _sweep(closed.jaxpr, report)
    if jaxpr is closed.jaxpr:
        return closed

    # top level only: constants orphaned by the sweep drop with their vars
    live = {v for eqn in jaxpr.eqns for v in eqn.invars
            if isinstance(v, jex.Var)}
    live |= {v for v in jaxpr.outvars if isinstance(v, jex.Var)}
    constvars, consts = [], []
    for cv, c in zip(jaxpr.constvars, closed.consts):
        if cv in live:
            constvars.append(cv)
            consts.append(c)
        else:
            report.dve_consts_dropped += 1

    from ._util import rebuild
    return rebuild(jaxpr, constvars, consts, list(jaxpr.eqns), jaxpr.outvars)
