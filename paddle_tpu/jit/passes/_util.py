"""Shared jaxpr-surgery helpers for the pass pipeline."""
from __future__ import annotations

from jax.extend import core as jex


def subst_fn(env: dict):
    """Atom substituter over an env of Var -> Atom (chases chains)."""
    def subst(a):
        while isinstance(a, jex.Var) and a in env:
            a = env[a]
        return a
    return subst


def rebuild(jaxpr, constvars, consts, eqns, outvars):
    """New ClosedJaxpr with recomputed effects, preserving debug info."""
    effects = frozenset()
    for e in eqns:
        if e.effects:
            effects = effects | frozenset(e.effects)
    new = jex.Jaxpr(list(constvars), list(jaxpr.invars), list(outvars),
                      list(eqns), effects=effects,
                      debug_info=getattr(jaxpr, "debug_info", None))
    return jex.ClosedJaxpr(new, list(consts))


def atom_token(a):
    """Hashable identity token for an equation input atom.

    Vars key by object identity (SSA binding); Literals by (value, aval)
    — Literal itself is unhashable in this jax. Raises TypeError when the
    literal payload cannot be keyed (caller treats the eqn as un-CSE-able).
    """
    if isinstance(a, jex.Literal):
        v = a.val
        if hasattr(v, "item") and getattr(v, "size", 2) == 1:
            v = v.item()
        return ("lit", v, str(a.aval))
    return ("var", id(a))
