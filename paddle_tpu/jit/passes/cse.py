"""Common-subexpression elimination + duplicate-constant folding.

Two eager dispatch sites that compute the same value (same primitive, same
params, same inputs) become two equations in the captured program — e.g.
per-layer causal masks, repeated broadcasts of the same scalar, the rope
cos/sin tables retraced per decoder block. One program-level walk folds
them: later duplicates are rewritten to reuse the first result, and
value-identical trace constants collapse to a single buffer (duplicate
weights/tables embedded as consts otherwise each occupy device memory).

Soundness: equations with effects are never folded; an equation whose
params cannot be hashed keys by object identity (false negatives only).
jax's PRNG is a pure function of its key, so folding identical random
equations is value-preserving.
"""
from __future__ import annotations

import numpy as np

import jax.core as jcore
from jax.extend import core as jex

from ._util import atom_token, rebuild, subst_fn

_MAX_CONST_BYTES = 1 << 16   # dedupe consts up to 64 KiB by value; id() above


def _params_token(params: dict):
    parts = []
    for k in sorted(params):
        v = params[k]
        try:
            hash(v)
        except TypeError:
            v = ("id", id(v))
        parts.append((k, v))
    return tuple(parts)


def _const_token(c):
    try:
        arr = np.asarray(c)
    except Exception:  # noqa: BLE001 — non-array const: identity only
        return ("id", id(c))
    if arr.nbytes > _MAX_CONST_BYTES or arr.dtype == object:
        return ("id", id(c))
    return ("val", str(arr.dtype), arr.shape, arr.tobytes())


def fold(closed, report):
    jaxpr = closed.jaxpr
    env: dict = {}
    subst = subst_fn(env)

    # ---- duplicate-constant folding ----
    constvars, consts, seen_consts = [], [], {}
    for cv, c in zip(jaxpr.constvars, closed.consts):
        tok = _const_token(c)
        canon = seen_consts.get(tok)
        if canon is None:
            seen_consts[tok] = cv
            constvars.append(cv)
            consts.append(c)
        else:
            env[cv] = canon
            report.consts_deduped += 1

    # ---- equation-level CSE ----
    seen_eqns: dict = {}
    kept = []
    for eqn in jaxpr.eqns:
        invars = [subst(v) for v in eqn.invars]
        eqn = eqn.replace(invars=invars)
        key = None
        if not eqn.effects:
            try:
                key = (eqn.primitive.name, _params_token(eqn.params),
                       tuple(atom_token(v) for v in invars))
            except TypeError:
                key = None
        if key is not None:
            prev = seen_eqns.get(key)
            if prev is not None:
                for o, p in zip(eqn.outvars, prev):
                    if not isinstance(o, jcore.DropVar):
                        env[o] = p
                report.cse_folded += 1
                continue
            if not any(isinstance(o, jcore.DropVar) for o in eqn.outvars):
                seen_eqns[key] = list(eqn.outvars)
        kept.append(eqn)

    if not report.cse_folded and not report.consts_deduped:
        return closed
    outvars = [subst(v) if isinstance(v, jex.Var) else v
               for v in jaxpr.outvars]
    return rebuild(jaxpr, constvars, consts, kept, outvars)
