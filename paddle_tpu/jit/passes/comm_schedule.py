"""Comm-schedule pass: collectives as first-class scheduled equations.

GC3 (arxiv 2201.11840) argues collectives should be explicit program
objects the compiler schedules, not opaque calls.  Over a captured step
program this pass:

1. **tags** every collective equation (``psum/pmax/pmin/all_gather/
   ppermute/all_to_all/reduce_scatter``) at every nesting level —
   shard_map bodies, inlined pjit regions, scan/while/cond sub-jaxprs —
   and registers a ``CommOp`` per site into the comms schedule registry
   (owner ``xla``), so ``profiler.comm_summary()`` shows the compiler-
   level collectives of a captured step next to the api-level ones;

2. **slots** them: the dependency depth of each collective equation is
   its overlap slot — collectives sharing a slot have no data dependence
   on each other and may run concurrently (the fused dp-grad psums of a
   layer, the two wire legs of a quantized two-shot);

3. **reorders**: each collective equation is hoisted to the earliest
   position its data dependencies allow, maximizing the window between
   issue and first use so XLA's latency-hiding scheduler can overlap the
   wire time with compute.  Pure equations only (effects pin order);
   value semantics are unchanged — only equation order moves, and only
   within what the SSA dependencies already permitted.

Like every pass in the pipeline, failure is an optimization loss, never a
correctness loss (run_pipeline skips a raising pass).
"""
from __future__ import annotations

import jax.core as jcore
from jax.extend import core as jex

from ._util import rebuild

# collective primitive names at the jaxpr level (pmean lowers to psum+div,
# so it shows up as psum here)
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "all_gather", "ppermute", "all_to_all",
    "reduce_scatter", "psum_scatter",
})

# eqn param keys that hold sub-jaxprs to recurse into
_SUBJAXPR_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr", "body_jaxpr",
                  "cond_jaxpr", "branches")


def _order_free(eqn) -> bool:
    """True when the equation's effects don't pin its program order.
    Collectives under this jax carry NamedAxisEffect — a scoping marker
    (which axis the eqn uses), not an IO/ordering effect — so an eqn whose
    only effects are named-axis markers may still be hoisted."""
    return all(type(e).__name__ == "NamedAxisEffect" for e in eqn.effects)


def _eqn_axes(eqn) -> tuple:
    ax = eqn.params.get("axes") or eqn.params.get("axis_name") or ()
    if isinstance(ax, str):
        ax = (ax,)
    return tuple(a for a in ax if isinstance(a, str))


def _payload_bytes(eqn) -> int:
    total = 0
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "size"):
            total += int(aval.size) * int(getattr(aval.dtype, "itemsize", 4))
    return total


def _iter_subjaxprs(params: dict):
    """-> [(key, index_or_None, Jaxpr-or-ClosedJaxpr)] found in params."""
    found = []
    for k in _SUBJAXPR_KEYS:
        v = params.get(k)
        if v is None:
            continue
        if isinstance(v, (tuple, list)):
            for i, item in enumerate(v):
                if isinstance(item, (jex.Jaxpr, jex.ClosedJaxpr)):
                    found.append((k, i, item))
        elif isinstance(v, (jex.Jaxpr, jex.ClosedJaxpr)):
            found.append((k, None, v))
    return found


def _open(j):
    return j.jaxpr if isinstance(j, jex.ClosedJaxpr) else j


# ---------------------------------------------------------------------------
# scheduling one jaxpr level
# ---------------------------------------------------------------------------

def _schedule_level(jaxpr: jex.Jaxpr, report, tagged: list):
    """Hoist + slot the collectives of one jaxpr; recurse into sub-jaxprs.
    Returns a new Jaxpr (or the original when nothing changed)."""
    changed = False
    eqns = []
    for eqn in jaxpr.eqns:
        subs = _iter_subjaxprs(eqn.params)
        if subs:
            new_params = dict(eqn.params)
            sub_changed = False
            for k, i, sub in subs:
                inner = _schedule_level(_open(sub), report, tagged)
                if inner is not _open(sub):
                    sub_changed = True
                    new_sub = jex.ClosedJaxpr(inner, sub.consts) \
                        if isinstance(sub, jex.ClosedJaxpr) else inner
                    if i is None:
                        new_params[k] = new_sub
                    else:
                        seq = list(new_params[k])
                        seq[i] = new_sub
                        new_params[k] = type(new_params[k])(seq) \
                            if isinstance(new_params[k], tuple) else seq
            if sub_changed:
                eqn = eqn.replace(params=new_params)
                changed = True
        eqns.append(eqn)

    # ---- dependency depth (the overlap slot) ----
    depth_of_var: dict = {}
    coll_idx = []
    depths = []
    for i, eqn in enumerate(eqns):
        d = 0
        for v in eqn.invars:
            if isinstance(v, jex.Var):
                d = max(d, depth_of_var.get(v, 0))
        d += 1
        for o in eqn.outvars:
            if not isinstance(o, jcore.DropVar):
                depth_of_var[o] = d
        depths.append(d)
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            coll_idx.append(i)

    if coll_idx:
        slot_levels = sorted({depths[i] for i in coll_idx})
        slot_of_depth = {d: s for s, d in enumerate(slot_levels)}
        for i in coll_idx:
            eqn = eqns[i]
            tagged.append({
                "kind": eqn.primitive.name,
                "axes": _eqn_axes(eqn),
                "bytes": _payload_bytes(eqn),
                "slot": slot_of_depth[depths[i]],
            })
        report.comm_tagged += len(coll_idx)
        report.comm_slots = max(report.comm_slots, len(slot_levels))

        # ---- hoist: earliest-legal placement for pure collectives ----
        placed: list = []
        pos_of_var: dict = {}
        hoisted = 0
        for eqn in eqns:
            earliest = 0
            for v in eqn.invars:
                if isinstance(v, jex.Var) and v in pos_of_var:
                    earliest = max(earliest, pos_of_var[v] + 1)
            if eqn.primitive.name in COLLECTIVE_PRIMS \
                    and _order_free(eqn) and earliest < len(placed):
                placed.insert(earliest, eqn)
                hoisted += 1
                # re-index every shifted equation's outvars
                for j in range(earliest, len(placed)):
                    for o in placed[j].outvars:
                        if not isinstance(o, jcore.DropVar):
                            pos_of_var[o] = j
            else:
                placed.append(eqn)
                for o in eqn.outvars:
                    if not isinstance(o, jcore.DropVar):
                        pos_of_var[o] = len(placed) - 1
        if hoisted:
            report.comm_hoisted += hoisted
            eqns = placed
            changed = True

    if not changed:
        return jaxpr
    return jaxpr.replace(eqns=eqns)


# ---------------------------------------------------------------------------
# pass entry points
# ---------------------------------------------------------------------------

def schedule(closed, report):
    """The pipeline pass: tag + slot + hoist the collectives of a captured
    program, and register the tally with the comms schedule registry."""
    tagged: list = []
    new_jaxpr = _schedule_level(closed.jaxpr, report, tagged)
    _register(tagged)
    if new_jaxpr is closed.jaxpr:
        return closed
    return rebuild(new_jaxpr, new_jaxpr.constvars, closed.consts,
                   new_jaxpr.eqns, new_jaxpr.outvars)


def analyze(closed) -> dict:
    """Read-only comm analysis of a (Closed)Jaxpr: collective count, total
    payload bytes, per-kind tally, overlap-slot count — the columns the
    multichip dryrun (`__graft_entry__.dryrun_multichip`) prints."""
    from . import PassReport
    tagged: list = []
    _schedule_level(_open(closed), PassReport(), tagged)
    kinds: dict = {}
    for t in tagged:
        kinds[t["kind"]] = kinds.get(t["kind"], 0) + 1
    return {
        "collectives": len(tagged),
        "payload_bytes": sum(t["bytes"] for t in tagged),
        "overlap_slots": len({t["slot"] for t in tagged}),
        "by_kind": dict(sorted(kinds.items())),
    }


def _register(tagged: list) -> None:
    """CommOp records (owner 'xla') for the compiler-level collectives of
    one lowering — once per capture, not per invocation."""
    if not tagged:
        return
    try:
        from ...distributed.comms.schedule import CommOp, record
        for t in tagged:
            ax = "+".join(t["axes"]) or None
            record(CommOp(
                owner="xla", site=f"xla/{t['kind']}/{ax or 'unnamed'}",
                kind=t["kind"], axis=ax, shape=(), dtype="",
                bytes_logical=t["bytes"], bytes_wire=t["bytes"],
                quantized=None, slot=t["slot"]))
    except Exception:  # noqa: BLE001 — accounting must never break lowering
        pass
