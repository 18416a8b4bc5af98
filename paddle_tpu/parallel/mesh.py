"""Global device-mesh management.

TPU-native replacement for the reference's CommunicateTopology /
HybridCommunicateGroup (python/paddle/distributed/fleet/base/topology.py:60,146)
and the ProcessGroup ring registry: instead of per-ring NCCL communicators,
a single jax.sharding.Mesh whose named axes (dp, pp, sharding, mp, sp, ep)
carry XLA collectives over ICI; groups are views onto mesh axes.
"""
from __future__ import annotations

import threading
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_state = threading.local()

# canonical hybrid-parallel axis order, outermost (slowest, DCN-friendly) first —
# matches fleet's order=[dp, pp, sharding, sep, mp] (topology.py:30)
HYBRID_ORDER = ("dp", "pp", "sharding", "sep", "mp")


def init_mesh(shape: dict | Sequence[int], axis_names: Optional[Sequence[str]] = None,
              devices=None) -> Mesh:
    """Create + install the global mesh.

    init_mesh({"dp": 2, "mp": 4}) or init_mesh([2, 4], ["dp", "mp"]).
    Axes of size 1 are kept (harmless) so strategy code can always name them.
    """
    if isinstance(shape, dict):
        axis_names = tuple(shape.keys())
        dims = tuple(int(v) for v in shape.values())
    else:
        dims = tuple(int(v) for v in shape)
        axis_names = tuple(axis_names)
    devices = devices if devices is not None else jax.devices()
    n = int(np.prod(dims))
    if n > len(devices):
        raise RuntimeError(f"mesh {dict(zip(axis_names, dims))} needs {n} devices, "
                           f"have {len(devices)}")
    dev_array = np.asarray(devices[:n]).reshape(dims)
    mesh = Mesh(dev_array, axis_names)
    _state.mesh = mesh
    return mesh


def elastic_mesh_shape(template: dict, n_devices: int,
                       elastic_axis: str = "dp") -> dict:
    """Re-derive a mesh shape for a new device/node count after an elastic
    shrink or grow: every non-elastic axis keeps its extent, the elastic
    axis absorbs the change (n_devices / prod(others)). Raises when the
    new count cannot host the fixed axes — the caller then HOLDs or falls
    back to a full restart instead of building a wrong-world mesh."""
    import math
    fixed = math.prod(int(v) for k, v in template.items()
                      if k != elastic_axis)
    if elastic_axis not in template:
        raise ValueError(f"elastic axis {elastic_axis!r} not in mesh "
                         f"template {template}")
    if n_devices <= 0 or n_devices % fixed != 0:
        raise ValueError(
            f"{n_devices} devices cannot host mesh template {template}: "
            f"non-elastic axes need a multiple of {fixed}")
    out = dict(template)
    out[elastic_axis] = n_devices // fixed
    return out


def set_mesh(mesh: Optional[Mesh]):
    _state.mesh = mesh


def get_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def has_mesh() -> bool:
    return get_mesh() is not None


def mesh_axis_size(axis: str) -> int:
    mesh = get_mesh()
    if mesh is None or axis not in mesh.axis_names:
        return 1
    return mesh.shape[axis]


def axis_index(axis: str):
    """Inside shard_map: this device's coordinate along `axis`."""
    return jax.lax.axis_index(axis)


def named_sharding(*spec) -> Optional[NamedSharding]:
    mesh = get_mesh()
    if mesh is None:
        return None
    clean = tuple(s if (s is None or isinstance(s, tuple)) else str(s) for s in spec)
    return NamedSharding(mesh, PartitionSpec(*clean))


def _context_mesh(mesh):
    """(mesh to annotate against, its manual axes): inside shard_map the
    context is an AbstractMesh whose manual axes (e.g. 'pp') must not appear
    in constraints or inner shard_maps — use it and report them."""
    cur = jax.sharding.get_abstract_mesh()
    if cur is None or not cur.axis_names:
        return mesh, set()
    return cur, {n for n, t in zip(cur.axis_names, cur.axis_types)
                 if "Manual" in str(t)}


def _live_spec(spec, use_mesh, manual):
    """Drop axes the mesh lacks, of size 1, or already manual.  None
    (replicated) and PartitionSpec.UNCONSTRAINED (left to propagation)
    pass through."""
    def ok(a):
        return (a in use_mesh.axis_names and use_mesh.shape[a] > 1
                and a not in manual)

    clean = []
    for s in spec:
        if s is None or s is PartitionSpec.UNCONSTRAINED:
            clean.append(s)
        elif isinstance(s, tuple):
            kept = tuple(a for a in s if ok(a))
            clean.append(kept if kept else None)
        else:
            clean.append(s if ok(s) else None)
    return clean


def shard_kernel(fn, in_specs, out_specs):
    """Run ``fn`` — a call into a Pallas kernel — once per shard.

    GSPMD cannot partition a Mosaic custom call ("Mosaic kernels cannot be
    automatically partitioned"), so a kernel fed sharded operands must sit
    in a shard_map.  ``in_specs`` holds one tuple of mesh-axis names per
    operand (batch on 'dp', heads on 'mp'), ``out_specs`` the single
    output's; axes that are absent, of size 1 or already manual drop out of
    the specs.  The map is manual over EVERY axis not manual yet, named or
    not — Mosaic also refuses a kernel under a partly manual mesh, as inside
    the 'pp' pipeline.  ``fn`` is returned as is when nothing shards its
    operands and no enclosing shard_map is open (single device), or when
    the enclosing one already holds every axis."""
    mesh = get_mesh()
    if mesh is None:
        return fn
    use_mesh, manual = _context_mesh(mesh)

    def live(spec):
        return PartitionSpec(*_live_spec(spec, use_mesh, manual))

    ins = tuple(live(s) for s in in_specs)
    free = set(use_mesh.axis_names) - manual
    if not free or not (manual or any(a for spec in ins for a in spec)):
        return fn
    return jax.shard_map(fn, mesh=use_mesh, in_specs=ins,
                         out_specs=live(out_specs), axis_names=free,
                         check_vma=False)


def shard_constraint(value, *spec):
    """with_sharding_constraint that degrades to no-op without a mesh.

    A None in ``spec`` forces its dim replicated; a dim the caller does not
    own (the batch, to a tensor-parallel layer) takes
    PartitionSpec.UNCONSTRAINED and keeps whatever sharding propagation
    gives it.

    The GSPMD annotation primitive — the analog of the reference's per-op
    TensorDistAttr (phi/core/distributed/auto_parallel/dist_attr.h): XLA's
    sharding propagation plays the role of the Completer/Resharder
    (SURVEY.md §3.6).
    """
    mesh = get_mesh()
    if mesh is None:
        return value
    use_mesh, manual = _context_mesh(mesh)
    clean = _live_spec(spec, use_mesh, manual)
    try:
        return jax.lax.with_sharding_constraint(
            value, NamedSharding(use_mesh, PartitionSpec(*clean)))
    except Exception:
        return value
