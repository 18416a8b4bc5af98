"""Shared helpers for the Pallas TPU kernels in this package."""
from __future__ import annotations

import re

import jax

_NEG_INF = -1e30


def kernel_names(stablehlo_text: str) -> list:
    """The ``pl.pallas_call(name=...)`` of every Mosaic kernel in a lowered
    program (``jitted.lower(...).as_text()``), in program order: each is a
    ``tpu_custom_call`` whose backend config carries its kernel name.  Empty
    when the program took a jnp path instead of a kernel."""
    return [m.group(1) for line in stablehlo_text.splitlines()
            if "@tpu_custom_call" in line
            for m in [re.search(r'kernel_name = "(\w+)"', line)] if m]


def _x32():
    """Trace kernels in x32 mode: the package enables jax_enable_x64 globally
    (reference float64 parity), but x64 constants break Mosaic lowering."""
    return jax.enable_x64(False)


def _interpret() -> bool:
    """Pallas interpret mode off-TPU, so the same kernels unit-test on CPU."""
    from ...core.device import is_tpu_backend
    return not is_tpu_backend()
