"""Device management.

TPU-native analog of the reference's DeviceManager / paddle.device API
(paddle/phi/backends/device_manager.h:133, python/paddle/device/__init__.py:244).
Devices are jax devices; "tpu" maps to the default accelerator platform.
"""
from __future__ import annotations

import threading

import jax

_state = threading.local()

_PLATFORM_ALIASES = {"gpu": ("gpu", "cuda", "rocm")}


_portable_trace = False  # ONNX export: force backend-neutral lowerings


def is_tpu_backend() -> bool:
    """True when the default jax backend is the TPU.
    False while a portable trace (ONNX export) is active, so ops pick their
    backend-neutral form instead of Pallas kernels."""
    if _portable_trace:
        return False
    return jax.default_backend() == "tpu"


class portable_trace:
    """Context manager: trace with backend-neutral op lowerings."""

    def __enter__(self):
        global _portable_trace
        self._prev = _portable_trace
        _portable_trace = True
        return self

    def __exit__(self, *exc):
        global _portable_trace
        _portable_trace = self._prev
        return False


def _platform_devices(platform: str):
    for alias in _PLATFORM_ALIASES.get(platform, (platform,)):
        try:
            devs = jax.devices(alias)
            if devs:
                return devs
        except RuntimeError:
            continue
    return []


def device_count(platform: str | None = None) -> int:
    if platform is None:
        return len(jax.devices())
    return len(_platform_devices(platform))


def is_compiled_with_tpu() -> bool:
    return bool(_platform_devices("tpu"))


def set_device(device: str):
    """set_device('tpu') / 'cpu' / 'tpu:0'."""
    if ":" in device:
        platform, idx = device.split(":")
        idx = int(idx)
    else:
        platform, idx = device, 0
    devs = _platform_devices(platform)
    if not devs:
        raise RuntimeError(f"no devices found for platform {platform!r}; "
                           f"available: {[d.platform for d in jax.devices()]}")
    _state.device = devs[idx]
    _state.device_str = f"{platform}:{idx}"
    return _state.device


def get_device() -> str:
    if not hasattr(_state, "device_str"):
        # default: first device of the default backend
        d = jax.devices()[0]
        _state.device = d
        _state.device_str = f"{d.platform}:{d.id}"
    return _state.device_str


def current_jax_device():
    get_device()
    return _state.device
