"""paddle_tpu.device — device + allocator introspection surface.

Analog of python/paddle/device/__init__.py (get/set_device, synchronize,
stream API) and python/paddle/device/cuda/__init__.py:215-281
(memory_allocated / max_memory_allocated / memory_reserved).  The allocator
is PJRT's BFC allocator; its live counters come from
`jax.Device.memory_stats()`, so these report what the runtime actually
holds — no shadow bookkeeping."""
from __future__ import annotations

import jax

from ..core.device import (  # noqa: F401
    current_jax_device, device_count, get_device, is_compiled_with_tpu,
    set_device,
)
from ..utils.memo import LockedLRU

__all__ = [
    "get_device", "set_device", "device_count", "is_compiled_with_tpu",
    "synchronize", "memory_stats", "memory_allocated", "max_memory_allocated",
    "memory_reserved", "max_memory_reserved", "empty_cache", "get_all_device_type",
    "get_available_device", "get_available_custom_device", "cuda", "Stream",
    "Event", "current_stream", "stream_guard",
]


def _resolve(device=None):
    if device is None:
        return current_jax_device()
    if isinstance(device, int):
        return jax.devices()[device]
    if isinstance(device, str):
        from ..core.device import _platform_devices
        plat, _, idx = device.partition(":")
        devs = _platform_devices(plat)
        if not devs:
            raise RuntimeError(
                f"no devices found for platform {plat!r}; "
                f"available: {[d.platform for d in jax.devices()]}")
        return devs[int(idx or 0)]
    return device


def synchronize(device=None):
    """Block until all queued work on the device finished (cuda.synchronize
    analog): realized by blocking on a trivial transfer barrier."""
    d = _resolve(device)
    jax.device_put(0, d).block_until_ready()


def memory_stats(device=None) -> dict:
    """Raw PJRT allocator counters (bytes_in_use, peak_bytes_in_use,
    bytes_limit, num_allocs, ...). Empty dict on backends that don't track
    (plain CPU)."""
    d = _resolve(device)
    try:
        stats = d.memory_stats()
    except Exception:
        stats = None
    return dict(stats) if stats else {}


def memory_allocated(device=None) -> int:
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    return int(memory_stats(device).get("peak_bytes_in_use", 0))


def memory_reserved(device=None) -> int:
    s = memory_stats(device)
    return int(s.get("bytes_reserved", s.get("bytes_in_use", 0)))


def max_memory_reserved(device=None) -> int:
    s = memory_stats(device)
    return int(s.get("peak_bytes_reserved", s.get("peak_bytes_in_use", 0)))


def empty_cache():
    """Release cached device buffers (cuda.empty_cache analog): under PJRT
    the arena is runtime-managed; clearing jax's internal caches drops dead
    references so their buffers free."""
    jax.clear_caches()


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()
            if d.platform not in ("cpu", "gpu", "tpu")]


class Stream:
    """Stream API surface (device/__init__.py Stream). PJRT orders work per
    device internally; separate streams are a no-op container here, kept so
    reference code constructing/synchronizing streams runs unchanged."""

    def __init__(self, device=None, priority=2):
        self.device = _resolve(device)
        self.priority = priority

    def synchronize(self):
        synchronize(self.device)

    def wait_stream(self, other):
        other.synchronize()

    def record_event(self, event=None):
        ev = event or Event()
        ev.record(self)
        return ev

    def wait_event(self, event):
        event.synchronize()


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        self._stream = None

    def record(self, stream=None):
        self._stream = stream or current_stream()

    def query(self):
        return True

    def synchronize(self):
        if self._stream is not None:
            self._stream.synchronize()


# one-slot audited registry ("current" -> Stream): lazily created by
# current_stream, pushed/popped by stream_guard (memo idiom)
_stream_state = LockedLRU(maxsize=None)


def current_stream(device=None):
    return _stream_state.get_or_create("current", lambda: Stream(device))


class stream_guard:
    def __init__(self, stream):
        self.stream = stream

    def __enter__(self):
        self._prev = _stream_state.get("current")
        _stream_state.put("current", self.stream)
        return self.stream

    def __exit__(self, *exc):
        if self._prev is None:
            _stream_state.pop("current")
        else:
            _stream_state.put("current", self._prev)
        return False


class cuda:
    """paddle.device.cuda compat: maps onto the single logical accelerator
    space (reference device/cuda/__init__.py:215-281)."""
    memory_allocated = staticmethod(memory_allocated)
    max_memory_allocated = staticmethod(max_memory_allocated)
    memory_reserved = staticmethod(memory_reserved)
    max_memory_reserved = staticmethod(max_memory_reserved)
    empty_cache = staticmethod(empty_cache)
    synchronize = staticmethod(synchronize)
    device_count = staticmethod(lambda: device_count())

    @staticmethod
    def get_device_properties(device=None):
        d = _resolve(device)
        stats = memory_stats(d)
        class _Props:  # noqa: N801
            name = getattr(d, "device_kind", d.platform)
            total_memory = int(stats.get("bytes_limit", 0))
            major, minor = 0, 0
            multi_processor_count = getattr(d, "num_cores", 1) or 1
        return _Props()
