"""What every mode shares: the device check, the compile counter, the
profiler session, the run's output directory."""
from __future__ import annotations

import json
import os
import time

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts every lowering (jaxpr to MLIR: a new signature of any jitted
    function, whether or not the persistent cache then serves it) and every
    backend compilation in this process, from jax's own monitoring events."""

    def __init__(self):
        import jax
        self.lowerings = 0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == LOWERING_EVENT:
            self.lowerings += 1
        elif event == COMPILE_EVENT:
            self.compiles += 1

    def snapshot(self) -> dict:
        from paddle_tpu.jit import capture
        cap = capture.capture_info()
        return {"jax_lowerings": self.lowerings, "jax_compiles": self.compiles,
                "capture_lowerings": cap["lowerings"],
                "capture_bailouts": cap["bailouts"],
                "capture_fallback_calls": cap["fallback_calls"]}


def counter_delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in before}


class TraceSession:
    """A jax.profiler trace of the LAST `trace_seconds` of the window, so
    that stopping it (which blocks the host while the trace is written)
    falls after the window.  `bench.trace_window` marks the traced span on
    the host's line of the trace; the reduction clips to it."""

    def __init__(self, enabled: bool, out_dir: str, t_end: float,
                 trace_seconds: float):
        self.enabled = enabled
        self.dir = os.path.join(out_dir, "trace")
        self.start_at = t_end - trace_seconds
        self.started = self.stopped = False
        self._mark = None
        self.t_start = self.t_stop = None

    def poll(self, now: float):
        if self.enabled and not self.started and now >= self.start_at:
            import jax
            jax.profiler.start_trace(self.dir)
            self._mark = jax.profiler.TraceAnnotation("bench.trace_window")
            self._mark.__enter__()
            self.started = True
            self.t_start = time.perf_counter()

    def mark_end(self):
        """Close the traced span; the profiler may run on (a serving cell
        still waits for late first tokens) and is stopped afterwards."""
        if self.started and self.t_stop is None:
            self.t_stop = time.perf_counter()
            self._mark.__exit__(None, None, None)

    def stop(self):
        if self.started and not self.stopped:
            import jax
            self.mark_end()
            jax.profiler.stop_trace()
            self.stopped = True


def annotate(name: str):
    """A host span in the profiler's own trace, round one of the
    benchmark's calls into the program."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def device_report(devices) -> dict:
    d = devices[0]
    peak = 0
    for dev in devices:
        try:
            stats = dev.memory_stats() or {}
        except Exception:  # noqa: BLE001 - a backend without the call
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def write_json(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(obj, fh)
