"""Runs one cell of BENCHMARK.json and prints one JSON line.

    python3 -m benchmarks.run --workload <name> --seed <n> --seconds <s>
                              --trace <0|1>

With --trace 0 the line's metrics are the cell's end-to-end metrics, with
--trace 1 its per-layer metrics (and `breakdown`).  The per-step stamps or
per-request records of the run, the checks behind `correct` and the detail
of the per-layer readers go to files under --out (default bench_out/).
Exits non-zero, printing no result, without the TPU chips the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()      # set-up is counted from here

import argparse          # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402
from dataclasses import dataclass, field   # noqa: E402

from . import arithmetic, harness, spec   # noqa: E402


@dataclass
class Env:
    seed: int
    seconds: float
    trace: bool
    out_dir: str
    compiles: object = None
    setup_s: float = None
    rehearsal: bool = False
    phases: list = field(default_factory=list)

    def phase(self, name: str):
        """Stamps the end of one part of set-up (seconds since the start),
        for the run's detail.json: where set-up goes is not in the line."""
        self.phases.append([name, time.perf_counter() - T_START])

    def window_opens(self, t0: float):
        self.setup_s = t0 - T_START
        self.phase("window opens")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default="bench_out")
    return ap.parse_args(argv)


def end_to_end(ev: dict, env: Env) -> dict:
    """Every end-to-end number this evidence supports, by name.  The line
    carries those BENCHMARK.json names for the cell; all of them go to the
    run's detail.json.  The TTFT statistics are in no cell's list since the
    check of PR 23: on a host whose cores are shared, a stall of half a
    second moves the slowest fifth's mean by 60% (PERF.md, Findings), so a
    cell that can hold one of them to a bound names it and needs no code."""
    t0, t1 = ev["t0"], ev["t1"]
    out = {"setup_s": env.setup_s}
    if "stamps" in ev:
        out["train_tokens_per_s"] = arithmetic.whole_step_throughput(
            ev["stamps"], t0, t1, ev["tokens_per_step"])
    if "requests" in ev:
        rows = ev["requests"]
        out["serve_out_tokens_per_s"] = arithmetic.out_tokens_per_s(
            rows, t0, t1)
        ttft = arithmetic.ttft_values(rows, t0, t1)
        tail = arithmetic.slowest_fifth_mean(ttft)
        out["ttft_ms_slowest_fifth"] = None if tail is None else 1e3 * tail
        for q in (50, 80):
            v = arithmetic.percentile(ttft, q)
            out[f"ttft_ms_p{q}"] = None if v is None else 1e3 * v
        p99 = arithmetic.percentile(arithmetic.itl_gaps(rows, t0, t1), 99)
        out["itl_ms_p99"] = None if p99 is None else 1e3 * p99
    return out


def per_layer(cell, ev: dict) -> tuple:
    """(values, details) of the cell's per-layer metrics; a reader that
    finds nothing to read leaves its metric out."""
    values, details = {}, {}
    for m in cell.per_layer:
        lm = spec.layer_metric(cell, m["name"])
        reader = cell.module("readers", lm["reader"])
        got = reader.read(ev, **lm.get("params", {}))
        if isinstance(got, dict):
            details[m["name"]] = got.get("detail")
            got = got["value"]
        if got is not None:
            values[m["name"]] = float(got)
    return values, details


def main(argv=None, rehearsal: bool = False) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload, tiny=rehearsal)
    seconds = args.seconds if args.seconds is not None else cell.run_seconds
    out_dir = os.path.join(args.out, cell.name,
                           f"seed{args.seed}-trace{args.trace}")
    import jax

    devices = jax.devices()
    kind = devices[0].device_kind
    if not rehearsal:
        if devices[0].platform != "tpu":
            sys.exit(f"benchmarks.run needs a TPU: jax found "
                     f"{devices[0].platform!r} ({kind})")
        if len(devices) < cell.chips:
            sys.exit(f"{cell.name} needs {cell.chips} chips, jax found "
                     f"{len(devices)}")
    peaks = None if rehearsal else spec.peaks_for(kind)
    devices = devices[:cell.chips]

    if not rehearsal:
        # the program's own placement of the cache (JAX_COMPILATION_CACHE_DIR
        # or <checkout>/.jax_cache); every program goes in, the small ones
        # too, so that a cell's second run compiles nothing
        from paddle_tpu.utils.compile_cache import configure_compile_cache
        configure_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    env = Env(seed=args.seed, seconds=seconds, trace=bool(args.trace),
              out_dir=out_dir, compiles=harness.CompileCounter(),
              rehearsal=rehearsal)
    env.phase("imports, devices")
    mode = cell.module("modes", cell.mode.split("-")[0])
    ev = mode.run(cell, env)
    ev.update(cell=cell, peaks=peaks, device=harness.device_report(devices))

    trace = ev["trace"]
    ev["reduced"] = None
    if trace.started:
        from . import reduce_trace
        ev["reduced"] = reduce_trace.reduce(
            reduce_trace.load_events(trace.dir))
        reduce_trace.drop_large(trace.dir)

    correct = all(ok for *_, ok in ev["checks"])
    if args.trace:
        values, details = per_layer(cell, ev)
        units = {m["name"]: m["unit"] for m in cell.per_layer}
        sources = {m["name"]: m["source"] for m in cell.per_layer}
        if rehearsal:
            # all names, and a number only where the CPU can count it
            values = {n: values.get(n) if sources[n] == "program_counter"
                      else None for n in units}
    else:
        supported, details = end_to_end(ev, env), {}
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = {n: supported.get(n) for n in units}
        if rehearsal:
            values = {n: None for n in units}
        elif any(v is None for v in values.values()):
            sys.exit(f"no reading for {[n for n, v in values.items() if v is None]}")

    device = dict(ev["device"])
    line = {"correct": bool(correct), "attempted": int(ev["attempted"]),
            "failed": int(ev["failed"]),
            "metrics": {n: {"value": v, "unit": units[n]}
                        for n, v in values.items()},
            "device": device}
    red = ev["reduced"]
    if args.trace and red is not None and not rehearsal:
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    elif args.trace and not rehearsal:
        sys.exit("--trace 1, and no device event in the profiler's trace")

    harness.write_json(os.path.join(out_dir, "records.json"), ev["records"])
    harness.write_json(os.path.join(out_dir, "detail.json"), {
        "workload": cell.name, "seed": args.seed, "seconds": seconds,
        "setup_s": env.setup_s, "setup_phases": env.phases,
        "window": [ev["t0"], ev["t1"]],
        "checks": [{"what": w, "got": g, "want": x, "ok": bool(ok)}
                   for w, g, x, ok in ev["checks"]],
        "counters": ev["counters"], "per_layer_detail": details,
        "end_to_end_supported": None if args.trace or rehearsal else supported,
        "engine_info": ev.get("engine_info"),
        "reduced": {k: v for k, v in (red or {}).items()
                    if k not in ("kernel_s", "kernel_calls")} or None,
        "kernel_s": (red or {}).get("kernel_s"),
        "kernel_calls": (red or {}).get("kernel_calls"),
        "line": line})
    if not correct:
        for w, g, x, ok in ev["checks"]:
            if not ok:
                print(f"# check failed: {w}: got {g}, want {x}",
                      file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
