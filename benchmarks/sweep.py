"""Finds the knee of an open-loop serving cell once, on the chip.

    python3 -m benchmarks.sweep --workload <cell> --rates 3,4,5,6,7 --seconds 20

One process, one engine; for each offered rate one lead-in and one window of
the cell's traffic at that rate.  A line a rate: completed output tokens/s,
the tails, and the backlog when the window closed.  The knee is the highest
rate whose backlog does not grow; the cell's traffic file then takes four
fifths of it as `rate_per_s`.  Not part of a check: the builder runs it.
"""
from __future__ import annotations

import argparse
import json
import sys

from . import arithmetic as A, harness, spec
from .run import Env


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2147483659)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("benchmarks.sweep needs a TPU")
    from paddle_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    env = Env(seed=args.seed, seconds=args.seconds, trace=False,
              out_dir="bench_out/sweep", compiles=harness.CompileCounter())
    serve = cell.module("modes", "serve")
    _, eng, _ = serve.build(cell, env)
    for rate in (float(r) for r in args.rates.split(",")):
        d = serve.drive(eng, cell, env, dict(cell.traffic, rate_per_s=rate))
        rows = [r.as_dict() for r in d["records"]]
        t0, t1 = d["t0"], d["t1"]
        ttft = A.ttft_values(rows, t0, t1)
        print(json.dumps({
            "rate_per_s": rate,
            "out_tokens_per_s": A.out_tokens_per_s(rows, t0, t1),
            "ttft_ms_p50": 1e3 * A.percentile(ttft, 50),
            "ttft_ms_slowest_fifth": 1e3 * A.slowest_fifth_mean(ttft),
            "itl_ms_p99": 1e3 * A.percentile(A.itl_gaps(rows, t0, t1), 99),
            "queued_at_close": d["info1"]["queued"],
            "active_at_close": d["info1"]["active"],
            "no_first_token": sum(1 for r in rows if t0 <= r["due"] < t1
                                  and not r["token_times"]),
            "requests": len(ttft)}), flush=True)
        eng.run()       # drain before the next rate
    return 0


if __name__ == "__main__":
    sys.exit(main())
