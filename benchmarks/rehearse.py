"""The same code path as benchmarks.run at the `tiny` sizes, on the CPU.

    python3 -m benchmarks.rehearse --workload <name> [--seconds 2] [--trace 1]

Pallas kernels run interpreted, a four-chip cell on virtual CPU devices.  It
proves paths, arguments and the shape of the last line; it prints
`"platform": "cpu"` and gives no time, rate or share a value, because a CPU
run says nothing about the device.  Counts (lowerings, occupancy) are real.
"""
from __future__ import annotations

import os
import sys


def main(argv=None) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
    from . import run
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--seconds" not in argv:
        argv += ["--seconds", "2"]
    return run.main(argv, rehearsal=True)


if __name__ == "__main__":
    sys.exit(main())
