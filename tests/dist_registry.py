"""Declarative multi-process test registry (VERDICT r4 item 5).

The reference registers distributed tests as DATA
(/root/reference/test/collective/testslist.csv:1-5: name / launcher /
num_port / ENVS rows feeding generated ctest entries).  This module is the
analog: one `DistTest` row per multi-process test — name, worker payload,
nprocs, devices per process, timeout, env, launcher flags — and one shared
runner that writes the worker script (with the CPU-platform prelude), drives
`python -m paddle_tpu.distributed.launch`, gathers per-rank JSON results and
per-rank logs.  Adding a new distributed test is ONE row here plus a payload
file in tests/dist_workers/.

Payload contract: the worker reads `sys.argv[1]` as its scratch/output
directory (extra args follow) and writes `res{rank}.json` there; ranks come
from PADDLE_TRAINER_ID.  Device count per process arrives via
PT_DIST_DEVICES (consumed by the prelude, never hand-rolled per worker).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from dataclasses import dataclass, field

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKERS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "dist_workers")

# every jax-using worker pins the CPU platform the same way, before any
# backend query: a test worker must never take the chip
PRELUDE = """\
import os as _os
_os.environ["JAX_PLATFORMS"] = "cpu"
_ndev = int(_os.environ.get("PT_DIST_DEVICES", "1"))
_flags = _os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    _os.environ["XLA_FLAGS"] = (
        _flags + f" --xla_force_host_platform_device_count={_ndev}")
import jax as _jax
_jax.config.update("jax_platforms", "cpu")
"""


@dataclass(frozen=True)
class DistTest:
    name: str
    worker: str                      # file under tests/dist_workers/
    nprocs: int = 2
    devices_per_proc: int = 1
    timeout: int = 300
    env: dict = field(default_factory=dict)
    launch_extra: tuple = ()         # extra launcher flags (--max_restart=N)
    prelude: bool = True             # prepend the CPU-platform prelude
    launcher: str = "launch"         # "launch" | "popen" (custom orchestration)
    expect_rc: int | None = 0        # None: caller checks rc itself


REGISTRY = {t.name: t for t in [
    # name                worker              np dev timeout  extras
    DistTest("hybrid_2proc", "hybrid.py", nprocs=2, devices_per_proc=4,
             timeout=900),
    DistTest("hybrid_ref", "hybrid.py", nprocs=1, devices_per_proc=8,
             timeout=600),
    DistTest("controller_collectives", "controller.py", nprocs=2,
             timeout=300),
    DistTest("elastic_train_killrank", "elastic_train.py", nprocs=2,
             timeout=420, launch_extra=("--max_restart=3",)),
    DistTest("elastic_member", "elastic_member.py", nprocs=1,
             prelude=False, launcher="popen"),
    DistTest("launch_env", "launch_env.py", nprocs=3, prelude=False,
             timeout=120),
    DistTest("launch_flaky", "launch_flaky.py", nprocs=1, prelude=False,
             timeout=120, launch_extra=("--max_restart=2",)),
    DistTest("launch_exit3", "launch_exit3.py", nprocs=1, prelude=False,
             timeout=120, launch_extra=("--max_restart=1",), expect_rc=3),
]}


def _materialize(dt: DistTest, tmp_path) -> str:
    src = open(os.path.join(WORKERS, dt.worker)).read()
    if dt.prelude:
        src = PRELUDE + src
    script = os.path.join(str(tmp_path), f"{dt.name}_worker.py")
    with open(script, "w") as f:
        f.write(src)
    return script


def _env(dt: DistTest) -> dict:
    env = dict(os.environ,
               PYTHONPATH=REPO + ":" + os.environ.get("PYTHONPATH", ""),
               PT_DIST_DEVICES=str(dt.devices_per_proc))
    env.pop("XLA_FLAGS", None)  # the prelude sets its own device count
    env.update(dt.env)
    return env


def collect_logs(tmp_path) -> str:
    logs = ""
    logdir = os.path.join(str(tmp_path), "log")
    if os.path.isdir(logdir):
        for p in sorted(os.listdir(logdir)):
            with open(os.path.join(logdir, p)) as f:
                logs += f"\n--- {p} ---\n" + f.read()[-3000:]
    return logs


def collect_results(dt: DistTest, tmp_path, prefix="res") -> dict:
    out = {}
    for rank in range(dt.nprocs):
        path = os.path.join(str(tmp_path), f"{prefix}{rank}.json")
        if os.path.exists(path):
            with open(path) as f:
                out[rank] = json.load(f)
    return out


def run_dist(name: str, tmp_path, args=()):
    """Run one registered distributed test to completion.

    Returns (CompletedProcess, {rank: result_json}, logs).  Asserts the
    launcher exit code when the row declares expect_rc."""
    dt = REGISTRY[name]
    assert dt.launcher == "launch", f"{name} is popen-orchestrated"
    script = _materialize(dt, tmp_path)
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           f"--nproc_per_node={dt.nprocs}",
           f"--log_dir={os.path.join(str(tmp_path), 'log')}",
           *dt.launch_extra, script, str(tmp_path), *map(str, args)]
    r = subprocess.run(cmd, cwd=REPO, env=_env(dt), capture_output=True,
                       text=True, timeout=dt.timeout)
    logs = collect_logs(tmp_path)
    if dt.expect_rc is not None:
        assert r.returncode == dt.expect_rc, (
            f"{name}: launcher rc={r.returncode} (want {dt.expect_rc})\n"
            f"{r.stderr[-2500:]}\n{logs}")
    return r, collect_results(dt, tmp_path), logs


def start_dist(name: str, tmp_path, args=(), rank: int = 0, **popen_kw):
    """Start one rank of a popen-orchestrated registered test and return the
    Popen handle (fault-injection tests drive kills/joins themselves)."""
    dt = REGISTRY[name]
    script = _materialize(dt, tmp_path)
    env = _env(dt)
    env.setdefault("PADDLE_TRAINER_ID", str(rank))
    return subprocess.Popen(
        [sys.executable, script, str(tmp_path), *map(str, args)],
        cwd=REPO, env=env, text=True, **popen_kw)
