"""Smoke-level guard for the whole-step capture microbenchmark.

bench_step must stay CPU-runnable and keep its one-JSON-line contract (it
is the capture-tier perf trajectory when the TPU probe reports
tpu-unavailable). A tiny-iteration run lives in tier-1; the acceptance
ratios themselves (captured >= 2x per-op, within 1.10x of hand-written
jit) are asserted only in the slow battery — tiny iteration counts on a
loaded single-core CI box make ratios noisy.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(iters: int):
    env = dict(os.environ, PT_STEP_BENCH_ITERS=str(iters),
               PT_STEP_BENCH_WARMUP="3")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench_step.py")],
                       capture_output=True, text=True, timeout=600, env=env,
                       cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 1, r.stdout  # exactly ONE JSON line on stdout
    return json.loads(lines[0]), r.stderr


@pytest.mark.skipif(os.environ.get("PT_TIGHT_BUDGET") == "1",
                    reason="wall-clock budget is tight; perf smoke skipped")
def test_bench_step_smoke_json_contract():
    payload, stderr = _run_bench(iters=5)
    assert payload["metric"] == "step_capture_speedup_vs_perop"
    assert payload["unit"] == "x"
    assert payload["value"] > 0 and payload["captured_vs_handjit"] > 0
    for k in ("per_op_steps_per_sec", "captured_steps_per_sec",
              "hand_jit_steps_per_sec"):
        assert payload[k] > 0
    assert "artifact ->" in stderr
    art = stderr.split("artifact ->", 1)[1].strip().splitlines()[0]
    with open(art) as f:
        self_json = json.load(f)
    tiers = self_json["detail"]["tiers"]
    assert set(tiers) == {"per_op", "captured", "captured_traced",
                          "hand_jit"}
    # the observability cost gate (smoke ceiling; the documented 1.25x
    # floor is pinned in the slow battery — tiny iteration counts on the
    # shared box make ratios noisy)
    assert 0 < payload["trace_overhead"] <= 1.5, payload
    assert tiers["captured_traced"]["iters_per_sec"] > 0
    # the captured tier really captured: one lowering, served hits, and the
    # pass pipeline + donation inference ran on the llama-proxy step
    cap = tiers["captured"]
    assert cap["step_info"]["lowerings"] == 1, cap["step_info"]
    assert cap["step_info"]["hits"] >= 4, cap["step_info"]
    assert cap["step_info"]["bailouts"] == 0, cap["step_info"]
    assert cap["pass_report"] is not None
    assert cap["donated"], cap  # params inferred donatable
    # per-op leg really rode the compiled-op cache
    assert tiers["per_op"]["cache_info"]["hits"] > 0
    # the three tiers agree on the training trajectory
    losses = [tiers[t]["final_loss"]
              for t in ("per_op", "captured", "hand_jit")]
    assert max(losses) - min(losses) < 5e-2, losses
    os.unlink(art)  # tiny-iteration artifacts are not trajectory evidence


@pytest.mark.slow
def test_bench_step_meets_acceptance_floor():
    payload, _ = _run_bench(iters=60)
    assert payload["value"] >= 2.0, payload
    assert payload["captured_vs_handjit"] <= 1.10, payload
    # tracing the captured step costs one span per call — the documented
    # observability ceiling
    assert payload["trace_overhead"] <= 1.25, payload
