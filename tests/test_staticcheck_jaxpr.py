"""Jaxpr tier of graftcheck (tools/staticcheck/jaxpr + jit/passes/lint).

Mirrors tests/test_staticcheck.py's structure, one layer up the stack:
1. known-answer fixtures (tests/staticcheck_proj/jaxpr_steps.py): one
   deliberately hazardous CAPTURED step per jaxpr rule, traced through the
   real capture machinery — each rule fires exactly where expected, the
   clean step and the pragma'd step stay quiet;
2. ratchet semantics over jaxpr findings (same baseline.json mechanics as
   the AST tier — both tiers share one ratchet);
3. the real gate: the repo's canonical steps (TrainStep on the proxy
   llama, the serving slot/verify steps, a to_static program) must lint
   CLEAN — zero unbaselined jaxpr findings on the shipped tree;
4. the CLI demonstration: `python -m tools.staticcheck --ci` exits
   nonzero on a NEW jaxpr-tier finding.
"""
import os
import runpy
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE_STEPS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "staticcheck_proj", "jaxpr_steps.py")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.staticcheck import load_baseline, new_findings, save_baseline  # noqa: E402
from tools.staticcheck.baseline import DEFAULT_BASELINE  # noqa: E402
from tools.staticcheck.jaxpr import (  # noqa: E402
    JAXPR_RULES, collect_findings)


@pytest.fixture(scope="module")
def fixture_findings():
    steps = runpy.run_path(FIXTURE_STEPS)["collect"](REPO)
    return collect_findings(REPO, steps=steps)


@pytest.fixture(scope="module")
def canonical_findings():
    # shared: tracing the canonical steps is this module's expensive call
    return collect_findings(REPO)


# ---------------- rule engine parity ----------------

def test_jaxpr_rule_ids_mirror_lint_rules():
    from paddle_tpu.jit.passes import lint
    assert JAXPR_RULES == tuple("jaxpr-" + r for r in lint.RULES)


# ---------------- known-answer fixtures ----------------

def test_every_jaxpr_rule_fires_on_fixtures(fixture_findings):
    assert {f.rule for f in fixture_findings} == set(JAXPR_RULES), \
        [f.context for f in fixture_findings]


def test_known_answer_contexts(fixture_findings):
    by_ctx = {f.context: f.rule for f in fixture_findings}
    assert by_ctx == {
        "fixture/callback:callbacks=debug_print": "jaxpr-host-callback",
        "fixture/dead_in_scan:dead=3": "jaxpr-dead-compute",
        "fixture/weak_scalar:weak_type_invars=(1,)":
            "jaxpr-recompile-hazard",
        "fixture/signature_churn:signature-churn": "jaxpr-recompile-hazard",
        "fixture/naked_collective:untagged=1":
            "jaxpr-unscheduled-collective",
        "fixture/fp32_beside_quantized:fp32_beside_quantized_axes=i":
            "jaxpr-unscheduled-collective",
        "fixture/quantized_writeback:donated_unmatched=(0,)":
            "jaxpr-donation-miss",
        "fixture/partial_donation:missed=(1,)": "jaxpr-donation-miss",
    }, by_ctx


def test_findings_anchor_at_fixture_file(fixture_findings):
    assert all(f.path == "tests/staticcheck_proj/jaxpr_steps.py"
               and f.line > 0 for f in fixture_findings), fixture_findings


def test_clean_and_pragma_steps_stay_quiet(fixture_findings):
    ctxs = {f.context for f in fixture_findings}
    assert not any(c.startswith("fixture/clean") for c in ctxs)
    # same violation as fixture/callback, allowlisted at the def line
    assert not any(c.startswith("fixture/pragma_callback") for c in ctxs)


def test_donation_regression_net_for_multichip_writeback(fixture_findings):
    """The PR-10 MULTICHIP write_back-before-rebuild donation bug: a
    donated fp32 param rebuilt at int8 leaves the donation unmatched —
    the jaxpr-donation-miss rule is the regression net that would have
    caught it at lowering time."""
    f = next(f for f in fixture_findings
             if f.context == "fixture/quantized_writeback:"
                             "donated_unmatched=(0,)")
    assert f.rule == "jaxpr-donation-miss"
    assert "deleted" in f.message and "write_back" in f.message


# ---------------- ratchet semantics (shared baseline mechanics) -------------

def test_jaxpr_findings_ride_the_ratchet(fixture_findings, tmp_path):
    bl = str(tmp_path / "bl.json")
    save_baseline(fixture_findings[:-1], bl)
    fresh = new_findings(fixture_findings, load_baseline(bl))
    assert fresh == fixture_findings[-1:]
    save_baseline(fixture_findings, bl)
    assert new_findings(fixture_findings, load_baseline(bl)) == []


def test_fast_mode_skips_the_trace(monkeypatch):
    """PT_STATICCHECK_FAST=1 is the tier-1 timing guard: the jaxpr trace
    is skipped entirely (the AST tier still runs elsewhere)."""
    monkeypatch.setenv("PT_STATICCHECK_FAST", "1")
    assert collect_findings(REPO) == []


# ---------------- in-process capture-tier integration ----------------

def test_lint_records_flow_to_profiler_summary():
    import numpy as np

    import paddle_tpu as P
    from paddle_tpu.jit import capture_step
    from paddle_tpu.jit.passes import lint

    @capture_step
    def _linted_fixture_step(x):
        import jax
        jax.debug.print("s={s}", s=x.sum()._value)
        return P.tanh(x)

    _linted_fixture_step(P.to_tensor(np.ones((4, 4), np.float32)))
    rec = lint.lint_records().get("_linted_fixture_step")
    assert rec is not None and rec["rules_hit"] == ["host-callback"], rec
    from paddle_tpu.profiler import lint_summary
    assert "_linted_fixture_step" in lint_summary()
    assert "host-callback" in lint_summary()


# ---------------- the real gate: canonical steps lint clean ----------------

def test_canonical_steps_all_capture(canonical_findings):
    # a canonical step failing capture surfaces as a capture-bailout
    # finding — assert the stronger form for a readable failure
    bails = [f for f in canonical_findings if "capture-bailout" in f.context]
    assert bails == [], [f.message for f in bails]


def test_clean_tree_zero_unbaselined_jaxpr_findings(canonical_findings):
    """The jaxpr-tier half of `python -m tools.staticcheck --ci`: the
    shipped tree's canonical steps must lint clean (nothing to baseline,
    so any finding at all is NEW and fails)."""
    fresh = new_findings(canonical_findings,
                         load_baseline(DEFAULT_BASELINE))
    assert fresh == [], "\n".join(f.format() for f in fresh)
    assert canonical_findings == [], \
        "\n".join(f.format() for f in canonical_findings)


# ---------------- the CLI gate ----------------

def test_cli_ci_exits_nonzero_on_new_jaxpr_finding(tmp_path):
    """`--ci` with the fixture steps swapped in (PT_STATICCHECK_STEPS)
    and an empty baseline: the jaxpr tier alone must fail the gate."""
    bl = str(tmp_path / "bl.json")
    save_baseline([], bl)
    env = dict(os.environ,
               PT_STATICCHECK_STEPS=FIXTURE_STEPS,
               PT_STATICCHECK_FAST="0")
    r = subprocess.run(
        [sys.executable, "-m", "tools.staticcheck", "--ci",
         "--rules", ",".join(JAXPR_RULES), "--baseline", bl],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "NEW violation" in r.stderr
    assert "jaxpr-donation-miss" in r.stdout
