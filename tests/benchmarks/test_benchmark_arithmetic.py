"""The benchmark's arithmetic on inputs whose answers are known: the trace
reduction on a small recorded trace, the metrics on synthetic stamps and
records, the traffic's multisets, and BENCHMARK.json against its own rules."""
import json
import os
import re
import shutil
from collections import Counter

import numpy as np
import pytest

from benchmarks import arithmetic as A
from benchmarks import loadgen, reduce_trace as RT, spec

BENCH = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# ---- the trace reduction ------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """One training step of mistral7b-train-1chip on a TPU v5 lite, cut
    from the profiler's trace of a chip run of PR 23 by load_events()."""
    with open(os.path.join(FIXTURES, "trace_events.json")) as fh:
        return json.load(fh)


def _timeline(intervals, t0, t1, step_ns=100):
    """Brute force: a boolean a `step_ns`, true where an interval covers."""
    grid = np.zeros(int((t1 - t0) // step_ns) + 1, bool)
    for s, e in intervals:
        lo = int(max(0, (s - t0) // step_ns))
        hi = int(max(0, min(len(grid), -(-(e - t0) // step_ns))))
        grid[lo:hi] = True
    return grid


def test_recorded_trace_busy_union_and_idle_share(recorded):
    red = RT.reduce(recorded)
    t0, t1 = RT.window_of(recorded)
    assert red["devices"] == 1 and red["window_s"] == pytest.approx((t1 - t0) / 1e9)
    evs = recorded["devices"]["0"]
    grid = _timeline([(s, s + d) for _, s, d in evs if d > 0], t0, t1)
    brute_busy = grid.mean() * (t1 - t0) / 1e9
    assert red["busy_s"] == pytest.approx(brute_busy, rel=2e-3)
    assert red["idle_share"] == pytest.approx(1 - brute_busy / red["window_s"],
                                              abs=2e-3)
    assert 0.0 < red["idle_share"] < 0.5
    # a `while` spans its body: it may not be counted beside it
    assert not any(n.startswith("while") for n, _ in red["device_ops"])
    assert sum(s for _, s in red["device_ops"]) <= red["busy_s"] * 1.001


def test_recorded_trace_kernel_time_by_name(recorded):
    red = RT.reduce(recorded)
    t0, t1 = RT.window_of(recorded)
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkv", "fused_ce_fwd",
                   "fused_ce_bwd_dh", "fused_ce_bwd_dw"):
        mine = sum(min(s + d, t1) - max(s, t0)
                   for n, s, d in recorded["devices"]["0"]
                   if re.match(rf"%(\w*_)?{kernel}_*(\.\d+)? = ", n)
                   and s + d > t0 and s < t1)
        assert mine > 0, kernel
        assert RT.kernel_events(red, kernel)[0] == pytest.approx(mine / 1e9)
    # remat runs the forward kernel twice a layer: more forward calls
    assert RT.kernel_events(red, "flash_attention_fwd")[1] \
        == 2 * RT.kernel_events(red, "flash_attention_bwd_dq")[1]


def test_recorded_trace_gap_attribution(recorded):
    red = RT.reduce(recorded)
    gaps = dict(red["idle_gaps"])
    total_idle = red["window_s"] - red["busy_s"]
    assert gaps and sum(gaps.values()) <= total_idle * 1.001
    assert all(NAME.match(k) for k in gaps), list(gaps)
    assert len(red["idle_gaps"]) <= 10 and len(red["device_ops"]) <= 10


def test_exposed_collective_share_on_a_made_trace():
    """Two devices; on device 1 an all-reduce of 30 us overlaps 10 us of a
    fusion (async pair on the ops line: start marker, compute, done wait)."""
    def dev(extra):
        return [["%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8] %p)", 0, 40_000],
                *extra,
                ["%fusion.2 = bf16[8,8]{1,0} fusion(bf16[8,8] %p)", 90_000, 10_000]]
    ar = "%all-reduce-start.1 = f32[64]{0} all-reduce-start(f32[64] %g)"
    done = "%all-reduce-done.1 = f32[64]{0} all-reduce-done(f32[64] %s)"
    events = {
        "devices": {
            "0": dev([]),
            "1": dev([[ar, 40_000, 10], ["%fusion.3 = f32[8]{0} fusion(f32[8] %q)",
                                          40_010, 9_990], [done, 50_000, 20_000]])},
        "host": {"main": [[RT.WINDOW_MARK, 0, 100_000],
                          ["bench.wait_prev_step", 50_000, 35_000]]}}
    red = RT.reduce(events)
    assert red["devices"] == 2
    # exposed: the 20 us the core waits in all-reduce-done (the 10 ns start
    # marker is a collective event too, beside no compute)
    assert red["collective_exposed_share_worst"] == pytest.approx(
        (20_000 + 10) / 100_000)
    # device 0 is the idlest: 50 us of gap between its fusions, and the
    # host was waiting for the previous step through more than half of it
    assert red["busy_s_by_device"]["0"] == pytest.approx(50e-6)
    assert dict(red["idle_gaps"]) == {"bench.wait_prev_step":
                                      pytest.approx(50e-6)}
    assert red["idle_share"] == pytest.approx(1 - (50 + 80) / 2 / 100)


def test_op_names_from_hlo_text():
    assert RT.op_name("%fusion.13 = bf16[2,4096,14336]{2,1,0:T(8,128)(2,1)} "
                      "fusion(bf16[2] %a)") == "fusion-bf16_2x4096x14336"
    t = ("%flash_attention_fwd.1 = (bf16[2,8,1024,128]{3,2,1,0}, "
         "f32[2,8,1024,1]{3,2,1,0}) custom-call(bf16[2,8,1024,128] %q)")
    assert RT.base_name(t) == "flash_attention_fwd"
    assert RT.op_name(t) == "flash_attention_fwd-bf16_2x8x1024x128"
    w = "%while.2 = (u32[]{:T(128)}, bf16[4]{0}) while((u32[], bf16[4]) %t), body=%b"
    assert RT.is_container(w) and not RT.is_container(t)
    assert RT.is_collective("%all-gather-done.2 = bf16[8]{0} all-gather-done(%s)")
    assert not RT.is_collective(t)


# ---- training throughput --------------------------------------------------------

def test_whole_step_throughput_shows_a_stall_and_not_the_windows_edges():
    step, tokens = 0.310, 8192
    clean = [10.0 + i * step for i in range(100)]
    want = tokens / step
    t0, t1 = clean[0] - 0.1, clean[-1] + 0.1
    assert A.whole_step_throughput(clean, t0, t1, tokens) == pytest.approx(want)
    # a step that stalls for 0.5 s is 0.5 s of the window's time: all the
    # work over all the time falls by it, where the median gap hides it
    stalled = [s + (0.5 if i >= 40 else 0.0) for i, s in enumerate(clean)]
    got = A.whole_step_throughput(stalled, t0, t1 + 1, tokens)
    assert got == pytest.approx(99 * tokens / (99 * step + 0.5))
    assert got < 0.985 * want
    gaps = A.step_intervals(stalled, t0, t1 + 1)
    assert A.percentile(gaps, 50) == pytest.approx(step)
    # a stamp read late by the host is made up by the next one: the device
    # did not stall, and the rate does not move
    late = [s + (0.03 if i == 40 else 0.0) for i, s in enumerate(clean)]
    assert A.whole_step_throughput(late, t0, t1, tokens) == pytest.approx(want)
    # the window's edges fall anywhere inside a step: nothing moves
    for cut0, cut1 in ((0.0, 0.0), (0.05, 0.29), (0.3, 0.01), (0.155, 0.155)):
        got = A.whole_step_throughput(
            clean, clean[3] + cut0, clean[90] - cut1, tokens)
        assert got == pytest.approx(want)
    # tokens completed over the window's length, PR 22's definition, moves
    # by a step's worth with the same edges
    naive = [tokens * sum(clean[3] + a <= s <= clean[90] - b for s in clean)
             / (clean[90] - b - clean[3] - a) for a, b in ((0.0, 0.0), (0.3, 0.01))]
    assert abs(naive[0] - naive[1]) / want > 0.008
    assert A.whole_step_throughput([1.0], 0, 2, tokens) is None


def test_train_mfu_arithmetic_for_the_two_layer_mistral_cut():
    cfg = spec.load_json(os.path.join(spec.HERE, "configs", "mistral-7b-v0.3.json"))
    assert A.matmul_params(cfg, 1) - A.matmul_params(cfg, 0) == 218_103_808
    assert A.matmul_params(cfg, 0) == 4096 * 32768          # the head alone
    fpt = A.train_flops_per_token(cfg, 2, 4096)
    assert fpt == pytest.approx(3.62e9, rel=2e-3)
    # PR 22's check: 26,458.6 tokens/s on one v5e chip was 48.67% (ledger)
    assert A.mfu_percent(26458.6, fpt, 1, 197e12) == pytest.approx(48.67, abs=0.02)


# ---- serving latencies -----------------------------------------------------------

def _req(due, times, failed=False):
    return {"due": due, "sent": due + 0.001, "token_times": times,
            "failed": failed, "prompt_len": 10}


def test_ttft_from_due_time_itl_gaps_and_the_slowest_fifth():
    rows = [_req(1.0, [1.2, 1.3, 1.45]), _req(2.0, [2.5, 2.6]),
            _req(3.0, []),                       # no first token: the window
            _req(4.0, [4.1], failed=True),       # failed: the window
            _req(0.5, [0.9, 1.1]),               # due before the window
            _req(9.5, [10.4, 10.6])]             # first token after the window
    t0, t1 = 1.0, 10.0
    ttft = A.ttft_values(rows, t0, t1)
    assert ttft == pytest.approx([0.2, 0.5, 9.0, 9.0, 0.9])
    assert A.slowest_fifth_mean(ttft) == pytest.approx(9.0)
    assert A.slowest_fifth_mean(list(range(1, 11))) == pytest.approx(9.5)
    assert A.slowest_fifth_mean([]) is None
    gaps = A.itl_gaps(rows, t0, t1)
    assert sorted(gaps) == pytest.approx(sorted([0.1, 0.15, 0.1, 0.2]))
    assert A.tokens_in_window(rows, t0, t1) == 7
    assert A.out_tokens_per_s(rows, t0, t1) == pytest.approx(7 / 9.0)
    assert loadgen.lag_ms_p99(rows) == pytest.approx(1.0)


def test_percentile_and_spread():
    assert A.percentile([1, 2, 3, 4, 5], 50) == 3
    assert A.percentile([1, 2, 3, 4], 99) == pytest.approx(3.97)
    assert A.percentile([], 50) is None
    assert A.spread([100, 101, 99, 100, 102, 98]) == pytest.approx(0.025)


# ---- traffic ---------------------------------------------------------------------

@pytest.mark.parametrize("traffic", ["chat", "decode-heavy"])
def test_every_seed_runs_the_same_schedule_with_other_tokens(traffic):
    tr = spec.load_json(os.path.join(spec.HERE, "traffic", traffic + ".json"))
    if tr["mode"] == "serve-open":
        a = loadgen.open_schedule(tr, 30.0, 1, 1000)
        b = loadgen.open_schedule(tr, 30.0, 2**31 + 11, 1000)
        shape = lambda s: [(r[0], len(r[1]), r[2]) for r in s]
        assert shape(a) == shape(b)                       # dues and lengths
        assert any((x[1] != y[1]).any() for x, y in zip(a, b))     # tokens
        win = [r for r in a if r[0] >= 0]
        assert len(win) == round(tr["rate_per_s"] * 30.0)
        assert all(-tr["lead_in_s"] <= r[0] < 30.0 for r in a)
        # the window's lengths and gaps are the stratified multisets
        assert Counter(len(r[1]) for r in win) == Counter(
            loadgen.stratified(tr["prompt"], len(win)).tolist())
        assert Counter(r[2] for r in win) == Counter(
            loadgen.stratified(tr["answer"], len(win)).tolist())
        gaps = np.diff([r[0] for r in win] + [30.0])
        assert np.sort(gaps) == pytest.approx(np.sort(
            loadgen.stratified_gaps(tr["rate_per_s"], len(win))))
        # another schedule_seed is another order of the same multisets
        c = loadgen.open_schedule(dict(tr, schedule_seed=7), 30.0, 1, 1000)
        assert shape(c) != shape(a)
        assert Counter(len(r[1]) for r in c) == Counter(len(r[1]) for r in a)
    else:
        (f1, p1), (f2, p2) = (loadgen.closed_pool(tr, s, 1000)
                              for s in (1, 2**31 + 11))
        shape = lambda rows: [(len(p), a) for p, a in rows]
        assert shape(f1) == shape(f2) and shape(p1) == shape(p2)
        assert any((x[0] != y[0]).any() for x, y in zip(p1, p2))
        assert len(f1) == tr["clients"] and len(p1) == tr["pool"]
        assert Counter(a for _, a in p1) == Counter(
            loadgen.stratified(tr["answer"], tr["pool"]).tolist())


def test_stratified_is_the_distribution_without_sampling_noise():
    u = loadgen.stratified({"dist": "uniform", "min": 64, "max": 256}, 193)
    assert u.min() >= 64 and u.max() <= 256 and abs(u.mean() - 160) < 1
    g = loadgen.stratified_gaps(4.0, 120)
    assert g.sum() == pytest.approx(30.0) and g.min() > 0
    assert np.std(g) / np.mean(g) == pytest.approx(1.0, abs=0.1)   # exponential


# ---- BENCHMARK.json against its own rules ------------------------------------------

def test_benchmark_json_names_units_and_moves():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    reports = lambda m: set(m.get("workloads", cells))
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e, m
        assert reports(m) <= reports(e2e[m["moves"]]), \
            f"{m['name']} moves {m['moves']}, which not every cell of it reports"
        lm = spec.load_json(os.path.join(spec.HERE, "layer_metrics",
                                         m["name"] + ".json"))
        assert (lm["layer"], lm["unit"], lm["moves"], lm["source"]) == \
            (m["layer"], m["unit"], m["moves"], m["source"])
        assert os.path.exists(os.path.join(spec.HERE, "readers",
                                           lm["reader"] + ".py"))
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        cell = spec.load_cell(w["name"])
        assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 4)
    for c in BENCH["configs"]:
        cfg = spec.load_json(os.path.join(spec.ROOT, c["file"]))
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in c["reduced"])
    for root, _, files in os.walk(spec.HERE):
        if "__pycache__" in root:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(root, f)


# ---- additions are new files only ----------------------------------------------------

def test_a_cell_is_added_with_new_files_and_no_edit(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a kernel's
    roofline, each a new file in a directory of their own, found by the
    names a BENCHMARK.json gives: what a later PR does."""
    d = tmp_path / "ext"
    for sub in ("configs", "traffic", "layer_metrics", "readers", "kernels"):
        (d / sub).mkdir(parents=True)
    shutil.copy(os.path.join(spec.HERE, "configs", "internlm2-1.8b.json"),
                d / "configs" / "other-model.json")
    tr = spec.load_json(os.path.join(spec.HERE, "traffic", "chat.json"))
    tr["rate_per_s"] = 1.0
    (d / "traffic" / "slow-chat.json").write_text(json.dumps(tr))
    (d / "layer_metrics" / "answer_tokens_mean.json").write_text(json.dumps(
        {"layer": "traffic generator", "unit": "tokens", "better": "higher",
         "source": "program_counter", "moves": "serve_out_tokens_per_s",
         "reader": "answer_tokens", "params": {}}))
    (d / "readers" / "answer_tokens.py").write_text(
        "def read(ev, **_):\n"
        "    rows = ev['requests']\n"
        "    return sum(len(r['token_times']) for r in rows) / len(rows)\n")
    (d / "layer_metrics" / "new_kernel_roofline.json").write_text(json.dumps(
        {"layer": "Pallas kernels", "unit": "%", "better": "higher",
         "source": "device_trace", "moves": "itl_ms_p99",
         "reader": "kernel_roofline", "params": {"kernel": "new_kernel"}}))
    (d / "kernels" / "new_kernel.py").write_text(
        "def work(ev, calls):\n"
        "    return {'new_kernel': (197e12 * 0.5, 1.0)}\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "other-model", "source": "x",
                             "file": str(d / "configs" / "other-model.json"),
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "other-slow-chat", "config": "other-model",
                               "traffic": "slow-chat", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("serve_out_tokens_per_s", "itl_ms_p99"):
            m["workloads"].append("other-slow-chat")
    for name, unit, src, moves in (
            ("answer_tokens_mean", "tokens", "program_counter",
             "serve_out_tokens_per_s"),
            ("new_kernel_roofline", "%", "device_trace", "itl_ms_p99")):
        bench["per_layer"].append(
            {"name": name, "unit": unit, "better": "higher", "source": src,
             "layer": "x", "moves": moves, "workloads": ["other-slow-chat"]})
    bj = tmp_path / "BENCHMARK.json"
    bj.write_text(json.dumps(bench))

    from benchmarks import run
    cell = spec.load_cell("other-slow-chat", benchmark_json=str(bj),
                          data_dir=str(d))
    assert cell.traffic["rate_per_s"] == 1.0 and cell.config["hidden_size"] == 2048
    assert {m["name"] for m in cell.per_layer} == {
        "answer_tokens_mean", "new_kernel_roofline"}
    ev = {"cell": cell, "t0": 0.0, "t1": 10.0, "peaks": spec.peaks_for("TPU v5e"),
          "requests": [_req(1.0, [1.5, 1.6, 1.7]), _req(2.0, [2.5])],
          "reduced": {"kernel_s": {"new_kernel": 1.0},
                      "kernel_calls": {"new_kernel": 3},
                      "devices": 1}}
    values, details = run.per_layer(cell, ev)
    assert values == {"answer_tokens_mean": 2.0,
                      "new_kernel_roofline": pytest.approx(50.0)}
    assert details["new_kernel_roofline"]["new_kernel"]["bound"] == "compute"
    # a reader with nothing to read leaves its metric out
    ev["reduced"] = None
    assert set(run.per_layer(cell, ev)[0]) == {"answer_tokens_mean"}
