"""What ISSUE 32 added to the benchmark, without the chip: the
configuration's file against the catalog row, the work the three kernels'
rooflines count on hand-counted cases, the reader of the experts' imbalance,
the seeded weights, and the mode module that runs serve.py over another
family's builder and reference and adds its checks of the ring and of every
layer."""
import json
import types

import jax
import numpy as np
import pytest

from benchmarks import model_mimo, spec
from benchmarks.kernels import (
    grouped_expert_matmul, mimo_decode_attention, window_flash_attention)
from benchmarks.readers import expert_tokens_max_over_mean

CELL = "mimo-v2-flash-serve-mixed"
# the row of the model-configs guide's catalog this configuration was drawn
# from (`config`), as ISSUE 32 copied it
CATALOG = {
    "attention_value_scale": 0.707, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 16384, "max_position_embeddings": 262144,
    "model_type": "mimo_v2_flash", "num_attention_heads": 64, "head_dim": 192,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "layernorm_epsilon": 1e-05, "rope_theta": 5000000,
    "tie_word_embeddings": False, "vocab_size": 152576,
    "partial_rotary_factor": 0.334, "sliding_window": 128,
    "swa_rope_theta": 10000, "attention_bias": False, "v_head_dim": 128,
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "sliding_window_size": 128,
    "attention_chunk_size": 128, "moe_intermediate_size": 2048,
    "n_routed_experts": 256, "n_shared_experts": None,
    "num_experts_per_tok": 8, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "routed_scaling_factor": None,
    "swa_num_attention_heads": 64, "swa_num_key_value_heads": 8,
    "swa_head_dim": 192, "swa_v_head_dim": 128}
REDUCED = ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
           "n_routed_experts", "vocab_size"]


def _span(name, ts, dur, **args):
    return {"name": name, "ts": ts, "dur": dur, "args": args}


def test_the_configuration_holds_the_catalog_row_and_cuts_no_width():
    cfg = spec.load_json(spec.os.path.join(
        spec.HERE, "configs", "mimo-v2-flash.json"))
    bench = spec.load_json(spec.os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "mimo-v2-flash")
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    assert entry["source"] == cfg["source"]
    differs = sorted(k for k, v in CATALOG.items() if cfg.get(k) != v)
    assert differs == sorted(set(REDUCED) - {"hybrid_layer_pattern",
                                             "moe_layer_freq"})
    assert cfg["num_hidden_layers"] == {"published": 48, "serve": 7,
                                        "train": "not run"}
    assert (cfg["n_routed_experts"], cfg["n_routed_experts_published"],
            cfg["held_experts"]) == (16, 256, [0, 16])
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"] == 152576
    # one regular period after the leading dense layer: 5 window : 1 full
    assert cfg["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0]
    assert cfg["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    mcfg = model_mimo.mimo_config(cfg, 7)
    assert mcfg.n_routed_experts == 256 and mcfg.held_experts == (0, 16)
    assert mcfg.rotary_dim == 64
    for key in ("assumed", "departures", "deployment", "cut", "tiny"):
        assert cfg[key]
    cell = spec.load_cell(CELL)
    assert cell.depth() == 7 and cell.chips == 1
    tr = cell.traffic
    assert (tr["slots"], tr["positions"], tr["clients"]) == (128, 8192, 128)
    assert tr["prompt"]["max"] + tr["answer"]["max"] == tr["positions"]
    assert tr["prefill_buckets"][-1] == tr["prompt"]["max"]


def test_the_weights_as_sized_fill_the_chip_as_issue_32_counted():
    """Parameters and cache from the shapes alone (nothing is allocated):
    3.43 B parameters, 6.86 GB; the two kinds of cache at 128 x 8192."""
    cfg = spec.load_cell(CELL).config
    h, hd, vd, heads = 4096, 192, 128, 64
    attn = lambda kv: h * heads * hd + h * kv * (hd + vd) + heads * vd * h
    expert_layer = 16 * 3 * h * 2048 + h * 256
    params = (attn(4) + 3 * h * 16384            # layer 0: full, dense
              + 5 * (attn(8) + expert_layer) + attn(4) + expert_layer
              + 2 * cfg["vocab_size"] * h)
    assert round(params / 1e9, 2) == 3.43
    full = 2 * 4 * (256 + vd) * 2                 # K allocated as 256 lanes
    ring = 5 * 128 * 8 * (256 + vd) * 2
    assert (full, ring) == (6144, 3932160)
    assert round((128 * 8192 * full + 128 * ring) / 1e9, 2) == 6.95


def test_grouped_matmul_work_follows_the_local_assignments():
    flops, rows = grouped_expert_matmul.per_assignment(4096, 2048)
    assert flops == 6 * 4096 * 2048
    assert rows == 2 * (4096 + 4096 + 2048 + 4096)   # x, gate|up, act, out
    assert grouped_expert_matmul.expert_bytes(4096, 2048) == 50331648
    assert [grouped_expert_matmul.chunks_of(b)
            for b in (256, 1024, 2048, 6144)] == [1, 1, 2, 6]
    cell = spec.load_cell(CELL)
    trace = types.SimpleNamespace(t_start=12.0, t_stop=14.0)
    spans = [_span("engine.decode_step", 10.5e9, 0.02e9),      # window only
             _span("engine.decode_step", 12.5e9, 0.02e9),      # traced
             _span("engine.prefill", 13.0e9, 0.05e9, bucket=2048),
             _span("engine.prefill", 13.98e9, 0.04e9, bucket=256)]  # half in
    info = {"before": {"moe_assignments": 0, "moe_assignments_local": 0,
                       "moe_experts_hit": 0},
            "after": {"moe_assignments": 16000, "moe_assignments_local": 1000,
                      "moe_experts_hit": 6 * 4.5 * 12}}
    ev = {"cell": cell, "trace": trace, "clock_skew_ns": 0, "t0": 10.0,
          "t1": 14.0, "spans": spans, "engine_info": info}
    got = grouped_expert_matmul.work(
        ev, lambda name: 2 * 6 * 3.5)["grouped_expert_matmul"]
    positions = 128 + 2048 + 0.5 * 256
    assignments = 6 * positions * 8 / 16
    assert got[0] == pytest.approx(assignments * flops)
    # the window holds 6 x (1 + 1 + 2 + 0.5) chunks with 12 experts hit each
    assert got[1] == pytest.approx(6 * 3.5 * 12 * 50331648
                                   + assignments * rows)
    # a program without the counters: nothing to read
    assert grouped_expert_matmul.work(
        dict(ev, engine_info={"before": {}, "after": {"prefills": 1}}),
        lambda name: 0) == {}


def test_decode_attention_work_counts_live_lanes_of_both_kinds_of_cache():
    cell = spec.load_cell(CELL)
    trace = types.SimpleNamespace(t_start=0.0, t_stop=3.0)
    ev = {"cell": cell, "trace": trace, "requests": [
        {"prompt_len": 100, "token_times": [0.5, 1.0, 2.0, 4.0]},
        {"prompt_len": 1000, "token_times": [2.5, 2.9]}]}
    flops, nbytes = mimo_decode_attention.work(ev, None)[
        "ragged_decode_attention"]
    full, ring = 101 + 102 + 1001, 101 + 102 + 128
    assert nbytes == full * 2560 * 2 + ring * 5120 * 5
    assert flops == 2 * 64 * 320 * (full * 2 + ring * 5)


def test_flash_work_counts_the_band_where_a_layer_has_a_window():
    assert window_flash_attention.pairs(4) == 10
    assert window_flash_attention.pairs(6, 3) == 1 + 2 + 3 + 3 * 3
    assert window_flash_attention.pairs(100, 128) == 5050
    f, b = window_flash_attention.per_call(1024, 64, 8, 192, 128, 128)
    assert f == 2 * 64 * 320 * (128 * 129 // 2 + 896 * 128)
    assert b == 2 * 1024 * (64 * 320 + 8 * 320)
    cell = spec.load_cell(CELL)
    trace = types.SimpleNamespace(t_start=100.0, t_stop=103.0)
    ev = {"cell": cell, "trace": trace, "clock_skew_ns": 0, "spans": [
        _span("engine.prefill", 99.0e9, 0.5e9, bucket=512),     # before
        _span("engine.prefill", 101.0e9, 0.04e9, bucket=1024)]}
    got = window_flash_attention.work(ev, None)["flash_attention_fwd"]
    causal = window_flash_attention.per_call(1024, 64, 4, 192, 128)
    assert got[0] == pytest.approx(2 * causal[0] + 5 * f)
    assert got[1] == pytest.approx(2 * causal[1] + 5 * b)


def test_imbalance_reader_over_the_window_and_on_a_program_without_it():
    info = {"before": {"moe_expert_tokens": [10, 10, 10, 10],
                       "moe_assignments": 100, "moe_assignments_local": 40},
            "after": {"moe_expert_tokens": [40, 20, 30, 10],
                      "moe_assignments": 400, "moe_assignments_local": 100}}
    got = expert_tokens_max_over_mean.read({"engine_info": info})
    assert got["value"] == pytest.approx(30 / 15)
    assert got["detail"] == {"tokens": [30, 10, 20, 0], "local_share": 0.2}
    assert expert_tokens_max_over_mean.read(
        {"engine_info": {"before": {}, "after": {"prefills": 3}}}) is None
    assert expert_tokens_max_over_mean.read({}) is None


def test_seeded_weights_keep_the_router_and_the_sinks_float32():
    import jax.numpy as jnp
    cfg = spec.load_cell(CELL, tiny=True).config
    model = model_mimo.build_model(cfg, 3, 2147483659, jnp.bfloat16)
    p = {n: v._value for n, v in model.named_parameters()}
    layer = "model.layers.1."
    assert p[layer + "mlp.router_weight"].dtype == jnp.float32
    assert p[layer + "mlp.router_bias"].dtype == jnp.float32
    assert p[layer + "self_attn.sink"].dtype == jnp.float32
    assert p[layer + "mlp.gate_up_proj"].dtype == jnp.bfloat16
    assert p[layer + "mlp.gate_up_proj"].shape == (4, 64, 64)
    assert p[layer + "mlp.router_weight"].shape == (64, 16)    # all experts
    assert layer + "self_attn.sink" in p and \
        "model.layers.0.self_attn.sink" not in p               # full: no sink
    sink = np.asarray(p[layer + "self_attn.sink"])
    assert 1.0 < sink.mean() < 5.0 and sink.std() > 0.2        # round 3
    bias = np.asarray(p[layer + "mlp.router_bias"])
    assert 0.005 < bias.std() < 0.05
    assert (np.asarray(p["model.norm.weight"], np.float32) == 1).all()
    # layer i's draws do not depend on the depth built
    two = model_mimo.build_model(cfg, 2, 2147483659, jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(p[layer + "self_attn.sink"]),
        np.asarray(dict(two.named_parameters())[
            layer + "self_attn.sink"]._value))
    with pytest.raises(RuntimeError, match="no rule"):
        model_mimo._draw("mlp.unknown", (4,), jax.random.key(0))


def _layer_readings(tweak=None):
    """`modes/mimo.py layer_checks` at the rehearsal's sizes over a model
    built as the cell builds it; `tweak(model)` alters the PROGRAM's side."""
    import jax.numpy as jnp
    from benchmarks import reference_mimo
    from benchmarks.modes import mimo as mode
    cell = spec.load_cell(CELL, tiny=True)
    cfg = cell.config
    ids = np.random.default_rng(3).integers(0, cfg["vocab_size"], 24)
    model = model_mimo.build_model(cfg, cell.depth(), 7, jnp.bfloat16)
    model.eval()
    weights = model_mimo.weights_of(model)
    if tweak is not None:
        tweak(model)
    layers_of, experts_on = reference_mimo.make_layer_reference(cfg)
    return cell.traffic, mode.layer_checks(
        model, cfg, layers_of, experts_on, weights, ids, 24)


def _passes(tr, r) -> bool:
    return max(r["attn_prefill"], r["attn_decode"]) <= tr["attn_tol"] and (
        "expert" not in r or (r["selected_alike"] == r["decided"] > 12
                              and r["expert"] <= tr["expert_tol"]))


def test_the_layer_checks_pass_on_every_layer():
    """The rehearsal's stack: full + dense, window, window, full, window,
    the last four with experts; every layer passes the traffic file's
    limits."""
    tr, got = _layer_readings()
    assert [(r["layer"], r["window"], "expert" in r) for r in got] == [
        (0, False, False), (1, True, True), (2, True, True),
        (3, False, True), (4, True, True)]
    assert all(_passes(tr, r) for r in got)


@pytest.mark.parametrize("layer,attention,tweak", [
    (2, True, lambda l: setattr(l.self_attn, "use_sink", False)),
    (4, True, lambda l: setattr(l.self_attn, "window", 7)),
    (3, True, lambda l: setattr(l.self_attn, "value_scale", 1.0)),
    (3, False, lambda l: l.mlp.options.update(router_dtype="bfloat16")),
], ids=["sink_left_out", "window_one_short", "full_layer_v_scale",
        "later_router_bf16"])
def test_the_layer_checks_see_a_variant_in_any_layer(layer, attention,
                                                     tweak):
    """Each variant ISSUE 32 names, put into a LATER layer than the first of
    its kind (and one into a full layer), fails that layer's check and no
    other's: every layer of the served model is compared, each on the
    reference's own hidden states."""
    tr, got = _layer_readings(lambda m: tweak(m.model.layers[layer]))
    assert [r["layer"] for r in got if not _passes(tr, r)] == [layer]
    r = got[layer]
    if attention:
        assert min(r["attn_prefill"], r["attn_decode"]) > 2 * tr["attn_tol"]
    else:
        assert r["selected_alike"] < r["decided"]


def test_the_ring_check_reads_a_ring_and_a_ring_one_row_off():
    """`ring_check` on rows laid out as the engine holds them, made from the
    reference's own keys and values: exact as they should lie, far off when
    every row lies one position on."""
    import jax.numpy as jnp
    from benchmarks import reference_mimo
    from benchmarks.modes import mimo as mode
    cell = spec.load_cell(CELL, tiny=True)
    cfg = cell.config
    model = model_mimo.build_model(cfg, 2, 11, jnp.float32)
    weights = model_mimo.weights_of(model)
    layers_of, _ = reference_mimo.make_layer_reference(cfg)
    w, n = cfg["sliding_window"], 21
    ids = np.random.default_rng(5).integers(0, cfg["vocab_size"], n)
    ref = layers_of(weights, 2, jnp.asarray(ids))[1]

    def ring(a, lanes, shift):
        rows = np.zeros((w,) + a.shape[1:-1] + (lanes,), np.float32)
        for p in range(n - w, n):
            rows[(p + shift) % w, :, :a.shape[-1]] = a[p]
        return rows.reshape(w * a.shape[1], lanes)

    for shift, good in ((0, True), (1, False)):
        got = mode.ring_check(cfg, layers_of, weights, ids,
                              ring(np.asarray(ref["k"]), 128, shift),
                              ring(np.asarray(ref["v"]), 128, shift))
        assert (got["ring"] < 1e-6) == good
        assert (got["ring_rolled"] < 1e-6) == (not good)
        assert max(got.values()) > 0.5


def test_the_mode_keeps_serve_py_untouched_and_its_limits_between_readings():
    from benchmarks import model as bmodel, reference
    from benchmarks.modes import mimo as mode, serve
    info = {k: 0 for k in ("prefills", "decode_steps", "tokens_generated",
                           "avg_occupancy", "submitted", "admitted",
                           "finished", "timed_out", "evicted", "rejected",
                           "queued", "active") + mode.KEPT}
    assert set(mode._slim(dict(info, other=1))) == set(info)
    assert serve.bmodel is bmodel and serve.reference is reference
    assert serve._slim is mode._serve_slim and serve.MARGIN == 0.01
    # the cell's limits against their readings on the chip (PERF.md, PR 32):
    # the largest this program read, and the smallest a variant read
    assert 1.5 * 0.0056 < mode.ATTN_TOL < 0.0206 / 1.5
    assert 2 * 0.0141 < mode.MARGIN < 0.084 / 2
    assert 2 * 0.0040 < mode.EXPERT_TOL < 0.21
    assert 2 * 0.0078 < mode.RING_TOL < 0.131 / 2
    bench = json.load(open(spec.os.path.join(spec.ROOT, "BENCHMARK.json")))
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == ["grouped_expert_matmul_roofline",
                    "mimo_decode_attention_roofline",
                    "window_flash_attention_roofline",
                    "expert_tokens_max_over_mean.serve",
                    "lowerings_in_window.mimo"]
    roof = next(m for m in bench["per_layer"]
                if m["name"] == "ragged_decode_attention_roofline")
    assert CELL not in roof["workloads"]     # its work assumes one D a layer
