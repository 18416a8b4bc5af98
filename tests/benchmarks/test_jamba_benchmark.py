"""What ISSUE 27 added to the benchmark, without the chip: the reader of the
prefill padding share over both of its sources, the work the two new
kernels' rooflines count, the seeded weights' ranges, and the mode module
that runs serve.py over another family's builder and reference."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import model_jamba, spec
from benchmarks.kernels import mqa_decode_attention, selective_scan
from benchmarks.readers import prefill_padding_share

CELL = "jamba2-3b-serve-reasoning"


def _prefill_span(ts, dur, bucket, prompt_len, pad=True):
    args = {"rid": 1, "bucket": bucket, "prompt_len": prompt_len}
    if pad:
        args["pad"] = bucket - prompt_len
    return {"name": "engine.prefill", "ts": ts, "dur": dur, "args": args}


def test_padding_share_from_the_counters():
    ev = {"engine_info": {
        "before": {"prefill_positions": 100, "prefill_positions_padded": 128},
        "after": {"prefill_positions": 400, "prefill_positions_padded": 528}}}
    got = prefill_padding_share.read(ev)
    assert got["value"] == pytest.approx(25.0)
    assert got["detail"] == {"positions": 300, "with_padding": 400}


@pytest.mark.parametrize("pad,want", [(True, 25.0), (False, None)],
                         ids=["spans_with_pad", "parent_has_neither"])
def test_padding_share_from_the_spans_where_the_mode_slims_the_info(pad, want):
    """serve.py keeps none of the new counters in `engine_info`; the window's
    `engine.prefill` spans carry the same sums.  The parent's spans have no
    `pad`: nothing to read, and the metric is left out of the line."""
    ev = {"engine_info": {"before": {"prefills": 1}, "after": {"prefills": 3}},
          "t0": 10.0, "t1": 20.0, "clock_skew_ns": 5,
          "spans": [_prefill_span(9.0e9, 1e6, 2048, 100, pad),     # lead-in
                    _prefill_span(11.0e9, 1e6, 2048, 1536, pad),
                    _prefill_span(15.0e9, 1e6, 2048, 1536, pad),
                    {"name": "engine.decode_step", "ts": 12e9, "dur": 1,
                     "args": {}}]}
    got = prefill_padding_share.read(ev)
    assert (got is None) if want is None else \
        got["value"] == pytest.approx(want)


def test_selective_scan_work_counts_each_array_once_at_the_bucket():
    flops, nbytes = selective_scan.per_call(512, 5120, 16)
    assert nbytes == (512 * 5120 * (2 + 2 + 2 + 4)      # u, z, y; dt float32
                      + 2 * 512 * 16 * 4                 # B, C
                      + 5120 * 16 * 4 + 5120 * 4         # A, D
                      + 2 * 16 * 5120 * 4)               # the state in and out
    assert flops == 512 * 5120 * (7 * 16 + 7)
    cell = spec.load_cell(CELL)
    trace = types.SimpleNamespace(t_start=100.0, t_stop=103.0)
    ev = {"cell": cell, "trace": trace, "clock_skew_ns": 0, "spans": [
        _prefill_span(99.0e9, 0.5e9, 512, 400),          # before the trace
        _prefill_span(101.0e9, 0.04e9, 512, 400),        # inside
        _prefill_span(102.98e9, 0.04e9, 128, 100)]}      # half inside
    got = selective_scan.work(ev, lambda name: 0)["selective_scan"]
    small = selective_scan.per_call(128, 5120, 16)
    assert got[0] == pytest.approx(26 * (flops + 0.5 * small[0]))
    assert got[1] == pytest.approx(26 * (nbytes + 0.5 * small[1]))


def test_mqa_decode_work_counts_the_attention_layers_only():
    cell = spec.load_cell(CELL)
    assert cell.depth() == 28
    trace = types.SimpleNamespace(t_start=0.0, t_stop=3.0)
    ev = {"cell": cell, "trace": trace, "requests": [
        {"prompt_len": 100, "token_times": [0.5, 1.0, 2.0, 4.0]}]}
    flops, nbytes = mqa_decode_attention.work(ev, None)[
        "mqa_decode_attention"]
    assert nbytes == (101 + 102) * 2 * 128 * 2 * 2      # K, V bf16; 2 layers
    assert flops == nbytes * 20


def test_seeded_mamba_parameters_lie_where_the_published_initialiser_puts_them():
    names = ("mamba.A_log", "mamba.D", "mamba.conv1d_weight",
             "mamba.conv1d_bias", "mamba.dt_proj.bias", "mamba.dt_proj.weight",
             "mamba.in_proj.weight", "input_layernorm.weight")
    shapes = ((64, 8), (64,), (64, 4), (64,), (64,), (8, 64), (32, 128), (32,))
    key = jax.random.key(2147483659, impl="threefry2x32")
    p = {n: np.asarray(model_jamba._draw(n, s, jax.random.fold_in(key, i)))
         for i, (n, s) in enumerate(zip(names, shapes))}
    dt = np.log1p(np.exp(p["mamba.dt_proj.bias"]))
    assert 1e-3 <= dt.min() and dt.max() <= 1e-1 + 1e-6
    np.testing.assert_allclose(np.exp(p["mamba.A_log"])[5], np.arange(1, 9),
                               rtol=1e-6)
    decay = np.exp(-dt[:, None] * np.exp(p["mamba.A_log"]))
    assert 0.4 < decay.min() and decay.max() < 1.0       # not 0, not 1
    assert np.abs(p["mamba.conv1d_weight"]).max() <= 0.5
    assert np.abs(p["mamba.dt_proj.weight"]).max() <= 8 ** -0.5
    assert (p["mamba.D"] == 1).all() and (p["input_layernorm.weight"] == 1).all()
    assert p["mamba.in_proj.weight"].std() == pytest.approx(
        (2 / 160) ** 0.5, rel=0.1)
    with pytest.raises(RuntimeError, match="no rule"):
        model_jamba._draw("mamba.unknown", (4,), key)


def test_the_configuration_holds_the_catalog_row_and_cuts_nothing():
    cfg = spec.load_json(spec.os.path.join(
        spec.HERE, "configs", "jamba2-3b.json"))
    bench = spec.load_json(spec.os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "jamba2-3b")
    assert entry["reduced"] == cfg["reduced"] == []
    assert entry["source"] == cfg["source"]
    assert cfg["num_hidden_layers"] == 28 == cfg["layers_run"]["serve"]
    jcfg = model_jamba.jamba_config(cfg, 28)
    assert [i for i in range(28) if jcfg.is_attention(i)] == [7, 21]
    assert jcfg.d_inner == 5120


def test_the_mode_keeps_serve_py_untouched_after_a_run():
    from benchmarks import model as bmodel, reference
    from benchmarks.modes import jamba as mode, serve
    info = {k: 0 for k in ("prefills", "decode_steps", "tokens_generated",
                           "avg_occupancy", "submitted", "admitted",
                           "finished", "timed_out", "evicted", "rejected",
                           "queued", "active") + mode.KEPT}
    assert set(mode._slim(dict(info, other=1))) == set(info)
    assert serve.bmodel is bmodel and serve.reference is reference
    assert serve._slim is mode._serve_slim and serve.MARGIN == 0.01
    # the cell's limits lie between their two readings (PERF.md, PR 27)
    assert 2 * 0.0229 < mode.MARGIN and 0.0036 < mode.STATE_TOL < 0.0177
