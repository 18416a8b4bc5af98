"""`ssm_decode_roofline` without the chip: the work `kernels/ssm_decode_update.py`
counts a call at each Mamba cell's geometry, checked against hand
arithmetic, and what the accepted `kernel_roofline` reader makes of it on a
trace with the kernel and on one without (a program that has none)."""
import pytest

from benchmarks import spec
from benchmarks.kernels import ssm_decode_update
from benchmarks.readers import kernel_roofline

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("cell_name,layers,slots", [
    ("jamba2-3b-serve-reasoning", 26, 192),
    ("phi4-flash-serve-longctx", 9, 64),
], ids=["jamba", "phi4flash"])
def test_the_work_a_call_is_the_state_in_and_out_and_the_rows(cell_name,
                                                              layers, slots):
    cell = spec.load_cell(cell_name)
    assert ssm_decode_update.mamba_geometry(cell) == (layers, 5120, 16)
    assert int(cell.traffic["slots"]) == slots
    di, n = 5120, 16
    flops, nbytes = ssm_decode_update.per_call(slots, di, n)
    assert nbytes == (2 * slots * n * di * 4           # the state, in and out
                      + n * di * 4 + di * 4            # A, D
                      + slots * di * (2 + 2 + 2 + 4)   # u, z, y; dt float32
                      + 2 * slots * n * 4)             # B, C
    assert flops == slots * di * (7 * n + 7)
    # the state is most of what a call moves: 62.9 MB in and as much out
    # at 192 slots, beside 10 bytes a slot and channel of rows
    assert 0.92 < 2 * slots * n * di * 4 / nbytes < 0.93
    calls = layers * 114
    got = ssm_decode_update.work(
        {"cell": cell}, lambda name: calls if name == "ssm_decode_update"
        else 0)
    assert got == {"ssm_decode_update": (calls * flops, calls * nbytes)}

    trace = "ssm_decode_update-f32_%dx16x5120" % slots
    ev = {"cell": cell, "peaks": PEAKS, "reduced": {
        "kernel_s": {trace: 0.5, "fusion-f32_%d" % slots: 1.0},
        "kernel_calls": {trace: calls, "fusion-f32_%d" % slots: calls}}}
    share = kernel_roofline.read(ev, kernel="ssm_decode_update")
    least = calls * nbytes / PEAKS["hbm_bytes_per_s"]
    assert share["value"] == pytest.approx(100 * least / 0.5)
    assert share["detail"]["ssm_decode_update"]["bound"] == "memory"
    # the parent's trace has no such kernel: nothing to read, and the line
    # leaves the metric out
    del ev["reduced"]["kernel_s"][trace], ev["reduced"]["kernel_calls"][trace]
    assert kernel_roofline.read(ev, kernel="ssm_decode_update") is None
