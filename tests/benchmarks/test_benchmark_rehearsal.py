"""The CPU rehearsal of every cell of BENCHMARK.json: the same code path as
the chip run at the `tiny` sizes, in this process (tests/conftest.py gives 8
virtual CPU devices, Pallas kernels run interpreted).  A change to the
program that breaks what the benchmark reads fails here, not on the chip."""
import json
import os

import pytest

from benchmarks import rehearse, spec

BENCH = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]


def _last_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("trace", [0, 1], ids=["end_to_end", "per_layer"])
@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal(workload, trace, tmp_path, capsys):
    rc = rehearse.main(["--workload", workload, "--seed", "2147483659",
                        "--seconds", "1.5", "--trace", str(trace),
                        "--out", str(tmp_path)])
    assert rc == 0
    line = _last_line(capsys)
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == want, "the last line has the contract's keys only"
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"

    cell = spec.load_cell(workload)
    named = cell.per_layer if trace else cell.end_to_end
    assert set(line["metrics"]) == {m["name"] for m in named}
    for m in named:
        got = line["metrics"][m["name"]]
        assert got["unit"] == m["unit"]
        # a time, a rate or a share of the device is never a CPU's number
        if m["source"] != "program_counter" or m["name"].startswith("peak_hbm"):
            assert got["value"] is None, m["name"]
    if trace:
        assert line["metrics"][
            f"lowerings_in_window.{cell.mode.split('-')[0]}"]["value"] == 0.0
    # the run's records and the checks behind `correct` are on disk
    run_dir = tmp_path / workload / f"seed2147483659-trace{trace}"
    detail = json.loads((run_dir / "detail.json").read_text())
    assert all(c["ok"] for c in detail["checks"]) and len(detail["checks"]) >= 3
    records = json.loads((run_dir / "records.json").read_text())
    assert records["stamps"] if cell.mode == "train" else records["requests"]


def test_a_run_without_a_tpu_exits_non_zero(capsys):
    from benchmarks import run
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    assert e.value.code not in (0, None)
    assert "needs a TPU" in str(e.value.code)
    assert capsys.readouterr().out.strip() == ""


def test_a_device_kind_without_peaks_is_refused():
    with pytest.raises(SystemExit) as e:
        spec.peaks_for("TPU v99 imaginary")
    assert "peaks.json" in str(e.value.code)
    assert spec.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
