"""The run's last line, its device check and its exit without a chip."""
import json
import subprocess
import sys
import time

import jax
import pytest

from conftest import CELLS, ROOT
from harness import cell_run, device

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def run_cell(bench, name, trace=False, **kw):
    cell = bench.cell(name)
    rec, checked = cell_run.measure(bench, cell, 2**31 + 99, 0.3, trace,
                                    time.perf_counter(), jax.devices(), **kw)
    return cell, rec, checked, cell_run.result(bench, cell, rec, checked,
                                               trace)


@pytest.mark.parametrize("name", CELLS)
def test_untraced_line(tiny_bench, name):
    cell, rec, checked, line = run_cell(tiny_bench, name)
    assert list(line) == KEYS + ["compared"]
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end:
        assert line["metrics"][m["name"]]["unit"] == m["unit"]
        assert line["metrics"][m["name"]]["value"] > 0
    assert line["compared"] == {"inexact_answers": {"value": 0, "limit": 0}}
    assert checked["checked"] == line["attempted"] > 0
    assert rec["compiles_in_window"] == 0
    json.dumps(line)


def test_traced_line(tiny_bench, monkeypatch):
    """With ``--trace 1`` the line carries the per-layer metrics, the
    device's busy and window seconds, and the breakdown. The CPU has no
    device plane, so the reduction of the recorded chip trace stands in."""
    red = {"window_s": 2.0, "busy_s": 1.5, "devices": 1,
           "ops": {"%fusion.1 = u32[8]{0} fusion()": 1.0},
           "modules": {"jit_traced(1)": [1.25, 40], "jit_other": [0.1, 1]},
           "idle_by_host": {"serve.sync": 0.4, "bench.draw": 0.1}}
    monkeypatch.setattr(cell_run, "reduce_trace", lambda d, s: red)
    monkeypatch.setitem(device.PEAK_GBPS, "cpu", 100.0)
    cell, rec, checked, line = run_cell(tiny_bench, "ycsb200M-sosd-lookup",
                                        trace=True)
    assert list(line) == KEYS + ["breakdown", "compared"]
    assert line["device"]["busy_s"] == 1.5
    assert line["device"]["window_s"] == 2.0
    m = line["metrics"]
    assert set(m) <= {x["name"] for x in cell.per_layer}
    assert m["device_idle_pct.batch"]["value"] == pytest.approx(25.0)
    assert m["pipeline_ns_per_lookup.batch"]["value"] == pytest.approx(
        1.25e9 / line["attempted"])
    assert m["pipeline_roofline"]["value"] == pytest.approx(
        100 * rec["fixed_bytes_per_lookup"] * line["attempted"] / 1.25
        / 100e9)
    assert m["host_ms_per_block.batch"]["value"] > 0
    assert "lookups_per_s" not in m and "setup_s" not in m
    assert line["breakdown"]["device_ops"] == [
        ["%fusion.1 = u32[8] fusion()", 1.0]]
    assert line["breakdown"]["idle_gaps"][0] == ["serve.sync", 0.4]


def test_device_check_fails_off_the_chip():
    with pytest.raises(device.NoChip, match="no TPU"):
        device.require(1)


def test_unknown_device_kind_fails(monkeypatch):
    class Dev:
        platform, device_kind, id = "tpu", "TPU v99", 0
    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(device.NoChip, match="no published peak"):
        device.require(1)
    monkeypatch.setattr(jax, "devices", lambda: [
        type("D", (), {"platform": "tpu", "device_kind": "TPU v5 lite"})()])
    with pytest.raises(device.NoChip, match="needs 4 chips"):
        device.require(4)


def test_command_prints_no_result_without_a_chip():
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "ycsb200M-sosd-lookup", "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=120)
    assert out.returncode != 0 and out.stdout == ""
    assert "no TPU" in out.stderr


def test_command_fails_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=
                    shutil.ignore_patterns(".cache", ".run", "__pycache__"))
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "ycsb200M-sosd-lookup", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=120)
    assert out.returncode != 0 and out.stdout == ""


def test_compiles_inside_the_window_are_counted():
    with cell_run.Compiles() as c:
        jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7.0)).block_until_ready()
    assert c.count >= 1
    with cell_run.Compiles() as c:
        pass
    assert c.count == 0
