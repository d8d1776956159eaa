"""Record the small chip trace that ``test_xplane.py`` reads.

    python3 bench/tests/record_trace.py <out_dir>

Runs the harness's traced window at a tiny size (``conftest.write_bench``:
a few thousand keys, a short closed loop) on the chip, and writes the
``.xplane.pb`` and the program's span records beside it as
``tiny.xplane.pb`` and ``tiny_spans.json``. Copy both into
``bench/tests/data``.
"""
import json
import pathlib
import shutil
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent), str(HERE.parents[1] / "src")]


def main(out_dir: str) -> int:
    from conftest import write_bench
    from harness import cell_run, device, xplane
    from harness.spec import Bench
    devs = device.require(1)
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory(dir=HERE.parent / ".run") as tmp:
        root = pathlib.Path(tmp)
        bench = Bench(root, write_bench(root, n_keys=20_000))
        cell = bench.cell("ycsb200M-sosd-lookup")
        rec, checked = cell_run.measure(bench, cell, 7, 0.25, True,
                                        time.perf_counter(), devs)
        assert checked["wrong"] == 0 and checked["missing"] == 0, checked
        shutil.copy(xplane.find(bench.bench_dir / ".run" /
                                cell_run.TRACE_DIR),
                    out / "tiny.xplane.pb")
    (out / "tiny_spans.json").write_text(json.dumps(rec["spans"]))
    print(json.dumps({"modules": rec["trace"]["modules"],
                      "busy_s": rec["trace"]["busy_s"],
                      "window_s": rec["trace"]["window_s"],
                      "idle": rec["trace"]["idle_by_host"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
