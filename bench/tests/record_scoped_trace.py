"""Record the small chip trace that ``test_stages.py`` reads.

    python3 bench/tests/record_scoped_trace.py <out_dir>

As ``record_trace.py``: the harness's traced window at a tiny size
(``conftest.write_bench``: 20000 keys in four shards, a quarter-second
closed loop) on the chip, from a program whose stacked pipeline names its
stages (``plex.*`` scopes). Writes the ``.xplane.pb``, the program's span records
and the program's own stage map (``PlexService.stage_of_ops``, taken after
the window and before the service closes) as ``tiny_scoped.xplane.pb``,
``tiny_scoped_spans.json`` and ``tiny_scoped_stages.json``. Copy all three
into ``bench/tests/data_scoped``: a directory of their own, since
``test_xplane.py`` reads the newest trace under ``bench/tests/data``.
"""
import json
import pathlib
import shutil
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
SHARDS = 4      # more than one, so that the routing stage runs
sys.path[:0] = [str(HERE), str(HERE.parent), str(HERE.parents[1] / "src")]


def main(out_dir: str) -> int:
    from conftest import write_bench
    from harness import cell_run, device, stages, xplane
    from harness.spec import Bench

    class Scoped(cell_run.Service):
        def close(self) -> None:
            self.stage_map = self.svc.stage_of_ops()
            super().close()

    made = []

    def make_service(cfg, keys):
        made.append(Scoped(cfg, keys))
        return made[-1]

    devs = device.require(1)
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    (HERE.parent / ".run").mkdir(exist_ok=True)
    with tempfile.TemporaryDirectory(dir=HERE.parent / ".run") as tmp:
        root = pathlib.Path(tmp)
        bench_dir = write_bench(root, n_keys=20_000)
        for path in (bench_dir / "configs").glob("*.json"):
            cfg = json.loads(path.read_text())
            cfg["service"]["n_shards"] = SHARDS
            path.write_text(json.dumps(cfg))
        bench = Bench(root, bench_dir)
        cell = bench.cell("ycsb200M-sosd-lookup")
        rec, checked = cell_run.measure(bench, cell, 7, 0.25, True,
                                        time.perf_counter(), devs,
                                        make_service)
        assert checked["wrong"] == 0 and checked["missing"] == 0, checked
        shutil.copy(xplane.find(bench.bench_dir / ".run" /
                                cell_run.TRACE_DIR),
                    out / "tiny_scoped.xplane.pb")
        by_stage = stages.run_stages(bench.bench_dir)
    (out / "tiny_scoped_spans.json").write_text(json.dumps(rec["spans"]))
    (out / "tiny_scoped_stages.json").write_text(
        json.dumps(made[0].stage_map, sort_keys=True))
    print(json.dumps({"modules": rec["trace"]["modules"],
                      "busy_s": rec["trace"]["busy_s"],
                      "stages": by_stage}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
