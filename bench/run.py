"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``, this
directory and the program under ``src/``. The configuration fixes the
keys and ``--seed`` draws the requests; set-up builds and warms
``PlexService``, then the cell's traffic runs for ``--seconds``.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a run under the profiler. Every answer of the
window is compared with the configuration's plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
traced, and last ``compared``, each number compared beside its limit
(also the last lines of standard error). Without a TPU, with fewer chips
than the cell asks for, or without the program, the run exits non-zero
and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # libtpu would otherwise keep its logs under a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from harness import cell_run, device
    from harness.spec import Bench
    log = cell_run.log
    bench = Bench(ROOT, BENCH_DIR)
    try:
        cell = bench.cell(args.workload)
    except KeyError as e:
        log(f"error: {e}")
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        log(f"error: the program (src/repro) is not beside {BENCH_DIR}")
        return 4
    sys.path.insert(0, str(ROOT / "src"))
    try:
        devs = device.require(cell.chips)
    except device.NoChip as e:
        log(f"error: {e}")
        return 3
    log(f"devices: {[f'{d.platform}:{d.id} {d.device_kind}' for d in devs]}")
    log(f"compile cache: {cell_run.enable_compile_cache(BENCH_DIR)}")
    rec, checked = cell_run.measure(bench, cell, args.seed, args.seconds,
                                    bool(args.trace), T_START, devs)
    line = cell_run.result(bench, cell, rec, checked, bool(args.trace))
    cell_run.report(rec, checked)
    cell_run.print_compared(line)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
