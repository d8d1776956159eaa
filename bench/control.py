"""Read the numbers that decide a cell's ``correct`` over several seeds.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 11 12 13
    python3 bench/control.py --workload <cell> --seconds <s> --seeds 11 \\
        --program --data insertstart=1000000000

By default the control runs in the program's place: the configuration's
reference computed in the nearest lower precision, at the cell's own
size and load. It has to fail, which shows that the comparison can tell
an exact index from a truncated one. ``--program`` runs the program
instead, and ``--data key=value`` changes one of the configuration's
data parameters for this reading, so that the program can be checked on
other key sets than the one the cell serves. Prints one line per seed
with the numbers compared beside their limits. The benchmark's own runs
never run this.
"""
import argparse
import json
import os
import pathlib
import sys
import time

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true",
                    help="run the program, not the control")
    ap.add_argument("--data", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="change a data parameter of the configuration")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from harness import cell_run, device
    from harness.spec import Bench
    bench = Bench(ROOT, BENCH_DIR)
    cell = bench.cell(args.workload)
    for kv in args.data:
        key, value = kv.split("=", 1)
        cell.config["data"][key] = json.loads(value)
    devs = device.require(cell.chips)
    cell_run.enable_compile_cache(BENCH_DIR)
    ref = bench.reference(cell.config)
    make = (cell_run.Service if args.program else
            lambda cfg, keys: cell_run.Control(ref.control(keys)))
    out = []
    for seed in args.seeds:
        rec, checked = cell_run.measure(
            bench, cell, seed, args.seconds, False, time.perf_counter(),
            devs, make_service=make)
        line = cell_run.result(bench, cell, rec, checked, False)
        out.append({"seed": seed, "data": cell.config["data"],
                    "program": args.program, "correct": line["correct"],
                    "checked": checked["checked"],
                    "compared": line["compared"]})
        print(json.dumps(out[-1]), flush=True)
    want = args.program
    return 0 if all(o["correct"] == want for o in out) else 1


if __name__ == "__main__":
    sys.exit(main())
