"""Benchmark driver — one section per paper table/figure + roofline summary.

  PYTHONPATH=src python -m benchmarks.run [--quick]

Sections: fig2 (build/size), fig3 (lookup/size), autotune (vs grid search),
kernel (device lookup path), serve (PlexService per-backend throughput),
build_scale (parallel sharded build throughput), roofline (bytes per
lookup against the chip's peak bandwidth; fails on a device without a
published peak, the CPU included).

Each section's CSV rows are also written to ``BENCH_<section>.json`` so CI
can archive per-PR artifacts; the serve and build_scale sections
additionally emit the schema-stable ``BENCH_lookup.json`` /
``BENCH_build.json`` perf-trajectory files.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small N for CI (BENCH_N=60000)")
    ap.add_argument("--only", default=None,
                    help="comma-list: fig2,fig3,autotune,kernel,serve,"
                         "build_scale,roofline")
    args = ap.parse_args()
    if args.quick and "BENCH_N" not in os.environ:
        os.environ["BENCH_N"] = "60000"
        os.environ["BENCH_QUERIES"] = "40000"

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache(pathlib.Path(__file__).resolve().parents[1])

    # imports AFTER env so common.py picks BENCH_N up
    from . import autotune_grid, build_scale, fig2_build, fig3_lookup
    from . import kernel_bench, roofline, serve_bench

    sections = {
        "fig2": fig2_build.run,
        "fig3": fig3_lookup.run,
        "autotune": autotune_grid.run,
        "kernel": kernel_bench.run,
        "serve": serve_bench.run,
        "build_scale": build_scale.run,
        "roofline": roofline.run,
    }
    wanted = args.only.split(",") if args.only else list(sections)
    rows: list[str] = []
    failed = False
    for name in wanted:
        t0 = time.perf_counter()
        start = len(rows)
        try:
            sections[name](rows)
        except Exception as e:  # keep the harness honest but resilient
            failed = True
            rows.append(f"{name},ERROR,{e!r}")
        secs = time.perf_counter() - t0
        pathlib.Path(f"BENCH_{name}.json").write_text(json.dumps(
            {"section": name, "seconds": round(secs, 1),
             "rows": rows[start:]}, indent=1))
        rows.append(f"# {name} took {secs:.1f}s")
    print("\n".join(rows))
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
