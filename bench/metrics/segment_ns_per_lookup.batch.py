"""Device time per lookup of routing, the radix descent and the spline
segment search and interpolation: the stacked pipeline's ops in the traced
window under the program's ``plex.route`` and ``plex.segment`` scopes
(``harness.stages``), over the window's lookups."""
import pathlib

from harness.stages import ns_per_lookup

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]


def read(rec):
    return ns_per_lookup(rec, BENCH_DIR, ("plex.route", "plex.segment"))
