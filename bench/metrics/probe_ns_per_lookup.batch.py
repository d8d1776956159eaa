"""Device time of the eps-window probe per lookup: the stacked pipeline's
ops in the traced window under the program's ``plex.probe`` scope
(``harness.stages``), over the window's lookups."""
import pathlib

from harness.stages import ns_per_lookup

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]


def read(rec):
    return ns_per_lookup(rec, BENCH_DIR, ("plex.probe",))
