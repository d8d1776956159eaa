"""Device time of the stacked pipeline per lookup: the summed durations
of the serving program's events in the trace over the window's lookups."""
from harness.pipeline import pipeline_device


def read(rec):
    if not rec["trace"]:
        return None
    seconds, _ = pipeline_device(rec["trace"])
    return seconds * 1e9 / rec["attempted"] if seconds else None
