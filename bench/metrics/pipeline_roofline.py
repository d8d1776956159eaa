"""The stacked pipeline's share of its memory roofline: the fixed-work
bytes of the window's lookups (``harness.fixed_work``) over the pipeline's
device time, against the chip's peak bandwidth."""
from harness.pipeline import pipeline_device


def read(rec):
    if not rec["trace"]:
        return None
    seconds, _ = pipeline_device(rec["trace"])
    if not seconds or not rec["fixed_bytes_per_lookup"]:
        return None
    achieved = rec["fixed_bytes_per_lookup"] * rec["attempted"] / seconds
    return 100.0 * achieved / (rec["peak_gbps"] * 1e9)
