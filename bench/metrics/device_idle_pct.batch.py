"""Share of the traced window in which no operation ran on the device,
under the closed-loop lookup mix."""


def read(rec):
    if not rec["trace"]:
        return None
    t = rec["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
