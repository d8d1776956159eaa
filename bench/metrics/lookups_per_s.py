"""Keys looked up and answered on the host in the window, per second of
the window (from the first lookup sent to the last answer back)."""


def read(rec):
    return rec["attempted"] / rec["window_s"]
