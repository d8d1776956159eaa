"""Host time of the lookup path per dispatched block: the program's
``serve.staging`` and ``serve.dispatch`` spans over the window's blocks."""


def read(rec):
    if not rec["spans"]:
        return None
    blocks = rec["stats"]["batches"]
    host_us = sum(ev["dur_us"] for ev in rec["spans"]
                  if ev["name"] in ("serve.staging", "serve.dispatch"))
    return host_us / 1e3 / blocks if blocks and host_us else None
