"""``PlexService.warmup()`` by the harness's clock: stacked planes built
and uploaded, the serving programs compiled or read from the cache."""


def read(rec):
    return rec["warmup_s"]
