"""The program's own build time of the index (``PlexService.build_s``:
``Snapshot.build`` over the process pool)."""


def read(rec):
    return rec["build_s"]
