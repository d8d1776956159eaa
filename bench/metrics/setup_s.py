"""Process start to the first timed request: keys from the seed, the
build, plane upload, compilation (or the compile cache) and warm-up."""


def read(rec):
    return rec["setup_s"]
