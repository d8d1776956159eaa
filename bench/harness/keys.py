"""A configuration's keys: made once from its data parameters, then read
back.

The configuration fixes its data as a loaded database fixes its records:
the generator (``datasets/<generator>.py``) and its ``data`` parameters.
So every run of a cell serves the same index, and ``--seed`` varies the
requests. The first run in a checkout makes the keys (about half a
minute for 200M) and writes them to ``.cache/keys``; later runs read them
back. One file per configuration is kept, named for everything that
makes it, the generator's source included.
"""
from __future__ import annotations

import hashlib
import json
import os

import numpy as np


def load(bench, cfg: dict) -> tuple[np.ndarray, str]:
    """(sorted uint64 keys, ``"read"`` or ``"made"``)."""
    src = bench.bench_dir / "datasets" / f"{cfg['generator']}.py"
    made_of = json.dumps(cfg["data"], sort_keys=True).encode()
    tag = hashlib.sha256(src.read_bytes() + made_of).hexdigest()[:16]
    cache = bench.bench_dir / ".cache" / "keys"
    path = cache / f"{cfg['name']}.{tag}.u64"
    if path.is_file():
        return np.fromfile(path, dtype=np.uint64), "read"
    keys = bench.module("datasets", cfg["generator"]).generate(cfg["data"])
    cache.mkdir(parents=True, exist_ok=True)
    for old in cache.glob(f"{cfg['name']}.*.u64"):
        old.unlink()
    tmp = path.with_name(f"{path.name}.{os.getpid()}.tmp")
    with open(tmp, "wb") as f:
        keys.tofile(f)
        f.flush()
        os.fsync(f.fileno())      # written back here, not in the window
    os.replace(tmp, path)
    return keys, "made"
