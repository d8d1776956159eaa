"""Bring-up smoke of the PLEX serving path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the routed mesh over four chips

One process drives ``repro.serving.PlexService``, the entry point a user
calls, at the paper's SOSD scale: 200M unsigned 64-bit keys of the seeded
``amzn`` stand-in (``repro.data.generate``), eps 64, the ``jnp`` backend,
no fallback chain. Every answer is checked exactly against
``np.searchsorted`` over the logical key array.

One chip: build (process-pool workers, while this process already holds
the chip), the stacked single-dispatch route with its planes on the chip,
warm-up, present / absent / queued (``submit`` + ``drain``) lookups, then
2,000 inserts and 2,000 deletes served by the merged (delta-fold) lookup,
and zero backend failures or fallbacks.

``--chips 4``: only ``PlexService(plan=4)``, the collective-free routed
mesh path, with each device holding its own plane slab, compared with
``np.searchsorted`` and with a one-device plan (``plan=1``) on the same
keys and updates.

The last line of standard output is one JSON object, ``{"ok": true,
"device": {...}}``. It is printed only when every check passed on a TPU:
the script exits non-zero without it when JAX finds no TPU or when the
repository's ``src/`` is not beside it. Wall times printed on the way are
smoke timings of a single run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
N_KEYS = 200_000_000          # PLEX paper, section 4: 200M keys per dataset
DATASET = "amzn"
EPS = 64
SEED = 0
N_QUERIES = 1_000_000
N_UPDATES = 2_000


def say(msg: str) -> None:
    print(msg, flush=True)


def timed(label: str, fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    say(f"{label}: {time.perf_counter() - t0:.3f} s (smoke timing, "
        "not a benchmark)")
    return out


def searchsorted(keys: np.ndarray, q: np.ndarray) -> np.ndarray:
    """The plain reference: first index whose key is >= q."""
    return np.searchsorted(keys, q, side="left").astype(np.int64)


def check(name: str, got: np.ndarray, want: np.ndarray) -> None:
    bad = np.flatnonzero(np.asarray(got) != want)
    if bad.size:
        raise AssertionError(
            f"{name}: {bad.size} of {want.size} results differ from "
            f"searchsorted (first at {bad[0]}: got {got[bad[0]]}, "
            f"want {want[bad[0]]})")
    say(f"{name}: {want.size} results equal searchsorted exactly")


def absent_keys(keys: np.ndarray, n: int, rng) -> np.ndarray:
    """``n`` keys inside ``[keys[0], keys[-1]]`` that the array does not
    hold: half uniform over the range, half right after a present key."""
    lo, hi = int(keys[0]), int(keys[-1])
    uni = rng.integers(lo, hi, size=2 * n, dtype=np.uint64, endpoint=True)
    nxt = keys[rng.integers(0, keys.size - 1, size=2 * n)] + np.uint64(1)
    out = []
    for cand in (uni, nxt):
        pos = np.minimum(np.searchsorted(keys, cand), keys.size - 1)
        keep = (keys[pos] != cand) & (cand <= keys[-1])
        out.append(cand[keep][:n // 2 + n % 2])
    q = np.concatenate(out)[:n]
    assert q.size == n
    return rng.permutation(q)


def update_sets(keys: np.ndarray, rng):
    """2,000 new keys (absent, distinct) and 2,000 distinct present keys to
    delete, plus the logical key array after both, built independently of
    the service."""
    ins = np.unique(absent_keys(keys, N_UPDATES + 64, rng))[:N_UPDATES]
    dels = np.unique(keys[rng.integers(0, keys.size, N_UPDATES + 64)])
    dels = rng.permutation(dels)[:N_UPDATES]
    # a delete removes every occurrence of its key: drop each key's run
    lo = np.searchsorted(keys, dels, "left")
    run = np.searchsorted(keys, dels, "right") - lo
    gone = np.repeat(lo - np.cumsum(run) + run, run) + np.arange(run.sum())
    kept = np.delete(keys, gone)
    logical = np.insert(kept, np.searchsorted(kept, ins), ins)
    return ins, dels, logical


def forbid(svc, name: str) -> None:
    """Fail loudly if the service ever takes route ``name``."""
    def taken(*args, **kw):
        raise AssertionError(f"PlexService took the {name} route")
    setattr(svc, name, taken)


def plane_arrays(planes) -> list:
    from repro.kernels.planes import STACKED_ARRAYS
    out = []
    for f in STACKED_ARRAYS:
        v = getattr(planes, f)
        out.extend(v.values() if isinstance(v, dict) else [v])
    return out


def memory_line(dev) -> str:
    m = dev.memory_stats() or {}
    return (f"device {dev.id} memory: bytes_in_use={m.get('bytes_in_use')} "
            f"peak_bytes_in_use={m.get('peak_bytes_in_use')} "
            f"bytes_limit={m.get('bytes_limit')}")


def assert_healthy(svc) -> None:
    h = svc.health()
    assert svc.stats.backend_failures == 0, svc.stats.backend_failures
    assert svc.stats.fallback_lookups == 0, svc.stats.fallback_lookups
    assert not h["last_errors"], h["last_errors"]
    assert not h["degraded"], h
    say(f"health: backend_failures=0 fallback_lookups=0 last_errors=[] "
        f"chain={h['fallback_chain']} merges={svc.stats.merges}")


def make_service(keys: np.ndarray, **kw):
    from repro.core.parallel_build import _mp_context
    from repro.serving import PlexService
    workers = os.cpu_count() or 1
    say(f"build: {workers} process-pool workers, start method "
        f"{_mp_context().get_start_method()!r} (this process already "
        "holds the chip)")
    t0 = time.perf_counter()
    svc = PlexService(keys, eps=EPS, backend="jnp", fallback=None,
                      merge_threshold=0, build_workers=workers, **kw)
    say(f"build: {svc.n_shards} shards in {svc.build_s:.3f} s "
        f"(service ready in {time.perf_counter() - t0:.3f} s)")
    return svc


def one_chip(keys: np.ndarray, dev) -> None:
    rng = np.random.default_rng(SEED + 1)
    svc = make_service(keys)
    forbid(svc, "_lookup_shard")
    st = timed("stacked planes built and uploaded", svc.stacked_impl, None,
               "jnp")
    assert st is not None, "shards did not unify into the stacked route"
    sp = st.planes
    arrays = plane_arrays(sp)
    for a in arrays:
        assert a.devices() == {dev}, (a.shape, a.devices())
    say(f"route: stacked single dispatch ({type(st).__name__}), probe "
        f"{st.probe}, block {st.block}; all {len(arrays)} plane arrays on "
        f"{dev.platform}:{dev.id}")
    say(f"planes: kind={sp.kind} n_shards={sp.n_shards} "
        f"n_spline_max={sp.n_spline_max} n_data_max={sp.n_data_max} "
        f"eps_eff={sp.eps_eff} window={sp.window} static={sp.static} "
        f"layer_arrays={ {k: v.shape for k, v in sp.layer_arrays.items()} }")
    say(f"planes: {sum(a.nbytes for a in arrays)} bytes on the device")
    say(memory_line(dev))
    timed("warmup (first call: compile of the delta-free and merged "
          "programs)", svc.warmup)

    q = keys[rng.integers(0, keys.size, N_QUERIES)]
    check("present keys",
          timed(f"present keys, {q.size} lookups", svc.lookup, q),
          searchsorted(keys, q))
    qa = absent_keys(keys, N_QUERIES, rng)
    check("absent keys",
          timed(f"absent keys, {qa.size} lookups", svc.lookup, qa),
          searchsorted(keys, qa))

    batches = [keys[rng.integers(0, keys.size, 100_000)],
               absent_keys(keys, 50_001, rng),
               keys[rng.integers(0, keys.size, 4_097)]]
    tickets = [svc.submit(b) for b in batches]
    timed("three submit() tickets, then drain()", svc.drain)
    for i, (t, b) in enumerate(zip(tickets, batches)):
        check(f"queued ticket {i}", t.result(), searchsorted(keys, b))

    ins, dels, logical = update_sets(keys, rng)
    svc.insert(ins)
    removed = svc.delete(dels)
    say(f"updates: inserted {ins.size} keys, deleted {dels.size} key "
        f"values ({removed} occurrences); merges={svc.stats.merges}")
    assert svc.stats.merges == 0, "merge_threshold=0 must not merge"
    qm = np.concatenate([logical[rng.integers(0, logical.size, 400_000)],
                         ins, dels, absent_keys(logical, 200_000, rng)])
    qm = rng.permutation(qm)
    check("merged lookup after updates",
          timed("merged lookup (delta fold), "
                f"{qm.size} lookups", svc.lookup, qm),
          searchsorted(logical, qm))
    assert svc.n_keys == logical.size, (svc.n_keys, logical.size)
    assert_healthy(svc)
    say(memory_line(dev))
    svc.close()


def mesh(keys: np.ndarray, devs) -> None:
    rng = np.random.default_rng(SEED + 1)
    q = np.concatenate([keys[rng.integers(0, keys.size, N_QUERIES)],
                        absent_keys(keys, N_QUERIES, rng)])
    ins, dels, logical = update_sets(keys, rng)
    qm = rng.permutation(np.concatenate(
        [logical[rng.integers(0, logical.size, 400_000)], ins, dels,
         absent_keys(logical, 200_000, rng)]))
    results = {}
    for n_dev in (4, 1):
        say(f"-- PlexService(plan={n_dev})")
        svc = make_service(keys, plan=n_dev)
        forbid(svc, "_lookup_shard")
        forbid(svc, "_stacked_lookup")
        plan = svc.plan
        assert plan is not None and plan.n_devices == n_dev, plan
        parts = svc._state.router.parts
        held = set()
        for d, part in enumerate(parts):
            assert part.impl is not None, f"device {d} holds no slab"
            arrays = plane_arrays(part.impl.planes)
            for a in arrays:
                assert a.devices() == {part.device}, (d, a.devices())
            held.add(part.device)
            say(f"device {part.device.id}: shards [{part.shard_lo}, "
                f"{part.shard_hi}), slab {sum(a.nbytes for a in arrays)} "
                "bytes, every plane on this device only")
        assert held == set(devs[:n_dev]), held
        for dev in devs[:n_dev]:
            say(memory_line(dev))
        timed(f"plan={n_dev} warmup (compile)", svc.warmup)
        got = timed(f"plan={n_dev} {q.size} lookups", svc.lookup, q)
        check(f"plan={n_dev} present and absent keys", got,
              searchsorted(keys, q))
        svc.insert(ins)
        svc.delete(dels)
        assert svc.stats.merges == 0
        gm = timed(f"plan={n_dev} merged lookup, {qm.size} lookups",
                   svc.lookup, qm)
        check(f"plan={n_dev} merged lookup after updates", gm,
              searchsorted(logical, qm))
        assert_healthy(svc)
        results[n_dev] = (got, gm)
        svc.close()
    assert all(np.array_equal(a, b)
               for a, b in zip(results[4], results[1]))
    say("plan=4 results equal plan=1 results exactly")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip path; 4: only the routed mesh "
                         "phase over four chips")
    args = ap.parse_args(argv)

    import jax
    say(f"jax {jax.__version__}")
    devs = jax.devices()
    for d in devs:
        say(f"device {d.id}: platform={d.platform} kind={d.device_kind}")
    say(f"device count: {len(devs)}")
    if devs[0].platform != "tpu":
        say(f"no TPU: JAX found {devs[0].platform!r} devices only")
        return 2
    if len(devs) < args.chips:
        say(f"--chips {args.chips} needs {args.chips} chips; JAX found "
            f"{len(devs)}")
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.data import generate
    from repro.utils.compile_cache import enable_compile_cache
    say(f"compile cache: {enable_compile_cache(ROOT)}")

    keys = timed(f"generate {DATASET} stand-in, {N_KEYS} keys, seed {SEED}",
                 generate, DATASET, N_KEYS, seed=SEED)
    distinct = 1 + int(np.count_nonzero(keys[1:] != keys[:-1]))
    say(f"keys: {keys.size} u64 ({keys.nbytes} bytes), {distinct} distinct")
    if args.chips == 4:
        mesh(keys, devs)
    else:
        one_chip(keys, devs[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
