"""Lookup roofline: analytic bytes moved per lookup vs measured throughput.

PLEX serving is memory-bound — every stage of the stacked pipeline is a
handful of gathers and compares, so the right efficiency lens is bytes
moved per lookup against the platform's peak memory bandwidth, not FLOPs.
This section derives the analytic per-lookup traffic straight from the
stacked layout's static parameters (``kernels.planes.StackedPlanes``) —
shard routing sweep, layer descent (radix table probe or CHT descent),
spline segment search, interpolation, the eps-window data probe, and the
clamp/offset fold — then measures ns/lookup through each registered
stacked backend (``kernels.backends``) and reports

    achieved_gbps  = bytes_per_lookup / ns_per_lookup
    roofline_frac  = achieved_gbps / peak_gbps

Peak bandwidth comes from ``PEAK_GBPS``, keyed by the device's
``device_kind`` as JAX reports it, each entry with its published source.
A device that is not in the table is an error, not a default: a roofline
share only means something against the chip it ran on, so a CPU run
fails here instead of timing the host.

Records are appended to ``results/``-bound ``BENCH_lookup.json``
schema-additively (``workload: "roofline"``, carrying
``bytes_per_lookup`` / ``achieved_gbps`` / ``peak_gbps`` /
``roofline_frac``); ``bench_diff`` keys on (dataset, n, eps, backend,
workload), so roofline rows gate their own trajectory without touching
the serve rows.
"""
from __future__ import annotations

import json

import numpy as np

# peak HBM bandwidth of one chip, GB/s, keyed by ``jax.Device.device_kind``
PEAK_GBPS = {
    # TPU v5e: 16 GB of HBM at 819 GB/s — Google Cloud documentation,
    # "TPU v5e" (system architecture)
    "TPU v5 lite": 819.0,
}

EPS = 64


def peak_gbps(device_kind: str) -> float:
    """Published peak bandwidth of ``device_kind``; raises for a device
    the table does not know."""
    try:
        return PEAK_GBPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak bandwidth for device kind {device_kind!r}; "
            "add it to PEAK_GBPS with its source") from None


def bytes_per_lookup(sp, probe: str) -> int:
    """Analytic bytes touched per query through the stacked pipeline.

    Counts every gathered element at its plane width (uint32/int32/float32
    = 4 B; a (hi, lo) key pair = 8 B), assuming no cache reuse across
    stages — the cold-traffic upper bound a roofline wants. Stage by stage
    (mirroring ``jnp_lookup._stacked_pipeline``):

    * routing: predecessor count sweeps both shard-minima planes, [S] each
    * layer descent: radix — 5 [S] parameter gathers + 2 table entries;
      CHT — 2 [S] parameter gathers + one cell per unrolled level
    * spline segment search: over ``max_win`` (radix) / ``delta_max + 1``
      (CHT) spline key pairs — full sweep in "count" mode, one pair per
      fixed bisect trip otherwise
    * interpolation: the bracketing spline points' key pairs + ranks
    * data probe: ``window`` key pairs ("count") or one per bisect trip
    * clamp + global fold: n_spline, n_real, row_off gathers
    * the query pair in and the int32 result out
    """
    s = sp.static
    S = sp.n_shards
    route = S * 8
    if sp.kind == "radix":
        width = s["max_win"]
        descent = 5 * 4 + 2 * 4
    else:
        width = s["delta_max"] + 1
        descent = 2 * 4 + s["levels"] * 4
    if s["mode"] == "count":
        seg_search = width * 8
    else:
        seg_search = max(int(width - 1).bit_length(), 0) * 8
    interp = 2 * (8 + 4)
    if probe == "count":
        data_probe = sp.window * 8
    else:
        data_probe = int(sp.window).bit_length() * 8
    fold = 3 * 4
    return route + descent + seg_search + interp + data_probe + fold + 8 + 4


def run(out_rows: list[str] | None = None) -> list[str]:
    import jax

    from repro.kernels.backends import backend_names, get_backend
    from repro.serving import PlexService

    from .common import datasets, queries
    from .serve_bench import OUT_PATH, QUERY_CAPS, REPEATS

    rows = out_rows if out_rows is not None else []
    rows.append("roofline,dataset,n,eps,backend,probe,ns_per_lookup,"
                "bytes_per_lookup,achieved_gbps,peak_gbps,roofline_frac")
    peak = peak_gbps(jax.devices()[0].device_kind)
    stacked = [b for b in backend_names()
               if get_backend(b).stacked_factory is not None]
    records: list[dict] = []
    for dname, keys in datasets().items():
        q = queries(keys)
        want = np.searchsorted(keys, q, side="left")
        svc = PlexService(keys, eps=EPS)
        for backend in stacked:
            qb = q[:QUERY_CAPS[backend]] if backend in QUERY_CAPS else q
            got = svc.lookup(qb, backend=backend)
            assert np.array_equal(got, want[:qb.size]), (
                dname, backend, "roofline lookup wrong")
            st = svc.stacked_impl(backend=backend)
            bpl = bytes_per_lookup(st.planes, st.probe)
            ns = svc.throughput(qb, backends=(backend,),
                                repeats=REPEATS.get(backend, 3))[backend]
            gbps = bpl / ns            # B/ns == GB/s
            frac = gbps / peak
            rows.append(f"roofline,{dname},{keys.size},{EPS},{backend},"
                        f"{st.probe},{ns:.1f},{bpl},{gbps:.2f},{peak:.0f},"
                        f"{frac:.4f}")
            records.append({
                "dataset": dname, "n": int(keys.size), "eps": int(EPS),
                "backend": backend, "workload": "roofline",
                "ns_per_lookup": round(float(ns), 1),
                "build_s": round(float(svc.build_s), 4),
                "size_bytes": int(svc.size_bytes),
                "probe": st.probe,
                "bytes_per_lookup": int(bpl),
                "achieved_gbps": round(float(gbps), 3),
                "peak_gbps": float(peak),
                "roofline_frac": round(float(frac), 5),
            })
    # schema-additive append into the shared perf-trajectory file: replace
    # any previous roofline rows, never touch the serve section's records
    try:
        existing = json.loads(OUT_PATH.read_text())
    except (OSError, ValueError):
        existing = []
    existing = [r for r in existing if r.get("workload") != "roofline"]
    OUT_PATH.write_text(json.dumps(existing + records, indent=1))
    rows.append(f"# roofline appended {len(records)} records to {OUT_PATH}")
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
