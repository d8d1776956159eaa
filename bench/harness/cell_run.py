"""One run of one cell: set-up, the measured window, the check, the line.

    set-up   the configuration's keys (``harness.keys``), ``PlexService``
             as the configuration states it, its warm-up, the mix's
             untimed requests drawn from the seed
    window   the mix's loop for ``seconds`` (``--trace 1``: under the
             profiler, with the program's spans on)
    check    device memory read, the service closed, then every operation
             of the window replayed by the configuration's plain reference
             and every answer compared
    result   the cell's metrics from their readers, as one JSON line

A run on anything but the chips the cell asks for prints no result.
"""
from __future__ import annotations

import gc
import os
import shutil
import sys
import time
from typing import Callable

import numpy as np

from . import device, fixed_work, traffic, xplane
from . import keys as key_cache
from .spec import Bench, Cell

TRACE_DIR = "trace"
STATS = ("queries", "batches", "padded_lanes", "backend_failures",
         "fallback_lookups")


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator ``stream`` of ``seed`` (any integer)."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64), stream]))


def enable_compile_cache(bench_dir) -> str:
    """JAX's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else a fixed directory inside the checkout, every program kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        bench_dir / ".cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Service:
    """The system under test as the configuration states it:
    ``PlexService(keys, eps, **service)``, with every core building. A
    loop calls the operations it sends (``lookup``, ``insert``, ...) on
    this object, and they go to the ``PlexService`` unchanged."""

    def __init__(self, cfg: dict, keys: np.ndarray):
        from repro.serving import PlexService
        kw = dict(cfg.get("service", {}))
        self.svc = PlexService(keys, eps=int(cfg["eps"]),
                               build_workers=os.cpu_count(), **kw)
        self.build_s = float(self.svc.build_s)

    def __getattr__(self, name: str):
        if "svc" not in self.__dict__:
            raise AttributeError(name)
        return getattr(self.__dict__["svc"], name)

    def stats(self) -> dict:
        return {k: int(getattr(self.svc.stats, k)) for k in STATS}

    def statics(self) -> dict:
        st = self.svc.stacked_impl()
        if st is None:
            return {}
        sp = st.planes
        return {"n_shards": sp.n_shards, "kind": sp.kind,
                "static": dict(sp.static), "window": sp.window,
                "n_spline_max": sp.n_spline_max, "probe": st.probe,
                "block": st.block}

    def close(self) -> None:
        self.svc.close()


class Compiles:
    """Counts the programs loaded inside a ``with`` block (none may be in
    the window): ``count`` of them in all, ``cached`` of them read from
    the persistent compile cache rather than compiled."""

    HIT_EVENT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        from jax._src import dispatch
        self._event = dispatch.BACKEND_COMPILE_EVENT
        self.count = 0
        self.cached = 0

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self._event:
            self.count += 1

    def _on_hit(self, event: str, **kw) -> None:
        if event == self.HIT_EVENT:
            self.cached += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_hit)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)
        jax.monitoring.unregister_event_listener(self._on_hit)
        return False


def compare(reference, keys: np.ndarray, win: traffic.Window) -> dict:
    """Every operation of the window against the reference's replay. An
    operation that got no answer, or one of the wrong shape, counts each
    of its keys as missing."""
    wrong = missing = checked = 0
    for op, want in zip(win.ops, reference.expected(keys, win.ops)):
        got = op.answer
        if got is None or got.shape != want.shape:
            missing += want.size
            continue
        wrong += int(np.count_nonzero(got.astype(np.int64) != want))
        checked += want.size
    return {"wrong": wrong, "missing": missing, "checked": checked}


def program_spans(events: list, offset_ns: float) -> list:
    """The program's span records as (start_ns, end_ns, name) on the
    profiler's clock."""
    out = []
    for ev in events:
        s = ev["ts"] * 1e9 + offset_ns
        out.append((s, s + ev["dur_us"] * 1e3, ev["name"]))
    return out


def set_up(bench: Bench, cell: Cell, seed: int, t_start: float,
           make_service: Callable = Service) -> tuple:
    """Keys, the service, its warm-up and the mix's untimed requests
    -> (service, keys, warm-up seconds, planes' statics)."""
    cfg, mix = cell.config, cell.traffic
    keys, how = key_cache.load(bench, cfg)
    log(f"keys: {keys.size} {cfg['generator']} {cfg['data']}, {how} at "
        f"{time.perf_counter() - t_start:.3f} s")
    svc = make_service(cfg, keys)
    log(f"build: {svc.build_s:.3f} s")
    t0 = time.perf_counter()
    svc.warmup()
    warmup_s = time.perf_counter() - t0
    statics = svc.statics()
    log(f"warmup: {warmup_s:.3f} s; planes {statics}")
    bench.module("loops", mix["loop"]).warm(
        svc, mix, draw_for(bench, mix, keys, seed_rng(seed, 9)))
    return svc, keys, warmup_s, statics


def draw_for(bench: Bench, mix: dict, keys: np.ndarray,
             rng: np.random.Generator):
    """The mix's key draw (``draws/<distribution>.py``)."""
    return bench.module("draws", mix["distribution"]).make(mix, keys, rng)


def run_window(bench: Bench, svc, mix: dict, keys: np.ndarray, seed: int,
               seconds: float, trace: bool = False) -> traffic.Window:
    """The mix's loop (``loops/<loop>.py``) against ``svc`` for
    ``seconds``."""
    draw = draw_for(bench, mix, keys, seed_rng(seed, 1))
    return bench.module("loops", mix["loop"]).run(
        svc, mix, draw, seconds, traffic.annotation(trace))


def measure(bench: Bench, cell: Cell, seed: int, seconds: float,
            trace: bool, t_start: float, devs: list,
            make_service: Callable = Service) -> tuple[dict, dict]:
    """Set-up, window and check of one run -> (record, comparison)."""
    cfg, mix = cell.config, cell.traffic
    with Compiles() as setup_compiles:
        svc, keys, warmup_s, statics = set_up(bench, cell, seed, t_start,
                                              make_service)
    log(f"programs loaded in set-up: {setup_compiles.count}, "
        f"{setup_compiles.cached} of them from the compile cache")
    compiles = Compiles()

    from repro.obs.trace import TRACE
    trace_dir = bench.bench_dir / ".run" / TRACE_DIR
    window_span = traffic.annotation(trace)
    if trace:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # host spans, not every frame
        shutil.rmtree(trace_dir, ignore_errors=True)
        TRACE.clear()
        TRACE.enable()
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        with jax.profiler.TraceAnnotation("bench.anchor"):
            TRACE.record("bench.anchor", 0.0)
    stats0 = svc.stats()
    setup_s = time.perf_counter() - t_start
    with compiles, window_span("bench.window"):
        win = run_window(bench, svc, mix, keys, seed, seconds, trace)
    stats1 = svc.stats()
    spans = None
    if trace:
        import jax
        jax.profiler.stop_trace()
        spans = TRACE.events()
        TRACE.disable()
        TRACE.clear()
    dev = device.describe(devs)
    build_s = svc.build_s
    svc.close()
    del svc
    gc.collect()

    checked = compare(bench.reference(cfg), keys, win)
    rec = {
        "cell": cell.name, "traffic": mix["name"], "seed": seed,
        "setup_s": setup_s, "build_s": build_s, "warmup_s": warmup_s,
        "window_s": win.window_s, "attempted": win.attempted,
        "requests": len(win.ops), "failed": checked["missing"],
        "op_seconds": [op.seconds for op in win.ops
                       if op.seconds is not None],
        "stats": {k: stats1[k] - stats0[k] for k in STATS},
        "block": statics.get("block"), "statics": statics,
        "compiles_in_window": compiles.count,
        "fixed_bytes_per_lookup": (fixed_work.bytes_per_lookup(
            cfg["eps"], statics["n_shards"], statics["kind"],
            statics["static"]) if statics else None),
        "peak_gbps": device.PEAK_GBPS.get(dev["kind"]),
        "device": dev, "spans": spans,
        "trace": reduce_trace(trace_dir, spans) if trace else None,
        "errors": win.errors[:4],
    }
    return rec, checked


def reduce_trace(trace_dir, spans: list) -> dict:
    """The traced window's device reduction, the program's spans put on
    the profiler's clock by the ``bench.anchor`` pair."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(xplane.find(trace_dir)))
    anchor = xplane.find_event(pd, "bench.anchor")
    mine = [ev for ev in spans if ev["name"] == "bench.anchor"]
    offset = anchor[0] - mine[0]["ts"] * 1e9 if anchor and mine else None
    prog = program_spans([e for e in spans if e["name"] != "bench.anchor"],
                         offset) if offset is not None else []
    window = xplane.find_event(pd, "bench.window")
    return xplane.reduce(pd, window=window, extra_spans=prog)


def result(bench: Bench, cell: Cell, rec: dict, checked: dict,
           trace: bool) -> dict:
    """The run's last line: correct, attempted, failed, metrics, device,
    breakdown when traced, and last the numbers compared with their
    limits."""
    metrics = {}
    for m in cell.metrics(trace):
        value = bench.reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = dict(rec["device"])
    if trace:
        dev["busy_s"] = rec["trace"]["busy_s"]
        dev["window_s"] = rec["trace"]["window_s"]
    compared = compared_numbers(cell.config, checked)
    correct = rec["attempted"] > 0 and all(
        c["value"] <= c["limit"] for c in compared.values())
    out = {"correct": bool(correct), "attempted": int(rec["attempted"]),
           "failed": int(rec["failed"]), "metrics": metrics, "device": dev}
    if trace:
        out["breakdown"] = xplane.breakdown(rec["trace"])
    out["compared"] = compared
    return out


def compared_numbers(cfg: dict, checked: dict) -> dict:
    """Each number that decides ``correct``, with the limit the
    configuration states: answers that are wrong or never came."""
    return {"inexact_answers": {
        "value": checked["wrong"] + checked["missing"],
        "limit": cfg["limits"]["inexact_answers"]}}


def report(rec: dict, checked: dict) -> None:
    """The run's readings on standard error, before the compared numbers."""
    log(f"setup: {rec['setup_s']:.3f} s (build {rec['build_s']:.3f} s, "
        f"warmup {rec['warmup_s']:.3f} s)")
    log(f"window: {rec['window_s']:.3f} s, {rec['requests']} requests, "
        f"{rec['attempted']} keys, stats {rec['stats']}, compiles in "
        f"window {rec['compiles_in_window']}")
    if rec["op_seconds"]:
        t = np.asarray(rec["op_seconds"])
        log(f"request seconds: min {t.min():.6f} median "
            f"{np.median(t):.6f} max {t.max():.6f}")
    for i, e in rec["errors"]:
        log(f"request {i} failed: {e}")
    log(f"checked {checked['checked']} answers against the reference: "
        f"{checked['wrong']} wrong, {checked['missing']} never came")


def print_compared(line: dict) -> None:
    for name, c in line["compared"].items():
        log(f"compared: {name} {c['value']} (limit {c['limit']})")


class Control:
    """The configuration's control in the program's place: the operations
    of its reference computed in the nearest lower precision
    (``references/<name>.control``). Its answers have to come out as not
    correct."""

    build_s = 0.0

    def __init__(self, ops: dict):
        for kind, fn in ops.items():
            setattr(self, kind, fn)

    def warmup(self) -> None:
        pass

    def stats(self) -> dict:
        return dict.fromkeys(STATS, 0)

    def statics(self) -> dict:
        return {}

    def close(self) -> None:
        pass
