"""Reduce a profiler trace (``.xplane.pb``) to the device's share of work.

``jax.profiler.ProfileData`` reads the trace. A device plane is one whose
name starts with ``/device:``; on it, the events of the ``XLA Ops`` line
are the operations that ran, and those of the ``XLA Modules`` line the
compiled programs they belong to, by the name the trace gives them.

Busy time is the union of a device's operation intervals inside the
traced window, averaged over the devices; idle is the rest of the window.
Each idle gap is put down to the innermost host span open at its middle:
the profiler's own host events and the harness's annotations, plus any
spans the caller supplies on the same clock.
"""
from __future__ import annotations

import collections
import pathlib
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:"
HOST_PREFIX = "/host:"
TOP = 10


def find(trace_dir: pathlib.Path) -> pathlib.Path:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def host_events(pd) -> list[tuple[float, float, str]]:
    """(start_ns, end_ns, name) of every host-plane event."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith(HOST_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name))
    return out


def find_event(pd, name: str) -> tuple[float, float] | None:
    """(start_ns, end_ns) of the first host event called ``name``."""
    for s, e, n in host_events(pd):
        if n == name:
            return s, e
    return None


def _innermost(spans: list, points: list) -> list[str]:
    """Name of the shortest span open at each of the sorted ``points``
    (a sweep: spans sorted by start, the open ones kept in a list)."""
    names, open_, i = [], [], 0
    for x in points:
        while i < len(spans) and spans[i][0] <= x:
            open_.append(spans[i])
            i += 1
        open_ = [sp for sp in open_ if sp[1] >= x]
        inner = min(open_, key=lambda sp: sp[1] - sp[0], default=None)
        names.append(inner[2] if inner else "(no host span)")
    return names


def reduce(pd, window: tuple[float, float] | None = None,
           extra_spans: list[tuple[float, float, str]] = ()) -> dict:
    """Per-device busy time, per-op and per-program device time, and idle
    gaps by host span, within ``window`` (start_ns, end_ns; default: from
    the first to the last device event).

    Returns ``window_s``, ``busy_s`` (mean over devices), ``devices``,
    ``ops`` ({name: seconds}), ``modules`` ({name: [seconds, count]}) and
    ``idle_by_host`` ({host span: idle seconds})."""
    devices = [p for p in pd.planes if p.name.startswith(DEVICE_PREFIX)
               and any(line.name == OPS_LINE for line in p.lines)]
    if not devices:
        raise ValueError("the trace holds no device plane with XLA ops")
    per_dev = []
    ops: dict[str, float] = collections.defaultdict(float)
    modules: dict[str, list] = collections.defaultdict(lambda: [0.0, 0])
    for plane in devices:
        ivs = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if window and (e <= window[0] or s >= window[1]):
                    continue
                if line.name == OPS_LINE:
                    ivs.append((s, e))
                    ops[ev.name] += ev.duration_ns * 1e-9
                else:
                    modules[ev.name][0] += ev.duration_ns * 1e-9
                    modules[ev.name][1] += 1
        per_dev.append(ivs)
    if window is None:
        flat = [iv for ivs in per_dev for iv in ivs]
        window = (min(s for s, _ in flat), max(e for _, e in flat))
    lo, hi = window
    busy = [_union(_clip(ivs, lo, hi)) for ivs in per_dev]
    busy_s = sum(sum(e - s for s, e in b) for b in busy) / len(busy) * 1e-9

    gaps = []
    for b in busy:
        edges = [lo] + [x for iv in b for x in iv] + [hi]
        gaps += [((gs + ge) / 2, ge - gs)
                 for gs, ge in zip(edges[0::2], edges[1::2]) if ge > gs]
    idle: dict[str, float] = collections.defaultdict(float)
    for (mid, length), name in zip(sorted(gaps), _innermost(
            sorted(list(host_events(pd)) + list(extra_spans)),
            sorted(mid for mid, _ in gaps))):
        idle[name] += length * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s,
        "devices": len(devices),
        "ops": dict(ops),
        "modules": {k: list(v) for k, v in modules.items()},
        "idle_by_host": {k: v / len(busy) for k, v in idle.items()},
    }


def short_name(op: str, width: int = 160) -> str:
    """An XLA op's trace name without layouts and index comments: the
    instruction, its result type and operands, cut to ``width``."""
    op = re.sub(r"\{[^{}]*\}|/\*[^*]*\*/", "", op)
    return op if len(op) <= width else op[:width - 3] + "..."


def breakdown(red: dict) -> dict:
    """The result line's ``breakdown``: the device operations that took most
    time and the host spans that the longest idle time fell in."""
    def top(d: dict) -> list:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:TOP]]
    ops: dict[str, float] = collections.defaultdict(float)
    for name, seconds in red["ops"].items():
        ops[short_name(name)] += seconds
    return {"device_ops": top(ops),
            "idle_gaps": top(red["idle_by_host"])}
