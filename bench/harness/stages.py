"""The stacked pipeline's device time by stage, from a profiler trace.

The serving program names its stages with ``jax.named_scope``:
``plex.route``, ``plex.segment``, ``plex.probe`` and ``plex.fold``. A scope
reaches the compiled HLO as each instruction's ``op_name`` metadata. The
trace's op events do not carry it (an ``XLA Ops`` event is named by its
instruction's text, ``%fusion.22 = ...``), but its ``/host:metadata``
plane holds the optimized HLO of every program that ran, a serialized
``HloProto`` under the stat ``Hlo Proto``, keyed by the name the
``XLA Modules`` line gives the program (``jit_traced(<id>)``).

Each instruction takes the innermost ``plex.*`` scope of its ``op_name``,
or ``UNSCOPED`` without one; a fusion carries the ``op_name`` XLA gives it,
its fused root's. An op belongs to the pipeline module whose event on the
same device holds the op's start. A program without the scopes (one
older than them) reads as all ``UNSCOPED``, and a stage metric then
reads nothing.
"""
from __future__ import annotations

import collections
import functools
import pathlib
import re

from . import xplane
from .cell_run import TRACE_DIR
from .pipeline import PIPELINE_MODULE

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
UNSCOPED = "(unscoped)"
STAGE_PREFIX = "plex."
WINDOW_SPAN = "bench.window"
TRACE_SUBDIR = (".run", TRACE_DIR)    # where ``cell_run.measure`` traces

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = ')
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            v, i = buf[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _map_entry(buf) -> tuple:
    e = dict(_fields(buf))
    return e.get(1), e.get(2)


def module_hlo(path) -> dict[str, str]:
    """``{program name as the trace gives it: optimized HLO text}`` from
    the trace's metadata plane (XSpace.planes = 1; XPlane name = 2,
    event_metadata = 4, stat_metadata = 5; XEventMetadata name = 2,
    stats = 5; XStat metadata_id = 1, bytes_value = 6; HloProto
    hlo_module = 1)."""
    from jax._src.lib import xla_client
    data = memoryview(pathlib.Path(path).read_bytes())
    out = {}
    for f, plane in _fields(data):
        if f != 1:
            continue
        pf = list(_fields(plane))
        if not any(k == 2 and bytes(v) == METADATA_PLANE.encode()
                   for k, v in pf):
            continue
        stat_names = {}
        for k, v in pf:
            if k == 5:
                sid, meta = _map_entry(v)
                names = [bytes(x).decode() for kk, x in _fields(meta)
                         if kk == 2]
                stat_names[sid] = names[0] if names else ""
        for k, v in pf:
            if k != 4:
                continue
            _, meta = _map_entry(v)
            name, proto = None, None
            for kk, x in _fields(meta):
                if kk == 2:
                    name = bytes(x).decode()
                elif kk == 5:
                    st = dict(_fields(x))
                    if stat_names.get(st.get(1)) == HLO_PROTO_STAT:
                        proto = st.get(6)
            if name and proto is not None:
                mod = dict(_fields(proto)).get(1)
                if mod is not None:
                    out[name] = xla_client._xla.HloModule \
                        .from_serialized_hlo_module_proto(bytes(mod)) \
                        .to_string()
    return out


def stage_of_hlo(text: str) -> dict[str, str]:
    """``{instruction name: stage}`` over an HLO module's text."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line)
        scoped = [p for p in (op.group(1).split("/") if op else ())
                  if p.startswith(STAGE_PREFIX)]
        out[m.group(1)] = scoped[-1] if scoped else UNSCOPED
    return out


def instruction(op_event_name: str) -> str:
    """The instruction name of an ``XLA Ops`` event (``%fusion.22 = ...``
    -> ``fusion.22``)."""
    return op_event_name.split(" = ", 1)[0].strip().lstrip("%")


def stage_seconds(pd, hlo: dict[str, str],
                  window: tuple[float, float] | None,
                  module: str = PIPELINE_MODULE) -> dict[str, float]:
    """Device seconds of ``module``'s ops in ``window`` by stage, summed
    over the devices (an op counts whole when it overlaps the window, as
    in ``xplane.reduce``). ``hlo`` is ``module_hlo``'s result."""
    maps = {name: stage_of_hlo(text) for name, text in hlo.items()
            if name.split("(")[0] == module}
    out: dict[str, float] = collections.defaultdict(float)
    for plane in pd.planes:
        if not plane.name.startswith(xplane.DEVICE_PREFIX):
            continue
        mods, ops = [], []
        for line in plane.lines:
            if line.name == xplane.MODULES_LINE:
                mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                               ev.name) for ev in line.events
                              if ev.name in maps)
            elif line.name == xplane.OPS_LINE:
                ops = sorted((ev.start_ns, ev.duration_ns, ev.name)
                             for ev in line.events)
        j = 0
        for s, dur, name in ops:
            if window and (s + dur <= window[0] or s >= window[1]):
                continue
            while j < len(mods) and mods[j][1] < s:
                j += 1
            if j == len(mods) or mods[j][0] > s:
                continue                  # not inside a pipeline module
            stage = maps[mods[j][2]].get(instruction(name), UNSCOPED)
            out[stage] += dur * 1e-9
    return dict(out)


@functools.lru_cache(maxsize=2)
def _run_stages(path: str, mtime_ns: int) -> dict[str, float]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return stage_seconds(pd, module_hlo(path),
                         xplane.find_event(pd, WINDOW_SPAN))


def run_stages(bench_dir) -> dict[str, float]:
    """Stage seconds of the traced run's window, from the trace that
    ``cell_run.measure`` left under ``bench_dir``."""
    path = xplane.find(pathlib.Path(bench_dir).joinpath(*TRACE_SUBDIR))
    return _run_stages(str(path), path.stat().st_mtime_ns)


def ns_per_lookup(rec: dict, bench_dir, names: tuple[str, ...]):
    """Device nanoseconds per lookup of the stages ``names`` in a traced
    run, or ``None`` untraced or where the program has none of them."""
    if not rec["trace"] or not rec["attempted"]:
        return None
    by = run_stages(bench_dir)
    if not any(n in by for n in names):
        return None
    return sum(by.get(n, 0.0) for n in names) * 1e9 / rec["attempted"]
