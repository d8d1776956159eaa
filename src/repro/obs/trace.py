"""Span-based pipeline tracing with a bounded JSON-lines event log.

Usage at a hook site::

    with TRACE.span("serve.dispatch", n=q.size):
        ...                               # timed body

Spans nest per thread. Every emitted event carries its own ``id``, the
``parent`` span's id (``None`` at the root), ``depth`` (the nesting level
at entry) and, under a request, the ``request`` id: ``TRACE.request``
opens a span that also starts a request (``serve.lookup``,
``serve.submit``), and every span, record and event nested under it
inherits that id. Two post-hoc forms cover work that was timed
elsewhere: ``record(name, dur_s, **attrs)`` emits a span that *ended now*
with a known duration (queue waits, a compile), whose parent is the
innermost open span that began before it did; ``event(name, **attrs)``
emits a zero-duration marker (breaker transitions, CPU-seconds summed
over parallel build workers, which are no interval on any clock).

While enabled, every ``span`` also opens a ``jax.profiler.TraceAnnotation``
of the same name, so a profiler trace holds the span on the profiler's
own clock in its host plane (post-hoc records cannot be back-dated there).
The annotation is bound in ``enable()`` when jax is already imported:
this module never imports jax itself.

Disabled (default), ``span`` returns one shared null context manager and
``record``/``event`` return immediately — a hook site costs an attribute
read and a predictable branch, never an allocation. Enabled, events append
to a bounded deque (thread-safe by CPython contract), so a long soak
keeps the newest ``maxlen`` events instead of growing without bound.
``timed(name, into)`` is the always-on form for set-up phases: it adds the
phase's wall seconds to a dict whether or not tracing is on, and is a span
when it is.

``sample_n`` is the always-on production dial (the flight recorder sets
it when armed): with ``sample_n = N > 1``, ``span`` and ``record`` keep
every Nth call per thread and the rest cost one thread-local counter
bump — no ``_Span`` allocation, no deque append. ``event`` is never
sampled: events mark rare state transitions (breaker opens, SLO
breaches) that an incident bundle must not miss.

The span taxonomy threaded through the repo (see README "Observability"):

    serve.lookup / serve.submit (requests) / serve.queue_wait /
    serve.staging / serve.dispatch / serve.sync / serve.drain
    build.pool / build.shards / build.assemble (spans)
    build.shard / build.spline / build.tune / build.layer (events, cpu_s)
    warmup.planes / warmup.compile (program="plain"|"merged")
    merge.capture / merge.build / merge.publish
    wal.append / wal.fsync / persist.open / breaker.transition

and, inside the compiled serving program, the device scopes
``plex.route`` / ``plex.segment`` / ``plex.probe`` / ``plex.fold``
(``jax.named_scope``: HLO ``op_name`` metadata, read back by
``kernels.planes.BoundPlanes.stage_of_ops``).
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import json
import sys
import threading
import time

__all__ = ["TRACE", "Tracer"]

DEFAULT_MAXLEN = 65536


def _jsonable(v):
    """Coerce an attr value to something json.dumps accepts (numpy scalars
    arrive from counter folds and jax sync points)."""
    if isinstance(v, (str, bool, int, float)) or v is None:
        return v
    if hasattr(v, "item"):
        try:
            return v.item()
        except Exception:           # pragma: no cover - exotic array attr
            pass
    return repr(v)


class _NullSpan:
    """The shared disabled-path context manager (no state, no allocation)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _Span:
    __slots__ = ("_tr", "name", "attrs", "_t0", "_depth", "_ann", "id",
                 "parent", "request")

    def __init__(self, tr: "Tracer", name: str, attrs: dict,
                 request: bool = False):
        self._tr = tr
        self.name = name
        self.attrs = attrs
        self.id = next(tr._ids)
        self.request = self.id if request else None

    def __enter__(self):
        stack = self._tr._stack()
        self._depth = len(stack)
        top = stack[-1] if stack else None
        self.parent = top.id if top is not None else None
        if self.request is None and top is not None:
            self.request = top.request
        stack.append(self)
        ann = self._tr._annotation
        self._ann = ann(self.name) if ann is not None else None
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        stack = self._tr._stack()
        # Truncate back to this span's frame rather than popping only an
        # exact top-of-stack match: a mismatched or exception-crossed exit
        # (inner span leaked by a generator, exits out of order) must not
        # leave stale frames inflating every later span's depth. Identity
        # scan from the top — the common case is still one comparison.
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is self:
                del stack[i:]
                break
        self._tr._emit(self.name, self._t0, dur, self._depth, self.attrs,
                       self.id, self.parent, self.request)
        return False                # exceptions propagate; the span records


class Tracer:
    """Process-global span recorder (see the module docstring)."""

    def __init__(self, maxlen: int = DEFAULT_MAXLEN):
        self.enabled = False
        self.sample_n = 1          # keep 1-in-N spans/records per thread
        self._events: collections.deque = collections.deque(maxlen=maxlen)
        self._tls = threading.local()
        self._ids = itertools.count(1)   # span / event ids, process-unique
        self._annotation = None          # jax.profiler.TraceAnnotation
        # perf_counter -> wall-clock offset, so exported timestamps are
        # epoch seconds while in-process timing stays monotonic
        self._wall_offset = time.time() - time.perf_counter()

    def enable(self) -> None:
        jax = sys.modules.get("jax")
        self._annotation = (jax.profiler.TraceAnnotation
                            if jax is not None else None)
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _sampled(self) -> bool:
        """Per-thread 1-in-``sample_n`` admission (True when unsampled)."""
        n = self.sample_n
        if n <= 1:
            return True
        c = getattr(self._tls, "ctr", 0) + 1
        self._tls.ctr = c
        return c % n == 0

    def span(self, name: str, **attrs):
        """Timed context manager; the shared null context when disabled
        (and for the skipped fraction under ``sample_n`` sampling)."""
        if not self.enabled or not self._sampled():
            return _NULL
        return _Span(self, name, attrs)

    def request(self, name: str, **attrs):
        """``span`` that also opens a request id (its own span id), which
        every span, record and event nested under it inherits."""
        if not self.enabled or not self._sampled():
            return _NULL
        return _Span(self, name, attrs, request=True)

    @contextlib.contextmanager
    def timed(self, name: str, into: dict, **attrs):
        """Always-on phase timing: adds the body's wall seconds to
        ``into[name]``, and is a ``span`` of the same name while enabled.
        For set-up phases, never for a per-request path."""
        t0 = time.perf_counter()
        try:
            with self.span(name, **attrs):
                yield
        finally:
            into[name] = into.get(name, 0.0) + time.perf_counter() - t0

    def _under(self, t0: float):
        """The innermost open span that began at or before ``t0``."""
        for sp in reversed(self._stack()):
            if sp._t0 <= t0:
                return sp
        return None

    def record(self, name: str, dur_s: float, **attrs) -> None:
        """Post-hoc span that ended now with a known duration."""
        if not self.enabled or not self._sampled():
            return
        t1 = time.perf_counter()
        self._emit_under(name, t1 - dur_s, dur_s, attrs)

    def event(self, name: str, **attrs) -> None:
        """Zero-duration marker (state transitions, one-shot facts)."""
        if not self.enabled:
            return
        self._emit_under(name, time.perf_counter(), 0.0, attrs)

    def _emit_under(self, name: str, t0: float, dur_s: float,
                    attrs: dict) -> None:
        top = self._under(t0)
        depth = self._stack().index(top) + 1 if top is not None else 0
        self._emit(name, t0, dur_s, depth, attrs, next(self._ids),
                   top.id if top is not None else None,
                   top.request if top is not None else None)

    def _emit(self, name: str, t0: float, dur_s: float, depth: int,
              attrs: dict, span_id: int, parent: int | None,
              request: int | None) -> None:
        ev = {
            "name": name,
            "ts": round(self._wall_offset + t0, 6),
            "dur_us": round(dur_s * 1e6, 3),
            "depth": depth,
            "thread": threading.current_thread().name,
            "id": span_id,
            "parent": parent,
        }
        if request is not None:
            ev["request"] = request
        if attrs:
            ev["attrs"] = {k: _jsonable(v) for k, v in attrs.items()}
        self._events.append(ev)

    # -- inspection / export -------------------------------------------------
    def events(self) -> list[dict]:
        """Snapshot of the recorded events, oldest first."""
        return list(self._events)

    def span_names(self) -> set[str]:
        return {ev["name"] for ev in self._events}

    def clear(self) -> None:
        self._events.clear()

    def to_jsonl(self) -> str:
        """The event log as JSON lines (one event per line)."""
        return "\n".join(json.dumps(ev, sort_keys=True)
                         for ev in self._events)


# THE process-global tracer every hook site records into
TRACE = Tracer()
