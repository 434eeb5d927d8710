"""Span-based tracer with context propagation and Chrome trace export.

Spans nest through a ``contextvars`` context: a span opened while
another is active becomes its child and inherits the trace id; a span
opened with no active context starts a fresh trace.  Cross-thread
hand-offs (the sweep's dispatch pool, the micro-batcher worker) pass
the parent explicitly via ``parent=tracer.current()`` since context
vars do not flow into pre-existing pool threads.

Finished spans land in a bounded ring (``GATEKEEPER_TRACE_RING``,
default 4096) so memory is flat no matter how long the process runs;
open spans are tracked separately so a crash dump can include the
in-flight sweep.  ``export()`` renders the ring as Chrome trace-event
JSON (``ph:"X"`` complete events, microsecond timestamps) which
Perfetto and chrome://tracing load directly.

While a ``jax.profiler`` session records, every span also opens a host
TraceMe (``jax.profiler.TraceAnnotation``) under the span's own name,
with its string and number args as stats, so the spans land in the
profile's ``.xplane.pb`` on the device ops' clock.  With no session
that costs one check per span, and jax is never imported here: a
process that has not loaded jax cannot be profiling.

Tracing is on by default; ``GATEKEEPER_TRACE=off`` kills it, making
``span()`` a no-op yielding ``None`` and emitting no profiler event.
On the host of one TPU v5e a span costs 5.2 us with no profiler
session (2.6 us with tracing off: the context manager alone) and 26 us
while a session records.  A capped audit of 150,000 Pods opens 18-20,
about 0.1 ms of a 0.35 s memoized sweep; ``PERF.md`` has the
end-to-end runs.

Importing this module registers a context provider with
``utils.log`` so every structured log line emitted under a span
carries ``trace=<id> span=<id>``.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import json
import os
import sys
import threading
import time
from typing import Any, Iterator, Optional, Tuple

from gatekeeper_tpu.utils import log as _log

# (trace_id, span_id) of the innermost active span on this context
_CTX: contextvars.ContextVar[Optional[Tuple[str, int]]] = \
    contextvars.ContextVar("gatekeeper_span", default=None)

_span_ids = itertools.count(1)
_trace_ids = itertools.count(1)


class Span:
    """One timed region. ``args`` may be mutated while the span is
    open to attach results (e.g. allowed/denied) post-hoc."""

    __slots__ = ("name", "cat", "trace_id", "span_id", "parent_id",
                 "t0_us", "dur_us", "tid", "args", "_tm")

    def __init__(self, name: str, cat: str, trace_id: str, span_id: int,
                 parent_id: int, t0_us: float, tid: int, args: dict):
        self.name = name
        self.cat = cat
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0_us = t0_us
        self.dur_us: Optional[float] = None  # None while open
        self.tid = tid
        self.args = args
        self._tm = None       # the profiler's TraceMe while open

    def event(self, now_us: Optional[float] = None) -> dict:
        """Chrome trace-event dict (ph "X" complete event)."""
        dur = self.dur_us
        args = dict(self.args)
        args["trace_id"] = self.trace_id
        args["span_id"] = self.span_id
        if self.parent_id:
            args["parent_span_id"] = self.parent_id
        if dur is None:  # still open: clamp to "now", flag it
            dur = max(0.0, (now_us or self.t0_us) - self.t0_us)
            args["incomplete"] = True
        return {
            "name": self.name, "cat": self.cat, "ph": "X",
            "ts": round(self.t0_us, 3), "dur": round(dur, 3),
            "pid": os.getpid(), "tid": self.tid, "args": args,
        }


class Tracer:
    """Process-wide span collector.  Thread-safe; near-zero cost when
    ``enabled`` is False (one attribute check per span site)."""

    def __init__(self, ring: Optional[int] = None):
        if ring is None:
            ring = int(os.environ.get("GATEKEEPER_TRACE_RING", "4096"))
        self._lock = threading.Lock()
        self._done: collections.deque[Span] = collections.deque(maxlen=ring)
        self._open: dict[int, Span] = {}
        self._epoch = time.perf_counter()
        self.enabled = os.environ.get("GATEKEEPER_TRACE", "on") != "off"

    # -- clock -------------------------------------------------------
    def _now_us(self) -> float:
        return (time.perf_counter() - self._epoch) * 1e6

    # -- context -----------------------------------------------------
    def current(self) -> Optional[Tuple[str, int]]:
        """(trace_id, span_id) of the active span, for explicit
        cross-thread parenting."""
        return _CTX.get()

    def current_trace_id(self) -> Optional[str]:
        ctx = _CTX.get()
        return ctx[0] if ctx else None

    # -- span lifecycle ----------------------------------------------
    def begin(self, name: str, cat: str = "host",
              parent: Optional[Tuple[str, int]] = None, root: bool = False,
              **args: Any) -> Optional[Span]:
        """Open a span without entering its context, for a region that
        ends at several exits; close it with ``end`` on the same thread.
        Spans opened meanwhile do not nest under it.  ``root`` starts a
        new trace whatever span is active."""
        if not self.enabled:
            return None
        ctx = None if root else (parent if parent is not None
                                 else _CTX.get())
        if ctx is None:
            trace_id = f"t{next(_trace_ids):06d}.{os.getpid()}"
            parent_id = 0
        else:
            trace_id, parent_id = ctx
        sid = next(_span_ids)
        sp = Span(name, cat, trace_id, sid, parent_id, self._now_us(),
                  threading.get_ident() & 0xFFFFFFFF, dict(args))
        sp._tm = _profiler_event(name, args)
        with self._lock:
            self._open[sid] = sp
        return sp

    def end(self, sp: Optional[Span]) -> None:
        """Close a span opened by ``begin`` (None: tracing was off)."""
        if sp is None:
            return
        sp.dur_us = max(0.0, self._now_us() - sp.t0_us)
        tm, sp._tm = sp._tm, None
        if tm is not None:
            tm.__exit__(None, None, None)
        with self._lock:
            self._open.pop(sp.span_id, None)
            self._done.append(sp)

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "host",
             parent: Optional[Tuple[str, int]] = None, root: bool = False,
             **args: Any) -> Iterator[Optional[Span]]:
        sp = self.begin(name, cat, parent, root, **args)
        if sp is None:
            yield None
            return
        token = _CTX.set((sp.trace_id, sp.span_id))
        try:
            yield sp
        finally:
            _CTX.reset(token)
            self.end(sp)

    def add_complete(self, name: str, cat: str, t0: float, t1: float,
                     parent: Optional[Tuple[str, int]] = None,
                     **args: Any) -> None:
        """Record an already-measured region (``t0``/``t1`` are
        ``time.perf_counter()`` values) as a complete span, for code
        that meters itself.  It never reaches the profiler's timeline:
        use ``span`` or ``begin``/``end`` where that matters."""
        if not self.enabled:
            return
        ctx = parent if parent is not None else _CTX.get()
        if ctx is None:
            trace_id = f"t{next(_trace_ids):06d}.{os.getpid()}"
            parent_id = 0
        else:
            trace_id, parent_id = ctx
        sp = Span(name, cat, trace_id, next(_span_ids), parent_id,
                  (t0 - self._epoch) * 1e6,
                  threading.get_ident() & 0xFFFFFFFF, dict(args))
        sp.dur_us = max(0.0, (t1 - t0) * 1e6)
        with self._lock:
            self._done.append(sp)

    # -- export ------------------------------------------------------
    def export(self, trace_id: Optional[str] = None) -> dict:
        """Chrome trace-event JSON object.  Open spans are included as
        clamped-to-now complete events flagged ``incomplete`` so a
        mid-sweep dump still shows the sweep's span tree."""
        now = self._now_us()
        with self._lock:
            spans = list(self._done) + list(self._open.values())
        if trace_id is not None:
            spans = [s for s in spans if s.trace_id == trace_id]
        return {
            "traceEvents": [s.event(now) for s in spans],
            "displayTimeUnit": "ms",
        }

    def export_json(self, trace_id: Optional[str] = None) -> str:
        return json.dumps(self.export(trace_id), sort_keys=True)

    def reset(self) -> None:
        """Drop all recorded spans (tests)."""
        with self._lock:
            self._done.clear()
            self._open.clear()


_annotation: Any = None    # jax.profiler.TraceAnnotation, once jax is loaded


def _profiler_event(name: str, args: dict) -> Any:
    """An entered host TraceMe named ``name`` while a ``jax.profiler``
    session records, else None.  String and number args become its
    stats, so the event name stays the span's own."""
    global _annotation
    cls = _annotation
    if cls is None:
        prof = sys.modules.get("jax.profiler")
        cls = getattr(prof, "TraceAnnotation", None)
        if cls is None:
            return None
        _annotation = cls
    if not cls.is_enabled():
        return None
    tm = cls(name, **{k: v for k, v in args.items()
                      if isinstance(v, (str, int, float))})
    tm.__enter__()
    return tm


_tracer: Optional[Tracer] = None
_tracer_lock = threading.Lock()


def get_tracer() -> Tracer:
    global _tracer
    if _tracer is None:
        with _tracer_lock:
            if _tracer is None:
                _tracer = Tracer()
    return _tracer


def _log_context() -> Optional[dict]:
    ctx = _CTX.get()
    if ctx is None:
        return None
    return {"trace": ctx[0], "span": ctx[1]}


_log.set_context_provider(_log_context)
