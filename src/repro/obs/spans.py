"""Structured spans: nested, thread-safe, cross-process phase timing.

A span is one timed phase of the pipeline (``engine.exact``, ``pool.chunk``,
``serve.price`` ...) recorded as a context manager:

    with obs.span("engine.sweep", kind="pruned") as sp:
        ...
        sp.add(cells=12)           # counters attached at exit

Design constraints (DESIGN.md §14):

  * **off by default, near-zero overhead** — ``span()`` performs one
    module-global flag check when telemetry is disabled, plus, once ``jax``
    is loaded, one call of the profiler's ``TraceMe.is_enabled``, and
    returns a shared no-op singleton; no allocation beyond the caller's
    kwargs, no locking, no clock reads.  The overhead contract (<2%
    disabled on the paper-grid cold sweep) is gated by
    ``benchmarks/bench_obs.py``;
  * **thread safety** — finished records append under one lock; the active
    span stack is thread-local, so concurrent scheduler/client threads nest
    independently;
  * **follows the JAX profiler** — while a JAX profiler session captures
    in the process, spans record exactly as if ``enable()`` had been
    called, and each is mirrored as a ``jax.profiler.TraceAnnotation``
    named ``repro.<span name>`` whose metadata carries its ``span_id``,
    ``obs_t0_us`` and ``obs_pair_us``: a (monotonic clock, profiler clock)
    pair, with its width, that places any record, shipped from another
    process too, on the device trace.  obs
    imports nothing: the check is looked up only once ``jax`` is loaded;
  * **request scopes** — a :class:`Scope` records one request's spans
    (across the threads that handle it) into a buffer of its own, without
    turning on process-wide collection; the pricing daemon returns that
    buffer in the request's result line.  While a scope is open, or the
    profiler captures, each collector pause is a ``gc.collect`` span;
  * **cross-process merge** — timestamps are ``time.perf_counter_ns`` based
    (CLOCK_MONOTONIC on Linux: one clock across fork/spawn children on the
    same host), so pool-worker spans shipped back with chunk results align
    with the parent timeline.  ``current_context()`` captures the parent
    identity that travels in task metadata; a worker records its chunk on
    a :class:`Scope` of that context and returns the scope's records with
    the chunk — the same env/metadata discipline as
    ``faults.ensure_env_plan``, and nothing stays behind in the worker.

Records are plain named tuples — cheap to pickle across the pool boundary
and stable for exporters (``obs.export``).
"""
from __future__ import annotations

import contextlib
import gc
import itertools
import os
import sys
import threading
import time
from typing import NamedTuple


class SpanRecord(NamedTuple):
    """One finished span.  Times are microseconds; ``t0_us`` is on the
    host-wide monotonic clock so records from different processes share a
    timeline."""

    name: str
    cat: str
    trace_id: str
    span_id: str          # "<pid hex>:<seq>" — unique across processes
    parent_id: str | None
    pid: int
    tid: int
    t0_us: float
    dur_us: float
    cpu_us: float         # thread CPU time consumed inside the span
    args: dict


_enabled = False
# the disabled path's one flag: process-wide collection is on, or some
# request scope is open in this process
_on = False
_lock = threading.Lock()
_records: list = []
_trace_id: str | None = None
_ids = itertools.count(1)
_owner_pid = os.getpid()
_tls = threading.local()
_open_scopes: list = []     # Scope objects open in this process
# the JAX profiler's "is a session capturing" check and its annotation
# class, looked up once jax is loaded (obs itself imports nothing)
_capturing = None
_Annotation = None


def _fork_check() -> None:
    """Reset inherited collector state in a forked child.

    A fork()ed pool worker inherits the parent's finished records and the
    forking thread's span stack; both belong to the parent's timeline, so
    the first touch in a new pid starts clean (the parent keeps its own
    copies untouched)."""
    global _owner_pid, _records, _open_scopes, _on
    if os.getpid() != _owner_pid:
        _owner_pid = os.getpid()
        _records = []
        _open_scopes = []
        _on = _enabled
        _tls.__dict__.clear()


def _never() -> bool:
    return False


def _find_profiler():
    """The profiler's capture check once jax is loaded (cached from then
    on); a check that always says no before."""
    global _capturing, _Annotation
    if "jax" not in sys.modules:
        return _never
    lib = getattr(sys.modules.get("jax._src.lib"), "_profiler", None)
    trace_me = getattr(lib, "TraceMe", None)
    annotation = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation",
                         None)
    if trace_me is None or annotation is None:
        return _never       # jax still importing: look again next time
    _Annotation = annotation
    _capturing = trace_me.is_enabled
    return _capturing


def profiling() -> bool:
    """True while a JAX profiler session captures in this process."""
    return (_capturing or _find_profiler())()


def _ensure_trace_id() -> str:
    global _trace_id
    if _trace_id is None:
        _trace_id = f"{os.getpid():x}-{time.time_ns():x}"
    return _trace_id


def _new_id() -> str:
    # a colon, not a dot: the profiler reads an annotation's metadata value
    # that looks like a number as one ("127.10" would come back as 127.1)
    return f"{os.getpid():x}:{next(_ids)}"


def enabled() -> bool:
    return _enabled


def enable() -> None:
    """Turn span collection on (idempotent; keeps existing records)."""
    global _enabled, _on
    _fork_check()
    _ensure_trace_id()
    _enabled = _on = True


def disable() -> None:
    """Stop collecting (records already gathered are kept until reset)."""
    global _enabled, _on
    _enabled = False
    _on = bool(_open_scopes)


def reset() -> None:
    """Drop every collected record (enablement is unchanged)."""
    _fork_check()
    with _lock:
        _records.clear()


def spans() -> list:
    """Snapshot of the finished records collected so far."""
    _fork_check()
    with _lock:
        return list(_records)


def ingest(records) -> None:
    """Merge records harvested elsewhere (pool workers, remote daemons)
    into this process's timeline, or into the request scope active in
    this thread."""
    if not records:
        return
    _fork_check()
    recs = [r if isinstance(r, SpanRecord) else SpanRecord(*r)
            for r in records]
    scope = getattr(_tls, "scope", None)
    if scope is not None:
        scope.records.extend(recs)
        return
    with _lock:
        _records.extend(recs)


# ---------------------------------------------------------------------------
# Context propagation (fork and spawn workers alike)
# ---------------------------------------------------------------------------
def current_context() -> tuple | None:
    """(trace_id, parent span id) identifying the innermost active span.

    None when nothing records here — callers pass the context through task
    metadata (pickled with the chunk) or a request line, so a disabled
    sweep ships nothing.  Inside a request scope with no span open, the
    parent is the scope's root.
    """
    stack = getattr(_tls, "stack", None)
    if stack:
        return (stack[-1].trace_id, stack[-1].span_id)
    scope = getattr(_tls, "scope", None)
    if scope is not None:
        return (scope.trace_id, scope.root_id)
    if _enabled or profiling():
        return (_ensure_trace_id(), None)
    return None


class _NullSpan:
    """Shared disabled-path singleton: every method is a no-op."""

    __slots__ = ()
    enabled = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def add(self, **counters):
        pass


_NULL = _NullSpan()


def _mirror(name: str, span_id: str) -> tuple:
    """Open the profiler annotation ``repro.<name>`` and read the monotonic
    clock: (annotation, t0 ns).

    The annotation's start lies between two clock readings taken around
    its opening; the later one is the span's start (``obs_t0_us``), and
    their distance (``obs_pair_us``) bounds how far the pair can misplace
    the clocks' offset.  It is a few microseconds, unless a collection or
    a thread switch falls between the two readings."""
    mirror = _Annotation(f"repro.{name}")
    pre = time.perf_counter_ns()
    mirror.__enter__()
    t0 = time.perf_counter_ns()
    mirror.set_metadata(span_id=span_id, obs_t0_us=t0 / 1e3,
                        obs_pair_us=(t0 - pre) / 1e3)
    return mirror, t0


class _Span:
    __slots__ = ("name", "cat", "args", "trace_id", "span_id", "parent_id",
                 "_t0", "_cpu0", "_scope", "_mirror")
    enabled = True

    def __init__(self, name: str, cat: str, args: dict):
        self.name = name
        self.cat = cat
        self.args = args

    def add(self, **counters) -> None:
        """Attach counters/attributes; they ride in the record's args."""
        self.args.update(counters)

    def __enter__(self):
        _fork_check()
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        scope = self._scope = getattr(_tls, "scope", None)
        if stack:
            self.trace_id = stack[-1].trace_id
            self.parent_id = stack[-1].span_id
        elif scope is not None:
            self.trace_id, self.parent_id = scope.trace_id, scope.root_id
        else:
            self.trace_id, self.parent_id = _ensure_trace_id(), None
        self.span_id = _new_id()
        stack.append(self)
        self._mirror = None
        if (_capturing or _find_profiler())():
            self._mirror, self._t0 = _mirror(self.name, self.span_id)
        self._cpu0 = time.thread_time_ns()
        if self._mirror is None:
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter_ns() - self._t0
        cpu = time.thread_time_ns() - self._cpu0
        if self._mirror is not None:
            self._mirror.__exit__(None, None, None)
        stack = getattr(_tls, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        elif stack and self in stack:       # mispaired exit: stay consistent
            stack.remove(self)
        if exc_type is not None:
            self.args.setdefault("error", exc_type.__name__)
        rec = SpanRecord(self.name, self.cat, self.trace_id, self.span_id,
                         self.parent_id, os.getpid(), threading.get_ident(),
                         self._t0 / 1e3, dur / 1e3, cpu / 1e3, self.args)
        if self._scope is not None:
            self._scope.records.append(rec)
            return False
        with _lock:
            _records.append(rec)
        return False


def span(name: str, cat: str = "phase", **args):
    """Open a span context manager (``_NULL`` no-op while nothing records:
    telemetry disabled, no request scope active in this thread, and no JAX
    profiler session capturing)."""
    if (_on and (_enabled or getattr(_tls, "scope", None) is not None)
            or (_capturing or _find_profiler())()):
        return _Span(name, cat, args)
    return _NULL


# ---------------------------------------------------------------------------
# Request scopes
# ---------------------------------------------------------------------------
class Scope:
    """One request's spans, recorded apart from the process-wide buffer.

    ``ctx`` is the ``current_context()`` the request carried from its
    client.  The scope owns the span id of the request's root
    (``root_id``), so spans recorded under it nest there before the root
    itself is recorded: the root crosses threads, so :meth:`close` builds
    it from two clock readings, ``t0_ns`` and the moment of closing.
    Threads that work on the request enter :meth:`active`; spans they open
    then record into ``records`` and nowhere else, whether or not
    process-wide collection is on.  While a scope is open, collector pauses
    in this process record into it too (``gc.collect``).
    """

    __slots__ = ("trace_id", "parent_id", "root_id", "t0_ns", "records",
                 "_open")

    def __init__(self, ctx, t0_ns: int | None = None, *,
                 register: bool = True):
        global _on
        _fork_check()
        self.trace_id = str(ctx[0] or "")
        self.parent_id = ctx[1]
        self.root_id = _new_id()
        self.t0_ns = time.perf_counter_ns() if t0_ns is None else t0_ns
        self.records: list = []
        self._open = register
        if register:
            with _lock:
                _open_scopes.append(self)
                _on = True

    @contextlib.contextmanager
    def active(self):
        """Record this thread's spans into the scope, on a span stack of
        their own, until the block ends."""
        prev_scope = getattr(_tls, "scope", None)
        prev_stack = getattr(_tls, "stack", None)
        _tls.scope, _tls.stack = self, []
        try:
            yield self
        finally:
            _tls.scope, _tls.stack = prev_scope, prev_stack

    def stamp(self, name: str, cat: str, t0_ns: int, t1_ns: int) -> None:
        """Record a span beneath the root from two clock readings taken
        apart (e.g. on two threads)."""
        self.records.append(SpanRecord(
            name, cat, self.trace_id, _new_id(), self.root_id, os.getpid(),
            threading.get_ident(), t0_ns / 1e3, (t1_ns - t0_ns) / 1e3, 0.0,
            {}))

    def close(self, name: str, cat: str = "phase", **args) -> list:
        """Record the root (from ``t0_ns`` to now, with ``args``), stop
        taking collector pauses, and return every record of the scope."""
        t1 = time.perf_counter_ns()
        self.records.append(SpanRecord(
            name, cat, self.trace_id, self.root_id, self.parent_id,
            os.getpid(), threading.get_ident(), self.t0_ns / 1e3,
            (t1 - self.t0_ns) / 1e3, 0.0, args))
        self.discard()
        return self.records

    def discard(self) -> None:
        """Stop taking collector pauses without recording a root (the
        request was abandoned).  Idempotent."""
        global _on
        if not self._open:
            return
        self._open = False
        with _lock:
            try:
                _open_scopes.remove(self)
            except ValueError:
                pass
            _on = _enabled or bool(_open_scopes)


def collect(scopes, requests: int = 1):
    """Context manager recording this thread's spans into the request
    scopes ``scopes``.

    With no scope it is the shared no-op (one check).  Work that one
    request's scope alone asked for records straight into it.  Work
    shared by several requests (a coalesced sweep, joined waiters) records
    once; then each scope gets a copy whose top spans nest under its own
    root, every record tagged ``requests`` (how many requests the work
    served), so a reader can charge each request its share."""
    if not scopes:
        return _NULL
    if len(scopes) == 1 and requests == 1:
        return scopes[0].active()
    return _shared(scopes, requests)


@contextlib.contextmanager
def _shared(scopes, requests):
    shared = Scope((scopes[0].trace_id, None), register=False)
    try:
        with shared.active():
            yield
    finally:
        for sc in scopes:
            sc.records.extend(
                r._replace(trace_id=sc.trace_id,
                           parent_id=(sc.root_id
                                      if r.parent_id == shared.root_id
                                      else r.parent_id),
                           args=dict(r.args, requests=requests))
                for r in shared.records)


# ---------------------------------------------------------------------------
# Collector pauses
# ---------------------------------------------------------------------------
_gc_open = None     # (t0 ns, cpu0 ns, mirror, span id) of a recorded pause


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: one ``gc.collect`` span per collection while
    a recording scope is open in this process: in every open request scope
    (a pause stalls every request in flight), and, while a JAX profiler
    session captures, in the process-wide buffer.  Process-wide
    ``enable()`` alone leaves the collector out of the timeline."""
    global _gc_open
    try:
        if phase == "start":
            _gc_open = None
            capturing = profiling()
            if not (_open_scopes or capturing):
                return
            sid = _new_id()
            if capturing:
                mirror, t0 = _mirror("gc.collect", sid)
            else:
                mirror, t0 = None, time.perf_counter_ns()
            _gc_open = (t0, time.thread_time_ns(), mirror, sid)
            return
        if _gc_open is None:
            return
        t0, cpu0, mirror, sid = _gc_open
        _gc_open = None
        dur = time.perf_counter_ns() - t0
        cpu = time.thread_time_ns() - cpu0
        if mirror is not None:
            mirror.__exit__(None, None, None)
        args = {"generation": info.get("generation"),
                "collected": info.get("collected")}
        stack = getattr(_tls, "stack", None)
        top = stack[-1] if stack else None
        mine = getattr(_tls, "scope", None)
        pid, tid = os.getpid(), threading.get_ident()

        def record(trace_id, parent_id):
            return SpanRecord("gc.collect", "gc", trace_id, sid, parent_id,
                              pid, tid, t0 / 1e3, dur / 1e3, cpu / 1e3,
                              dict(args))

        for sc in list(_open_scopes):
            parent = (top.span_id if top is not None and sc is mine
                      else sc.root_id)
            sc.records.append(record(sc.trace_id, parent))
        if mirror is not None:
            if top is not None and mine is None:
                rec = record(top.trace_id, top.span_id)
            else:
                rec = record(_ensure_trace_id(), None)
            # no lock: a collection can start inside a ``with _lock``
            # block of this very thread, and one append is atomic
            _records.append(rec)
    except Exception:       # never let telemetry break a collection
        _gc_open = None


# installed once per process; it costs a flag check and the profiler's
# check per collection while nothing records
gc.callbacks.append(_on_gc)
# a fork()ed child starts clean before anything in it, the collector
# included, can touch the parent's buffers and scopes
os.register_at_fork(after_in_child=_fork_check)


__all__ = [
    "SpanRecord", "span", "enable", "disable", "enabled", "reset",
    "spans", "ingest", "current_context", "profiling",
    "Scope", "collect",
]
