"""Span tracing for the checker pipeline — Dapper-style nested spans
(Sigelman et al., 2010) over the host phases of a check.

A span is a named, attributed wall-clock + CPU-time interval:

    with obs.span("pipeline.dispatch", tier=8, chunk=2):
        ...

Spans nest through a ``contextvars.ContextVar``: the span active when a
child opens becomes its parent, which is what makes the per-key /
per-chunk trees in the Chrome trace render as stacks. Worker-pool
threads do not inherit contextvars automatically — the pipeline
captures the submitting thread's context via :func:`ctx_runner` so
spans opened on pool threads still hang off the submitting span (one
``Context.copy()`` per task: a Context object cannot be entered by two
threads at once).

Gating: ``JEPSEN_TPU_TRACE`` via the validated accessor
(``envflags.env_path``) — unset/``0`` disabled, ``1`` enabled,
``<path>`` enabled + the Chrome trace additionally written there at
export time. When DISABLED, ``span()`` returns a process-wide singleton
no-op context manager: no span object, no clock read, no lock — the
hot path (one call per key per stage) costs two attribute loads and a
``None`` check, and tests/test_obs.py pins a per-call CPU budget and
zero retained allocations. Flag changes after import are picked up via
:func:`reset` (tests) — a real run sets the env before the process
starts.

Clocks: with full tracing on, every span also opens a
``jax.profiler.TraceAnnotation`` of its name and attributes (once jax
is imported; the import stays lazy), so a profiler capture shows the
program's spans on its host plane, on the same clock as the device's
ops. Each collector pause becomes a ``python.gc`` span on its own
track ``gc`` (a ``gc.callbacks`` hook, installed while a full tracer
is active and removed on ``configure(on=False)``/``reset()``).

Thread-safety: finished spans append to one lock-protected list; the
contextvar handles per-thread currency. Only :func:`timer` spans read
``process_time()`` (process-wide: ``cpu`` is "CPU seconds the process
spent while this span was open", comparable across spans only when the
machine isn't oversubscribed): a process-CPU clock read is a system
call, about 5.5 us on a TPU v5e host against 0.09 us for
``perf_counter``, so the hot-path :func:`span` records wall time alone
and leaves ``cpu`` None.
"""

from __future__ import annotations

import contextvars
import gc
import itertools
import sys
import threading
from collections import deque
from time import perf_counter, process_time
from time import time as wall_time
from typing import Callable, Dict, List, Optional

from jepsen_tpu import envflags

_current: contextvars.ContextVar = contextvars.ContextVar(
    "jepsen_tpu_obs_span", default=None)

# the track of the collector's python.gc spans
GC_TRACK = "gc"

# default flight-recorder ring capacity (closed spans) when
# JEPSEN_TPU_FLIGHT_RECORDER=1; N>=2 sets the capacity explicitly
FLIGHT_DEFAULT_SPANS = 256


class Tracer:
    """Collects finished spans for one tracing session.

    Two retention modes, combinable:

    * the ordinary unbounded per-run buffer (``spans()``/``drain()``),
      exported into store run dirs — full tracing;
    * a bounded ring of the last ``ring`` CLOSED spans (the flight
      recorder, ``JEPSEN_TPU_FLIGHT_RECORDER``) that survives drains —
      what a crash dump reads. ``flight_only=True`` records into the
      ring ALONE (no unbounded list: a long-lived serve process must
      stay bounded-memory with tracing off), and is invisible to
      ``enabled()``/``export_run`` so run-dir artifacts and bench trace
      pointers keep their tracing-off behavior byte-identical.
    """

    def __init__(self, path: str = "", ring: Optional[int] = None,
                 flight_only: bool = False):
        self.path = path            # JEPSEN_TPU_TRACE=<path> ("" = none)
        self.epoch = perf_counter()  # trace time origin (ts 0 in exports)
        # the same origin on the WALL clock: exports stamp it so the
        # fleet trace merge (`jepsen trace`) can align several
        # replicas' traces on one time axis — perf_counter epochs are
        # per-process and incomparable across machines/restarts
        self.epoch_unix = wall_time()
        self.flag_exports = 0       # export_run count, for <path> runs
        self.flight_only = flight_only
        self._lock = threading.Lock()
        self._spans: List["Span"] = []
        # counter-track samples: (name, t, value) triples exported as
        # Chrome "C" events (Perfetto counter tracks) — gauge levels
        # (pipeline.inflight, breaker state, serve queue depth) and the
        # search-stats trajectories line up with the span tracks
        self._counters: List[tuple] = []
        # python.gc spans, appended by the collector hook WITHOUT the
        # lock: a collection can start between any two bytecodes,
        # including inside this tracer's own locked sections
        self._gc_spans: List["Span"] = []
        self._ring: Optional[deque] = (deque(maxlen=ring)
                                       if ring else None)
        self.flight_baseline: Optional[dict] = None
        if self._ring is not None:
            # metrics state at arm time, so a crash dump reports what
            # moved SINCE the recorder started, not process totals
            # (import here, not at module scope: metrics has no deps,
            # and tracer must stay importable first)
            from jepsen_tpu.obs import metrics as _metrics
            self.flight_baseline = _metrics.registry().snapshot()
        self._ids = itertools.count(1)

    def next_id(self) -> int:
        return next(self._ids)

    def record(self, span: "Span"):
        with self._lock:
            if self._ring is not None:
                self._ring.append(span)
            if not self.flight_only:
                self._spans.append(span)

    def ring_spans(self) -> List["Span"]:
        """The flight ring's retained spans, oldest first (empty when
        no ring is configured). NOT cleared by :meth:`drain` — the
        recorder must still answer after a per-run export."""
        with self._lock:
            return list(self._ring) if self._ring is not None else []

    def spans(self) -> List["Span"]:
        with self._lock:
            out = list(self._spans)
        return out + self._gc_spans

    def record_counter(self, name: str, t: float, value) -> None:
        """Record one counter-track sample. Flight-only recorders skip
        it: the ring retains spans alone, and counter samples must not
        grow unbounded state in a tracing-off process."""
        if self.flight_only:
            return
        with self._lock:
            self._counters.append((name, t, value))

    def counters(self) -> List[tuple]:
        with self._lock:
            return list(self._counters)

    def drain(self) -> List["Span"]:
        """Hand over the finished spans and clear the buffer — how
        export_run keeps artifacts per-run (and memory bounded) in a
        process that analyzes several runs (`--test-count`,
        test-all). Counter samples clear with the spans: they share
        the per-run window."""
        with self._lock:
            out = self._spans
            self._spans = []
            self._counters = []
        gcs, self._gc_spans = self._gc_spans, []
        return out + gcs

    def gc_hook(self) -> Callable:
        """The ``gc.callbacks`` hook that records each collector pause
        as a ``python.gc`` span on track ``gc``, with no parent: a
        collection inside a span is not taken out of its self time."""
        started = [0.0]

        def hook(phase, info):
            if phase == "start":
                started[0] = perf_counter()
                return
            s = Span(None, "python.gc", {"generation": info["generation"]})
            s.sid = self.next_id()
            s.t0, s.t1 = started[0], perf_counter()
            s.track = GC_TRACK
            self._gc_spans.append(s)
        return hook

    def add_span(self, name: str, t0: float, t1: float,
                 track: Optional[str] = None, parent: Optional[int] = None,
                 **args) -> "Span":
        """Record an interval measured elsewhere (e.g. a device
        program's dispatch->finalize window) as a finished span on an
        explicit `track` — these become the per-device-bucket rows in
        the Chrome trace, since no host thread "runs" them."""
        s = Span(self, name, args)
        s.sid = self.next_id()
        s.parent = parent
        s.t0, s.t1 = t0, t1
        s.cpu = 0.0
        s.track = track if track is not None else "device"
        s.thread = None
        self.record(s)
        return s


class Span:
    """One open (then finished) span. Context-manager protocol; also
    usable pre-populated via Tracer.add_span. ``wall`` (and ``cpu``,
    for a `timed` span) are valid after ``__exit__`` — :func:`timer`
    exploits that to make the recorded span and the caller's measured
    number one and the same clock read."""

    __slots__ = ("tracer", "name", "args", "sid", "parent", "t0", "t1",
                 "cpu", "_cpu0", "_tok", "_ann", "thread", "track")

    def __init__(self, tracer: Optional[Tracer], name: str, args: dict,
                 timed: bool = False):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.sid = 0
        self.parent = None
        self.t0 = self.t1 = 0.0
        # process CPU seconds, measured for timed spans only
        self.cpu = 0.0 if timed else None
        self._cpu0 = None
        self.thread = None
        self.track = None
        self._ann = None

    def __enter__(self) -> "Span":
        tr = self.tracer
        ann = None
        if tr is not None:
            self.sid = tr.next_id()
            par = _current.get()
            self.parent = par.sid if par is not None else None
            self._tok = _current.set(self)
            if not tr.flight_only:
                ann = _annotation_cls()
        else:
            self._tok = None
        t = threading.current_thread()
        self.thread = (t.ident, t.name)
        if self.cpu is not None:
            self._cpu0 = process_time()
        if ann is not None and ann.is_enabled():
            # only while a profiler session records; opened right
            # before the clock read, so the profiler's start and t0 are
            # the same moment on two clocks
            self._ann = ann(self.name, **self.args)
            self._ann.__enter__()
        self.t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = perf_counter()
        if self._cpu0 is not None:
            self.cpu = process_time() - self._cpu0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        if self._tok is not None:
            _current.reset(self._tok)
        if self.tracer is not None:
            self.tracer.record(self)
        return False

    def set(self, **kw):
        """Attach attributes discovered mid-span (e.g. the resolved
        capacity tier)."""
        self.args.update(kw)

    @property
    def wall(self) -> float:
        return self.t1 - self.t0

    def to_dict(self) -> dict:
        d = {"type": "span", "name": self.name, "id": self.sid,
             "parent": self.parent,
             "t0": round(self.t0, 6), "wall": round(self.wall, 6)}
        if self.cpu is not None:
            d["cpu"] = round(self.cpu, 6)
        if self.thread is not None:
            d["thread"] = self.thread[1]
            d["tid"] = self.thread[0]
        if self.track is not None:
            d["track"] = self.track
        if self.args:
            d["args"] = dict(self.args)
        return d


class _NoopSpan:
    """The disabled-path singleton: enters/exits without touching a
    clock, a lock, or the heap. `set` swallows attributes (they were
    built by the caller either way)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **kw):
        pass

    wall = 0.0
    cpu = 0.0


_NOOP = _NoopSpan()

# module tracer state: None = disabled; _UNSET = not yet resolved from
# the env (first span()/enabled() call resolves — import stays cheap
# and monkeypatched env in tests is honored if they reset() first)
_UNSET = object()
_state = _UNSET
_state_lock = threading.Lock()
_gc_hook: Optional[Callable] = None   # the installed collector hook
_annotation = None   # jax.profiler.TraceAnnotation, once jax is imported


def _annotation_cls():
    """``jax.profiler.TraceAnnotation``, or None while jax is not
    imported: obs never imports jax itself, and with no jax there is no
    profiler session for an annotation to land in."""
    global _annotation
    if _annotation is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


def _set_state(st) -> None:
    """Make `st` the session (caller holds _state_lock) and move the
    collector hook to it: installed for a full tracer, removed
    otherwise, so tracing off costs the collector nothing."""
    global _state, _gc_hook
    if _gc_hook is not None:
        try:
            gc.callbacks.remove(_gc_hook)
        except ValueError:
            pass
        _gc_hook = None
    _state = st
    if isinstance(st, Tracer) and not st.flight_only:
        _gc_hook = st.gc_hook()
        gc.callbacks.append(_gc_hook)


def _flight_capacity() -> int:
    """JEPSEN_TPU_FLIGHT_RECORDER: unset/0 -> 0 (off), 1 -> the
    default ring capacity, N>=2 -> that capacity in spans."""
    v = envflags.env_int("JEPSEN_TPU_FLIGHT_RECORDER", default=0,
                         min_value=0, what="flight-recorder capacity")
    if v == 1:
        return FLIGHT_DEFAULT_SPANS
    return v or 0


def _resolve():
    with _state_lock:
        if _state is _UNSET:
            path = envflags.env_path("JEPSEN_TPU_TRACE",
                                     what="trace output path")
            if path is None and jax_profile_dir() is not None:
                # a profiler capture keeps its named steps: the
                # annotations are the spans
                path = ""
            ring = _flight_capacity()
            if path is not None:
                _set_state(Tracer(path, ring=ring or None))
            elif ring:
                # flight recorder alone: spans land in the bounded
                # ring only, invisible to enabled()/export_run
                _set_state(Tracer("", ring=ring, flight_only=True))
            else:
                _set_state(None)
    return _state


def enabled() -> bool:
    """Full tracing on? A flight-only recorder answers False — every
    tracing-gated consumer (run-dir export, bench trace pointers,
    ctx_runner) must keep its tracing-off behavior when only the
    crash ring is armed."""
    st = _state
    if st is _UNSET:
        st = _resolve()
    return st is not None and not st.flight_only


def flight_active() -> bool:
    """Is a flight-recorder ring retaining spans (with or without full
    tracing)? The hook sites (supervisor wedge, breaker open, serve
    shed/worker-error) check this before dumping."""
    st = _state
    if st is _UNSET:
        st = _resolve()
    return st is not None and st._ring is not None


def tracer() -> Optional[Tracer]:
    """The active Tracer, or None when tracing is off."""
    st = _state
    if st is _UNSET:
        st = _resolve()
    return st


def span(name: str, **args):
    """A traced interval — the hot-path entry point. Disabled: returns
    the no-op singleton (nothing allocated beyond the caller's own
    kwargs, nothing timed)."""
    st = _state
    if st is _UNSET:
        st = _resolve()
    if st is None:
        return _NOOP
    return Span(st, name, args)


def timer(name: str, **args) -> Span:
    """An ALWAYS-measuring interval: the context manager's
    ``wall``/``cpu`` are valid whether tracing is on or off, and when
    it is on the recorded span is the SAME clock reads — the mechanism
    by which bench split lines and trace spans can never disagree
    (one measurement site). Not for hot paths: it allocates a Span per
    call even when disabled, and reads the process CPU clock; use
    :func:`span` there."""
    st = _state
    if st is _UNSET:
        st = _resolve()
    return Span(st, name, args, timed=True)


def counter_sample(name: str, value, t: Optional[float] = None) -> None:
    """Record one sample on a Perfetto counter track (a Chrome "C"
    event at export time): a gauge level, a queue depth, a breaker
    state, a frontier width. No-op when full tracing is off — the
    disabled path is one attribute load and a None/flight check, the
    same hot-path standard as span(). ``t`` (a perf_counter() read)
    backdates the sample — the search-stats exporter synthesizes a
    time axis across a device search's span window."""
    st = _state
    if st is _UNSET:
        st = _resolve()
    if st is None or st.flight_only:
        return
    st.record_counter(name, t if t is not None else perf_counter(),
                      value)


def configure(on: bool = True, path: str = "",
              ring: Optional[int] = None,
              flight_only: bool = False) -> Optional[Tracer]:
    """Programmatic gate (tests, embedding): force tracing on/off
    regardless of the env flag. Returns the new tracer (or None).
    ``ring``/``flight_only`` arm the flight recorder the way the
    JEPSEN_TPU_FLIGHT_RECORDER flag would."""
    with _state_lock:
        _set_state(Tracer(path, ring=ring, flight_only=flight_only)
                   if on else None)
    return _state


def reset():
    """Drop the session and re-resolve from the env on next use —
    how tests flip JEPSEN_TPU_TRACE mid-process."""
    with _state_lock:
        _set_state(_UNSET)


def current_span() -> Optional[Span]:
    return _current.get()


def ctx_runner() -> Callable:
    """Span-context propagation for worker pools. Captures the calling
    thread's context ONCE; returns ``wrap(fn) -> fn'`` where each
    ``fn'`` call runs under a fresh copy of that context, so spans
    opened on the pool thread nest under the span active at capture
    time. Disabled tracing returns the identity wrap (zero overhead).
    One ``Context.copy()`` per call is mandatory, not defensive: a
    Context raises if entered concurrently from two threads."""
    if not enabled():
        return lambda fn: fn
    ctx = contextvars.copy_context()

    def wrap(fn):
        def run(*a, **kw):
            return ctx.copy().run(fn, *a, **kw)
        return run
    return wrap


# ------------------------------------------------- jax.profiler bridge


def jax_profile_dir() -> Optional[str]:
    """The JEPSEN_TPU_JAX_PROFILE directory, or None when off. "1"
    maps to the default capture dir so the flag composes with the
    runbook's `JEPSEN_TPU_JAX_PROFILE=1 jepsen test ...` shorthand."""
    d = envflags.env_path("JEPSEN_TPU_JAX_PROFILE", what="profile dir")
    if d == "":
        return "store/jax_profile"
    return d


class _MaybeCtx:
    """Context manager that delegates to a lazily-built inner context
    (or nothing). Exists so the obs module never imports jax at import
    time — engine modules must stay import-safe under a wedged
    runtime."""

    __slots__ = ("_factory", "_inner")

    def __init__(self, factory):
        self._factory = factory
        self._inner = None

    def __enter__(self):
        if self._factory is not None:
            self._inner = self._factory()
            self._inner.__enter__()
        return self

    def __exit__(self, *exc):
        if self._inner is not None:
            return bool(self._inner.__exit__(*exc))
        return False


def maybe_jax_profile() -> _MaybeCtx:
    """jax.profiler.trace(dir) when JEPSEN_TPU_JAX_PROFILE is set, else
    a no-op — wraps a whole batched check so the TPU capture and the
    host spans share a session. The session runs without the Python
    function tracer (``python_tracer_level=0``), which would slow the
    host several times over: the spans' annotations name the host
    steps instead."""
    d = jax_profile_dir()
    if d is None:
        return _MaybeCtx(None)

    def make():
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        return jax.profiler.trace(d, profiler_options=opts)
    return _MaybeCtx(make)
