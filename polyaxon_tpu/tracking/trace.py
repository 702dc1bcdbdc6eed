"""Lightweight structured span tracing across the gang.

The reference stack stops at StatsD counters + Sentry (SURVEY §5); it has
no way to answer "where did this step/request/trial spend its time" across
the control plane, the gang workers, and the serving engine.  This module
is the worker-side half of that answer:

- :class:`Tracer` hands out ``span(name, **attrs)`` context managers that
  record wall-clock start (``time.time()``, so spans from different hosts
  line up on one timeline) and a ``perf_counter`` duration, plus
  trace/span/parent ids maintained per thread for nesting.
- Finished spans land in a thread-safe ring buffer and, when a ``sink`` is
  configured (the worker wires ``Reporter.span``), ship through the
  existing report channel as a typed ``span`` event.  ``GangWatcher``
  ingests those into the registry, and the control plane exports the
  cross-process timeline as Chrome-trace JSON (:func:`chrome_trace`,
  served at ``GET /api/v1/runs/<id>/timeline``).
- Sampling is decided *before* any ids or timestamps are taken: a
  sampled-out ``span()`` call returns a shared no-op context manager, so
  hot-path call sites (per step / per decode tick, gated on
  ``tracer.hot_sample``) cost about as much as a ``perf_counter`` call.

- :class:`PhaseClock` (``tracer.phase_clock(...)``) is the accounting of ONE
  thread's loop: an exclusive clock over a small fixed catalog of phases,
  one ``perf_counter`` read per transition, plain seconds and counts that
  sum to the loop's wall time; on the same readings, the seconds a phase ran
  with nothing dispatched to the device, the named laps of a phase, and the
  thread's CPU seconds.  The serving engine's loop runs on one.
- ``tracer.profiler_hook`` puts both on the device profiler's clock: the
  capture agent sets it to ``jax.profiler.TraceAnnotation`` while an xplane
  trace is on (this module never imports jax), and every span and phase is
  then also an annotation of its name in the ``.xplane.pb``.

Process-wide singleton: library code calls :func:`get_tracer` and never
configures it; the worker entrypoint calls :func:`configure` once with the
report sink, its process id, and the run uuid.  Control-plane spans stay
buffer-only (no sink) unless something attaches one.

Request-scoped *distributed* tracing rides on the same records: a
W3C-traceparent-style :class:`TraceContext` (``inject`` / ``extract``
header helpers) carries one trace id across the serving hops (router →
replica lm_server → engine), and spans created with explicit
``trace_id`` / ``parent_id`` overrides (or recorded after the fact via
:meth:`Tracer.record_span`) stitch the per-process records into one
cross-host timeline.  ``chrome_trace`` keys its rows by *(process
label, pid)* so router + replica spans — which all default to
``process_id=0`` — land on distinct named tracks.
"""

from __future__ import annotations

import itertools
import os
import random
import threading
import time
from collections import deque
from typing import (
    Any,
    Callable,
    Dict,
    Iterable,
    List,
    Mapping,
    NamedTuple,
    Optional,
    Sequence,
)

from polyaxon_tpu.conf.knobs import knob_float

__all__ = [
    "Tracer",
    "PhaseClock",
    "PhaseSnapshot",
    "get_tracer",
    "configure",
    "chrome_trace",
    "TraceContext",
    "TRACEPARENT_HEADER",
    "new_trace_id",
    "inject",
    "extract",
]

_UNSET = object()

#: The propagation header, lowercase per W3C Trace Context.
TRACEPARENT_HEADER = "traceparent"


def new_trace_id() -> str:
    """A fresh 32-hex-char trace id (W3C trace-id width)."""
    return os.urandom(16).hex()


class TraceContext:
    """Propagated trace state: one trace id + the remote parent span.

    ``span_id`` is the *caller's* span — the hop that injected the
    header — so spans the receiving process creates parent to it and
    the merged timeline nests correctly across hosts.
    """

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(
        self, trace_id: str, span_id: str = "", sampled: bool = True
    ) -> None:
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled

    def header(self) -> str:
        """Serialize as a ``version-traceid-spanid-flags`` header value.

        The span-id field is 16 hex chars per the W3C layout; internal
        span ids (``<label>.<n>``) don't fit that alphabet, so they are
        carried verbatim — both ends of every hop are this module.
        """
        return "00-%s-%s-%s" % (
            self.trace_id,
            self.span_id or "0" * 16,
            "01" if self.sampled else "00",
        )

    def child(self, span_id: str) -> "TraceContext":
        """The context to inject on an outbound hop parented to
        ``span_id`` (a span of the current process)."""
        return TraceContext(self.trace_id, span_id, self.sampled)


def inject(ctx: Optional[TraceContext], headers: Dict[str, str]) -> Dict[str, str]:
    """Write ``ctx`` into an outbound header dict (no-op when None)."""
    if ctx is not None:
        headers[TRACEPARENT_HEADER] = ctx.header()
    return headers


def extract(headers: Optional[Mapping[str, Any]]) -> Optional[TraceContext]:
    """Parse a traceparent header from ``headers`` (case-insensitive).

    Malformed or missing headers return None — the caller degrades to a
    fresh trace; propagation must never turn into a 500.
    """
    if headers is None:
        return None
    try:
        raw = headers.get(TRACEPARENT_HEADER) or headers.get(
            TRACEPARENT_HEADER.title()
        )
    except Exception:
        return None
    if not raw or not isinstance(raw, str):
        return None
    parts = raw.strip().split("-")
    if len(parts) != 4:
        return None
    version, trace_id, span_id, flags = parts
    if len(version) != 2 or not trace_id or trace_id.strip("0") == "":
        return None
    if len(trace_id) != 32:
        return None
    try:
        int(trace_id, 16)
        int(flags, 16)
    except ValueError:
        return None
    sampled = False
    try:
        sampled = bool(int(flags, 16) & 1)
    except ValueError:
        pass
    if span_id.strip("0") == "":
        span_id = ""
    return TraceContext(trace_id, span_id, sampled)


class _NoopSpan:
    """Shared zero-state stand-in yielded when a span is sampled out."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False

    def set(self, **attrs: Any) -> None:
        pass


_NOOP = _NoopSpan()


class _Span:
    """A live (sampled-in) span; created by :meth:`Tracer.span`."""

    __slots__ = (
        "_tracer",
        "name",
        "attrs",
        "span_id",
        "parent_id",
        "_trace_id",
        "_explicit_parent",
        "_annotation",
        "_t0",
        "_p0",
    )

    def __init__(
        self,
        tracer: "Tracer",
        name: str,
        attrs: Dict[str, Any],
        trace_id: Optional[str] = None,
        parent_id: Optional[str] = None,
        annotation: Any = None,
    ) -> None:
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.span_id = ""
        self.parent_id: Optional[str] = parent_id
        self._trace_id = trace_id
        self._explicit_parent = parent_id is not None
        self._annotation = annotation
        self._t0 = 0.0
        self._p0 = 0.0

    def set(self, **attrs: Any) -> None:
        """Attach attributes discovered while the span body runs."""
        self.attrs.update(attrs)

    def __enter__(self) -> "_Span":
        tracer = self._tracer
        stack = tracer._stack()
        if not self._explicit_parent:
            self.parent_id = stack[-1] if stack else None
        self.span_id = tracer.next_span_id()
        stack.append(self.span_id)
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = time.time()
        self._p0 = time.perf_counter()
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> bool:
        duration = time.perf_counter() - self._p0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        stack = self._tracer._stack()
        if stack and stack[-1] == self.span_id:
            stack.pop()
        if exc_type is not None:
            self.attrs["error"] = getattr(exc_type, "__name__", str(exc_type))
        self._tracer.record_span(
            self.name,
            start=self._t0,
            duration=duration,
            trace_id=(
                self._trace_id
                if self._trace_id is not None
                else self._tracer.trace_id
            ),
            span_id=self.span_id,
            parent_id=self.parent_id,
            **self.attrs,
        )
        return False


#: Of the visits to a phase the thread waits in, the one in this many whose
#: CPU seconds the owner reads (two system calls).
_WAIT_SAMPLE = 64


def _thread_cpu_clock() -> Optional[int]:
    """The calling thread's CPU-time clock, which any thread may read with
    ``time.clock_gettime``; ``None`` where the platform has none."""
    get = getattr(time, "pthread_getcpuclockid", None)
    return None if get is None else get(threading.get_ident())


class _Phase:
    """The reusable ``with`` target of one phase of a :class:`PhaseClock`;
    ``as`` gives the ``perf_counter`` reading of the transition into it."""

    __slots__ = ("_clock", "name", "_waits")

    def __init__(self, clock: "PhaseClock", name: str, waits: bool) -> None:
        self._clock = clock
        self.name = name
        self._waits = waits  # the thread does not compute in this phase

    def __enter__(self) -> float:
        clock = self._clock
        stack = clock._stack
        if not stack:  # clock off
            return clock.t
        clock._seq += 1
        now = clock._charge()
        clock._lap = None
        stack.append(self.name)
        clock._counts[self.name] += 1
        clock._seq += 1
        if (
            self._waits
            and clock._counts[self.name] % _WAIT_SAMPLE == 1
            and clock._cpu_clock is not None
        ):
            clock._wait_cpu0 = time.clock_gettime(clock._cpu_clock)
        hook = clock._tracer.profiler_hook
        if hook is not None or clock._live is not None:
            clock._mark(hook, self.name)
        return now

    def __exit__(self, *exc: Any) -> bool:
        clock = self._clock
        stack = clock._stack
        if len(stack) < 2:  # clock off: the base phase is stop()'s to close
            return False
        if self._waits and clock._wait_cpu0 is not None:
            sample = clock._wait_cpu[self.name]
            sample[1] += time.clock_gettime(clock._cpu_clock) - clock._wait_cpu0
            sample[0] += 1
            clock._wait_cpu0 = None
        clock._seq += 1
        clock._charge()
        clock._lap = None
        stack.pop()
        clock._seq += 1
        hook = clock._tracer.profiler_hook
        if hook is not None or clock._live is not None:
            clock._mark(hook, stack[-1])
        return False


class PhaseSnapshot(NamedTuple):
    """A :class:`PhaseClock` as of one instant (:meth:`PhaseClock.snapshot`)."""

    wall: float  #: seconds between ``start()`` and ``stop()``, or now
    seconds: Dict[str, float]  #: by phase; they sum to ``wall``
    counts: Dict[str, int]  #: by phase: the times it was entered
    uncovered: Dict[str, float]  #: by phase: its seconds with nothing dispatched
    laps: Dict[str, float]  #: by ``<phase>.<lap>``: a part of the phase's seconds


class PhaseClock:
    """Exclusive self-time accounting of ONE thread over a fixed catalog.

    ``start()`` opens the ``base`` phase; ``with clock.phase(name) as t:``
    pauses whichever phase is open, runs ``name`` and resumes the outer one
    at the exit, so every instant between ``start()`` and ``stop()`` belongs
    to exactly one phase and the phases' seconds sum to the wall time.  A
    transition reads ``perf_counter`` once and calls nothing else: ``t`` is
    that reading (the phase's start), ``clock.t`` the latest one, and
    ``clock.epoch + t`` the same instant on ``time.time()``'s clock.  While
    the tracer's ``profiler_hook`` is set, each interval is also an
    annotation of the phase's name, one after the other on the thread's row
    of the device trace.  Off (before ``start()``, after ``stop()``) a phase
    does nothing, so code that also runs without the loop (a test calling a
    tick by hand, the drain after the thread is joined) needs no guard.

    Three more accounts ride on the same readings, and none changes a
    phase's seconds or count:

    - **Coverage.**  The owner says :meth:`dispatched` when a call that put
      a program on the device has returned and :meth:`drained` when a
      blocking read has; every interval charged while nothing is dispatched
      is also added to its phase's ``uncovered`` seconds.
    - **Laps** (``laps``: phase -> the names of its laps).  ``lap(name)``
      names the part of the open phase from now to the next lap or
      transition; its seconds are a subset of the phase's, its annotation
      ``<phase>.<lap>``.
    - **CPU** (``waits``: the phases the thread does not compute in).
      ``start()`` takes the owner's CPU-time clock and :meth:`cpu_seconds`
      reads it, from any thread and only when asked; the owner itself reads
      it at both edges of one visit in 64 of each of ``waits``, for what the
      waits burn.  (A read at every edge is a system call of 25 us in a
      serving process on the chip machine, and cost 3-4 % of the served rate
      at two a step.)  The wall seconds of the other phases, less the CPU
      seconds outside the waits, is time the thread waited for a CPU or for
      the interpreter lock.

    Only the owning thread enters phases.  Any thread may :meth:`snapshot`:
    the owner brackets each transition, its clock read included, with two
    increments of ``_seq`` (odd while one is in flight) and the reader retries
    until it has read, its own clock read included, between two.
    """

    def __init__(
        self,
        tracer: "Tracer",
        names: Sequence[str],
        base: str,
        laps: Optional[Mapping[str, Sequence[str]]] = None,
        waits: Sequence[str] = (),
    ) -> None:
        if base not in names:
            raise ValueError(f"base phase {base!r} is not in the catalog")
        unknown = [p for p in (*(laps or ()), *waits) if p not in names]
        if unknown:
            raise ValueError(f"phases {unknown!r} are not in the catalog")
        self._tracer = tracer
        self.base = base
        self._phases = {name: _Phase(self, name, name in waits) for name in names}
        self._seconds = {name: 0.0 for name in names}
        self._counts = {name: 0 for name in names}
        self._uncovered = {name: 0.0 for name in names}
        #: phase -> lap -> the lap's key and annotation, ``<phase>.<lap>``
        self._lap_names = {
            phase: {lap: f"{phase}.{lap}" for lap in of}
            for phase, of in (laps or {}).items()
        }
        self._lap_seconds = {
            full: 0.0 for of in self._lap_names.values() for full in of.values()
        }
        self._lap: Optional[str] = None  # the open lap's key
        self._covered = False  # a program is dispatched and no read has returned since
        #: The owner's CPU-time clock between start() and stop(), its reading
        #: at start(), and the CPU seconds of the spans already closed.
        self._cpu_clock: Optional[int] = None
        self._cpu0 = 0.0
        self._cpu = 0.0
        #: By phase of ``waits``: [the visits read at both edges, their CPU
        #: seconds]; the reading at the entry of the one in flight, if any.
        self._wait_cpu = {name: [0, 0.0] for name in waits}
        self._wait_cpu0: Optional[float] = None
        self._stack: List[str] = []
        self._live: Any = None  # the open profiler annotation, if any
        self._seq = 0
        self._wall = 0.0  # of the start()..stop() spans already closed
        self._started = 0.0
        self.t = 0.0
        self.epoch = 0.0

    def phase(self, name: str) -> _Phase:
        return self._phases[name]

    def anchor(self) -> None:
        """Re-read the offset between ``perf_counter`` and ``time.time()``
        (the wall clock is slewed; call where the loop has time to spare)."""
        self.epoch = time.time() - time.perf_counter()

    def _charge(self) -> float:
        """Read the clock and charge the interval since the last reading to
        the open phase, to its uncovered seconds while nothing is dispatched
        and to the open lap.  Called inside the ``_seq`` bracket, the clock
        on."""
        now = time.perf_counter()
        dt = now - self.t
        top = self._stack[-1]
        self._seconds[top] += dt
        if not self._covered:
            self._uncovered[top] += dt
        if self._lap is not None:
            self._lap_seconds[self._lap] += dt
        self.t = now
        return now

    def start(self) -> None:
        if self._stack:
            return
        self.anchor()
        self._seq += 1
        self._started = self.t = time.perf_counter()
        self._cpu_clock = _thread_cpu_clock()
        if self._cpu_clock is not None:
            self._cpu0 = time.clock_gettime(self._cpu_clock)
        self._stack.append(self.base)
        self._counts[self.base] += 1
        self._seq += 1

    def stop(self) -> None:
        """Close every open phase (an exception may have left some)."""
        if not self._stack:
            return
        self._seq += 1
        now = self._charge()
        self._lap = None
        self._wall += now - self._started
        if self._cpu_clock is not None:
            self._cpu += time.clock_gettime(self._cpu_clock) - self._cpu0
            self._cpu_clock = self._wait_cpu0 = None
        del self._stack[:]
        self._seq += 1
        self._mark(None, "")

    def lap(self, name: str) -> None:
        """The open phase's lap ``name`` begins now; it ends at the next lap
        or transition."""
        stack = self._stack
        if not stack:
            return
        full = self._lap_names[stack[-1]][name]
        self._seq += 1
        self._charge()
        self._lap = full
        self._seq += 1
        hook = self._tracer.profiler_hook
        if hook is not None or self._live is not None:
            self._mark(hook, full)

    def dispatched(self) -> None:
        """The call that put a program on the device has returned."""
        self._cover(True)

    def drained(self) -> None:
        """A blocking read has returned: whatever was dispatched before it
        has finished (programs run in the order they were dispatched)."""
        self._cover(False)

    def _cover(self, covered: bool) -> None:
        if covered == self._covered or not self._stack:
            return
        self._seq += 1
        self._charge()
        self._covered = covered
        self._seq += 1

    def _mark(self, hook: Optional[Callable[[str], Any]], name: str) -> None:
        """Close the open annotation and, while a capture is on, open
        ``name``'s.  A profiler that fails must never take the loop down."""
        live, self._live = self._live, None
        try:
            if live is not None:
                live.__exit__(None, None, None)
            if hook is not None:
                live = hook(name)
                live.__enter__()
                self._live = live
        except Exception:
            pass

    def cpu_seconds(self) -> Optional[float]:
        """The owner's CPU seconds outside ``waits``, between ``start()`` and
        ``stop()`` or now: all of them off its clock, less what each wait
        burns by the mean of the visits read at both edges, times its
        visits.  ``None`` where the platform has no per-thread CPU clock."""
        if not hasattr(time, "pthread_getcpuclockid"):
            return None
        while True:
            seq = self._seq
            cpu, cpu0, clock = self._cpu, self._cpu0, self._cpu_clock
            waits = [
                (self._counts[name], n, burnt)
                for name, (n, burnt) in self._wait_cpu.items()
            ]
            if not seq & 1 and seq == self._seq:
                break
            time.sleep(0)
        if clock is not None:
            try:
                cpu += time.clock_gettime(clock) - cpu0
            except OSError:  # the owner ended between the two lines above
                pass
        return cpu - sum(burnt / n * visits for visits, n, burnt in waits if n)

    def snapshot(self) -> PhaseSnapshot:
        """The clock as of now: the interval in flight is charged to the open
        phase (and to its uncovered seconds and the open lap, as the next
        reading will), so the seconds sum to ``wall``."""
        while True:
            seq = self._seq
            seconds = dict(self._seconds)
            counts = dict(self._counts)
            uncovered = dict(self._uncovered)
            laps = dict(self._lap_seconds)
            top = self._stack[-1:]
            t, wall, started = self.t, self._wall, self._started
            covered, lap = self._covered, self._lap
            # Read inside the bracket: were "now" taken after it, a phase
            # the owner closed meanwhile would be charged past its end and
            # read lower in the next snapshot.
            now = time.perf_counter()
            if not seq & 1 and seq == self._seq:
                break
            time.sleep(0)  # let the owner finish its transition
        if top:
            dt = now - t
            seconds[top[0]] += dt
            if not covered:
                uncovered[top[0]] += dt
            if lap is not None:
                laps[lap] += dt
            wall += now - started
        return PhaseSnapshot(wall, seconds, counts, uncovered, laps)


class Tracer:
    """Thread-safe span recorder with a bounded ring buffer.

    ``sample`` gates ordinary spans, ``hot_sample`` is the conventional
    rate call sites use for per-step/per-token spans (pass it explicitly:
    ``tracer.span("train.step", sample=tracer.hot_sample)``).  Both are
    env-tunable so a run can be re-launched fully traced without a code
    change.
    """

    def __init__(
        self,
        *,
        sink: Optional[Callable[[Dict[str, Any]], None]] = None,
        sample: float = 1.0,
        hot_sample: float = 0.05,
        buffer: int = 2048,
        process_id: int = 0,
        process: str = "",
        trace_id: Optional[str] = None,
    ) -> None:
        self.sink = sink
        self.sample = sample
        self.hot_sample = hot_sample
        self.process_id = process_id
        self.process = process
        self.trace_id = trace_id
        self._buffer: deque = deque(maxlen=buffer)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count(1)
        #: ``name -> context manager`` that writes a host event into the
        #: device profiler's trace.  The capture agent sets it to
        #: ``jax.profiler.TraceAnnotation`` while an xplane trace is on;
        #: spans then ignore sampling and wrap it, as a PhaseClock's phases do.
        self.profiler_hook: Optional[Callable[[str], Any]] = None

    # -- configuration ------------------------------------------------------

    def configure(
        self,
        *,
        sink: Any = _UNSET,
        sample: Any = _UNSET,
        hot_sample: Any = _UNSET,
        process_id: Any = _UNSET,
        process: Any = _UNSET,
        trace_id: Any = _UNSET,
    ) -> "Tracer":
        """Update settings in place (unset arguments keep current values)."""
        if sink is not _UNSET:
            self.sink = sink
        if sample is not _UNSET:
            self.sample = float(sample)
        if hot_sample is not _UNSET:
            self.hot_sample = float(hot_sample)
        if process_id is not _UNSET:
            self.process_id = int(process_id)
        if process is not _UNSET:
            self.process = str(process)
        if trace_id is not _UNSET:
            self.trace_id = trace_id
        return self

    # -- recording ----------------------------------------------------------

    def span(
        self,
        name: str,
        sample: Optional[float] = None,
        trace_id: Optional[str] = None,
        parent_id: Optional[str] = None,
        **attrs: Any,
    ):
        """Context manager timing ``name``; sampled-out calls are ~free.

        ``trace_id`` / ``parent_id`` override the process trace id and
        the thread-local parent stack — request-scoped spans pass the
        propagated :class:`TraceContext` ids so phases executed on a
        shared scheduler thread still nest under their own request.
        Sampling uses the module-level ``random.random()`` (its own lock
        via the shared Random's C implementation) — a per-instance RNG
        here would be raced by concurrent HTTP handler threads.
        """
        hook = self.profiler_hook
        if hook is not None:
            return _Span(
                self, name, attrs, trace_id=trace_id, parent_id=parent_id,
                annotation=hook(name),
            )
        rate = self.sample if sample is None else sample
        if rate < 1.0 and (rate <= 0.0 or random.random() >= rate):
            return _NOOP
        return _Span(self, name, attrs, trace_id=trace_id, parent_id=parent_id)

    def phase_clock(
        self,
        names: Sequence[str],
        base: str,
        laps: Optional[Mapping[str, Sequence[str]]] = None,
        waits: Sequence[str] = (),
    ) -> PhaseClock:
        """A :class:`PhaseClock` over ``names`` for the calling loop's
        thread, annotated through this tracer's ``profiler_hook``."""
        return PhaseClock(self, names, base, laps, waits)

    def next_span_id(self) -> str:
        """Allocate a span id unique within (and, when a process label is
        set, across) processes: ``[label.]pid.counter``."""
        n = next(self._ids)
        if self.process:
            return "%s.%d.%x" % (self.process, self.process_id, n)
        return "%d.%x" % (self.process_id, n)

    def record_span(
        self,
        name: str,
        *,
        start: float,
        duration: float,
        trace_id: Optional[str] = None,
        span_id: Optional[str] = None,
        parent_id: Optional[str] = None,
        **attrs: Any,
    ) -> Dict[str, Any]:
        """Record a completed span directly (no context manager).

        The engine uses this to emit request phases measured by its own
        accounting (queue wait, park intervals, the request root) whose
        start/end don't bracket a ``with`` block.
        """
        record: Dict[str, Any] = {
            "name": name,
            "trace_id": trace_id if trace_id is not None else self.trace_id,
            "span_id": span_id if span_id is not None else self.next_span_id(),
            "parent_id": parent_id,
            "start": start,
            "duration": duration,
            "process_id": self.process_id,
            "thread": threading.current_thread().name,
        }
        if self.process:
            record["process"] = self.process
        process = attrs.pop("process", None)
        if process:
            record["process"] = str(process)
        if attrs:
            record["attrs"] = attrs
        self._record(record)
        return record

    def _stack(self) -> List[str]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, record: Dict[str, Any]) -> None:
        with self._lock:
            self._buffer.append(record)
        sink = self.sink
        if sink is not None:
            try:
                sink(record)
            except Exception:
                pass  # a broken sink must never take down the traced code

    def spans(self) -> List[Dict[str, Any]]:
        """Snapshot of the ring buffer, oldest first."""
        with self._lock:
            return list(self._buffer)

    def clear(self) -> None:
        with self._lock:
            self._buffer.clear()


_tracer = Tracer(
    sample=knob_float("POLYAXON_TPU_TRACE_SAMPLE"),
    hot_sample=knob_float("POLYAXON_TPU_TRACE_HOT_SAMPLE"),
)


def get_tracer() -> Tracer:
    """The process-wide tracer (unconfigured: buffer-only, no sink)."""
    return _tracer


def configure(**kwargs: Any) -> Tracer:
    """Configure the process-wide tracer (see :meth:`Tracer.configure`)."""
    return _tracer.configure(**kwargs)


def chrome_trace(spans: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Render span records as Chrome-trace / Perfetto JSON.

    Each span becomes a complete ("ph": "X") event; timestamps are the
    original wall-clock epoch in microseconds, so spans reported by
    different gang processes land on one shared timeline.  Process rows
    are keyed by *(process label, process_id)* — serving processes
    (router, every replica) all default to ``process_id=0``, so the
    label is what keeps a merged fleet trace on distinct tracks — with
    process_name/thread_name metadata so the viewer labels each one.
    Unlabeled gang spans keep their process_id as the pid, preserving
    the existing run-timeline export.
    """
    events: List[Dict[str, Any]] = []
    tids: Dict[Any, int] = {}
    per_pid: Dict[Any, int] = {}
    pids: Dict[Any, int] = {}
    for span in spans:
        raw_pid = int(span.get("process_id") or 0)
        label = str(span.get("process") or "")
        pkey = (label, raw_pid)
        pid = pids.get(pkey)
        if pid is None:
            # Labeled processes get synthetic pids above the unlabeled
            # range so "router" and gang process 0 never share a row.
            pid = raw_pid if not label else 10_000 + len(pids)
            while label and pid in pids.values():
                pid += 1
            pids[pkey] = pid
            events.append(
                {
                    "ph": "M",
                    "name": "process_name",
                    "pid": pid,
                    "tid": 0,
                    "args": {
                        "name": label or ("process %d" % raw_pid),
                    },
                }
            )
        thread = str(span.get("thread") or "main")
        key = (pkey, thread)
        tid = tids.get(key)
        if tid is None:
            tid = per_pid.get(pkey, 0) + 1
            per_pid[pkey] = tid
            tids[key] = tid
            events.append(
                {
                    "ph": "M",
                    "name": "thread_name",
                    "pid": pid,
                    "tid": tid,
                    "args": {"name": thread},
                }
            )
        args: Dict[str, Any] = {}
        attrs = span.get("attrs")
        if isinstance(attrs, dict):
            args.update(attrs)
        for field in ("trace_id", "span_id", "parent_id"):
            value = span.get(field)
            if value:
                args[field] = value
        event: Dict[str, Any] = {
            "name": str(span.get("name") or "span"),
            "ph": "X",
            "cat": "span",
            "pid": pid,
            "tid": tid,
            "ts": float(span.get("start") or 0.0) * 1e6,
            "dur": float(span.get("duration") or 0.0) * 1e6,
        }
        if args:
            event["args"] = args
        events.append(event)
    return {"traceEvents": events, "displayTimeUnit": "ms"}
