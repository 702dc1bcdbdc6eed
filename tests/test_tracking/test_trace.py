"""Span tracer semantics: nesting, per-thread stacks, sampling, sinks.

Every test builds its own :class:`Tracer` — the process-global one (from
``get_tracer``) is shared with live instrumentation and must not be
reconfigured by tests.
"""

import sys
import threading
import time

import pytest

from polyaxon_tpu.tracking.trace import (
    TRACEPARENT_HEADER,
    TraceContext,
    Tracer,
    chrome_trace,
    extract,
    get_tracer,
    inject,
    new_trace_id,
)


def _spans_by_name(tracer):
    return {s["name"]: s for s in tracer.spans()}


class TestNesting:
    def test_parent_child_ids(self):
        t = Tracer(process_id=3)
        with t.span("outer"):
            with t.span("inner"):
                pass
        by_name = _spans_by_name(t)
        outer, inner = by_name["outer"], by_name["inner"]
        assert outer["parent_id"] is None
        assert inner["parent_id"] == outer["span_id"]
        assert inner["span_id"] != outer["span_id"]
        # Ids carry the process id so they stay unique across the gang.
        assert outer["span_id"].startswith("3.")
        assert outer["process_id"] == 3

    def test_children_close_before_parent(self):
        t = Tracer()
        with t.span("parent"):
            with t.span("a"):
                pass
            with t.span("b"):
                pass
        names = [s["name"] for s in t.spans()]
        assert names == ["a", "b", "parent"]  # completion order
        by_name = _spans_by_name(t)
        assert by_name["a"]["parent_id"] == by_name["parent"]["span_id"]
        assert by_name["b"]["parent_id"] == by_name["parent"]["span_id"]

    def test_siblings_after_child_pops(self):
        """The second sibling must parent to the outer span, not to the
        first sibling (the stack must actually pop)."""
        t = Tracer()
        with t.span("root"):
            with t.span("s1"):
                pass
            with t.span("s2"):
                pass
        by_name = _spans_by_name(t)
        assert by_name["s2"]["parent_id"] == by_name["root"]["span_id"]

    def test_duration_and_start_recorded(self):
        t = Tracer()
        with t.span("timed"):
            pass
        span = t.spans()[0]
        assert span["duration"] >= 0.0
        assert span["start"] > 1e9  # epoch seconds, not perf_counter

    def test_attrs_and_set(self):
        t = Tracer()
        with t.span("op", run_id=7) as sp:
            sp.set(rows=42)
        attrs = t.spans()[0]["attrs"]
        assert attrs == {"run_id": 7, "rows": 42}

    def test_exception_recorded_and_propagated(self):
        t = Tracer()
        try:
            with t.span("boom"):
                raise ValueError("nope")
        except ValueError:
            pass
        else:
            raise AssertionError("span swallowed the exception")
        assert t.spans()[0]["attrs"]["error"] == "ValueError"


class TestThreads:
    def test_per_thread_parent_stacks(self):
        """Spans opened on different threads must not parent to each
        other; nesting is tracked per thread."""
        t = Tracer()
        ready = threading.Barrier(2)

        def work(label):
            with t.span(f"outer-{label}"):
                ready.wait(timeout=10)  # both outers open simultaneously
                with t.span(f"inner-{label}"):
                    pass

        threads = [threading.Thread(target=work, args=(i,)) for i in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        by_name = _spans_by_name(t)
        for i in range(2):
            inner, outer = by_name[f"inner-{i}"], by_name[f"outer-{i}"]
            assert inner["parent_id"] == outer["span_id"]
            assert outer["parent_id"] is None
            assert inner["thread"] == outer["thread"]
        assert by_name["inner-0"]["thread"] != by_name["inner-1"]["thread"]

    def test_concurrent_recording_keeps_every_span(self):
        t = Tracer(buffer=10_000)
        n_threads, n_iter = 8, 200

        def work():
            for _ in range(n_iter):
                with t.span("w"):
                    pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        spans = t.spans()
        assert len(spans) == n_threads * n_iter
        assert len({s["span_id"] for s in spans}) == len(spans)


class TestSamplingAndBuffer:
    def test_sample_zero_is_noop(self):
        t = Tracer(sample=0.0)
        with t.span("dropped") as sp:
            sp.set(ignored=True)  # no-op span still honours the API
        assert t.spans() == []

    def test_hot_sample_rate_is_per_call(self):
        t = Tracer(sample=1.0, hot_sample=0.0)
        with t.span("hot", sample=t.hot_sample):
            pass
        with t.span("cold"):
            pass
        assert [s["name"] for s in t.spans()] == ["cold"]

    def test_ring_buffer_bounded(self):
        t = Tracer(buffer=4)
        for i in range(10):
            with t.span(f"s{i}"):
                pass
        names = [s["name"] for s in t.spans()]
        assert names == ["s6", "s7", "s8", "s9"]  # oldest evicted

    def test_sink_receives_records(self):
        got = []
        t = Tracer(sink=got.append, trace_id="abc")
        with t.span("shipped"):
            pass
        assert len(got) == 1
        assert got[0]["name"] == "shipped" and got[0]["trace_id"] == "abc"

    def test_broken_sink_never_raises(self):
        def sink(_):
            raise RuntimeError("sink down")

        t = Tracer(sink=sink)
        with t.span("survives"):
            pass
        # Record still lands in the buffer despite the sink exploding.
        assert t.spans()[0]["name"] == "survives"

    def test_configure_in_place(self):
        t = Tracer()
        t.configure(sample=0.0, process_id=5, trace_id="run-1")
        assert t.sample == 0.0 and t.process_id == 5 and t.trace_id == "run-1"
        t.configure(sample=1.0)  # unset args keep current values
        assert t.process_id == 5 and t.trace_id == "run-1"

    def test_global_tracer_singleton(self):
        assert get_tracer() is get_tracer()


class TestChromeTrace:
    def test_events_and_thread_metadata(self):
        t = Tracer(process_id=1)
        with t.span("step", step=3):
            pass
        doc = chrome_trace(t.spans())
        assert doc["displayTimeUnit"] == "ms"
        metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert [m["name"] for m in metas] == ["process_name", "thread_name"]
        assert metas[0]["args"]["name"] == "process 1"
        threads = [m for m in metas if m["name"] == "thread_name"]
        assert len(xs) == 1
        x = xs[0]
        assert x["name"] == "step" and x["pid"] == 1
        assert x["tid"] == threads[0]["tid"]
        assert x["ts"] > 1e15  # epoch µs
        assert x["args"]["step"] == 3 and "span_id" in x["args"]

    def test_multi_process_rows(self):
        spans = []
        for pid in (0, 1):
            t = Tracer(process_id=pid)
            with t.span("work"):
                pass
            spans.extend(t.spans())
        doc = chrome_trace(spans)
        pids = {e["pid"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert pids == {0, 1}

    def test_tids_stable_per_thread(self):
        t = Tracer()
        with t.span("a"):
            pass
        with t.span("b"):
            pass
        doc = chrome_trace(t.spans())
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert xs[0]["tid"] == xs[1]["tid"]  # same thread, one row
        threads = [
            e
            for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "thread_name"
        ]
        assert len(threads) == 1

    def test_process_labels_get_distinct_tracks(self):
        """Router and replicas all default to process_id=0 — the process
        LABEL is what keeps a merged fleet trace on distinct rows."""
        spans = []
        for label in ("router", "replica-a"):
            t = Tracer(process=label)  # both process_id=0
            with t.span("router.request"):
                pass
            spans.extend(t.spans())
        t = Tracer(process_id=0)  # unlabeled gang span keeps its raw pid
        with t.span("train.step"):
            pass
        spans.extend(t.spans())
        doc = chrome_trace(spans)
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len({e["pid"] for e in xs}) == 3
        proc_names = {
            e["args"]["name"]: e["pid"]
            for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"
        }
        assert {"router", "replica-a"} <= set(proc_names)
        # Labeled rows live in the synthetic-pid range, clear of raw pids.
        assert proc_names["router"] >= 10_000
        unlabeled = [e for e in xs if e["pid"] == 0]
        assert len(unlabeled) == 1


class TestTraceContext:
    def test_inject_extract_round_trip(self):
        tid = new_trace_id()
        ctx = TraceContext(tid, "router.0.2a")
        headers = inject(ctx, {})
        assert headers[TRACEPARENT_HEADER] == f"00-{tid}-router.0.2a-01"
        got = extract(headers)
        assert got is not None
        assert got.trace_id == tid
        assert got.span_id == "router.0.2a"
        assert got.sampled is True

    def test_unsampled_flag_round_trips(self):
        ctx = TraceContext(new_trace_id(), sampled=False)
        got = extract(inject(ctx, {}))
        assert got is not None and got.sampled is False

    def test_empty_span_id_serializes_as_zeros(self):
        tid = new_trace_id()
        header = TraceContext(tid).header()
        assert header == f"00-{tid}-{'0' * 16}-01"
        got = extract({TRACEPARENT_HEADER: header})
        assert got.span_id == ""  # all-zero parent = no remote parent

    def test_child_keeps_trace_id_and_flags(self):
        ctx = TraceContext(new_trace_id(), "a.1", sampled=False)
        kid = ctx.child("b.2")
        assert kid.trace_id == ctx.trace_id
        assert kid.span_id == "b.2"
        assert kid.sampled is False

    def test_inject_none_is_noop(self):
        assert inject(None, {}) == {}


class TestExtract:
    def test_missing_header_is_none(self):
        assert extract(None) is None
        assert extract({}) is None
        assert extract({"content-type": "application/json"}) is None

    def test_title_case_header_accepted(self):
        tid = new_trace_id()
        got = extract({"Traceparent": f"00-{tid}-{'0' * 16}-01"})
        assert got is not None and got.trace_id == tid

    def test_malformed_headers_degrade_to_none(self):
        """Every malformed shape extracts to None — the receiving hop
        mints a fresh trace instead of erroring (never a 500)."""
        tid = new_trace_id()
        for raw in (
            "garbage",
            "",
            "00-%s-abc" % tid,  # 3 parts
            "00-%s-abc-01-xx" % tid,  # 5 parts
            "00-short-abc-01",  # trace id not 32 chars
            "00-%s-abc-01" % ("z" * 32),  # non-hex trace id
            "00-%s-abc-01" % ("0" * 32),  # all-zero trace id
            "00-%s-abc-zz" % tid,  # non-hex flags
            "0-%s-abc-01" % tid,  # bad version width
            12345,  # non-string value
        ):
            assert extract({TRACEPARENT_HEADER: raw}) is None, raw


class TestRecordSpan:
    def test_explicit_ids_and_process_label(self):
        t = Tracer(process="router")
        rec = t.record_span(
            "router.request",
            start=1000.0,
            duration=0.25,
            trace_id="ab" * 16,
            span_id="router.0.7",
            parent_id="cli.0.1",
            status=200,
        )
        assert rec["trace_id"] == "ab" * 16
        assert rec["span_id"] == "router.0.7"
        assert rec["parent_id"] == "cli.0.1"
        assert rec["process"] == "router"
        assert rec["attrs"] == {"status": 200}
        assert t.spans()[-1] is not rec or t.spans()[-1] == rec

    def test_process_attr_overrides_tracer_label(self):
        """The control-plane router shares a process with other
        components — per-span ``process=`` labels its track without
        reconfiguring the global tracer."""
        t = Tracer()
        rec = t.record_span(
            "router.attempt", start=0.0, duration=0.0, process="router"
        )
        assert rec["process"] == "router"

    def test_span_ctx_manager_with_trace_overrides(self):
        t = Tracer(process="router")
        with t.span(
            "router.request",
            sample=1.0,
            trace_id="cd" * 16,
            parent_id="client.0.1",
        ) as sp:
            pass
        rec = t.spans()[-1]
        assert rec["trace_id"] == "cd" * 16
        assert rec["parent_id"] == "client.0.1"
        assert rec["span_id"] == sp.span_id
        assert rec["span_id"].startswith("router.")


A, B, C, BASE = "loop.a", "loop.b", "loop.c", "loop.base"


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: the log holds what
    was opened and closed, in order."""

    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Annotation.log.append(("open", self.name))

    def __exit__(self, *exc):
        _Annotation.log.append(("close", self.name))
        return False


def _nested(clock):
    with clock.phase(A):
        with clock.phase(B):
            with clock.phase(B):
                pass
        with clock.phase(C):
            pass


def _raises(clock):
    with pytest.raises(ZeroDivisionError):
        with clock.phase(A):
            with clock.phase(B):
                1 / 0
    with clock.phase(C):
        pass


def _flat(clock):
    for name in (A, B, C, A):
        with clock.phase(name):
            pass


class TestPhaseClock:
    """The exclusive clock alone: no timing is asserted, only that the
    accounting adds up."""

    def _clock(self, tracer=None):
        clock = (tracer or Tracer()).phase_clock([A, B, C, BASE], BASE)
        clock.start()
        return clock

    @pytest.mark.parametrize(
        "body, counts",
        [
            (_nested, {A: 1, B: 2, C: 1, BASE: 1}),
            (_raises, {A: 1, B: 1, C: 1, BASE: 1}),
            (_flat, {A: 2, B: 1, C: 1, BASE: 1}),
        ],
        ids=["nested", "exception", "flat"],
    )
    def test_phases_sum_to_the_wall_time(self, body, counts):
        clock = self._clock()
        body(clock)
        # Mid-run: the open phase is charged up to now.
        wall, seconds, seen = clock.snapshot()[:3]
        assert seen == counts
        assert clock._stack == [BASE]  # whatever happened inside
        assert sum(seconds.values()) == pytest.approx(wall, rel=1e-6)
        clock.stop()
        wall, seconds, _ = clock.snapshot()[:3]
        assert sum(seconds.values()) == pytest.approx(wall, rel=1e-6)
        assert all(v >= 0.0 for v in seconds.values()) and wall > 0.0
        assert clock.snapshot()[0] == wall  # stopped: the wall stands still

    def test_entering_an_inner_phase_pauses_the_outer_one(self, monkeypatch):
        from types import SimpleNamespace

        from polyaxon_tpu.tracking import trace as trace_mod

        now = [100.0]
        monkeypatch.setattr(trace_mod, "time", SimpleNamespace(
            perf_counter=lambda: now[0], time=lambda: 1e9 + now[0], sleep=time.sleep))
        clock = self._clock()
        now[0] = 101.0  # one second of the base phase
        with clock.phase(A) as t_a:
            now[0] = 103.0  # two of A
            with clock.phase(B) as t_b:
                now[0] = 108.0  # five of B, while A stands still
            now[0] = 109.0  # one more of A
        now[0] = 110.0  # one more of the base phase
        assert (t_a, t_b, clock.t) == (101.0, 103.0, 109.0)
        assert clock.snapshot()[:3] == (10.0, {A: 3.0, B: 5.0, C: 0.0, BASE: 2.0},
                                        {A: 1, B: 1, C: 0, BASE: 1})
        clock.stop()
        now[0] = 200.0
        assert clock.snapshot()[0] == 10.0
        assert clock.epoch + t_a == 1e9 + 101.0

    @pytest.mark.parametrize("when", ["before_start", "after_stop"])
    def test_off_the_clock_a_phase_does_nothing(self, when):
        clock = Tracer().phase_clock([A, BASE], BASE, laps={A: ["x"]}, waits=[A])
        if when == "after_stop":
            clock.start()
            clock.stop()
        frozen = clock.snapshot(), clock.cpu_seconds()
        with clock.phase(A):
            clock.lap("x")
            clock.dispatched()
            with clock.phase(A):
                clock.drained()
        assert (clock.snapshot(), clock.cpu_seconds()) == frozen and clock._stack == []

    def test_unknown_base_is_refused(self):
        with pytest.raises(ValueError, match="base phase"):
            Tracer().phase_clock([A], BASE)

    @pytest.mark.parametrize("more", [{"laps": {C: ["x"]}}, {"waits": [C]}],
                             ids=["laps", "waits"])
    def test_laps_and_waits_of_no_phase_are_refused(self, more):
        with pytest.raises(ValueError, match="not in the catalog"):
            Tracer().phase_clock([A, BASE], BASE, **more)

    def test_a_lap_its_phase_does_not_have_is_refused(self):
        clock = Tracer().phase_clock([A, B, BASE], BASE, laps={A: ["x"]})
        clock.start()
        with clock.phase(B), pytest.raises(KeyError):
            clock.lap("x")
        with clock.phase(A), pytest.raises(KeyError):
            clock.lap("y")
        wall, seconds, _ = clock.snapshot()[:3]
        assert sum(seconds.values()) == pytest.approx(wall, rel=1e-6)

    @pytest.fixture
    def fake(self, monkeypatch):
        """The module's clocks by hand: ``now`` is ``perf_counter``, ``cpu``
        the owner's CPU-time clock."""
        from types import SimpleNamespace

        from polyaxon_tpu.tracking import trace as trace_mod

        clocks = SimpleNamespace(now=100.0, cpu=7.0, cpu_reads=0)

        def clock_gettime(clock_id):
            clocks.cpu_reads += 1
            return clocks.cpu

        monkeypatch.setattr(trace_mod, "time", SimpleNamespace(
            perf_counter=lambda: clocks.now, time=lambda: 1e9 + clocks.now, sleep=time.sleep,
            pthread_getcpuclockid=lambda ident: 3, clock_gettime=clock_gettime))
        return clocks

    @pytest.mark.parametrize("with_laps", [False, True])
    def test_laps_are_parts_of_their_phase_and_move_nothing_of_its_account(
            self, fake, with_laps):
        clock = Tracer().phase_clock([A, B, C, BASE], BASE, laps={A: ["x", "y"]})
        lap = clock.lap if with_laps else lambda name: None
        clock.start()
        fake.now = 101.0
        with clock.phase(A):
            fake.now = 102.0  # one second of A before any lap: unnamed
            lap("x")
            fake.now = 104.0  # two of x
            lap("y")
            fake.now = 107.0  # three of y
            with clock.phase(B):  # a transition ends the lap
                fake.now = 111.0
            fake.now = 112.0  # back in A, unnamed
            lap("x")
            fake.now = 112.5  # half a second more of x, to A's exit
        fake.now = 113.0
        snap = clock.snapshot()
        assert snap[:3] == (13.0, {A: 7.5, B: 4.0, C: 0.0, BASE: 1.5},
                            {A: 1, B: 1, C: 0, BASE: 1})
        assert snap.laps == ({f"{A}.x": 2.5, f"{A}.y": 3.0} if with_laps
                             else {f"{A}.x": 0.0, f"{A}.y": 0.0})
        assert sum(snap.laps.values()) <= snap.seconds[A]
        with clock.phase(A):
            lap("y")
            fake.now = 114.0
            # Mid-lap: the interval in flight is the lap's too.
            assert clock.snapshot().laps[f"{A}.y"] == (4.0 if with_laps else 0.0)
            assert clock.snapshot().seconds[A] == 8.5
        clock.stop()

    def test_uncovered_seconds_stop_between_dispatched_and_drained(self, fake):
        clock = Tracer().phase_clock([A, B, BASE], BASE)
        clock.start()
        fake.now = 101.0  # nothing dispatched yet: the base phase's second is uncovered
        with clock.phase(A):
            fake.now = 103.0  # two uncovered seconds of A
            clock.dispatched()
            clock.dispatched()  # a second program behind the first: no change
            fake.now = 106.0  # three covered
            with clock.phase(B):
                fake.now = 110.0  # four covered seconds of B
                clock.drained()
                clock.drained()
                fake.now = 110.5  # half an uncovered one
            assert clock.snapshot().uncovered == {A: 2.0, B: 0.5, BASE: 1.0}
            fake.now = 112.0  # in flight, uncovered
            assert clock.snapshot().uncovered == {A: 3.5, B: 0.5, BASE: 1.0}
            clock.dispatched()
            fake.now = 120.0
            assert clock.snapshot().uncovered == {A: 3.5, B: 0.5, BASE: 1.0}
        clock.stop()
        snap = clock.snapshot()
        assert snap[:3] == (20.0, {A: 14.5, B: 4.5, BASE: 1.0}, {A: 1, B: 1, BASE: 1})
        assert all(snap.uncovered[k] <= snap.seconds[k] for k in snap.seconds)

    def test_cpu_seconds_leave_out_what_the_waits_burn_by_one_visit_in_64(self, fake):
        clock = Tracer().phase_clock([A, B, BASE], BASE, waits=[B])
        clock.start()  # one read
        for _ in range(129):
            fake.now += 1.0
            fake.cpu += 1.0  # a second of host work, all of it on the CPU
            with clock.phase(A):
                pass
            with clock.phase(B):
                fake.now += 2.0
                fake.cpu += 0.25  # what the wait burns
        # The 1st, 65th and 129th visit were read at both edges, no other.
        assert clock._wait_cpu == {B: [3, 0.75]} and fake.cpu_reads == 1 + 2 * 3
        assert clock.cpu_seconds() == pytest.approx(129.0)
        snap = clock.snapshot()
        assert snap.wall - snap.seconds[B] == pytest.approx(129.0)  # never off the CPU
        fake.now += 4.0
        fake.cpu += 1.0  # four seconds of host work, three of them off the CPU
        assert clock.cpu_seconds() == pytest.approx(130.0)
        clock.stop()  # one more read; then the clock is the owner's no more
        reads = fake.cpu_reads
        assert clock.cpu_seconds() == pytest.approx(130.0) and fake.cpu_reads == reads

    def test_cpu_seconds_are_the_owners_read_from_any_thread(self):
        """The owner burns CPU, then sleeps: whoever asks reads the owner's
        CPU seconds, not its own, and they stand still once the clock stops."""
        box, burnt, done = {}, threading.Event(), threading.Event()

        def owner():
            clock = box["clock"] = self._clock()
            t0 = time.thread_time()
            while time.thread_time() - t0 < 0.05:
                pass
            box["own"] = time.thread_time() - t0
            burnt.set()
            done.wait(30)
            clock.stop()

        thread = threading.Thread(target=owner, daemon=True)
        mine = time.thread_time()
        thread.start()
        assert burnt.wait(30)
        time.sleep(0.05)  # the owner is off its CPU meanwhile
        clock = box["clock"]
        cpu, wall = clock.cpu_seconds(), clock.snapshot().wall
        assert box["own"] <= cpu <= wall and cpu < box["own"] + 0.04
        assert clock.cpu_seconds() - cpu < 0.01 < time.thread_time() - mine + 0.01
        done.set()
        thread.join(30)
        assert not thread.is_alive()
        stopped = clock.cpu_seconds()  # the owner is gone: the closed sum, no error
        assert cpu <= stopped == clock.cpu_seconds() <= clock.snapshot().wall

    def test_without_a_thread_cpu_clock_there_are_no_cpu_seconds(self, monkeypatch):
        from types import SimpleNamespace

        from polyaxon_tpu.tracking import trace as trace_mod

        monkeypatch.setattr(trace_mod, "time", SimpleNamespace(
            perf_counter=time.perf_counter, time=time.time, sleep=time.sleep))
        clock = self._clock()
        with clock.phase(A):
            assert clock.cpu_seconds() is None
        clock.stop()
        assert clock.cpu_seconds() is None
        wall, seconds, _ = clock.snapshot()[:3]
        assert sum(seconds.values()) == pytest.approx(wall, rel=1e-6)

    def test_snapshot_from_another_thread_is_consistent(self):
        """The reader retries around transitions in flight: whatever it
        sees, the seconds sum to the wall time and never shrink."""
        clock_box, stop = [], threading.Event()

        def owner():
            clock = Tracer().phase_clock([A, B, C, BASE], BASE, laps={A: ["x", "y"]})
            clock.start()
            clock_box.append(clock)
            while not stop.is_set():
                _nested(clock)
                with clock.phase(A):
                    clock.lap("x")
                    clock.dispatched()
                    clock.lap("y")
                    with clock.phase(C):
                        clock.drained()
            clock.stop()

        switch = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        thread = threading.Thread(target=owner, daemon=True)
        try:
            thread.start()
            while not clock_box:
                time.sleep(0.001)
            clock, last, last_cpu = clock_box[0], None, 0.0
            for _ in range(2000):
                cpu, snap = clock.cpu_seconds(), clock.snapshot()
                wall, seconds, counts = snap[:3]
                assert sum(seconds.values()) == pytest.approx(wall, rel=1e-6)
                # The parts never exceed what they are parts of.
                assert all(snap.uncovered[k] <= seconds[k] + 1e-9 for k in seconds)
                assert sum(snap.laps.values()) <= seconds[A] + 1e-9
                assert 0.0 <= cpu <= wall + 1e-3
                if last is not None:
                    assert wall >= last.wall
                    assert all(seconds[k] >= last.seconds[k] for k in seconds)
                    assert all(counts[k] >= last.counts[k] for k in counts)
                    assert all(snap.uncovered[k] >= last.uncovered[k] for k in seconds)
                    assert all(snap.laps[k] >= last.laps[k] for k in snap.laps)
                    assert cpu >= last_cpu
                last, last_cpu = snap, cpu
        finally:
            stop.set()
            thread.join(10)
            sys.setswitchinterval(switch)
        assert not thread.is_alive()


class TestProfilerHook:
    """While the hook is set, spans ignore sampling and wrap an annotation,
    and a clock's phases are annotations one after the other."""

    def setup_method(self):
        _Annotation.log = []

    @pytest.mark.parametrize("sample", [1.0, 0.0])
    def test_span_wraps_the_annotation_whatever_the_sampling(self, sample):
        t = Tracer(sample=sample)
        with t.span("x.y"):
            pass
        assert _Annotation.log == []
        t.profiler_hook = _Annotation
        with t.span("x.y", sample=sample):
            pass
        t.profiler_hook = None
        with t.span("x.y"):
            pass
        assert _Annotation.log == [("open", "x.y"), ("close", "x.y")]
        assert len(t.spans()) == (3 if sample else 1)

    def test_phases_are_annotated_one_after_the_other(self):
        t = Tracer()
        clock = t.phase_clock([A, B, BASE], BASE)
        clock.start()
        with clock.phase(A):
            pass
        assert _Annotation.log == []
        t.profiler_hook = _Annotation
        with clock.phase(A):
            with clock.phase(B):
                pass
        t.profiler_hook = None
        with clock.phase(A):  # closes what was open, opens nothing
            pass
        clock.stop()
        names = [n for kind, n in _Annotation.log if kind == "open"]
        assert names == [A, B, A, BASE]
        # Exclusive on the profiler's clock too: never two open at once.
        depth = 0
        for kind, _ in _Annotation.log:
            depth += 1 if kind == "open" else -1
            assert depth in (0, 1)
        assert depth == 0

    def test_a_lap_is_annotated_as_its_phase_dot_its_name(self):
        t = Tracer()
        clock = t.phase_clock([A, B, BASE], BASE, laps={A: ["x", "y"]})
        clock.start()
        with clock.phase(A):
            clock.lap("x")  # no capture: nothing annotated
        assert _Annotation.log == []
        t.profiler_hook = _Annotation
        with clock.phase(A):
            clock.lap("x")
            clock.dispatched()  # a flag is no annotation
            clock.lap("y")
            with clock.phase(B):
                clock.drained()
        t.profiler_hook = None
        clock.stop()
        names = [n for kind, n in _Annotation.log if kind == "open"]
        assert names == [A, f"{A}.x", f"{A}.y", B, A, BASE]
        depth = 0
        for kind, _ in _Annotation.log:
            depth += 1 if kind == "open" else -1
            assert depth in (0, 1)  # exclusive on the profiler's clock too
        assert depth == 0

    def test_a_profiler_that_raises_does_not_reach_the_loop(self):
        def broken(name):
            raise RuntimeError("no profiler")

        t = Tracer()
        clock = t.phase_clock([A, BASE], BASE)
        clock.start()
        t.profiler_hook = broken
        with clock.phase(A):
            pass
        clock.stop()
        wall, seconds, counts = clock.snapshot()[:3]
        assert counts[A] == 1
        assert sum(seconds.values()) == pytest.approx(wall, rel=1e-6)

    def test_trace_module_stays_free_of_jax(self):
        import subprocess

        code = (
            "import sys; import polyaxon_tpu.tracking.trace; "
            "sys.exit(1 if 'jax' in sys.modules else 0)"
        )
        assert subprocess.run([sys.executable, "-c", code], timeout=60).returncode == 0
