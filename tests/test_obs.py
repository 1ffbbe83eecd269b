"""Tests for the unified telemetry subsystem (jepsen_tpu.obs).

Five contracts, per the observability PR's acceptance criteria:

1. disabled tracer = a true no-op: singleton context manager, a
   per-call CPU budget in the hundreds of nanoseconds, zero retained
   allocations inside the obs module on the hot path;
2. spans nest correctly ACROSS the pipeline's host worker-pool threads
   (contextvar propagation via ctx_runner);
3. the Chrome trace export is a valid trace-event array (loads as
   JSON, complete events carry ts/dur, metadata names the tracks);
4. the JSONL artifact round-trips through a store run dir;
5. checker results are BIT-IDENTICAL with tracing on vs off for all
   five packable model families (telemetry may never perturb
   verdicts).
"""

import json
import os
import threading
import tracemalloc
from time import process_time

import pytest

from jepsen_tpu import envflags, obs
from jepsen_tpu.histories import (corrupt_history, rand_fifo_history,
                                  rand_gset_history, rand_queue_history,
                                  rand_register_history)
from jepsen_tpu.history import History, invoke_op, ok_op
from jepsen_tpu.models import (CASRegister, FIFOQueue, GSet, Mutex,
                               UnorderedQueue)


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    """Every test starts from flag-driven state and leaves nothing
    behind — tracing misconfigured here must not leak spans into the
    rest of the suite."""
    import jepsen_tpu.obs.export as export_mod

    monkeypatch.delenv("JEPSEN_TPU_TRACE", raising=False)
    monkeypatch.delenv("JEPSEN_TPU_JAX_PROFILE", raising=False)
    monkeypatch.delenv("JEPSEN_TPU_SEARCH_STATS", raising=False)
    obs.reset()
    obs.drain_search_stats()
    export_mod._last_reg_snapshot = {}
    yield
    obs.reset()
    obs.registry().reset()
    obs.drain_search_stats()
    export_mod._last_reg_snapshot = {}


def _h(*ops):
    return History.wrap(ops).index()


def _families():
    """(model, histories) per packable family — the test_pipeline
    parity set, shrunk: clean + one corrupted key each."""
    reg = [rand_register_history(n_ops=30, n_processes=4, crash_p=0.05,
                                 fail_p=0.05, seed=s) for s in range(4)]
    reg[2] = corrupt_history(reg[2], seed=3, n_corruptions=2)
    gset = [rand_gset_history(n_ops=24, n_processes=4, n_elements=5,
                              crash_p=0.06, seed=s + 70) for s in range(3)]
    uq = [rand_queue_history(n_ops=24, n_processes=4, n_values=3,
                             crash_p=0.06, seed=s + 80) for s in range(3)]
    fifo = [rand_fifo_history(n_ops=24, n_processes=4, n_values=3,
                              crash_p=0.15, seed=s + 90) for s in range(3)]
    mutex = [_h(invoke_op(0, "acquire", None), ok_op(0, "acquire", None),
                invoke_op(1, "acquire", None), ok_op(1, "acquire", None))]
    return [(CASRegister(), reg), (GSet(), gset), (UnorderedQueue(), uq),
            (FIFOQueue(), fifo), (Mutex(), mutex)]


# the spans that put every stretch of a check under a name
NEW_SPANS = ("independent.split", "independent.assemble",
             "linearizable.check", "bitdense.issue", "bitdense.fetch",
             "bitdense.results", "transfer.place", "python.gc")


def _keyed_and_single_checks():
    """One keyed check through the device batch and one single-key
    check on the device engine."""
    from jepsen_tpu import independent
    from jepsen_tpu.checker import linearizable
    from jepsen_tpu.independent import KV

    lin = linearizable(CASRegister(), algorithm="jax")
    keyed = _h(invoke_op(0, "write", KV("x", 1)),
               ok_op(0, "write", KV("x", 1)),
               invoke_op(1, "read", KV("y", None)),
               ok_op(1, "read", KV("y", None)))
    independent.checker(lin).check({}, keyed)
    return lin.check({}, _h(invoke_op(0, "write", 1), ok_op(0, "write", 1),
                            invoke_op(1, "read", None),
                            ok_op(1, "read", 2)))


# ------------------------------------------------- disabled = no-op


def test_disabled_span_is_singleton_noop():
    assert not obs.enabled()
    s1 = obs.span("a")
    s2 = obs.span("b", key=1)
    assert s1 is s2, "disabled span() must return the no-op singleton"
    with s1 as s:
        s.set(anything=True)       # absorbed, not stored
    assert s1.wall == 0.0 and s1.cpu == 0.0


def test_disabled_span_cpu_budget_and_zero_allocations(monkeypatch):
    """The hot-path guard: with tracing off, span() must cost no more
    than a few hundred ns of CPU per call and retain NOTHING inside
    the obs module. Budgeted on process_time (load-insensitive, the
    test_interpreter throughput-floor precedent) with generous CI
    slack — a real Span construction (clock reads + contextvar + lock)
    costs microseconds and busts it."""
    N = 200_000
    for _ in range(1000):          # warm: resolve the env gate once
        obs.span("warm")
    c0 = process_time()
    for _ in range(N):
        with obs.span("hot"):
            pass
    cpu = process_time() - c0
    assert cpu / N < 2e-6, f"{cpu / N * 1e9:.0f}ns per disabled span"

    # zero retained allocations attributed to the obs package (the
    # package re-exports a `tracer` FUNCTION, which shadows the
    # submodule on attribute access — go through sys.modules)
    import sys
    trmod = sys.modules["jepsen_tpu.obs.tracer"]
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(50_000):
        with obs.span("hot"):
            pass
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    flt = (tracemalloc.Filter(True, trmod.__file__),)
    growth = sum(st.size_diff for st in
                 after.filter_traces(flt).compare_to(
                     before.filter_traces(flt), "filename"))
    assert growth <= 0, f"obs retained {growth} bytes over 50k no-ops"

    # the span sites of a check allocate no Span with tracing off, and
    # no collector hook is installed
    made = []
    real_init = trmod.Span.__init__

    def spy(self, tracer, name, args, timed=False):
        made.append(name)
        real_init(self, tracer, name, args, timed)

    monkeypatch.setattr(trmod.Span, "__init__", spy)
    _keyed_and_single_checks()
    import gc
    gc.collect()
    assert not set(made) & set(NEW_SPANS), made
    assert trmod._gc_hook is None


def test_timer_measures_even_when_disabled():
    with obs.timer("t", shape="x") as tm:
        sum(range(50_000))
    assert tm.wall > 0
    assert obs.tracer() is None    # nothing recorded anywhere


def test_only_timer_spans_read_the_process_cpu_clock():
    """A process-CPU clock read is a system call (microseconds on a
    TPU host): the hot-path span records wall time alone."""
    obs.configure(True)
    with obs.span("hot") as s:
        sum(range(50_000))
    with obs.timer("timed") as tm:
        sum(range(50_000))
    assert s.cpu is None and "cpu" not in s.to_dict()
    assert tm.cpu > 0 and tm.to_dict()["cpu"] > 0
    assert "hot" in obs.summary()


# ------------------------------------------------- span mechanics


def test_span_nesting_and_timer_identity():
    tr = obs.configure(True)
    with obs.span("outer", a=1) as o:
        with obs.span("inner") as i:
            pass
    assert i.parent == o.sid and o.parent is None
    # timer's handle IS the recorded span: the emitted number and the
    # trace can never disagree
    with obs.timer("measured") as tm:
        pass
    assert tm in tr.spans()
    rec = [s for s in tr.spans() if s.name == "measured"][0]
    assert rec.t0 == tm.t0 and rec.t1 == tm.t1


def test_ctx_runner_propagates_across_threads():
    obs.configure(True)
    out = []
    with obs.span("root") as root:
        wrap = obs.ctx_runner()

        def work(k):
            with obs.span("child", key=k) as c:
                out.append(c)

        ts = [threading.Thread(target=wrap(work), args=(k,))
              for k in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    assert len(out) == 4
    assert all(c.parent == root.sid for c in out)


def test_flag_gating_and_env_path_accessor(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_TRACE", "0")
    obs.reset()
    assert not obs.enabled()
    monkeypatch.setenv("JEPSEN_TPU_TRACE", "1")
    obs.reset()
    assert obs.enabled() and obs.tracer().path == ""
    monkeypatch.setenv("JEPSEN_TPU_TRACE", "/tmp/t.json")
    obs.reset()
    assert obs.tracer().path == "/tmp/t.json"
    monkeypatch.setenv("JEPSEN_TPU_TRACE", "  ")
    obs.reset()
    with pytest.raises(envflags.EnvFlagError):
        obs.enabled()
    monkeypatch.setenv("JEPSEN_TPU_JAX_PROFILE", "1")
    assert obs.jax_profile_dir() == "store/jax_profile"
    monkeypatch.setenv("JEPSEN_TPU_JAX_PROFILE", "/tmp/prof")
    assert obs.jax_profile_dir() == "/tmp/prof"


def test_jax_profile_flag_arms_tracing_without_the_python_tracer(
        monkeypatch, tmp_path):
    """JEPSEN_TPU_JAX_PROFILE alone turns span tracing on (the spans
    are the capture's named steps), and its session runs with the
    Python function tracer off."""
    import jax

    monkeypatch.setenv("JEPSEN_TPU_JAX_PROFILE", str(tmp_path))
    obs.reset()
    assert obs.enabled() and obs.tracer().path == ""
    seen = {}

    class Session:
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    def trace(d, profiler_options=None, **kw):
        seen["dir"], seen["opts"] = d, profiler_options
        return Session()

    monkeypatch.setattr(jax.profiler, "trace", trace)
    with obs.maybe_jax_profile():
        pass
    assert seen["dir"] == str(tmp_path)
    assert seen["opts"].python_tracer_level == 0


# ------------------------------------------------- the span tree


def _tree(spans):
    by_id = {s.sid: s for s in spans}
    out = {}
    for s in spans:
        if s.track is None:
            p = by_id.get(s.parent)
            out.setdefault(s.name, []).append(p.name if p else None)
    return out


def test_single_key_check_span_tree():
    _keyed_and_single_checks()          # compiles
    tr = obs.configure(True)
    r = _keyed_and_single_checks()
    assert r["valid?"] is False
    tree = _tree(tr.drain())
    assert tree["linearizable.check"] == [None]
    assert tree["engine.encode"] == ["linearizable.check"]
    assert tree["bitdense.check"] == ["linearizable.check"]
    assert tree["bitdense.results"] == ["engine.check_batch",
                                        "linearizable.check"]
    assert tree["bitdense.issue"] == ["engine.check_batch",
                                      "bitdense.check"]
    assert tree["bitdense.fetch"] == ["bitdense.finalize",
                                      "bitdense.check"]
    assert tree["transfer.place"] == ["bitdense.pad_place",
                                      "linearizable.check"]
    assert tree["independent.check"] == [None]
    assert set(NEW_SPANS) - set(tree) == {"python.gc"}


def test_gc_pauses_are_spans_on_their_own_track_with_no_parent():
    import gc

    tr = obs.configure(True)
    with obs.span("outer"):
        gc.collect()
    gcs = [s for s in tr.spans() if s.name == "python.gc"]
    assert gcs and all(s.parent is None and s.track == "gc"
                       for s in gcs)
    assert any(s.args == {"generation": 2} for s in gcs)
    outer = [s for s in tr.spans() if s.name == "outer"][0]
    assert outer.t0 <= gcs[-1].t0 <= gcs[-1].t1 <= outer.t1
    events = obs.chrome_trace(tr)
    track = [e for e in events if e["ph"] == "M"
             and e["name"] == "thread_name" and e["args"]["name"] == "gc"]
    assert [e["pid"] for e in track] == [1]      # under the host
    assert [s.name for s in tr.drain()].count("python.gc") == len(gcs)
    assert tr.spans() == []


def test_gc_callbacks_restored_after_tracing_turns_off():
    import gc

    before = list(gc.callbacks)
    obs.configure(True)
    obs.configure(True)                 # a new session replaces the hook
    assert len(gc.callbacks) == len(before) + 1
    obs.configure(False)
    assert gc.callbacks == before
    obs.configure(True)
    obs.reset()
    assert gc.callbacks == before
    obs.configure(True, ring=8, flight_only=True)
    assert gc.callbacks == before       # the flight recorder alone: none


def test_spans_are_profiler_annotations_on_the_span_clock(tmp_path):
    """Under a profiler session with the Python tracer off, each span
    appears by name on a /host: plane, and one offset (from an anchor
    annotation opened beside a perf_counter read, as the benchmark's
    `bench.window`) maps the trace's starts onto the spans' own within
    200 us."""
    import glob
    from time import perf_counter, sleep

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    tr = obs.configure(True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("anchor"):
            t_anchor = perf_counter()
            for i in range(40):
                with obs.span("demo.outer", i=i):
                    with obs.span("demo.inner"):
                        jnp.ones(8).block_until_ready()
                    sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    spans = tr.drain()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    starts: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    starts.setdefault(ev.name, []).append(ev.start_ns)
    a0 = starts["anchor"][0]
    for name in ("demo.outer", "demo.inner"):
        ours = sorted(s.t0 for s in spans if s.name == name)
        theirs = sorted(t_anchor + (ns - a0) / 1e9 for ns in starts[name])
        assert len(ours) == len(theirs) == 40
        worst = max(abs(a - b) for a, b in zip(ours, theirs))
        assert worst < 200e-6, f"{name}: {worst * 1e6:.0f} us"


# ------------------------------------------------- metrics registry


def test_registry_counter_gauge_histogram_and_delta():
    reg = obs.Registry()
    reg.counter("a.b").inc()
    reg.counter("a.b").inc(4)
    g = reg.gauge("depth")
    g.set(3)
    g.set(1)
    reg.histogram("secs").observe(0.5)
    reg.histogram("secs").observe(1.5)
    snap = reg.snapshot()
    assert snap["a.b"]["value"] == 5
    assert snap["depth"] == {"type": "gauge", "value": 1, "max": 3,
                             "nops": 2}
    assert snap["secs"]["count"] == 2 and snap["secs"]["mean"] == 1.0
    before = snap
    reg.counter("a.b").inc(2)
    d = reg.delta(before)
    assert d["a.b"]["value"] == 2 and "depth" not in d
    with pytest.raises(TypeError):
        reg.gauge("a.b")           # name/type collisions must raise


def test_delta_windows_gauges_and_histograms():
    """Per-window semantics: a gauge that MOVED but returned to its
    old level still shows up (with max None — its own peak stayed
    below the process high-water, so it is unknowable from
    snapshots); a window that raises the high-water reports it; a
    histogram window reports its own count/total/mean, with min/max
    only when every observation is the window's own."""
    reg = obs.Registry()
    g = reg.gauge("depth")
    g.inc(5), g.dec(5)                       # run 1 peaks at 5
    reg.histogram("secs").observe(2.0)
    before = reg.snapshot()
    d0 = reg.delta({})                       # first window vs empty
    assert d0["depth"] == {"type": "gauge", "value": 0, "max": 5,
                           "nops": 2}
    assert d0["secs"]["min"] == d0["secs"]["max"] == 2.0

    g.inc(1), g.dec(1)                       # run 2 peaks at 1 only
    reg.histogram("secs").observe(1.0)
    d = reg.delta(before)
    assert d["depth"] == {"type": "gauge", "value": 0, "max": None,
                          "nops": 2}
    secs = dict(d["secs"])
    # the bucket ladder subtracts window-correctly: this window owns
    # exactly its own 1.0s observation, cumulative from the 1.0 bound
    buckets = dict(secs.pop("buckets"))
    assert buckets[1.0] == 1 and buckets[0.25] == 0 and buckets[60.0] == 1
    assert secs == {"type": "histogram", "count": 1, "total": 1.0,
                    "min": None, "max": None, "mean": 1.0}

    g.inc(9), g.dec(9)                       # run 3 sets a new peak
    d = reg.delta(before)
    assert d["depth"]["max"] == 9
    assert reg.delta(reg.snapshot()) == {}   # quiet window: nothing


# ------------------------------------------------- pipeline nesting


def test_span_nesting_across_pipeline_worker_pool():
    """The acceptance nesting test: a pipelined multi-key run's
    prepare/encode spans (opened on pool threads) chain up to the
    pipeline.run root, and dispatch/finalize spans nest per chunk."""
    from jepsen_tpu.parallel import pipeline as pipe

    tr = obs.configure(True)
    model = CASRegister()
    hs = [rand_register_history(n_ops=30, n_processes=4, crash_p=0.04,
                                seed=900 + s) for s in range(6)]
    pipe.check_batch_pipelined(model, hs, cache=False, chunk_keys=2,
                               depth=2)
    spans = {s.sid: s for s in tr.spans()}
    by_name = {}
    for s in spans.values():
        by_name.setdefault(s.name, []).append(s)

    assert len(by_name["pipeline.run"]) == 1
    root = by_name["pipeline.run"][0]
    assert len(by_name["pipeline.prepare"]) == len(hs)

    def ancestry(s):
        while s.parent is not None:
            s = spans[s.parent]
        return s

    for s in by_name["pipeline.prepare"] + by_name["pipeline.encode"]:
        assert ancestry(s) is root, (s.name, s.args)
    # the pool actually ran these off the main thread (the thing
    # contextvar propagation exists for)
    assert any(s.thread[1] != "MainThread"
               for s in by_name["pipeline.prepare"])
    # per-chunk dispatch/finalize pairs, nested under the root, plus
    # one synthetic device-track span per chunk
    n_chunks = len(by_name["pipeline.dispatch"])
    assert n_chunks >= 3            # 6 keys at chunk_keys=2
    assert len(by_name["pipeline.finalize"]) == n_chunks
    assert len(by_name["device.search"]) == n_chunks
    for s in by_name["pipeline.dispatch"]:
        assert spans[s.parent].name == "pipeline.run"
    assert all(s.track and s.track.startswith("bucket-")
               for s in by_name["device.search"])
    # the registry absorbed the executor's counters
    snap = obs.registry().snapshot()
    assert snap["pipeline.keys"]["value"] >= len(hs)
    assert snap["pipeline.chunks"]["value"] >= n_chunks
    assert snap["pipeline.inflight"]["max"] >= 1


# ------------------------------------------------- exporters


def _traced_run():
    from jepsen_tpu.parallel import engine

    model = CASRegister()
    hs = [rand_register_history(n_ops=24, n_processes=3, seed=s)
          for s in range(4)]
    engine.check_batch(model, hs, pipeline=True, cache=False)


def test_chrome_trace_schema(tmp_path):
    obs.configure(True)
    _traced_run()
    path = obs.write_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as fh:
        events = json.load(fh)     # valid JSON document
    assert isinstance(events, list) and events
    # "C" joined the set with the counter tracks (pipeline.inflight
    # samples ride every traced pipelined run)
    assert {e["ph"] for e in events} <= {"X", "M", "C"}
    cs = [e for e in events if e["ph"] == "C"]
    assert any(e["name"] == "pipeline.inflight" for e in cs), cs
    for e in cs:
        assert "value" in e["args"] and e["ts"] >= 0
    procs = {e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert procs == {"host", "device"}
    xs = [e for e in events if e["ph"] == "X"]
    assert xs
    for e in xs:
        assert isinstance(e["name"], str) and e["name"]
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert "pid" in e and "tid" in e
        assert "span_id" in e["args"]
    # device-bucket tracks exist and are named
    tracks = {e["args"]["name"] for e in events
              if e["ph"] == "M" and e["name"] == "thread_name"
              and e["pid"] == 2}
    assert any(t.startswith("bucket-") for t in tracks), tracks


def test_jsonl_store_dir_roundtrip(tmp_path):
    from jepsen_tpu import store as jstore

    obs.configure(True, path=str(tmp_path / "flag_trace.json"))
    obs.counter("engine.test_counter").inc(7)
    _traced_run()
    st = jstore.Store("obs-test", base_dir=str(tmp_path))
    arts = st.save_telemetry()
    assert arts is not None
    lines = [json.loads(ln) for ln in
             open(os.path.join(st.dir, "telemetry.jsonl"))]
    kinds = {ln["type"] for ln in lines}
    assert kinds == {"span", "metric"}
    names = {ln["name"] for ln in lines if ln["type"] == "span"}
    assert {"pipeline.run", "pipeline.prepare",
            "pipeline.dispatch"} <= names
    mets = {ln["name"]: ln for ln in lines if ln["type"] == "metric"}
    assert mets["engine.test_counter"]["value"] == 7
    # trace.json in the run dir AND at the flag path
    assert json.load(open(os.path.join(st.dir, "trace.json")))
    assert json.load(open(tmp_path / "flag_trace.json"))
    # the human summary mentions the hottest span names
    txt = open(os.path.join(st.dir, "telemetry.txt")).read()
    assert "pipeline.run" in txt and "engine.test_counter" in txt

    # a SECOND run in the same process must not overwrite the flag
    # path (the buffer was drained — the file would hold only run 2):
    # it gets a numbered sibling instead
    _traced_run()
    st2 = jstore.Store("obs-test", base_dir=str(tmp_path))
    arts2 = st2.save_telemetry()
    assert arts2["flag_trace"] == str(tmp_path / "flag_trace.2.json")
    assert json.load(open(tmp_path / "flag_trace.2.json"))
    assert json.load(open(tmp_path / "flag_trace.json"))  # run 1 intact


def test_env_flag_end_to_end_acceptance(tmp_path, monkeypatch):
    """The PR's acceptance criterion verbatim: with JEPSEN_TPU_TRACE=1
    (the env flag, not the programmatic gate), a multi-key pipelined
    check_batch run produces a valid Chrome trace whose encode/
    dispatch spans nest per key and per chunk, plus the JSONL artifact
    in a store run dir."""
    from jepsen_tpu import store as jstore
    from jepsen_tpu.parallel import engine

    monkeypatch.setenv("JEPSEN_TPU_TRACE", "1")
    obs.reset()
    model = CASRegister()
    hs = [rand_register_history(n_ops=24, n_processes=3, seed=s)
          for s in range(5)]
    engine.check_batch(model, hs, pipeline=True, cache=False,
                       pipeline_stats={})
    st = jstore.Store("obs-accept", base_dir=str(tmp_path))
    arts = st.save_telemetry()
    assert arts is not None
    events = json.load(open(os.path.join(st.dir, "trace.json")))
    xs = {e["name"]: e for e in events if e["ph"] == "X"}
    assert "pipeline.run" in xs and "pipeline.dispatch" in xs
    # per-key prepare spans, per-chunk dispatch spans
    keys = {e["args"].get("key") for e in events if e["ph"] == "X"
            and e["name"] == "pipeline.prepare"}
    assert keys == set(range(len(hs)))
    chunks = [e for e in events if e["ph"] == "X"
              and e["name"] == "pipeline.dispatch"]
    assert chunks and all("chunk" in e["args"] for e in chunks)
    # nesting: every dispatch's parent_id is the run span's id
    run_id = xs["pipeline.run"]["args"]["span_id"]
    assert all(e["args"]["parent_id"] == run_id for e in chunks)
    assert os.path.exists(os.path.join(st.dir, "telemetry.jsonl"))


def test_export_run_noop_when_disabled(tmp_path):
    assert obs.export_run(str(tmp_path)) is None
    assert not os.path.exists(tmp_path / "telemetry.jsonl")


def test_export_run_is_per_run(tmp_path):
    """A process that analyzes several runs (`--test-count`,
    test-all) must not leak run 1's spans or counter totals into run
    2's artifacts: export_run drains the span buffer and reports
    counters as deltas since the previous export."""
    tr = obs.configure(True)
    g = obs.gauge("pipeline.test_inflight")
    with obs.span("run.one"):
        pass
    obs.counter("engine.test_counter").inc(5)
    g.inc(5), g.dec(5)               # run 1 peaks at depth 5
    obs.histogram("engine.test_secs").observe(2.0)
    obs.export_run(str(tmp_path / "r1"))
    with obs.span("run.two"):
        pass
    obs.counter("engine.test_counter").inc(2)
    g.inc(1), g.dec(1)               # run 2 peaks at 1 — below run 1
    obs.histogram("engine.test_secs").observe(1.0)
    obs.export_run(str(tmp_path / "r2"))

    def load(d):
        return [json.loads(ln) for ln in
                open(os.path.join(str(tmp_path), d, "telemetry.jsonl"))]

    names1 = {ln["name"] for ln in load("r1") if ln["type"] == "span"}
    names2 = {ln["name"] for ln in load("r2") if ln["type"] == "span"}
    assert names1 == {"run.one"} and names2 == {"run.two"}

    def metric(d, name):
        m = [ln for ln in load(d) if ln["type"] == "metric"
             and ln["name"] == name]
        return m[0] if m else None

    assert metric("r2", "engine.test_counter")["value"] == 2  # not 7
    # the gauge MOVED in run 2, so it must not vanish from run 2's
    # artifacts just because it ended at the same level; run 1's
    # peak of 5 must not masquerade as run 2's (max: None = this
    # run's own peak stayed below the process high-water)
    assert metric("r1", "pipeline.test_inflight")["max"] == 5
    g2 = metric("r2", "pipeline.test_inflight")
    assert g2 is not None and g2["max"] is None
    # histograms report the run's own window, not cumulative totals
    h2 = metric("r2", "engine.test_secs")
    assert h2["count"] == 1 and h2["total"] == 1.0
    assert tr.spans() == []          # buffer drained, memory bounded


# ------------------------------------------------- parity on vs off


@pytest.mark.parametrize("model,hs", _families(),
                         ids=lambda v: type(v).__name__
                         if not isinstance(v, list) else "")
def test_results_bit_identical_tracing_on_vs_off(model, hs):
    """Telemetry may never perturb verdicts: serial and pipelined
    check_batch results are the same dicts with tracing off, on, and
    off again — for every packable family, clean + corrupted keys."""
    from jepsen_tpu.parallel import engine

    assert not obs.enabled()
    rs_off = engine.check_batch(model, hs, capacity=64,
                                max_capacity=4096)
    rs_off_p = engine.check_batch(model, hs, capacity=64,
                                  max_capacity=4096, pipeline=True,
                                  cache=False)
    obs.configure(True)
    rs_on = engine.check_batch(model, hs, capacity=64,
                               max_capacity=4096)
    rs_on_p = engine.check_batch(model, hs, capacity=64,
                                 max_capacity=4096, pipeline=True,
                                 cache=False)
    obs.reset()
    assert rs_on == rs_off
    assert rs_on_p == rs_off_p == rs_off


# ------------------------------------------------- engine counters


def test_engine_false_invalid_counter(monkeypatch):
    """The hoisted-logging satellite: the device-false-invalid
    override increments engine.false_invalid (routed through the
    registry, not just a log line)."""
    from jepsen_tpu.checker import wgl
    from jepsen_tpu.parallel import encode as enc_mod, engine

    e = enc_mod.encode(CASRegister(),
                       _h(invoke_op(0, "write", 1), ok_op(0, "write", 1),
                          invoke_op(1, "read", None), ok_op(1, "read", 1)))
    monkeypatch.setattr(wgl, "check_calls",
                        lambda *a, **k: {"valid?": True})
    obs.registry().reset()
    r = engine._disagreement_recheck(CASRegister(), e, "test note")
    assert r["valid?"] is True
    assert obs.registry().counter("engine.false_invalid").value == 1


def test_engine_capacity_escalation_counter():
    """check_encoded's overflow-doubling ladder is counted."""
    from jepsen_tpu.histories import adversarial_register_history
    from jepsen_tpu.parallel import encode as enc_mod, engine

    e = enc_mod.encode(CASRegister(), adversarial_register_history(
        n_ops=120, k_crashed=8, seed=7))
    obs.registry().reset()
    r = engine.check_encoded(e, capacity=64, max_capacity=1 << 16)
    assert r["valid?"] is True
    assert r["capacity"] > 64      # it did escalate
    esc = obs.registry().counter("engine.capacity_escalations").value
    assert esc >= 1
    assert obs.registry().counter("engine.configs_stepped").value \
        == r["configs-stepped"]
