"""One run of one benchmark cell, in one process.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up makes the cell's pool of histories from the seed (the
configuration's generator), builds the checker the configuration's
entry names, and checks every history of the pool once, which compiles
(or loads from JAX's persistent cache) every program the window will
run. The window is a closed loop: one checker, the next check starts
when the last returns, cycling through the pool for `--seconds` (the
check under way at the deadline finishes and counts). The program keeps
no result or encode cache on this path, so a repeat costs full work.
Then the device's peak memory is read, the reference (the
configuration's model) answers each pool entry, and every check of the
window is compared with it; every key of every check, set-up's too, has
to have been answered on the device.

The configuration's `entry`, `generator` and `model` are found by name
(`named.py`), as each metric is (`metrics/<name>.py`).

`--trace 0` prints the cell's end-to-end metrics; `--trace 1` turns on
the program's spans and a profiler trace of the window's first
`PROFILE_SECONDS`, and prints the per-layer metrics and a breakdown.

The last lines of standard error are the compared numbers beside their
limits; the last line of standard output is one JSON object.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
PROFILE_SECONDS = 5.0
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")
LOAD_EVENTS = ("/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/cache_retrieval_time_sec")


class NoAccelerator(RuntimeError):
    pass


class Run:
    """What one run saw, for the metric readers. Times are
    `time.perf_counter()` seconds, the clock of the program's spans."""

    def __init__(self):
        self.setup_s = None
        self.window = None            # (start, end) of the measured window
        self.checks: list = []        # (pool index, start, end, ops)
        self.setup_compile_s = 0.0
        self.window_compiles = 0
        self.spans: list = []         # the program's spans, as dicts
        self.traced = None            # (start, end) of the profiled window
        self.device = None            # [(op name, start, end)] in it


class CompileWatch:
    """Counts compiles and cache loads, and sums the seconds spent
    tracing, lowering, compiling and loading programs, split at the
    window's start."""

    def __init__(self):
        self.in_window = False
        self.setup_s = 0.0
        self.window_loads = 0

    def __call__(self, name, secs, **_):
        if self.in_window:
            if name in LOAD_EVENTS:
                self.window_loads += 1
        elif name in COMPILE_EVENTS:
            self.setup_s += secs


def load_spec(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_metrics(spec: dict, cell: str, trace: bool) -> list:
    """The metric entries the cell reports in this mode."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(root: str, name: str):
    import named
    return named.load(root, "metrics", name).read


def device_info(jax, chips: int, require_accelerator: bool):
    devs = jax.devices()
    if require_accelerator and devs[0].platform not in ("tpu", "gpu"):
        raise NoAccelerator(
            f"no accelerator: JAX reports platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoAccelerator(f"{chips} chips asked for, {len(devs)} found")
    return devs


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(root: str, cell: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_accelerator: bool = True,
             shrink=None, keep_trace: bool = False, observe=None) -> dict:
    """One run; returns the result object. `shrink(cfg, traffic)` lets
    a test cut the cell's sizes, `keep_trace` keeps the profiler's
    files and `observe(run)` sees what the readers see; the command
    passes none of them."""
    spec = load_spec(root)
    entry = next(w for w in spec["workloads"] if w["name"] == cell)
    if root not in sys.path:
        sys.path.append(root)
    import traffic as traffic_mod
    cfg = traffic_mod.load(root, "configs", entry["config"])
    mix = traffic_mod.load(root, "traffic", entry["traffic"])
    if shrink is not None:
        cfg, mix = shrink(dict(cfg), dict(mix))

    import jax
    import jax.monitoring
    devs = device_info(jax, entry["chips"], require_accelerator)
    from jepsen_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    watch = CompileWatch()
    jax.monitoring.register_event_duration_secs_listener(watch)

    import compare
    import named
    program = named.load(root, "entries", cfg["entry"])
    pool = traffic_mod.pool(root, cfg, mix, seed)
    histories = [program.wrap(e) for e in pool]
    n_ops = [traffic_mod.n_invocations(e) for e in pool]
    checker = program.checker(cfg)
    notes = {"closures": set(), "off_device": 0}
    for h in histories:                 # warm every shape of the pool
        program_notes(program, notes, program.check(checker, h))

    run = Run()
    run.setup_compile_s = watch.setup_s
    from jepsen_tpu import obs
    profiler = None
    if trace:
        obs.configure(on=True)
        profiler = Profiler(root, cell, keep_trace)
    results = []
    watch.in_window = True
    run.setup_s = time.perf_counter() - t_start
    t0 = time.perf_counter()
    if profiler:
        profiler.start()
    deadline = t0 + seconds
    i = 0
    now = t0
    while now < deadline:
        k = i % len(histories)
        try:
            r = program.check(checker, histories[k])
        except Exception as err:  # noqa: BLE001 - an unanswered check is counted
            print(f"check {i} (pool entry {k}) raised: {err!r}",
                  file=sys.stderr)
            r = None
        end = time.perf_counter()
        run.checks.append((k, now, end, n_ops[k]))
        results.append((k, r))
        if profiler:
            profiler.maybe_stop(end)
        now = end
        i += 1
    run.window = (t0, now)
    watch.in_window = False
    run.window_compiles = watch.window_loads
    if profiler:
        profiler.maybe_stop(float("inf"))
        run.traced, run.device = profiler.collect()
        run.spans = [{"name": s.name, "id": s.sid, "parent": s.parent,
                      "t0": s.t0, "t1": s.t1}
                     for s in obs.tracer().drain()]
        obs.configure(on=False)
    peak = memory_peak(devs[:entry["chips"]])

    observed = []
    for k, r in results:
        if r is not None:
            program_notes(program, notes, r)
        observed.append((k, None if r is None
                         else program.answers(r, pool[k])))
    del results
    ref = named.load(root, "references", cfg["model"])
    refs = [compare.reference_answers(ref, e) for e in pool]
    numbers = compare.compare(observed, refs, notes["off_device"])
    ok = compare.correct(numbers)
    failed = numbers["checks_wrong"]["value"]

    if observe is not None:
        observe(run)
    metrics = {}
    for m in cell_metrics(spec, cell, trace):
        v = load_reader(root, m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"correct": ok, "attempted": len(observed), "failed": failed,
           "metrics": metrics,
           "device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs),
                      "memory_peak_bytes": peak}}
    if trace:
        import tracereduce
        out["device"]["busy_s"] = tracereduce.busy_seconds(run.device)
        out["device"]["window_s"] = run.traced[1] - run.traced[0]
        out["breakdown"] = tracereduce.breakdown(
            run.device, run.traced, run.spans, run.checks)
    out["closures"] = sorted(notes["closures"])
    out["compared"] = numbers
    return out


def program_notes(program, notes: dict, result: dict) -> None:
    n = program.path_notes(result)
    notes["closures"].update(n["closures"])
    notes["off_device"] += n["off_device"]


class Profiler:
    """A JAX profiler trace of the window's first PROFILE_SECONDS, with
    an annotation written into the trace and onto the harness's clock
    at the same moment, which aligns the two."""

    def __init__(self, root: str, cell: str, keep: bool):
        self.dir = os.path.join(root, "benchmark", ".profile", cell)
        self.keep = keep
        self.on = False
        self.t0 = self.t1 = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self.ann = jax.profiler.TraceAnnotation("bench.window")
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        self.on = True

    def maybe_stop(self, now: float):
        if not self.on or now - self.t0 < PROFILE_SECONDS:
            return
        import jax
        self.t1 = time.perf_counter()
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False

    def collect(self):
        import tracereduce
        device = tracereduce.device_events(self.dir, (self.t0, self.t1),
                                           "bench.window")
        if not self.keep:
            shutil.rmtree(self.dir, ignore_errors=True)
        return (self.t0, self.t1), device


def main(argv=None, t_start=None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    p = argparse.ArgumentParser(description="Run one benchmark cell once.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    root = os.path.dirname(HERE)
    try:
        out = run_cell(root, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start)
    except NoAccelerator as err:
        print(f"benchmark: {err}", file=sys.stderr)
        return 2
    for name, x in out["compared"].items():
        print(f"compared {name}: {x['value']} (limit {x['limit']})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0
