"""Compile economics for the engine/serve stacks (docs/performance.md
"Compile economics").

At fleet scale the jit compile is the tail: every new (step, capacity,
width, tier, dedupe, pack, probe_limit) tuple compiles on first touch,
a rehomed key's adopter recompiles everything its dead replica had
warm, and the escalation ladder walks shape sequences that each
compile mid-incident. Four cooperating pieces close that, all behind
``JEPSEN_TPU_COMPILE_CACHE``:

**Shape canonicalization** (``JEPSEN_TPU_CANON_SHAPES``) — the scan
step skips pad rows (``ev_slot < 0``) without touching the carry, so
quantizing event-row counts onto the ``EVENT_QUANTUM`` ladder (the
``parallel.extend`` chunk precedent) is parity-safe: verdicts,
counterexamples, max-frontier, and configs-stepped are identical, and
the fleet-wide program population collapses from one-per-history-
length to one-per-quantum-rung. Flag off: byte-identical shapes,
results, and schemas (the PIPELINE/DEDUPE precedent).

**The program registry + AOT** — a per-process table of
shape-tuple -> compiled executable. Armed, the engine's sparse jit
entries dispatch through ``jax.jit(...).lower().compile()`` programs
the registry owns, with ``engine.programs.{hits,misses,compiles,
preloads,load_errors,precompiles,manifest_warms}`` counters and a
``serve.compile_secs`` histogram (every compile/deserialize paid,
prewarm and ladder included) on /metrics.

**Persistence** — ``JEPSEN_TPU_COMPILE_CACHE=<dir>`` additionally
persists serialized executables (``jax.experimental.
serialize_executable``) so a restarted replica cold-starts warm.
Every load is version/fingerprint-guarded: a blob from a different
jax/jaxlib/backend, a foreign shape key, or a torn file degrades to a
fresh compile (counted ``load_errors``) — never a crash, never a
wrong program. Writes land tmp + ``os.replace`` so a kill mid-persist
leaves no torn final file. Pickles here carry the same trust posture
as the run store (docs/performance.md encode-cache precedent): load
only from directories this framework wrote.

**Warm handoff + ladder precompile** — ``manifest()`` serializes the
registry's program population (entry + statics + aval spec) as JSON;
``serve.ring.transfer_key`` ships it with the WAL segments and
``CheckerService.adopt_keys`` pre-warms it before replaying.
``JEPSEN_TPU_PRECOMPILE=1`` adds a background best-effort thread that
pre-compiles the next capacity rung above each live program, so a
mid-incident escalation re-dispatch finds its doubled-``N`` program
already resident.
"""

from __future__ import annotations

import json
import logging
import os
import pickle
import threading
from hashlib import sha256
from time import perf_counter
from typing import Dict, List, Optional, Tuple

import numpy as np

from jepsen_tpu import obs
from jepsen_tpu.envflags import env_bool, env_path

_log = logging.getLogger("jepsen_tpu.programs")

# The shape quantum every canonicalized row count snaps to — ONE
# source of truth; parallel.extend re-exports it (its chunk padding
# rode this ladder first).
EVENT_QUANTUM = 16

# capacity ceiling the ladder precompiler respects (the engine's own
# escalation ceiling — compiling past what dispatch can reach is waste)
_LADDER_CEILING = 1 << 20


def quantize_rows(n: int) -> int:
    """Smallest EVENT_QUANTUM multiple >= n (and >= one quantum)."""
    return max(EVENT_QUANTUM, -(-int(n) // EVENT_QUANTUM) * EVENT_QUANTUM)


def canon_armed() -> bool:
    """JEPSEN_TPU_CANON_SHAPES=1: quantize one-shot/resumable chunk
    row counts onto the EVENT_QUANTUM ladder (parity-safe padding)."""
    return bool(env_bool("JEPSEN_TPU_CANON_SHAPES", False))


def precompile_armed() -> bool:
    """JEPSEN_TPU_PRECOMPILE=1: background next-rung precompile."""
    return bool(env_bool("JEPSEN_TPU_PRECOMPILE", False))


def resolve_cache() -> Optional[str]:
    """The JEPSEN_TPU_COMPILE_CACHE destination: None = feature off,
    "" = registry armed with no persistence, path = registry armed +
    executables persisted there."""
    return env_path("JEPSEN_TPU_COMPILE_CACHE", what="cache directory")


def pad_rows(xs: Dict[str, np.ndarray], r_pad: int) -> Dict[str, np.ndarray]:
    """Pad an event-chunk dict's leading (row) axis to ``r_pad`` with
    pad rows — ev_slot=-1 / unoccupied slots, exactly the rows the
    scan step skips without advancing its event index or touching the
    carry (the parallel.extend._xs_slice fill contract), so padding is
    parity-safe by construction."""
    r = len(xs["ev_slot"])
    if r_pad <= r:
        return xs
    out = {}
    for k, v in xs.items():
        v = np.asarray(v)
        fill = False if v.dtype == np.bool_ else -1
        buf = np.full((r_pad,) + v.shape[1:], fill, v.dtype)
        buf[:r] = v
        out[k] = buf
    return out


def maybe_canon_rows(xs: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """``pad_rows`` onto the quantum ladder when JEPSEN_TPU_CANON_SHAPES
    is armed; the identity otherwise (flag off = byte-identical)."""
    if not canon_armed():
        return xs
    return pad_rows(xs, quantize_rows(len(xs["ev_slot"])))


# ------------------------------------------------------- shape specs


def _aval_spec(tree):
    """A JSON-able shape/dtype spec of a pytree of arrays — the
    manifest interchange form (tuples and dicts tagged so the spec
    round-trips to the exact treedef ``lower`` needs)."""
    if isinstance(tree, dict):
        return {"t": "d", "v": {k: _aval_spec(tree[k])
                                for k in sorted(tree)}}
    if isinstance(tree, tuple):
        return {"t": "t", "v": [_aval_spec(x) for x in tree]}
    if isinstance(tree, list):
        return {"t": "l", "v": [_aval_spec(x) for x in tree]}
    shape = tuple(int(d) for d in getattr(tree, "shape", ()))
    dtype = getattr(tree, "dtype", None)
    return {"t": "a", "s": list(shape),
            "d": np.dtype(dtype if dtype is not None
                          else type(tree)).name}


def _spec_to_shapes(spec):
    """Manifest spec -> pytree of jax.ShapeDtypeStruct (AOT lowering
    input)."""
    import jax
    t = spec["t"]
    if t == "d":
        return {k: _spec_to_shapes(v) for k, v in spec["v"].items()}
    if t == "t":
        return tuple(_spec_to_shapes(x) for x in spec["v"])
    if t == "l":
        return [_spec_to_shapes(x) for x in spec["v"]]
    return jax.ShapeDtypeStruct(tuple(spec["s"]), np.dtype(spec["d"]))


def _statics_spec(statics: tuple):
    """Statics tuple -> JSON-able form (nested tuples tagged — the
    config-pack spec is a tuple of ints)."""
    def enc(v):
        if isinstance(v, tuple):
            return {"t": "t", "v": [enc(x) for x in v]}
        if isinstance(v, (np.integer,)):
            return {"t": "i", "v": int(v)}
        if isinstance(v, (np.bool_,)):
            return {"t": "b", "v": bool(v)}
        if v is None or isinstance(v, (str, int, float, bool)):
            return {"t": "i", "v": v}
        raise TypeError(f"unserializable static {v!r}")
    return [enc(v) for v in statics]


def _spec_to_statics(spec) -> tuple:
    def dec(e):
        if e["t"] == "t":
            return tuple(dec(x) for x in e["v"])
        if e["t"] == "b":
            return bool(e["v"])
        return e["v"]
    return tuple(dec(e) for e in spec)


def _device_token(traced) -> str:
    """A stable token for where the traced arrays live — part of the
    program key, because an executable is compiled for a specific
    device assignment and must never answer a call placed elsewhere."""
    leaves: list = []

    def walk(t):
        if isinstance(t, dict):
            for v in t.values():
                walk(v)
        elif isinstance(t, (tuple, list)):
            for v in t:
                walk(v)
        else:
            leaves.append(t)
    walk(traced)
    for leaf in leaves:
        devs = getattr(leaf, "devices", None)
        if callable(devs):
            try:
                return ",".join(sorted(f"{d.platform}:{d.id}"
                                       for d in devs()))
            except Exception:  # noqa: BLE001 — abstract avals
                continue
    return "host"


def _versions() -> Tuple[str, str]:
    import jax
    try:
        import jaxlib.version
        jl = jaxlib.version.__version__
    except Exception:  # noqa: BLE001
        jl = "?"
    return jax.__version__, jl


def _no_persistent_cache():
    """Scope under which registry compiles bypass jax's persistent
    compilation cache (``jax_compilation_cache_dir``). An executable
    satisfied from that cache does not survive a
    ``serialize_executable`` round-trip on the CPU backend — the
    deserialized program aborts with "Symbols not found" — so a
    ``.jprog`` persisted from a cache-hit executable is poisoned and
    every restart that preloads it degrades to a fresh compile. The
    registry's own disk layer already covers these programs, so the
    global cache is redundant here anyway. The config state is
    context-managed (thread-local overlay): concurrent non-registry
    jits are unaffected."""
    try:
        from jax._src.config import enable_compilation_cache
        return enable_compilation_cache(False)
    except Exception:  # noqa: BLE001 — private API; degrade to no-op
        import contextlib
        return contextlib.nullcontext()


# XLA:CPU cannot serialize an executable that has already run (a sort
# comparator is built on first use: "`LessThan` is not serializable"),
# and jax hands one in-memory executable to every compile of the same
# program. So a registry that persists compiles with an option (at its
# default value) that keeps its programs apart from every other compile
# in the process, and each program is serialized on its first persist,
# before it has run, and the bytes reused.
_PERSIST_COMPILE_OPTIONS = {"xla_embed_ir_in_executable": False}
_PAYLOADS: Dict[str, tuple] = {}
_PAYLOADS_LOCK = threading.Lock()


def _serialize(digest: str, compiled) -> tuple:
    with _PAYLOADS_LOCK:
        payload = _PAYLOADS.get(digest)
    if payload is None:
        from jax.experimental import serialize_executable as se
        payload = se.serialize(compiled)
        with _PAYLOADS_LOCK:
            _PAYLOADS[digest] = payload
    return payload


class _Program:
    __slots__ = ("compiled", "spec", "aot")

    def __init__(self, compiled, spec, aot):
        self.compiled = compiled
        self.spec = spec
        self.aot = aot


class ProgramRegistry:
    """shape tuple -> compiled program, with hit/miss/compile/preload
    counters — the per-process program population ledger.

    AOT entries (the engine's sparse scan jits, proven serializable)
    run through ``call``: miss -> disk load -> ``lower().compile()``,
    hit -> the cached executable (the python jit dispatch layer is
    skipped entirely). Engines whose programs are not AOT-managed
    (shard_map meshes, pallas closures) still ``track`` their shape
    tuples so the population count perf_ab records covers the whole
    fleet surface.

    Lock discipline: the registry lock guards the table and the plain
    int counters ONLY — every compile, file read/write, and obs
    emission runs outside it (losers of a racing compile discard)."""

    def __init__(self, cache_dir: Optional[str] = None):
        self.cache_dir = cache_dir or None
        self._lock = threading.Lock()
        self._programs: Dict[tuple, _Program] = {}
        self._stats = {"hits": 0, "misses": 0, "compiles": 0,
                       "preloads": 0, "load_errors": 0,
                       "precompiles": 0, "manifest_warms": 0}
        self._queued: set = set()
        self._queue: list = []
        self._worker: Optional[threading.Thread] = None

    # ------------------------------------------------------ counters

    def _count(self, which: str, n: int = 1) -> None:
        with self._lock:
            self._stats[which] += n
        obs.counter(f"engine.programs.{which}").inc(n)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._stats)

    def population(self) -> int:
        with self._lock:
            return len(self._programs)

    # ---------------------------------------------------------- keys

    def _key(self, name: str, statics: tuple, traced) -> tuple:
        return (name, statics,
                json.dumps(_aval_spec(traced), sort_keys=True),
                _device_token(traced))

    def _digest(self, key: tuple) -> str:
        return sha256(repr(key).encode()).hexdigest()[:32]

    # ------------------------------------------------------ dispatch

    def call(self, name: str, entry, args: tuple, n_traced: int,
             static_names: tuple):
        """Dispatch one engine program through the registry: the first
        ``n_traced`` of ``args`` are traced pytrees, the rest statics
        in ``static_names`` order (exactly how the jit entry is
        declared). Results are the jit entry's, bit for bit — the
        executable is lowered from the same function with the same
        avals and statics."""
        traced = args[:n_traced]
        statics = tuple(args[n_traced:])
        key = self._key(name, statics, traced)
        with self._lock:
            rec = self._programs.get(key)
        if rec is not None and rec.compiled is not None:
            self._count("hits")
            out = rec.compiled(*traced)
        else:
            self._count("misses")
            compiled, spec = self._materialize(
                name, entry, key, statics, static_names,
                _aval_spec(traced), shapes=traced)
            out = compiled(*traced)
        self._maybe_precompile_rung(name, entry, key, statics,
                                    static_names)
        return out

    def track(self, name: str, traced, statics: tuple) -> None:
        """Population tracking for non-AOT engines: count the shape
        tuple's first touch as a miss (the jit layer compiles it) and
        every later touch as a hit, so the fleet-wide program count
        covers every engine."""
        key = self._key(name, tuple(statics), traced)
        with self._lock:
            seen = key in self._programs
            if not seen:
                self._programs[key] = _Program(None, None, aot=False)
        self._count("hits" if seen else "misses")

    # ----------------------------------------------------- materialize

    def _materialize(self, name, entry, key, statics, static_names,
                     aval_spec, shapes):
        """Disk load, else compile; install under the lock (racing
        loser discards its copy). Runs entirely OUTSIDE the registry
        lock."""
        digest = self._digest(key)
        compiled = self._load_disk(digest)
        fresh = compiled is None
        if fresh:
            kw = dict(zip(static_names, statics))
            t0 = perf_counter()
            with obs.span("serve.compile", program=name,
                          digest=digest), _no_persistent_cache():
                compiled = entry.lower(*shapes, **kw).compile(
                    compiler_options=(_PERSIST_COMPILE_OPTIONS
                                      if self.cache_dir else None))
            dt = perf_counter() - t0
            self._count("compiles")
            obs.histogram("serve.compile_secs").observe(dt)
        spec = {"entry": name, "statics": _statics_spec(statics),
                "avals": aval_spec, "dev": key[3]}
        with self._lock:
            rec = self._programs.get(key)
            if rec is None or rec.compiled is None:
                rec = _Program(compiled, spec, aot=True)
                self._programs[key] = rec
        if fresh and rec.compiled is compiled:
            self._persist(digest, compiled)
        return rec.compiled, rec.spec

    def _fingerprint(self, digest: str) -> dict:
        import jax
        jv, jl = _versions()
        return {"format": 1, "jax": jv, "jaxlib": jl,
                "backend": jax.default_backend(), "key": digest}

    def _path(self, digest: str) -> str:
        return os.path.join(self.cache_dir, f"{digest}.jprog")

    def _persist(self, digest: str, compiled) -> None:
        """Serialize one executable to the cache dir, atomically (tmp
        + os.replace — a kill mid-persist leaves no torn final file,
        only an ignorable tmp). Best-effort: persistence failure never
        fails the dispatch that just succeeded."""
        if not self.cache_dir:
            return
        try:
            payload = _serialize(digest, compiled)
            devices = compiled.runtime_executable().local_devices()
            blob = {"fingerprint": self._fingerprint(digest),
                    "payload": payload,
                    "devices": [d.id for d in devices]}
            os.makedirs(self.cache_dir, exist_ok=True)
            path = self._path(digest)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as fh:
                pickle.dump(blob, fh)
            os.replace(tmp, path)
        except Exception as err:  # noqa: BLE001 — cache is advisory
            _log.warning("program cache persist failed (%s): %s",
                         self.cache_dir, err)

    def _load_disk(self, digest: str):
        """A persisted executable, or None. Any mismatch — jax/jaxlib
        version, backend, shape-key digest, truncated or unpicklable
        bytes — degrades to a fresh compile with a counted
        load_error: never a crash, never a wrong program."""
        if not self.cache_dir:
            return None
        path = self._path(digest)
        if not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as fh:
                blob = pickle.load(fh)
            fp = blob["fingerprint"]
            want = self._fingerprint(digest)
            if fp != want:
                raise ValueError(
                    f"fingerprint mismatch: cached {fp} != {want}")
            import jax
            from jax.experimental import serialize_executable as se
            by_id = {d.id: d for d in jax.devices()}
            # the devices the program was compiled for: without them
            # the loader assumes every device of the backend, and a
            # one-device program then refuses its one-shard arguments
            devices = [by_id[i] for i in blob["devices"]]
            t0 = perf_counter()
            with obs.span("serve.compile", program="preload",
                          digest=digest):
                compiled = se.deserialize_and_load(
                    *blob["payload"], execution_devices=devices)
            obs.histogram("serve.compile_secs").observe(
                perf_counter() - t0)
            self._count("preloads")
            return compiled
        except Exception as err:  # noqa: BLE001 — degrade, loudly
            self._count("load_errors")
            _log.warning("program cache load failed (%s) — compiling "
                         "fresh: %s", path, err)
            return None

    # ------------------------------------------------------ manifests

    def manifest(self) -> List[dict]:
        """The AOT program population as JSON-able specs — what
        ``transfer_key`` ships beside the WAL segments."""
        with self._lock:
            return [rec.spec for rec in self._programs.values()
                    if rec.aot and rec.spec is not None]

    def write_manifest(self, path: str) -> int:
        """Persist the population manifest atomically; returns the
        program count (0 writes nothing — no file beats an empty
        one)."""
        specs = self.manifest()
        if not specs:
            return 0
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump({"format": 1, "programs": specs}, fh)
        os.replace(tmp, path)
        return len(specs)

    def warm_manifest(self, path: str, entries: Dict[str, tuple]) -> int:
        """Pre-warm every program a transferred manifest names —
        BEFORE the adopter replays (docs/streaming.md warm-handoff
        contract). ``entries`` maps entry name -> (jitted, n_traced,
        static_names) (engine.program_entries()). A malformed manifest
        or an unknown entry degrades to the plain first-dispatch
        compile (counted load_errors) — warm handoff is an
        optimization, never a correctness gate. Returns programs
        warmed."""
        try:
            with open(path, encoding="utf-8") as fh:
                specs = json.load(fh).get("programs") or []
        except Exception as err:  # noqa: BLE001
            self._count("load_errors")
            _log.warning("program manifest unreadable (%s): %s",
                         path, err)
            return 0
        warmed = 0
        for spec in specs:
            try:
                if self._warm_spec(spec, entries):
                    warmed += 1
            except Exception as err:  # noqa: BLE001
                self._count("load_errors")
                _log.warning("program manifest entry skipped "
                             "(%s): %s", spec.get("entry"), err)
        if warmed:
            self._count("manifest_warms", warmed)
        return warmed

    def _warm_spec(self, spec: dict, entries: Dict[str, tuple]) -> bool:
        name = spec.get("entry")
        ent = entries.get(name)
        if ent is None:
            return False
        entry, _n_traced, static_names = ent
        if not hasattr(entry, "lower"):
            return False
        statics = _spec_to_statics(spec["statics"])
        key = (name, statics,
               json.dumps(spec["avals"], sort_keys=True),
               spec.get("dev", "host"))
        with self._lock:
            if key in self._programs:
                return False
        shapes = _spec_to_shapes(spec["avals"])
        self._materialize(name, entry, key, statics, static_names,
                          spec["avals"], shapes=shapes)
        return True

    # ------------------------------------------- ladder precompile

    def _maybe_precompile_rung(self, name, entry, key, statics,
                               static_names) -> None:
        """Queue a background compile of the next capacity rung (N
        doubled, same avals) — the program the escalation ladder's
        re-dispatch will ask for. Best-effort and off the dispatch
        path; bounded by the engine's own escalation ceiling."""
        if not precompile_armed() or "N" not in static_names:
            return
        idx = static_names.index("N")
        n = statics[idx]
        if not isinstance(n, int) or n * 2 > _LADDER_CEILING:
            return
        statics2 = statics[:idx] + (n * 2,) + statics[idx + 1:]
        key2 = (name, statics2, key[2], key[3])
        with self._lock:
            if key2 in self._programs or key2 in self._queued:
                return
            self._queued.add(key2)
            self._queue.append((name, entry, key2, statics2,
                                static_names))
            started = self._worker is not None
            if not started:
                self._worker = threading.Thread(
                    target=self._precompile_loop, daemon=True,
                    name="jepsen-programs-precompile")
        if not started:
            self._worker.start()

    def _precompile_loop(self) -> None:
        while True:
            with self._lock:
                if not self._queue:
                    self._worker = None
                    return
                name, entry, key, statics, static_names = \
                    self._queue.pop(0)
            try:
                shapes = _spec_to_shapes(json.loads(key[2]))
                self._materialize(name, entry, key, statics,
                                  static_names,
                                  json.loads(key[2]), shapes=shapes)
                self._count("precompiles")
            except Exception as err:  # noqa: BLE001 — advisory work
                _log.warning("ladder precompile failed (%s N=%s): %s",
                             name, dict(zip(static_names,
                                            statics)).get("N"), err)
            finally:
                with self._lock:
                    self._queued.discard(key)


# -------------------------------------------------- process singleton

_REG: Optional[ProgramRegistry] = None
_REG_LOCK = threading.Lock()


def registry() -> Optional[ProgramRegistry]:
    """The process ProgramRegistry when JEPSEN_TPU_COMPILE_CACHE arms
    it, else None (every caller then takes the plain jit path — flag
    off is byte-identical)."""
    global _REG
    dest = resolve_cache()
    if dest is None:
        return None
    cache_dir = dest or None
    reg = _REG
    if reg is not None and reg.cache_dir == cache_dir:
        return reg
    # construct outside the module lock (constructor may mkdir), then
    # install; a racing loser's instance is discarded before any use
    fresh = ProgramRegistry(cache_dir)
    with _REG_LOCK:
        if _REG is None or _REG.cache_dir != cache_dir:
            _REG = fresh
        return _REG


def reset() -> None:
    """Drop the process registry — the restart seam tests use to model
    a fresh process against a populated on-disk cache."""
    global _REG
    with _REG_LOCK:
        _REG = None


def track(name: str, traced, statics: tuple) -> None:
    """Population-track a non-AOT engine's program (bitdense, the
    shard_map tiers) when the registry is armed; a no-op otherwise —
    the flag-off path touches nothing."""
    reg = registry()
    if reg is not None:
        reg.track(name, traced, statics)


# ------------------------------------------------- population math


def population_counts(row_counts) -> Dict[str, int]:
    """The program-population arithmetic perf_ab records: distinct
    event-row shapes a workload would compile, exact vs canonicalized
    (no compile, no jax — pure quantum math)."""
    exact = {int(r) for r in row_counts}
    canon = {quantize_rows(r) for r in exact}
    return {"exact": len(exact), "canon": len(canon)}
