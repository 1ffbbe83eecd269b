"""Per-key independence: lift a single-key test to a map of keys
(reference: jepsen/src/jepsen/independent.clj).

Expensive checkers (linearizability is exponential) only handle short
histories; independence splits one long multi-key history into many
short per-key subhistories. Ops carry `KV(k, v)` tuple values
(independent.clj:21-29); `subhistory` filters + unwraps per key
(independent.clj:250-261); `checker` lifts a checker over every key
(independent.clj:263-314).

TPU mapping (SURVEY.md §2.20 P5): the per-key subhistories are the
natural *batch axis* for the device engine — when the lifted checker is
`Linearizable` with a packable model, all keys are checked in ONE
batched device program (jepsen_tpu.parallel.engine.check_batch) instead
of bounded-pmap'd host processes.
"""

from __future__ import annotations

import logging
from typing import Callable, Iterable, Optional, Sequence

from jepsen_tpu import generator as gen
from jepsen_tpu import obs
from jepsen_tpu.checker.core import Checker, check_safe, merge_valid
from jepsen_tpu.history import History, Op
from jepsen_tpu.util import bounded_pmap

log = logging.getLogger(__name__)

DIR = "independent"  # results subdirectory (independent.clj:17-19)


class KV(tuple):
    """A [k v] tuple value produced by independent generators — the
    MapEntry analogue (independent.clj:21-29). Subclasses tuple so it
    serializes like a 2-vector, as the reference's history.edn does."""

    __slots__ = ()

    def __new__(cls, k, v):
        return super().__new__(cls, (k, v))

    @property
    def key(self):
        return self[0]

    @property
    def value(self):
        return self[1]


def ktuple(k, v) -> KV:
    return KV(k, v)


def is_tuple(v) -> bool:
    return isinstance(v, KV)


def kv_history(history) -> History:
    """Reinterpret 2-element list/tuple op values as KV tuples — for
    histories loaded from EDN/JSONL, where the reference serializes
    MapEntry values as plain [k v] vectors. Only client ops (integer
    process) are rewrapped: nemesis/info values like ["n1", "n2"] are
    payloads, not keys."""
    out = History()
    for o in history:
        v = o.get("value")
        if (isinstance(o.get("process"), int)
                and not isinstance(v, KV)
                and isinstance(v, (list, tuple)) and len(v) == 2):
            o = Op(o)
            o["value"] = KV(v[0], v[1])
        out.append(o)
    return out


def tuple_gen(k, g):
    """Wraps a generator so its ops carry KV(k, value) values
    (independent.clj:94-99)."""
    def wrap(op):
        o = Op(op)
        o["value"] = KV(k, o.get("value"))
        return o
    return gen.map(wrap, g)


def sequential_generator(keys: Iterable, fgen: Callable):
    """One key at a time: generator for k1 until exhausted, then k2...
    (independent.clj:31-47). fgen must be pure."""
    return [tuple_gen(k, fgen(k)) for k in keys]


def _group_threads(n: int, ctx: gen.Ctx):
    """Partition sorted worker threads into groups of n
    (independent.clj:49-76)."""
    threads = sorted(t for t in ctx.all_threads() if not isinstance(t, str))
    count = len(threads)
    groups = count // n
    assert n <= count, (
        f"With {count} worker threads, concurrent_generator cannot run a "
        f"key with {n} threads concurrently. Raise :concurrency to at "
        f"least {n}.")
    assert count == n * groups, (
        f"concurrent_generator has {count} threads but can only use "
        f"{n * groups} of them to run {groups} concurrent keys with {n} "
        f"threads apiece. Make :concurrency a multiple of {n}.")
    return [threads[i * n:(i + 1) * n] for i in range(groups)]


class LazyKeys:
    """Memoized view over a (possibly infinite) key iterable. Generator
    instances hold an *index* into the shared cache, so a discarded
    generator branch (the soonest-op race in gen.any calls op on every
    alternative and keeps one) never consumes keys — pulling index i
    always yields the same key. Thread-safe."""

    def __init__(self, iterable):
        self._it = iter(iterable)
        self._cache: list = []
        self._done = False
        self._lock = __import__("threading").Lock()

    def get(self, i: int):
        """Key at index i, or None past the end."""
        with self._lock:
            while len(self._cache) <= i and not self._done:
                try:
                    self._cache.append(next(self._it))
                except StopIteration:
                    self._done = True
            return self._cache[i] if i < len(self._cache) else None

    def has(self, i: int) -> bool:
        return self.get(i) is not None


class ConcurrentGenerator(gen.Generator):
    """Splits client threads into groups of n; each group works one key;
    exhausted groups lazily pull the next key. Key sequences may be
    infinite (independent.clj:101-236)."""

    def __init__(self, n, fgen, keys, key_idx=0, group_threads=None,
                 thread_group=None, gens=None):
        self.n = n
        self.fgen = fgen
        self.keys = keys if isinstance(keys, LazyKeys) else LazyKeys(keys)
        self.key_idx = key_idx  # next unconsumed key index
        self.group_threads = group_threads  # list[list[thread]]
        self.thread_group = thread_group    # {thread: group}
        self.gens = gens                    # list[gen|None] per group

    def _init(self, ctx):
        gt = self.group_threads or _group_threads(self.n, ctx)
        tg = self.thread_group or {t: g for g, ts in enumerate(gt) for t in ts}
        idx = self.key_idx
        gens = self.gens
        if gens is None:
            gens = []
            for _ in range(len(gt)):
                k = self.keys.get(idx)
                if k is None:
                    gens.append(None)
                else:
                    gens.append(tuple_gen(k, self.fgen(k)))
                    idx += 1
        return gt, tg, idx, gens

    def op(self, test, ctx):
        gt, tg, idx, gens = self._init(ctx)
        free_groups = {tg[t] for t in ctx.free_threads if t in tg}
        soonest = None
        gens = list(gens)
        for group in free_groups:
            while True:
                g = gens[group]
                gctx = ctx.restrict(lambda t, ts=set(gt[group]): t in ts)
                res = gen.gen_op(g, test, gctx)
                if res is not None:
                    o, g2 = res
                    soonest = gen.soonest_op_map(
                        soonest, {"op": o, "group": group, "gen": g2,
                                  "weight": len(gt[group])})
                    break
                # exhausted: replace with next key's generator, if any
                k = self.keys.get(idx)
                if k is not None:
                    idx += 1
                    gens[group] = tuple_gen(k, self.fgen(k))
                    continue
                gens[group] = None
                break
        if soonest is not None and soonest["op"] is not gen.PENDING:
            out = list(gens)
            out[soonest["group"]] = soonest["gen"]
            return soonest["op"], ConcurrentGenerator(
                self.n, self.fgen, self.keys, idx, gt, tg, out)
        if any(g is not None for g in gens):
            # busy groups may still have ops
            return gen.PENDING, ConcurrentGenerator(
                self.n, self.fgen, self.keys, idx, gt, tg, gens)
        return None

    def update(self, test, ctx, event):
        if self.thread_group is None:
            return self  # not initialized yet; nothing to route
        thread = ctx.process_to_thread(event.get("process"))
        group = self.thread_group.get(thread)
        if group is None or self.gens is None:
            return self
        gens = list(self.gens)
        gens[group] = gen.gen_update(gens[group], test, ctx, event)
        return ConcurrentGenerator(self.n, self.fgen, self.keys,
                                   self.key_idx, self.group_threads,
                                   self.thread_group, gens)



def concurrent_generator(n: int, keys: Iterable, fgen: Callable):
    """Groups of n client threads per key; nemesis excluded by design
    (independent.clj:211-236)."""
    assert n > 0 and isinstance(n, int)
    return gen.clients(ConcurrentGenerator(n, fgen, keys))


# ------------------------------------------------------------ analysis


def history_keys(history) -> list:
    """The set of KV keys in a history, in first-seen order
    (independent.clj:238-248)."""
    seen = set()
    out = []
    for o in history:
        v = o.get("value")
        if isinstance(v, KV) and v.key not in seen:
            seen.add(v.key)
            out.append(v.key)
    return out


def split_history(history) -> dict:
    """One pass over the history, bucketing ops per key (un-keyed ops go
    to every bucket): O(ops + keys), vs. calling subhistory once per key
    which is O(keys * ops). Returns {k: History} in first-seen order."""
    subs: dict = {}
    unkeyed: list = []  # prefix of un-keyed ops for late-appearing keys
    for o in history:
        v = o.get("value")
        if not isinstance(v, KV):
            unkeyed.append(o)
            for h in subs.values():
                h.append(o)
        else:
            k = v.key
            h = subs.get(k)
            if h is None:
                h = subs[k] = History(unkeyed)
            o2 = Op(o)
            o2["value"] = v.value
            h.append(o2)
    return subs


def subhistory(k, history) -> History:
    """All ops without a differing key, tuples unwrapped
    (independent.clj:250-261). Un-keyed ops (nemesis, logging) appear in
    every subhistory."""
    out = History()
    for o in history:
        v = o.get("value")
        if not isinstance(v, KV):
            out.append(o)
        elif v.key == k:
            o2 = Op(o)
            o2["value"] = v.value
            out.append(o2)
    return out


class IndependentChecker(Checker):
    """Lifts a checker over per-key subhistories: valid iff valid for
    all keys; results under {"results": {k: ...}, "failures": [...]}
    (independent.clj:263-314).

    When the wrapped checker is a device-capable Linearizable, the keys
    are checked as one batched device program (the P5 batch axis)
    rather than one host search per key.

    `pipeline` routes that batch through the pipelined executor
    (engine.check_batch(pipeline=...): host encode / transfer / device
    search overlapped, encode cache consulted). None defers to the
    JEPSEN_TPU_PIPELINE env flag — opt-in, results identical either
    way. `dedupe` likewise threads the frontier dedupe strategy to the
    sparse device buckets (None defers to JEPSEN_TPU_DEDUPE; results
    identical either way — engine._resolve_dedupe). `search_stats`
    threads the device-resident search telemetry the same way (None
    defers to JEPSEN_TPU_SEARCH_STATS): each keyed sub-result then
    carries its own per-event "stats" block."""

    def __init__(self, checker: Checker, batch_device: bool = True,
                 pipeline: Optional[bool] = None,
                 dedupe: Optional[str] = None,
                 search_stats: Optional[bool] = None,
                 reshard: Optional[bool] = None):
        self.checker = checker
        self.batch_device = batch_device
        self.pipeline = pipeline
        self.dedupe = dedupe
        self.search_stats = search_stats
        # device-recruiting escalation for overflow keys (None = the
        # JEPSEN_TPU_RESHARD flag) — results identical either way
        # (docs/performance.md "Elastic re-sharding")
        self.reshard = reshard

    def check(self, test, history, opts=None):
        opts = opts or {}
        with obs.span("independent.check") as root:
            with obs.span("independent.split"):
                # Histories reloaded from EDN/JSONL (the `analyze`
                # path) carry [k v] values as plain lists; by contract
                # every keyed op under an independent checker is a KV,
                # so if none survived serialization, re-wrap —
                # otherwise split_history finds zero keys and the check
                # is vacuously valid.
                if not any(isinstance(o.get("value"), KV)
                           for o in history):
                    history = kv_history(history)
                subs = split_history(history)
            ks = list(subs)
            root.set(keys=len(ks))
            obs.counter("independent.keys").inc(len(ks))

            rs, fallback = self._batched_device_results(test, subs)
            if rs is not None:
                pairs = zip(ks, ({**r, "analyzer": "jax"} for r in rs))
            else:
                # per-key host checks run on bounded_pmap threads:
                # propagate the span context so each key's span nests
                # under independent.check
                wrap = obs.ctx_runner()
                checker = self.checker
                if fallback is not None and fallback.get("no-redispatch"):
                    # the backend's breaker is open: the per-key path
                    # must NOT re-dispatch against it (that is the
                    # breaker's whole contract) — per-key checks run a
                    # host-only algorithm until the breaker's recovery
                    # probe readmits the device
                    from jepsen_tpu.checker.linearizable import \
                        Linearizable
                    model = (self.checker.model
                             or (test or {}).get("model"))
                    checker = Linearizable(model, algorithm="packed")

                def check_key(k):
                    with obs.span("independent.key", key=str(k)):
                        return (k, check_safe(
                            checker, test, subs[k],
                            {**opts,
                             "subdirectory":
                                 list(opts.get("subdirectory", []))
                                 + [DIR, k],
                             "history-key": k}))

                pairs = bounded_pmap(wrap(check_key), ks)

            with obs.span("independent.assemble"):
                results = dict(pairs)
                self._persist(test, opts, subs, results)
                # only proven-invalid keys; "unknown" (e.g. a crashed
                # per-key checker) is not a failure
                # (independent.clj:305-311)
                failures = [k for k, r in results.items()
                            if r.get("valid?") is False]
                out = {
                    "valid?": merge_valid(r.get("valid?")
                                          for r in results.values()),
                    "results": results,
                    "failures": failures,
                }
        if fallback is not None:
            # the reason stays a plain string under the historical key
            # (operators and tests grep it); the structured form —
            # class, backend, breaker interaction — rides "resilience"
            out["device-fallback"] = fallback["reason"]
            out["resilience"] = fallback
        return out

    # -- device batch fast path
    def _batched_device_results(self, test, subs):
        """(results, fallback): results, one per key of `subs` in its
        order, is None when the host per-key path should run. A None
        fallback means the device path was simply not applicable
        (non-device checker, unpackable model);
        otherwise it is a structured dict — {"reason", "class",
        "backend", "no-redispatch"} — saying the device path was
        attempted and FAILED (or was breaker-refused without an
        attempt). That is a loud event (warning + result tag + a
        class-labeled counter), since silently degrading to the host
        checker would hide a TPU regression behind a 100-300x
        slowdown. "no-redispatch" tells check() the backend's breaker
        is open, so the per-key path must not dispatch against it."""
        from jepsen_tpu.checker.linearizable import Linearizable
        c = self.checker
        if not (self.batch_device and isinstance(c, Linearizable)
                and c.algorithm in ("jax", "competition") and subs):
            return None, None
        model = c.model or (test or {}).get("model")
        if model is None:
            return None, None
        from jepsen_tpu import models as model_ns
        from jepsen_tpu.history import Intern
        from jepsen_tpu.parallel import engine
        from jepsen_tpu.parallel.encode import EncodeError
        from jepsen_tpu.resilience import breaker as breaker_mod
        from jepsen_tpu.resilience import supervisor as sup
        try:
            packable = model_ns.pack_spec(model, Intern()) is not None
        except Exception:  # noqa: BLE001 - spec probe blowing up is just
            packable = False  # "not packable": quiet host path, not a crash
        if not packable:
            return None, None
        # a mesh on the test map shards the key axis across devices
        # and lets overflow keys escalate to the frontier-sharded
        # engine (engine._escalate_overflow)
        mesh = (test or {}).get("mesh")
        if mesh is not None:
            import numpy as np
            backend = np.asarray(mesh.devices).flat[0].platform
        else:
            import jax
            backend = jax.default_backend()
        if breaker_mod.any_tripped():
            # consult the breaker BEFORE touching the device: an open
            # breaker means dispatch is refused outright (allow() runs
            # the half-open recovery probe when the backoff elapsed —
            # a recovered runtime readmits itself here)
            allowed, why = breaker_mod.breaker_for(backend).allow()
            if not allowed:
                return None, self._fallback("breaker-open", why,
                                            backend, skip=True)
        try:
            ks = list(subs)
            with obs.span("independent.device_batch", keys=len(ks)):
                rs = engine.check_batch(model, [subs[k] for k in ks],
                                        mesh=mesh, pipeline=self.pipeline,
                                        dedupe=self.dedupe,
                                        search_stats=self.search_stats,
                                        reshard=self.reshard)
            return rs, None
        except EncodeError as err:
            # legitimately not device-encodable (a gset key past the
            # 31-element budget, a > 64-slot crash pile-up): the host
            # path is correct but 100-300x slower, so still say so
            return None, self._fallback(
                "not-encodable", f"not device-encodable: {err}",
                backend, skip=True)
        except sup.DISPATCH_FAILURES as err:
            cls = ("wedged" if isinstance(err, sup.DispatchWedged)
                   else "breaker-open"
                   if isinstance(err, sup.DeviceUnavailable)
                   else "dispatch-error")
            return None, self._fallback(
                cls, f"{type(err).__name__}: {err}", backend)

    @staticmethod
    def _fallback(cls: str, reason: str, backend, skip=False) -> dict:
        """One structured fallback record + its labeled counters.
        `skip` marks paths where the device was never dispatched
        (breaker refusal, un-encodable) vs an attempted-and-FAILED
        dispatch, which warns louder."""
        from jepsen_tpu.resilience import breaker as breaker_mod
        obs.counter("independent.device_fallbacks").inc()
        obs.counter(f"independent.device_fallbacks.{cls}").inc()
        open_now = (cls == "breaker-open"
                    or breaker_mod.breaker_for(backend).state
                    != breaker_mod.CLOSED)
        if skip:
            log.warning("device batch check skipped (%s) — using the "
                        "host per-key checker", reason)
        else:
            log.warning(
                "device batch check FAILED (%s) — falling back to the "
                "host per-key checker; results will be correct but the "
                "TPU path is broken", reason)
        return {"class": cls, "reason": reason, "backend": backend,
                "no-redispatch": open_now}

    # -- results/history persistence per key (independent.clj:292-300)
    def _persist(self, test, opts, subs, results):
        store = (test or {}).get("store")
        if store is None:
            return
        for k in subs:
            try:
                store.write_file([DIR, str(k), "results.edn"],
                                 _edn_pprint(results[k]))
                store.write_file([DIR, str(k), "history.edn"],
                                 subs[k].to_edn())
            except Exception:  # noqa: BLE001
                pass


def _edn_pprint(x) -> str:
    from jepsen_tpu import edn
    return edn.dumps(x) + "\n"


def checker(c: Checker, batch_device: bool = True,
            pipeline: Optional[bool] = None,
            dedupe: Optional[str] = None,
            search_stats: Optional[bool] = None,
            reshard: Optional[bool] = None) -> IndependentChecker:
    return IndependentChecker(c, batch_device, pipeline=pipeline,
                              dedupe=dedupe, search_stats=search_stats,
                              reshard=reshard)
