"""Tendermint test suite assembly
(reference: tendermint/src/jepsen/tendermint/core.clj).

Clients (cas-register, set), the byzantine dup-validator grudges, the
crash/truncate and changing-validators nemeses, the nemesis menu, the
workload map, and the top-level test constructor. The system under
test's data plane is reached through a *transport*:
test["transport_for"](test, node) -> client.SocketTransport |
client.HttpTransport — local runs point every node at native
merkleeyes instances, cluster runs at tendermint RPC."""

from __future__ import annotations

import contextlib
import logging
import math
from typing import Callable, Dict, Optional

from jepsen_tpu import checker as jchecker
from jepsen_tpu import client as jclient
from jepsen_tpu import control as c
from jepsen_tpu import generator as gen
from jepsen_tpu import independent
from jepsen_tpu import nemesis as jnemesis
from jepsen_tpu.checker import timeline as jtimeline
from jepsen_tpu.history import Op
from jepsen_tpu.models import CASRegister
from jepsen_tpu.nemesis import time as nt
from jepsen_tpu.tendermint import client as tc
from jepsen_tpu.tendermint import db as td
from jepsen_tpu.tendermint import validator as tv
from jepsen_tpu.workloads import noop_test

log = logging.getLogger(__name__)


# ------------------------------------------------------- op generators


def r(test, ctx):
    return {"type": "invoke", "f": "read", "value": None}


def w(test, ctx):
    return {"type": "invoke", "f": "write", "value": gen.rand.randint(0, 9)}


def cas(test, ctx):
    return {"type": "invoke", "f": "cas",
            "value": [gen.rand.randint(0, 9), gen.rand.randint(0, 9)]}


# ------------------------------------------------------------- clients


def _transport(test, node):
    tf = test.get("transport_for")
    assert tf is not None, "test map has no :transport_for"
    return tf(test, node)


@contextlib.contextmanager
def _map_errors(o: Op, crash: str):
    """Shared tx-error classification (core.clj:57-75,116-138): code 8 ->
    :fail precondition-failed, code 7 -> :fail not-found, connection
    refused -> :fail, other network faults -> `crash` (:info for
    writes, :fail for reads) with an indeterminate error."""
    try:
        yield
    except tc.Unauthorized:
        o["type"] = "fail"
        o["error"] = "precondition-failed"
    except tc.BaseUnknownAddress:
        o["type"] = "fail"
        o["error"] = "not-found"
    except ConnectionRefusedError:
        o["type"] = "fail"
        o["error"] = "connection-refused"
    except (ConnectionError, TimeoutError, OSError) as e:
        o["type"] = crash
        o["error"] = f"indeterminate: {e}"


class CasRegisterClient(jclient.Client):
    """read/write/cas on independent [k v] tuples (core.clj:33-80).
    Error mapping: code 8 -> :fail precondition-failed; code 7 -> :fail
    not-found; connection refused -> :fail; timeouts and other network
    faults crash (:info) for writes, :fail for reads."""

    def __init__(self, node=None):
        self.node = node

    def open(self, test, node):
        return CasRegisterClient(node)

    def invoke(self, test, op):
        o = Op(op)
        k, v = op.get("value")
        crash = "fail" if op.get("f") == "read" else "info"
        t = _transport(test, self.node)
        with _map_errors(o, crash):
            f = op.get("f")
            if f == "read":
                o["type"] = "ok"
                o["value"] = independent.KV(k, tc.read(t, k))
            elif f == "write":
                tc.write(t, k, v)
                o["type"] = "ok"
            elif f == "cas":
                old, new = v
                tc.cas(t, k, old, new)
                o["type"] = "ok"
            else:
                raise ValueError(f"unknown f {f!r}")
        return o

    def is_reusable(self, test):
        return True


class SetClient(jclient.Client):
    """CAS-append to a vector per key (core.clj:82-139): :init writes
    [], :add CASes v onto the current vector, :read returns the set."""

    def __init__(self, node=None):
        self.node = node

    def open(self, test, node):
        return SetClient(node)

    def invoke(self, test, op):
        import time as _time
        o = Op(op)
        k, v = op.get("value")
        crash = "fail" if op.get("f") == "read" else "info"
        t = _transport(test, self.node)
        with _map_errors(o, crash):
            f = op.get("f")
            if f == "init":
                tries = 0
                while True:
                    try:
                        tc.write(t, k, [])
                        break
                    except Exception:  # noqa: BLE001 - retry w/ backoff
                        if tries >= 10:
                            raise
                        _time.sleep(0.05 * (2 ** tries))
                        tries += 1
                o["type"] = "ok"
            elif f == "add":
                s = tc.read(t, k) or []
                tc.cas(t, k, s, list(s) + [v])
                o["type"] = "ok"
            elif f == "read":
                got = tc.read(t, k)
                o["type"] = "ok"
                o["value"] = independent.KV(k, set(got or []))
            else:
                raise ValueError(f"unknown f {f!r}")
        return o

    def is_reusable(self, test):
        return True


# ------------------------------------------- byzantine partition shapes


def peekaboo_dup_validators_grudge(test) -> Callable:
    """Isolates all-but-one node of every dup group (core.clj:140-159):
    one randomly chosen member of each dup group stays with the
    majority; the rest are exiled into singleton components."""
    def grudge(nodes):
        cfg = test["validator_config"][0]
        groups = tv.dup_groups(cfg)
        chosen = [gen.rand.choice(sorted(g)) for g in groups["dups"]]
        exiles = [[n for n in g if n != ch]
                  for g, ch in zip(groups["dups"], chosen)]
        main = [n for g in groups["singles"] for n in g] + chosen
        return jnemesis.complete_grudge([main] + exiles)
    return grudge


def split_dup_validators_grudge(test) -> Callable:
    """Splits the net into n components, each with one member of the
    dup group and a share of the rest (core.clj:161-179)."""
    def grudge(nodes):
        cfg = test["validator_config"][0]
        groups = tv.dup_groups(cfg)
        n = max((len(g) for g in groups["dups"]), default=1)
        shuffled_groups = [sorted(g) for g in groups["groups"]]
        gen.rand.shuffle(shuffled_groups)
        for g in shuffled_groups:
            gen.rand.shuffle(g)
        flat = [node for g in shuffled_groups for node in g]
        components = [[] for _ in range(n)]
        for i, node in enumerate(flat):
            components[i % n].append(node)
        return jnemesis.complete_grudge([comp for comp in components
                                         if comp])
    return grudge


# ----------------------------------------------------- custom nemeses


class CrashTruncateNemesis(jnemesis.Nemesis):
    """Kill both daemons, truncate a file's tail, restart
    (core.clj:181-217), on a fixed random subset of nodes."""

    def __init__(self, test, file: str, fraction: float = 1 / 3):
        nodes = sorted(test.get("nodes") or [])
        gen.rand.shuffle(nodes)
        k = int(math.floor(fraction * len(nodes)))
        self.file = file
        self.faulty_nodes = nodes[:k]

    def invoke(self, test, op):
        if op.get("f") == "stop":
            return jnemesis._ok(op)
        assert op.get("f") == "crash"

        def crash(t, node):
            td.stop_tendermint(t, node)
            td.stop_merkleeyes(t, node)
            with c.su():
                c.exec_("truncate", "-c", "-s",
                        f"-{gen.rand.randint(0, 1048575)}",
                        td.base_dir(t) + self.file)
            td.start_merkleeyes(t, node)
            td.start_tendermint(t, node)
            return "crashed"

        res = c.on_nodes(test, crash, self.faulty_nodes)
        return jnemesis._ok(op, value=res)

    def teardown(self, test):
        c.on_nodes(test, td.start_merkleeyes, self.faulty_nodes)
        c.on_nodes(test, td.start_tendermint, self.faulty_nodes)

    def fs(self):
        return {"crash", "stop"}


def crash_nemesis() -> jnemesis.NodeStartStopper:
    """Kill merkleeyes + tendermint on all nodes (core.clj:219-223).
    Daemon control shells out, so each call runs inside on_nodes to
    bind the node's control session."""
    def bound(f):
        def g(test, node):
            return c.on_nodes(test, f, [node])[node]
        return g
    return jnemesis.NodeStartStopper(
        lambda nodes: list(nodes), bound(td.stop), bound(td.start))


class ChangingValidatorsNemesis(jnemesis.Nemesis):
    """Applies validator transitions to the cluster (core.clj:225-278):
    pre-step the local config, perform the change (valset CAS / node
    create / destroy), then post-step. On failure the local config is
    rolled back and the error propagates as an :info op."""

    def _invoke(self, test, op):
        if op.get("f") == "stop":
            return jnemesis._ok(op)
        assert op.get("f") == "transition", op
        t = op.get("value")
        box = test["validator_config"]
        before = box[0]
        box[0] = tv.pre_step(before, t)
        ty = t["type"]
        if ty == "add":
            v = t["validator"]
            tc.with_any_node(test, tc.validator_set_cas, t["version"],
                             v["pub_key"], v["votes"])
        elif ty == "remove":
            tc.with_any_node(test, tc.validator_set_cas, t["version"],
                             t["pub_key"], 0)
        elif ty == "alter-votes":
            tc.with_any_node(test, tc.validator_set_cas, t["version"],
                             t["pub_key"], t["votes"])
        elif ty == "create":
            def create(tst, node):
                td.write_validator(tst, node, t["validator"])
                td.start(tst, node)
            c.on_nodes(test, create, [t["node"]])
        elif ty == "destroy":
            def destroy(tst, node):
                td.stop(tst, node)
                td.reset_validator(tst, node)
            c.on_nodes(test, destroy, [t["node"]])
        else:
            box[0] = before
            raise ValueError(f"unknown transition {ty!r}")
        box[0] = tv.post_step(box[0], t)
        return jnemesis._ok(op, value="done")

    def invoke(self, test, op):  # noqa: F811 - wraps _invoke w/ rollback
        box = test["validator_config"]
        before = box[0]
        try:
            return self._invoke(test, op)
        except (tc.Unauthorized, tc.BaseUnknownAddress) as e:
            if getattr(e, "prior_indeterminate", False):
                # The rejection came after an earlier node's network
                # failure — the change may have landed there (and the
                # retry's CAS then lost against the new version). Not
                # definite: fall through to the indeterminate handling.
                raise
            # Definite failure: the valset CAS was rejected by the app
            # on the first attempt, so nothing changed on the cluster —
            # roll the local config back to the pre-transition state.
            box[0] = before
            raise
        except Exception:
            # Indeterminate (network error, timeout, node crash): the
            # change MAY have been applied on the cluster. Keep the
            # pre-step config — it retains the prospective validator so
            # the next refresh_config can reconcile either outcome
            # (core.clj leaves pre-step state in place for exactly this
            # reason). Rolling back here would make a landed validator
            # unrecognizable: validator_set_to_vote_map would raise on
            # every later refresh and the transition generator would be
            # stuck on a permanently stale config.
            raise

    def fs(self):
        return {"transition", "stop"}


# --------------------------------------------------------- nemesis menu


def refresh_config(test):
    """Reconcile the local validator config with a transactional read
    of the cluster's validator set (validator.clj:961-977
    refresh-config!). Returns the (possibly unchanged) config."""
    box = test["validator_config"]
    try:
        vs = tc.with_any_node(test, tc.validator_set)
        if vs is not None:
            box[0] = tv.current_config(box[0], vs)
    except Exception as e:  # noqa: BLE001 - cluster may be unreachable
        log.debug("refresh_config failed: %r", e)
    return box[0]


def nemesis_package(test) -> dict:
    """{nemesis, generator} per profile (core.clj:287-340)."""
    kind = test.get("nemesis_name", "none")
    if kind == "changing-validators":
        return {"nemesis": ChangingValidatorsNemesis(),
                "generator": gen.stagger(1, tv.generator(
                    test.get("refresh_config", refresh_config)))}
    if kind == "peekaboo-dup-validators":
        return {"nemesis":
                jnemesis.partitioner(peekaboo_dup_validators_grudge(test)),
                "generator": [{"type": "info", "f": "start"},
                              gen.sleep(5),
                              {"type": "info", "f": "stop"}]}
    if kind == "split-dup-validators":
        return {"nemesis":
                jnemesis.partitioner(split_dup_validators_grudge(test)),
                "generator": gen.once({"type": "info", "f": "start"})}
    if kind == "half-partitions":
        return {"nemesis": jnemesis.partition_random_halves(),
                "generator": [gen.sleep(5), {"type": "info", "f": "start"},
                              gen.sleep(30), {"type": "info", "f": "stop"}]}
    if kind == "ring-partitions":
        return {"nemesis": jnemesis.partition_majorities_ring(),
                "generator": [gen.sleep(5), {"type": "info", "f": "start"},
                              gen.sleep(30), {"type": "info", "f": "stop"}]}
    if kind == "single-partitions":
        return {"nemesis": jnemesis.partition_random_node(),
                "generator": [gen.sleep(5), {"type": "info", "f": "start"},
                              gen.sleep(30), {"type": "info", "f": "stop"}]}
    if kind == "clocks":
        return {"nemesis": nt.clock_nemesis(),
                "generator": gen.stagger(0.5, nt.clock_gen())}
    if kind == "crash":
        return {"nemesis": crash_nemesis(),
                "generator": [gen.sleep(15), {"type": "info", "f": "start"},
                              {"type": "info", "f": "stop"}]}
    if kind == "truncate-merkleeyes":
        return {"nemesis": CrashTruncateNemesis(
                    test, "/jepsen/jepsen.db/000001.log"),
                "generator": gen.delay(1, gen.repeat(
                    {"type": "info", "f": "crash"}))}
    if kind == "truncate-tendermint":
        return {"nemesis": CrashTruncateNemesis(test, "/data/cs.wal/wal"),
                "generator": gen.delay(1, gen.repeat(
                    {"type": "info", "f": "crash"}))}
    if kind == "deployed-mix":
        # The deployed-cluster fault sweep in one profile: a network
        # partition (MemNet grudge or iptables, whichever net the test
        # carries), one validator-set ADD through the live app, and a
        # crash+truncate cycle — staged deterministically so a single
        # e2e drives deploy -> faults -> final reads -> verdict (the
        # closest runnable parallel of the reference's docker run,
        # README.md:19-35). The ADD transition is the one family that
        # never touches node daemons, so the stage is safe on any
        # topology; destroy/create coverage lives in the
        # changing-validators profile.
        return {"nemesis": jnemesis.compose([
                    ({"start": "start", "stop": "stop"},
                     jnemesis.partition_random_halves()),
                    ({"transition": "transition"},
                     ChangingValidatorsNemesis()),
                    ({"crash": "crash"},
                     CrashTruncateNemesis(
                         test, "/jepsen/jepsen.db/000001.log")),
                ]),
                "generator": [gen.sleep(1),
                              {"type": "info", "f": "start"},
                              gen.sleep(2.5),
                              {"type": "info", "f": "stop"},
                              gen.sleep(0.5),
                              gen.once(_add_transition_op),
                              gen.sleep(0.5),
                              {"type": "info", "f": "crash"}]}
    if kind == "local-kill":
        return {"nemesis": LocalKillNemesis(),
                "generator": gen.cycle_gen([
                    gen.sleep(1.5), {"type": "info", "f": "kill"},
                    gen.sleep(0.7), {"type": "info", "f": "restart"}])}
    if kind == "none":
        return {"nemesis": jnemesis.noop(), "generator": None}
    raise ValueError(f"unknown nemesis profile {kind!r}")


class LocalKillNemesis(jnemesis.Nemesis):
    """Crash nemesis for LOCAL mode (LocalMerkleeyesDB): SIGKILLs the
    shared native merkleeyes mid-run and restarts it on the same WAL —
    the docker-less parallel of the cluster `crash` nemesis. Committed
    txs must survive via WAL replay; in-flight ops surface as
    indeterminate (client.py maps connection errors), and the history
    must still check linearizable."""

    def setup(self, test):
        self.db = test["db"]
        assert hasattr(self.db, "kill_server"), \
            "local-kill requires a LocalMerkleeyesDB"
        return self

    def invoke(self, test, op):
        if op["f"] == "kill":
            self.db.kill_server()
            return jnemesis._ok(op, value="killed (SIGKILL, WAL kept)")
        if op["f"] == "restart":
            self.db.restart_server()
            return jnemesis._ok(op, value="restarted (WAL replayed)")
        raise ValueError(f"unknown local-kill op {op['f']!r}")

    def teardown(self, test):
        # leave the server down/up as-is: DB.teardown owns shutdown
        return None


def _add_transition_op(test, ctx):
    """One validator-set ADD against the LIVE config (a transactional
    valset read via refresh_config, then a fresh random validator at
    the read version — the version CAS proves the read was current)."""
    cfg = test.get("refresh_config", refresh_config)(test)
    return {"type": "info", "f": "transition",
            "value": {"type": "add", "version": cfg["version"],
                      "validator": tv.gen_validator()}}


NEMESES = ["changing-validators", "peekaboo-dup-validators",
           "split-dup-validators", "half-partitions", "ring-partitions",
           "single-partitions", "clocks", "crash", "truncate-merkleeyes",
           "truncate-tendermint", "local-kill", "deployed-mix", "none"]


# ------------------------------------------------------------ workloads


def workload(test) -> dict:
    """{client, concurrency, generator, final_generator, checker}
    (core.clj:342-387)."""
    n = len(test.get("nodes") or [])
    kind = test.get("workload", "cas-register")
    ops_per_key = test.get("ops_per_key", 120)

    if kind == "cas-register":
        def per_key(k):
            return gen.limit(ops_per_key,
                             gen.stagger(0.1,
                                         gen.reserve(n, r,
                                                     gen.mix([w, cas]))))
        return {
            "client": CasRegisterClient(),
            "concurrency": 2 * n,
            "generator": independent.concurrent_generator(
                2 * n, _naturals(), per_key),
            "final_generator": None,
            "checker": {"linear": independent.checker(
                jchecker.linearizable(CASRegister(),
                                      algorithm=test.get(
                                          "algorithm", "linear")))}}

    if kind == "set":
        max_key = [0]

        def per_key(k):
            max_key[0] = max(max_key[0], k)

            def add(test_, ctx, _c=[0]):  # noqa: B006 - per-key counter
                _c[0] += 1
                return {"type": "invoke", "f": "add", "value": _c[0]}
            return gen.phases(gen.once({"type": "invoke", "f": "init",
                                        "value": None}),
                              gen.stagger(0.5, add))

        def final():
            return independent.concurrent_generator(
                2 * n, iter(range(max_key[0] + 1)),
                lambda k: gen.once({"type": "invoke", "f": "read",
                                    "value": None}))
        # linearizable mode: each per-key sub-history is additionally a
        # knossos-style GSet linearizability check — and GSet packs onto
        # the device (bitmask state), so the whole keyed batch rides the
        # TPU engine (analyzer :jax), not a host timeline scan
        checkers = {"set": independent.checker(jchecker.set_checker())}
        if test.get("linearizable"):
            from jepsen_tpu.models import GSet
            checkers["linear"] = independent.checker(
                jchecker.linearizable(GSet(), algorithm=test.get(
                    "algorithm", "competition")))
        return {
            "client": SetClient(),
            "concurrency": 2 * n,
            "generator": independent.concurrent_generator(
                2 * n, _naturals(), per_key),
            "final_generator": final,  # thunk: built after main phase
            "checker": checkers}

    raise ValueError(f"unknown workload {kind!r}")


WORKLOADS = ["cas-register", "set"]


def _naturals():
    k = 0
    while True:
        yield k
        k += 1


# --------------------------------------------------------- test builder


def test_map(opts: Optional[Dict] = None) -> Dict:
    """Assemble the full tendermint test map (core.clj:389-423)."""
    opts = dict(opts or {})
    t = noop_test()
    t.update(opts)
    t.setdefault("workload", "cas-register")
    t.setdefault("nemesis_name", "none")
    t["name"] = (f"tendermint {t['workload']} {t['nemesis_name']}")
    t.setdefault("validator_config", [None])
    t.setdefault("transport_for", td.local_transport_for)

    nem = nemesis_package(t)
    wl = workload(t)
    checker = jchecker.compose({
        "timeline": independent.checker(jtimeline.html()),
        "perf": jchecker.perf_checker(),
        **wl["checker"]})

    main = gen.time_limit(t.get("time_limit", 30),
                          gen.clients(wl["generator"],
                                      nem["generator"]))
    phases = [main,
              gen.nemesis(gen.once({"type": "info", "f": "stop"})),
              gen.sleep(t.get("quiesce", 1))]
    final = wl.get("final_generator")
    if final is not None:
        # built lazily after the main phase (core.clj:371-377 delay)
        phases.append(_DeferredClients(final))
    group = wl["concurrency"]
    user_c = opts.get("concurrency")
    if user_c and user_c != group:
        if user_c % group == 0:
            group = user_c
        else:
            # Round up to the nearest whole key-group (2 x nodes): the
            # independent generator needs full groups, and honoring the
            # user's magnitude loudly beats crashing on the CLI default.
            rounded = max(group, math.ceil(user_c / group) * group)
            log.warning(
                "concurrency %d is not a multiple of the workload's "
                "key-group size %d (2 x nodes); using %d",
                user_c, group, rounded)
            group = rounded
    t.update({"client": wl["client"],
              "concurrency": group,
              "generator": gen.phases(*phases),
              "nemesis": nem["nemesis"],
              "checker": checker})
    return t


class _DeferredClients(gen.Generator):
    """Builds its inner generator at first use — the reference's
    (delay ...) final generator (core.clj:371-377)."""

    def __init__(self, thunk):
        self.thunk = thunk
        self.inner = None

    def _force(self):
        if self.inner is None:
            self.inner = gen.clients(self.thunk())
        return self.inner

    def op(self, test, ctx):
        res = gen.gen_op(self._force(), test, ctx)
        if res is None:
            return None
        o, g2 = res
        self.inner = g2
        return o, self

    def update(self, test, ctx, event):
        if self.inner is not None:
            self.inner = gen.gen_update(self.inner, test, ctx, event)
        return self
