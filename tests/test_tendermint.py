"""Tendermint suite: gowire golden vectors, validator state-machine
math, dup-validator grudges, client error mapping, and a full local
end-to-end cas-register run against the native merkleeyes server with
a linearizability check (reference: tendermint/src/jepsen/tendermint/*
+ the docker quickstart run, /root/reference/README.md:26-52)."""

from __future__ import annotations

from fractions import Fraction

import pytest

from jepsen_tpu import generator as gen
from jepsen_tpu.tendermint import client as tc
from jepsen_tpu.tendermint import core as tcore
from jepsen_tpu.tendermint import db as td
from jepsen_tpu.tendermint import gowire as w
from jepsen_tpu.tendermint import merkleeyes as me
from jepsen_tpu.tendermint import validator as tv


# ------------------------------------------------------------- gowire


def test_uvarint_golden():
    # Go binary.PutUvarint reference values
    assert w.uvarint(0) == b"\x00"
    assert w.uvarint(1) == b"\x01"
    assert w.uvarint(127) == b"\x7f"
    assert w.uvarint(128) == b"\x80\x01"
    assert w.uvarint(300) == b"\xac\x02"
    for n in (0, 1, 127, 128, 300, 2 ** 40):
        v, pos = w.read_uvarint(w.uvarint(n))
        assert v == n and pos == len(w.uvarint(n))


def test_varint_zigzag():
    # Go binary.PutVarint: zigzag(-1)=1, zigzag(1)=2
    assert w.varint(0) == b"\x00"
    assert w.varint(-1) == b"\x01"
    assert w.varint(1) == b"\x02"
    for n in (-300, -1, 0, 1, 300, -(2 ** 40)):
        v, _ = w.read_varint(w.varint(n))
        assert v == n


def test_tx_layout():
    n = bytes(range(12))
    t = w.set_tx(b"abc", b"x", nonce_=n)
    # nonce ∥ 0x01 ∥ len(3) "abc" ∥ len(1) "x"  (merkleeyes README)
    assert t == n + b"\x01\x03abc\x01x"
    t = w.cas_tx(b"k", b"1", b"2", nonce_=n)
    assert t == n + b"\x04\x01k\x011\x012"
    t = w.valset_cas_tx(5, bytes(32), 9, nonce_=n)
    assert t[12] == 0x07
    assert t[13:21] == (5).to_bytes(8, "big")


# ----------------------------------------------------------- validator


def _test_map(nodes=("n1", "n2", "n3", "n4", "n5"), **kw):
    return {"nodes": list(nodes), **kw}


def _add_transition(cfg):
    v = tv.gen_validator()
    return {"type": "add", "version": cfg["version"], "validator": v}


def test_changing_validators_rollback_on_definite_failure(monkeypatch):
    """An Unauthorized valset CAS definitely did not apply: the local
    config must roll back (no stranded prospective validator)."""
    from jepsen_tpu.tendermint import core as tcore
    cfg = tv.initial_config(_test_map())
    test = {"nodes": _test_map()["nodes"], "validator_config": [cfg]}
    t = _add_transition(cfg)

    def boom_cas(*a, **k):
        raise tcore.tc.Unauthorized(8, "version mismatch")
    monkeypatch.setattr(tcore.tc, "with_any_node",
                        lambda test_, fn, *a: boom_cas())
    nem = tcore.ChangingValidatorsNemesis()
    with pytest.raises(tcore.tc.Unauthorized):
        nem.invoke(test, {"type": "info", "f": "transition", "value": t})
    assert test["validator_config"][0] is cfg  # rolled back
    assert t["validator"]["pub_key"] not in \
        cfg["prospective_validators"]


def test_with_any_node_flags_prior_indeterminate():
    """A TxError raised after another node's network failure carries
    prior_indeterminate=True — the failed attempt may have committed,
    so the app-level rejection is not proof nothing happened."""
    calls = []

    def transport_for(test, node):
        return node

    def cas(node, *args):
        calls.append(node)
        if len(calls) == 1:
            raise OSError("timeout after send")
        raise tc.Unauthorized(8, "version mismatch")

    test = {"nodes": ["n1", "n2"], "transport_for": transport_for}
    with pytest.raises(tc.Unauthorized) as ei:
        tc.with_any_node(test, cas)
    assert ei.value.prior_indeterminate is True

    # first-attempt rejection: definitively nothing happened
    calls.clear()

    def cas2(node, *args):
        raise tc.Unauthorized(8, "version mismatch")

    with pytest.raises(tc.Unauthorized) as ei:
        tc.with_any_node(test, cas2)
    assert ei.value.prior_indeterminate is False


def test_changing_validators_keeps_prospective_on_tainted_unauthorized(
        monkeypatch):
    """Unauthorized AFTER a swallowed indeterminate attempt must not
    roll back — the change may have landed via the earlier node."""
    from jepsen_tpu.tendermint import core as tcore
    cfg = tv.initial_config(_test_map())
    test = {"nodes": _test_map()["nodes"], "validator_config": [cfg]}
    t = _add_transition(cfg)

    def tainted(*a, **k):
        e = tcore.tc.Unauthorized(8, "version mismatch")
        e.prior_indeterminate = True
        raise e
    monkeypatch.setattr(tcore.tc, "with_any_node", tainted)
    nem = tcore.ChangingValidatorsNemesis()
    with pytest.raises(tcore.tc.Unauthorized):
        nem.invoke(test, {"type": "info", "f": "transition", "value": t})
    after = test["validator_config"][0]
    assert t["validator"]["pub_key"] in after["prospective_validators"]


def test_changing_validators_keeps_prospective_on_indeterminate(monkeypatch):
    """A network error is indeterminate — the change may have landed on
    the cluster. The pre-step config (prospective validator retained)
    must survive so refresh_config can reconcile either outcome; an
    eager rollback would make a landed validator unrecognizable."""
    from jepsen_tpu.tendermint import core as tcore
    cfg = tv.initial_config(_test_map())
    test = {"nodes": _test_map()["nodes"], "validator_config": [cfg]}
    t = _add_transition(cfg)

    monkeypatch.setattr(
        tcore.tc, "with_any_node",
        lambda *a, **k: (_ for _ in ()).throw(OSError("conn reset")))
    nem = tcore.ChangingValidatorsNemesis()
    with pytest.raises(OSError):
        nem.invoke(test, {"type": "info", "f": "transition", "value": t})
    after = test["validator_config"][0]
    assert t["validator"]["pub_key"] in after["prospective_validators"]


def test_initial_config_plain():
    cfg = tv.initial_config(_test_map())
    assert len(cfg["validators"]) == 5
    assert all(v["votes"] == 2 for v in cfg["validators"].values())
    assert tv.total_votes(cfg) == 10
    tv.assert_valid(cfg)
    assert not tv.byzantine_validators(cfg)


def test_initial_config_dup_validators():
    cfg = tv.initial_config(_test_map(dup_validators=True))
    # n1 runs n2's validator; 4 validators remain
    assert len(cfg["validators"]) == 4
    assert cfg["nodes"]["n1"] == cfg["nodes"]["n2"]
    bs = tv.byzantine_validators(cfg)
    assert len(bs) == 1
    # regular dup weighting: dup gets n-2 = 2 votes of total 3n-4 = 8
    # (validator.clj:267-337 derivation with n = 4 validators)
    n = len(cfg["validators"])
    assert bs[0]["votes"] == n - 2
    assert tv.total_votes(cfg) == 3 * n - 4
    frac = tv.vote_fractions(cfg)[bs[0]["pub_key"]]
    assert frac < Fraction(1, 3)
    tv.assert_valid(cfg)


def test_initial_config_super_byzantine():
    cfg = tv.initial_config(_test_map(dup_validators=True,
                                      super_byzantine_validators=True,
                                      max_byzantine_vote_fraction=
                                      Fraction(2, 3)))
    bs = tv.byzantine_validators(cfg)
    n = len(cfg["validators"])
    assert bs[0]["votes"] == 4 * (n - 1) - 1
    frac = tv.vote_fractions(cfg)[bs[0]["pub_key"]]
    assert Fraction(1, 3) < frac < Fraction(2, 3)


def test_invariants():
    cfg = tv.initial_config(_test_map())
    # removing validators until quorum breaks must fail
    ks = sorted(cfg["validators"])
    c1 = tv.step(cfg, {"type": "remove", "pub_key": ks[0]})
    with pytest.raises(tv.IllegalTransition):
        c2 = c1
        for k in ks[1:]:
            c2 = tv.step(c2, {"type": "remove", "pub_key": k})
    # destroying a node leaves a ghost; more than 2 ghosts is illegal
    c = cfg
    gone = 0
    with pytest.raises(tv.IllegalTransition):
        for n in sorted(cfg["nodes"]):
            c = tv.step(c, {"type": "destroy", "node": n})
            gone += 1
    assert gone >= 1


def test_step_add_promotes_prospective():
    cfg = tv.initial_config(_test_map())
    v = tv.gen_validator()
    pre = tv.pre_step(cfg, {"type": "add", "validator": v})
    assert v["pub_key"] in pre["prospective_validators"]
    post = tv.post_step(pre, {"type": "add", "validator": v})
    assert v["pub_key"] in post["validators"]
    assert v["pub_key"] not in post["prospective_validators"]


def test_rand_legal_transition_always_legal():
    cfg = tv.initial_config(_test_map())
    with gen.fixed_rand(11):
        for _ in range(60):
            t = tv.rand_legal_transition(_test_map(), cfg)
            cfg = tv.step(cfg, t)  # must not raise
    tv.assert_valid(cfg)


def test_reconciliation():
    cfg = tv.initial_config(_test_map(("n1", "n2", "n3")))
    ks = sorted(cfg["validators"])
    cluster = {"version": 7,
               "validators": [{"pub_key": k, "power": 5} for k in ks[:2]]}
    merged = tv.current_config(cfg, cluster)
    assert merged["version"] == 7
    assert set(merged["validators"]) == set(ks[:2])
    assert all(v["votes"] == 5 for v in merged["validators"].values())
    # unknown cluster validator is an error
    with pytest.raises(RuntimeError, match="recognize"):
        tv.current_config(cfg, {"version": 8, "validators":
                                [{"pub_key": "FF" * 32, "power": 1}]})


def test_genesis_structure():
    cfg = tv.initial_config(_test_map(("n1", "n2")))
    g = tv.genesis(cfg)
    assert g["chain_id"] == "jepsen"
    assert len(g["validators"]) == 2
    assert all(v["power"] == "2" for v in g["validators"])


# -------------------------------------------------------- dup grudges


def test_peekaboo_grudge():
    cfg = tv.initial_config(_test_map(dup_validators=True))
    test = {"nodes": ["n1", "n2", "n3", "n4", "n5"],
            "validator_config": [cfg]}
    with gen.fixed_rand(3):
        grudge = tcore.peekaboo_dup_validators_grudge(test)(test["nodes"])
    # one of the dup pair (n1, n2) is exiled from everyone else
    exiled = [n for n in ("n1", "n2") if len(grudge.get(n, [])) == 4]
    assert len(exiled) == 1
    kept = "n1" if exiled == ["n2"] else "n2"
    assert len(grudge.get(kept, [])) == 1  # only drops the exile


def test_split_grudge():
    cfg = tv.initial_config(_test_map(dup_validators=True))
    test = {"nodes": ["n1", "n2", "n3", "n4", "n5"],
            "validator_config": [cfg]}
    with gen.fixed_rand(3):
        grudge = tcore.split_dup_validators_grudge(test)(test["nodes"])
    # two components (dup group size 2), each dropping the other side
    assert set(grudge) == set(test["nodes"])
    comp_of = {}
    for node, drops in grudge.items():
        comp_of[node] = frozenset(set(test["nodes"]) - set(drops))
    comps = set(comp_of.values())
    assert len(comps) == 2
    # dup nodes n1, n2 land in different components
    assert comp_of["n1"] != comp_of["n2"]


# ------------------------------------------------- client error mapping


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    d = tmp_path_factory.mktemp("tm")
    with me.LocalServer(sock_path=str(d / "me.sock")) as srv:
        yield srv


def test_client_kv_roundtrip(server):
    t = tc.SocketTransport(("unix", server.sock_path))
    tc.write(t, "reg", 7)
    assert tc.read(t, "reg") == 7
    tc.cas(t, "reg", 7, 8)
    assert tc.read(t, "reg") == 8
    with pytest.raises(tc.Unauthorized):
        tc.cas(t, "reg", 99, 0)
    with pytest.raises(tc.BaseUnknownAddress):
        tc.cas(t, "missing", 1, 2)
    assert tc.read(t, "missing-key") is None
    assert tc.local_read(t, "reg") == 8
    # structured values round-trip (vectors, as the set workload uses)
    tc.write(t, "vec", [1, 2, 3])
    assert tc.read(t, "vec") == [1, 2, 3]


def test_client_valset_roundtrip(server):
    t = tc.SocketTransport(("unix", server.sock_path))
    vs = tc.validator_set(t)
    pk = "AB" * 32
    tc.validator_set_cas(t, vs["version"], pk, 11)
    vs2 = tc.validator_set(t)
    assert vs2["version"] == vs["version"] + 1
    assert {"pub_key": pk, "power": 11} in vs2["validators"]


def test_cas_register_client_against_server(server):
    test = {"transport_for":
            lambda t_, n_: tc.SocketTransport(("unix", server.sock_path))}
    cl = tcore.CasRegisterClient().open(test, "n1")
    from jepsen_tpu.history import Op
    ok = cl.invoke(test, Op({"type": "invoke", "f": "write",
                             "value": (1, 5), "process": 0}))
    assert ok["type"] == "ok"
    rd = cl.invoke(test, Op({"type": "invoke", "f": "read",
                             "value": (1, None), "process": 0}))
    assert rd["type"] == "ok" and tuple(rd["value"]) == (1, 5)
    bad = cl.invoke(test, Op({"type": "invoke", "f": "cas",
                              "value": (1, [9, 2]), "process": 0}))
    assert bad["type"] == "fail"
    assert bad["error"] == "precondition-failed"


def test_changing_validators_nemesis_against_server(tmp_path):
    """The changing-validators path: refresh reconciles version with
    the live cluster, valset transitions apply via CAS, failures roll
    the local config back (core.clj:225-278)."""
    from jepsen_tpu.history import Op
    with me.LocalServer(sock_path=str(tmp_path / "s.sock")) as srv:
        nodes = ["n1", "n2", "n3"]
        cfg = tv.initial_config({"nodes": nodes})
        test = {"nodes": nodes, "validator_config": [cfg],
                "ssh": {"dummy": True},
                "transport_for":
                lambda t_, n_: tc.SocketTransport(("unix", srv.sock_path))}
        # Seed the cluster with the initial validators so refresh
        # recognizes them.
        t0 = tc.SocketTransport(("unix", srv.sock_path))
        for k, v in cfg["validators"].items():
            tc.validator_set_change(t0, k, v["votes"])
        cfg2 = tcore.refresh_config(test)
        assert cfg2["version"] >= 1  # reconciled with the live valset

        nem = tcore.ChangingValidatorsNemesis().setup(test)
        with gen.fixed_rand(5):
            t = tv.rand_legal_transition(test, cfg2)
        out = nem.invoke(test, Op({"type": "info", "f": "transition",
                                   "value": t}))
        assert out["value"] == "done"

        # A valset transition with a hopelessly stale version raises and
        # rolls the local config back (no stranded prospectives).
        before = test["validator_config"][0]
        bad = {"type": "add", "version": 999_999,
               "validator": tv.gen_validator()}
        with pytest.raises(tc.Unauthorized):
            nem.invoke(test, Op({"type": "info", "f": "transition",
                                 "value": bad}))
        assert test["validator_config"][0] is before


def test_crash_nemesis_binds_sessions():
    """crash_nemesis must run daemon control inside node sessions; with
    the dummy remote every op completes rather than raising 'no session
    bound' (the regression this guards)."""
    from jepsen_tpu.history import Op
    test = {"nodes": ["n1", "n2"], "ssh": {"dummy": True}}
    nem = tcore.crash_nemesis().setup(test)
    out = nem.invoke(test, Op({"type": "info", "f": "start"}))
    assert set(out["value"]) == {"n1", "n2"}
    assert set(out["value"].values()) == {"stopped"}
    out = nem.invoke(test, Op({"type": "info", "f": "stop"}))
    assert set(out["value"].values()) == {"started"}


def test_concurrency_override():
    base = {"nodes": ["n1"], "ssh": {"dummy": True},
            "transport_for": td.local_transport_for}
    t = tcore.test_map({**base, "concurrency": 6})
    assert t["concurrency"] == 6  # multiple of 2*n honored
    # non-multiples round up to the nearest whole key-group
    t = tcore.test_map({**base, "concurrency": 3})
    assert t["concurrency"] == 4
    t = tcore.test_map({**base, "concurrency": 1})
    assert t["concurrency"] == 2


# --------------------------------------------------------- end-to-end


def test_local_cas_register_end_to_end(tmp_path):
    """The quickstart run (README.md:26-52): cas-register workload
    against the native merkleeyes, full lifecycle, linearizable."""
    from jepsen_tpu import core as jcore
    with gen.fixed_rand(42):
        t = tcore.test_map({
            "nodes": ["n1"],
            "ssh": {"dummy": True},
            "db": td.LocalMerkleeyesDB(workdir=str(tmp_path)),
            "transport_for": td.local_transport_for,
            "time_limit": 6,
            "quiesce": 0,
            "ops_per_key": 30,
            "concurrency": 4,
        })
        completed = jcore.run(t)
    res = completed["results"]
    assert res["valid?"] is True, res
    linear = res["linear"]
    assert linear["valid?"] is True
    # multiple keys were actually exercised
    history = completed["history"]
    kv_ops = [o for o in history if isinstance(o.get("value"), tuple)]
    assert len(kv_ops) > 40


def test_local_set_workload_end_to_end(tmp_path):
    from jepsen_tpu import core as jcore
    with gen.fixed_rand(7):
        t = tcore.test_map({
            "nodes": ["n1"],
            "ssh": {"dummy": True},
            "db": td.LocalMerkleeyesDB(workdir=str(tmp_path)),
            "transport_for": td.local_transport_for,
            "workload": "set",
            "time_limit": 5,
            "quiesce": 0,
            "concurrency": 4,
        })
        completed = jcore.run(t)
    res = completed["results"]
    assert res["valid?"] is True, res


def test_cli_local_run(tmp_path, monkeypatch):
    from jepsen_tpu.tendermint import cli as tcli
    monkeypatch.chdir(tmp_path)
    code = tcli.main(["test", "--local", "--node", "n1",
                      "--workload", "cas-register", "--nemesis", "none",
                      "--time-limit", "3", "--concurrency", "4"])
    assert code == 0


def test_cli_test_all_local(tmp_path, monkeypatch, capsys):
    """test-all sweeps two local configs (cas-register and set) through
    LocalMerkleeyesDB and collates both as successes (the reference's
    multi-test runner, cli.clj:478-503)."""
    from jepsen_tpu.tendermint import cli as tcli
    monkeypatch.chdir(tmp_path)
    code = tcli.main(["test-all", "--local", "--node", "n1",
                      "--workloads", "cas-register,set",
                      "--nemeses", "none",
                      "--time-limit", "3", "--concurrency", "4"])
    out = capsys.readouterr().out
    assert code == 0, out
    assert "2 successes" in out and "0 failures" in out


def test_local_kill_recover_end_to_end(tmp_path):
    """Crash-recovery e2e: the local-kill nemesis SIGKILLs the native
    merkleeyes mid-run and restarts it on the same WAL, repeatedly.
    Committed writes must survive replay — the history (with its
    connection-error fails/indeterminates) must still check
    linearizable, and the nemesis must actually have fired."""
    from jepsen_tpu import core as jcore
    with gen.fixed_rand(13):
        t = tcore.test_map({
            "nodes": ["n1"],
            "ssh": {"dummy": True},
            "db": td.LocalMerkleeyesDB(workdir=str(tmp_path)),
            "transport_for": td.local_transport_for,
            "nemesis_name": "local-kill",
            "time_limit": 8,
            "quiesce": 0,
            "ops_per_key": 30,
            "concurrency": 4,
        })
        completed = jcore.run(t)
    res = completed["results"]
    history = completed["history"]
    kills = [o for o in history
             if o.get("process") == "nemesis" and o.get("f") == "kill"
             and o.get("type") == "info" and o.get("value")]
    restarts = [o for o in history
                if o.get("process") == "nemesis"
                and o.get("f") == "restart" and o.get("value")]
    assert kills and restarts, "nemesis never fired"
    assert res["valid?"] is True, res
    assert res["linear"]["valid?"] is True


def test_local_kill_set_workload_end_to_end(tmp_path):
    """Crash-recovery e2e on the SET workload: set-full semantics must
    hold across SIGKILL/WAL-replay cycles — an element whose write was
    acknowledged before a kill must be readable after the restart."""
    from jepsen_tpu import core as jcore
    with gen.fixed_rand(29):
        t = tcore.test_map({
            "nodes": ["n1"],
            "ssh": {"dummy": True},
            "db": td.LocalMerkleeyesDB(workdir=str(tmp_path)),
            "transport_for": td.local_transport_for,
            "workload": "set",
            "nemesis_name": "local-kill",
            "time_limit": 7,
            "quiesce": 0,
            "concurrency": 4,
        })
        completed = jcore.run(t)
    res = completed["results"]
    history = completed["history"]
    assert any(o.get("process") == "nemesis" and o.get("f") == "kill"
               and o.get("value") for o in history), "nemesis never fired"
    assert res["valid?"] is True, res


def _deploy_gate():
    import os
    if not (os.path.exists("/.dockerenv")
            or os.path.exists("/run/.containerenv")
            or os.environ.get("JEPSEN_CLOCK_TESTS") == "1"):
        pytest.skip("writes /opt/jepsen on the host: container or "
                    "explicit opt-in only")


_STUB_TENDERMINT = '''\
#!/usr/bin/env python3
"""Stub tendermint: models the DEPLOY-visible behaviors of the real
binary the workload e2es cannot otherwise see — flag parsing with
persistent_peers validation, consensus-WAL replay logging on restart,
and an RPC /status endpoint that only comes up after a startup delay
(so readiness waits must actually wait). Consensus itself is out of
scope; the deployed merkleeyes daemons are the real native build."""
import json, os, re, sys, time

args = sys.argv[1:]
if "node" not in args:
    print("stub-ok")
    sys.exit(0)


def flag(name):
    return args[args.index(name) + 1] if name in args else None


home = flag("--home") or os.path.expanduser("~/.tendermint")
proxy = flag("--proxy_app") or ""
peers = flag("--p2p.persistent_peers") or ""
if not proxy.startswith(("unix://", "tcp://")):
    print("stub: bad --proxy_app %r" % proxy, flush=True)
    sys.exit(1)
plist = [p for p in peers.split(",") if p]
for p in plist:
    if not re.fullmatch(r"[0-9a-fA-F]{40}@[^@:]+:\\d+", p):
        print("stub: bad persistent peer %r" % p, flush=True)
        sys.exit(1)
print("stub: home=%s proxy_app=%s persistent_peers[%d]=%s"
      % (home, proxy, len(plist), peers), flush=True)

wal = os.path.join(home, "data", "cs.wal", "wal")
if os.path.exists(wal):
    print("stub: replayed wal bytes=%d" % os.path.getsize(wal),
          flush=True)
else:
    os.makedirs(os.path.dirname(wal), exist_ok=True)
with open(wal, "ab") as fh:
    fh.write(b"x" * 64)      # the consensus wal grows while running

port = 26657
try:
    cfg = open(os.path.join(home, "config", "config.toml")).read()
    m = re.search(r'laddr = "tcp://[^:"]*:(\\d+)"', cfg)
    if m:
        port = int(m.group(1))
except OSError:
    pass

time.sleep(float(os.environ.get("STUB_RPC_DELAY", "0.3")))
from http.server import BaseHTTPRequestHandler, HTTPServer


class H(BaseHTTPRequestHandler):
    def do_GET(self):
        body = json.dumps(
            {"result": {"node_info": {"moniker": "stub"}}}).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


print("stub: rpc listening on %d" % port, flush=True)
HTTPServer(("127.0.0.1", port), H).serve_forever()
'''


def _stub_tendermint_tarball(tmp_path):
    """The stub above, packed the way the reference's tarball is
    (cli.clj:18-19)."""
    import subprocess
    dist = tmp_path / "dist"
    dist.mkdir()
    stub = dist / "tendermint"
    stub.write_text(_STUB_TENDERMINT)
    stub.chmod(0o755)
    tarball = tmp_path / "tendermint.tar.gz"
    subprocess.run(["tar", "czf", str(tarball), "-C", str(dist),
                    "tendermint"], check=True)
    return tarball


def test_tendermint_db_full_deploy_local_remote(tmp_path):
    """The FULL cluster deploy path (TendermintDB.setup/teardown,
    reference db.clj:163-219), executed for real on this machine via
    the Local remote: install_archive from a file:// tarball (stub
    tendermint binary — the real one needs a cluster image; merkleeyes
    is the real native build, uploaded and daemonized), config +
    genesis + validator-key writes, pidfile daemon management, the
    Process kill/start protocol, log_files, teardown. The remaining
    distance to the reference's docker run is just the real tendermint
    binary and five containers (docker/README.md)."""
    import json as _json
    import os

    _deploy_gate()
    tarball = _stub_tendermint_tarball(tmp_path)

    from jepsen_tpu import control as jc
    bd = str(tmp_path / "deploy")
    test = {"nodes": ["n1"], "remote": jc.LocalRemote(),
            "base_dir": bd, "concurrency": 2,
            # the stub serves RPC now: keep it off the well-known port
            # so a busy 26657 on the host can't kill the daemon
            "rpc_ports": {"n1": 26705}}
    db = td.db({"tendermint_url": f"file://{tarball}"})

    try:
        # setup inside the try: a partial failure (daemons started,
        # then nt.install crashing) must still hit the teardown
        jc.on_nodes(test, db.setup, ["n1"])
        # real native merkleeyes answering on its socket —
        # start_daemon backgrounds with no readiness wait, so poll
        from jepsen_tpu.tendermint import merkleeyes as me
        import time as _time
        deadline = _time.monotonic() + 10
        while True:
            try:
                # the with-statement's __enter__ performs the connect
                with me.client_for(("unix", td.socket_file(test)),
                                   "abci") as cl:
                    cl.echo(b"ping")
                break
            except OSError:
                if _time.monotonic() > deadline:
                    raise
                _time.sleep(0.05)
        # deploy artifacts on disk and well-formed
        genesis = _json.loads(open(bd + "/config/genesis.json").read())
        assert genesis["validators"], genesis
        vkey = _json.loads(
            open(bd + "/config/priv_validator_key.json").read())
        assert vkey, vkey
        assert "proxy_app" not in open(bd + "/config/config.toml").read()
        # both daemons hold live pids
        tm_pid = int(open(td.tendermint_pid(test)).read().strip())
        me_pid = int(open(td.merkleeyes_pid(test)).read().strip())
        os.kill(tm_pid, 0)   # raises if dead
        os.kill(me_pid, 0)
        # Process protocol: kill stops BOTH, start revives BOTH
        # (session-bound, as the crash nemesis invokes them). Death is
        # checked via /proc state, accepting zombies: when the test
        # runner is PID 1 (bare container entrypoint) the nohup'd
        # daemons reparent to it and are never reaped, so a plain
        # os.kill(pid, 0) would still succeed on the corpse.
        def _gone(pid, timeout=10.0):
            end = _time.monotonic() + timeout
            have_proc = os.path.isdir("/proc/self")
            while _time.monotonic() < end:
                if have_proc:
                    try:
                        with open(f"/proc/{pid}/stat") as fh:
                            state = fh.read().rsplit(")", 1)[1].split()[0]
                        if state == "Z":
                            return True   # unreaped corpse: dead enough
                    except (FileNotFoundError, ProcessLookupError):
                        return True
                else:
                    # no procfs (macOS): plain liveness; zombies cannot
                    # occur there for us since the runner is never PID 1
                    try:
                        os.kill(pid, 0)
                    except OSError:
                        return True
                _time.sleep(0.05)
            return False

        jc.on_nodes(test, db.kill, ["n1"])
        for dead in (tm_pid, me_pid):
            assert _gone(dead), f"pid {dead} survived db.kill"
        jc.on_nodes(test, db.start, ["n1"])
        tm_pid2 = int(open(td.tendermint_pid(test)).read().strip())
        me_pid2 = int(open(td.merkleeyes_pid(test)).read().strip())
        os.kill(tm_pid2, 0)
        os.kill(me_pid2, 0)
        assert tm_pid2 != tm_pid and me_pid2 != me_pid
        for f in db.log_files(test, "n1"):
            assert os.path.exists(f), f
    finally:
        jc.on_nodes(test, db.teardown, ["n1"])
    assert not os.path.exists(bd)


def test_tendermint_5node_deployed_cluster_e2e(tmp_path):
    """Five Local-remote nodes, each with its own base dir, driven
    through the WHOLE lifecycle by jepsen.core.run: db.cycle deploys
    TendermintDB on all five (real native merkleeyes daemons, stub
    tendermint), a cas-register workload commits through the deployed
    consensus node's socket, and the deployed-mix nemesis fires all
    three fault families — a MemNet half-partition, a validator-set
    ADD through the live app, and a crash+truncate on a non-consensus
    node — before the history checks linearizable. The closest this
    dockerless environment gets to the reference's 5-container run
    (README.md:19-35); what remains is real consensus (the real
    tendermint binary replicating between nodes)."""
    import os

    from jepsen_tpu import control as jc
    from jepsen_tpu import core as jcore
    from jepsen_tpu import net as jnet

    _deploy_gate()
    tarball = _stub_tendermint_tarball(tmp_path)

    nodes = [f"n{i}" for i in range(1, 6)]
    base_dirs = {n: str(tmp_path / "deploy" / n) for n in nodes}
    rpc_ports = {n: 26710 + i for i, n in enumerate(nodes)}
    with gen.fixed_rand(61):
        t = tcore.test_map({
            "nodes": nodes,
            "remote": jc.LocalRemote(),
            "base_dirs": base_dirs,
            "rpc_ports": rpc_ports,
            "db": td.db({"tendermint_url": f"file://{tarball}"}),
            "transport_for": td.routed_transport_for,
            "net": jnet.mem(),
            "seed_app_valset": True,   # InitChain stand-in (stub tm)
            "nemesis_name": "deployed-mix",
            "time_limit": 12,
            "quiesce": 0.5,
            "ops_per_key": 25,
        })
        # truncation must not hit the node standing in for consensus:
        # in a REAL cluster replication recovers a truncated follower,
        # but with consensus collapsed the serving node's WAL is the
        # only copy — route clients to a node the crash nemesis will
        # not truncate
        ct = next(n for _, n in t["nemesis"].routes
                  if isinstance(n, tcore.CrashTruncateNemesis))
        assert len(ct.faulty_nodes) == 1, ct.faulty_nodes
        t["consensus_node"] = next(n for n in nodes
                                   if n not in ct.faulty_nodes)
        completed = jcore.run(t)

    res = completed["results"]
    history = completed["history"]
    nem = [o for o in history if o.get("process") == "nemesis"
           and o.get("type") == "info" and o.get("value") is not None]

    def fired(f):
        return [o for o in nem if o.get("f") == f]

    assert any("Cut off" in str(o["value"]) for o in fired("start")), nem
    assert any("fully connected" in str(o["value"])
               for o in fired("stop")), nem
    assert any(o["value"] == "done" for o in fired("transition")), \
        [o for o in nem if o.get("f") == "transition"]
    crash = fired("crash")
    assert crash and all(v == "crashed"
                         for o in crash
                         for v in dict(o["value"]).values()), crash
    assert set(dict(crash[0]["value"])) == set(ct.faulty_nodes)

    # per-node deploy artifacts were snarfed from every node's own dir
    # before teardown removed them
    store = completed["store"]
    for n in nodes:
        assert os.path.exists(store.path(n, "genesis.json")), n
        assert os.path.exists(store.path(n, "merkleeyes.log")), n
        assert not os.path.exists(base_dirs[n]), "teardown left " + n

    # real work committed through the deployed socket, and the
    # partition was visible to clients (indeterminate/failed ops)
    ok_kv = [o for o in history if o.get("type") == "ok"
             and isinstance(o.get("value"), tuple)]
    assert len(ok_kv) > 40, len(ok_kv)
    assert any(str(o.get("error", "")).startswith("indeterminate:")
               or "partition" in str(o.get("error", ""))
               for o in history), "no client ever saw the partition"

    assert res["valid?"] is True, res
    assert res["linear"]["valid?"] is True


def test_stub_tendermint_fidelity_rpc_wal_peers(tmp_path):
    """The deploy-visible behaviors of the real binary, surfaced by
    the stub and asserted through the SAME product paths a real
    cluster uses: (1) RPC answers /status only after a startup delay,
    so await_tendermint_rpc (the readiness wait the reference
    approximates with a flat sleep, db.clj:204) must actually poll;
    (2) every node's --p2p.persistent_peers carries exactly the other
    nodes' 40-hex ids at gossip port 26656 and never its own
    (db.clj:75-82); (3) a restart finds the consensus WAL the previous
    run left and replays it."""
    import json as _json
    import re
    import urllib.request

    from jepsen_tpu import control as jc

    _deploy_gate()
    tarball = _stub_tendermint_tarball(tmp_path)
    nodes = ["n1", "n2", "n3"]
    test = {"nodes": nodes,
            "remote": jc.LocalRemote(),
            "base_dirs": {n: str(tmp_path / "deploy" / n) for n in nodes},
            "rpc_ports": {"n1": 26720, "n2": 26721, "n3": 26722},
            "await_rpc_timeout": 20,
            "concurrency": 2}
    db = td.db({"tendermint_url": f"file://{tarball}"})
    try:
        jc.on_nodes(test, db.setup, nodes)
        # setup returned => the readiness poll held until RPC was up
        for n in nodes:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{test['rpc_ports'][n]}/status",
                    timeout=5) as resp:
                body = _json.loads(resp.read().decode())
            assert body["result"]["node_info"]["moniker"] == "stub", body

        vc = test["validator_config"][0]
        for n in nodes:
            log = open(test["base_dirs"][n] + "/tendermint.log").read()
            m = re.search(r"persistent_peers\[(\d+)\]=(\S*)", log)
            assert m, log[-500:]
            assert int(m.group(1)) == len(nodes) - 1, m.group(0)
            entries = m.group(2).split(",")
            assert all(e.endswith(":26656") for e in entries), entries
            got_ids = {e.split("@")[0] for e in entries}
            want_ids = {vc["node_keys"][o]["id"]
                        for o in nodes if o != n}
            assert got_ids == want_ids, (n, got_ids, want_ids)

        # restart: the wal written by run #1 must be seen by run #2
        jc.on_nodes(test, db.kill, ["n1"])
        jc.on_nodes(test, db.start, ["n1"])
        jc.on_nodes(test,
                    lambda t, n: td.await_tendermint_rpc(t, n, 20),
                    ["n1"])
        log = open(test["base_dirs"]["n1"] + "/tendermint.log").read()
        m = re.search(r"replayed wal bytes=(\d+)", log)
        assert m and int(m.group(1)) >= 64, log[-500:]
    finally:
        jc.on_nodes(test, db.teardown, nodes)


REAL_TENDERMINT_URL = ("https://github.com/melekes/katas/releases/"
                       "download/0.2.0/tendermint.tar.gz")  # cli.clj:18


@pytest.mark.slow
def test_real_tendermint_binary_deploy_network_gated(tmp_path):
    """Where the network allows it, deploy the reference's ACTUAL
    tendermint tarball (cli.clj:18) on a Local-remote node: install,
    config/genesis/key writes, daemonization, RPC readiness (the
    binary's era may ignore our [rpc] table, so candidate default
    ports are polled too), liveness, teardown. Skips with the probe
    evidence on zero-egress hosts."""
    import socket
    import time as _time

    from jepsen_tpu import control as jc

    _deploy_gate()
    try:
        socket.create_connection(("github.com", 443), timeout=5).close()
    except OSError as e:
        pytest.skip(f"no network to fetch the reference tarball: {e!r}")

    nodes = ["n1"]
    test = {"nodes": nodes,
            "remote": jc.LocalRemote(),
            "base_dirs": {"n1": str(tmp_path / "deploy")},
            "rpc_ports": {"n1": 26730},
            "concurrency": 2}
    db = td.db({"tendermint_url": REAL_TENDERMINT_URL})
    try:
        jc.on_nodes(test, db.setup, nodes)
        pid = int(open(
            test["base_dirs"]["n1"] + "/tendermint.pid").read().strip())
        _time.sleep(3)
        # /proc-state liveness: a plain kill(pid, 0) passes on an
        # unreaped zombie when the runner is PID 1 (see the _gone
        # helper in the single-node deploy test)
        with open(f"/proc/{pid}/stat") as fh:
            state = fh.read().rsplit(")", 1)[1].split()[0]
        assert state != "Z", "real tendermint died at startup"
        deadline = _time.monotonic() + 60
        ready = None
        while ready is None and _time.monotonic() < deadline:
            for port in (26730, 26657, 46657):
                try:
                    socket.create_connection(("127.0.0.1", port),
                                             timeout=2).close()
                    ready = port
                    break
                except OSError:
                    _time.sleep(0.5)
        log = open(test["base_dirs"]["n1"] + "/tendermint.log").read()
        assert ready is not None, f"RPC never listened; log: {log[-800:]}"
    finally:
        jc.on_nodes(test, db.teardown, nodes)


@pytest.mark.fuzz
@pytest.mark.slow
def test_local_kill_soak(tmp_path):
    """Soak tier (deselected by default, like the reference's :perf
    tier): 45s of cas-register at concurrency 8 through continuous
    SIGKILL/WAL-replay cycles. Carries BOTH markers: the fuzz mark
    alone only deselects under the addopts default — a tier-1 style
    `-m 'not slow'` invocation overrides addopts' `-m "not fuzz"` and
    was silently pulling this ~200s container-flaky soak (noted flaky
    in CHANGES.md PR 2) into every default-suite run. Stresses reconnect storms, indeterminate
    retry tainting, and WAL recovery under load far past the smoke
    e2es; the history must still check linearizable."""
    from jepsen_tpu import core as jcore
    with gen.fixed_rand(97):
        t = tcore.test_map({
            "nodes": ["n1"],
            "ssh": {"dummy": True},
            "db": td.LocalMerkleeyesDB(workdir=str(tmp_path)),
            "transport_for": td.local_transport_for,
            "nemesis_name": "local-kill",
            "time_limit": 45,
            "quiesce": 0,
            "ops_per_key": 40,
            "concurrency": 8,
        })
        completed = jcore.run(t)
    res = completed["results"]
    history = completed["history"]
    kills = [o for o in history
             if o.get("process") == "nemesis" and o.get("f") == "kill"
             and o.get("value")]
    # a loose floor: each cycle costs 2.2s of sleeps plus kill/replay
    # wall time, and the WAL replay grows over the run — on a loaded
    # box cycles stretch; the soak's real assertion is the verdict
    assert len(kills) >= 5, f"only {len(kills)} kill cycles in 45s"
    assert res["valid?"] is True, res
