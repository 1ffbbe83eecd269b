"""Tendermint + merkleeyes deployment
(reference: tendermint/src/jepsen/tendermint/db.clj).

Two modes:

- **Cluster mode** (`TendermintDB`): installs the tendermint and
  merkleeyes binaries on each node via the control plane, writes
  genesis / validator-key / node-key JSON, and runs both daemons with
  pidfiles (db.clj:21-219). The merkleeyes binary deployed is this
  repo's native C++ one — `make` locally, ship the binary (nodes are
  assumed ABI-compatible; pass merkleeyes_url to install a prebuilt
  archive instead, as the reference does for both components).
- **Local mode** (`LocalMerkleeyesDB`): one shared native merkleeyes
  process on a unix socket stands in for the whole replicated cluster —
  consensus collapses to a single linearizable app, which is exactly
  what a correctness test of the *harness* wants (the atom-db pattern,
  tests.clj:27-67, but through the real native server)."""

from __future__ import annotations

import json
import logging
import tempfile
import threading
from typing import Optional

from jepsen_tpu import control as c
from jepsen_tpu import db as jdb
from jepsen_tpu.control import util as cu
from jepsen_tpu.nemesis import time as nt
from jepsen_tpu.tendermint import merkleeyes as me
from jepsen_tpu.tendermint import validator as tv

log = logging.getLogger(__name__)

BASE_DIR = "/opt/tendermint"  # tendermint/util.clj:4


CONFIG_TOML = """\
# config.toml tuned for fast 5-node commits
# (tendermint/resources/config.toml:1-19)
[consensus]
timeout_commit = "0ms"
skip_timeout_commit = true
peer_gossip_sleep_duration = "10ms"

[p2p]
flush_throttle_timeout = "10ms"

[rpc]
laddr = "tcp://0.0.0.0:{rpc_port}"
"""


def node_base_dir(test, node) -> str:
    """Per-node base dir. A real cluster shares BASE_DIR per machine;
    a single-host multi-node deployment (Local remote, the docker-less
    parallel of the reference's 5-container run, docker/README.md)
    gives every node its own directory via test["base_dirs"]."""
    dirs = test.get("base_dirs") or {}
    if node is not None and node in dirs:
        return dirs[node]
    return test.get("base_dir", BASE_DIR)


def base_dir(test) -> str:
    """The CURRENT node's base dir: inside on_nodes the control scope
    carries the node, so every path helper below is per-node exactly
    where commands run per-node."""
    return node_base_dir(test, c.scope.host)


def socket_file(test) -> str:
    return base_dir(test) + "/merkleeyes.sock"


def socket_addr(test) -> str:
    return "unix://" + socket_file(test)


def merkleeyes_log(test) -> str:
    return base_dir(test) + "/merkleeyes.log"


def tendermint_log(test) -> str:
    return base_dir(test) + "/tendermint.log"


def merkleeyes_pid(test) -> str:
    return base_dir(test) + "/merkleeyes.pid"


def rpc_port(test, node=None) -> int:
    """The node's tendermint RPC port. Real clusters keep the default
    on every machine; single-host multi-node deployments give each
    node its own via test["rpc_ports"]."""
    ports = test.get("rpc_ports") or {}
    if node is None:
        node = c.scope.host
    return int(ports.get(node, 26657))


def tendermint_pid(test) -> str:
    return base_dir(test) + "/tendermint.pid"


# -------------------------------------------------- per-node file writes


def _write_json(path: str, data) -> None:
    import os as _os
    fd, tmp = tempfile.mkstemp(suffix=".json")
    try:
        with _os.fdopen(fd, "w") as f:
            json.dump(data, f)
        c.upload([tmp], path)
    finally:
        _os.unlink(tmp)


def write_validator(test, node, validator: dict) -> None:
    """priv_validator_key.json + empty state (db.clj:28-38)."""
    with c.su():
        _write_json(base_dir(test) + "/config/priv_validator_key.json",
                    validator)
        _write_json(base_dir(test) + "/data/priv_validator_state.json", {})


def write_node_key(test, node, node_key: dict) -> None:
    """(db.clj:40-47)."""
    with c.su():
        _write_json(base_dir(test) + "/config/node_key.json", node_key)


def write_genesis(test, genesis: dict) -> None:
    """(db.clj:49-56)."""
    with c.su():
        _write_json(base_dir(test) + "/config/genesis.json", genesis)


def write_config(test) -> None:
    """(db.clj:58-64)."""
    import os as _os
    with c.su():
        fd, tmp = tempfile.mkstemp(suffix=".toml")
        try:
            with _os.fdopen(fd, "w") as f:
                f.write(CONFIG_TOML.format(rpc_port=rpc_port(test)))
            c.upload([tmp], base_dir(test) + "/config/config.toml")
        finally:
            _os.unlink(tmp)


def node_id(test, node) -> Optional[str]:
    """(db.clj:66-73)."""
    cfg = (test.get("validator_config") or [None])[0] or {}
    return ((cfg.get("node_keys") or {}).get(node) or {}).get("id")


def persistent_peers(test, node) -> str:
    """--p2p.persistent_peers value (db.clj:75-82)."""
    return ",".join(f"{node_id(test, n)}@{n}:26656"
                    for n in test.get("nodes") or [] if n != node)


# ------------------------------------------------------ daemon control


def start_tendermint(test, node) -> str:
    """(db.clj:94-108)."""
    with c.su(), c.cd(base_dir(test)):
        cu.start_daemon(
            {"logfile": tendermint_log(test),
             "pidfile": tendermint_pid(test), "chdir": base_dir(test)},
            "./tendermint", "--home", base_dir(test), "node",
            "--proxy_app", socket_addr(test),
            "--p2p.persistent_peers", persistent_peers(test, node))
    return "started"


def start_merkleeyes(test, node) -> str:
    """(db.clj:110-122). Runs this repo's native server."""
    with c.su(), c.cd(base_dir(test)):
        cu.start_daemon(
            {"logfile": merkleeyes_log(test),
             "pidfile": merkleeyes_pid(test), "chdir": base_dir(test)},
            "./merkleeyes/merkleeyes", "--listen",
            f"unix:{socket_file(test)}",
            # the real tendermint binary drives --proxy_app over the
            # v0.34 ABCI socket protocol (native/merkleeyes/src/abci.h)
            "--proto", "abci",
            "--wal", base_dir(test) + "/jepsen/jepsen.db/000001.log")
    return "started"


def await_tendermint_rpc(test, node, timeout: float) -> None:
    """Bounded NODE-SIDE poll of tendermint's RPC /status endpoint —
    a real readiness wait where the reference sleeps a flat second
    after start (db.clj:204). Runs through the control plane (curl on
    the node against its own localhost), so Local remotes and real
    clusters behave identically. Raises TimeoutError when the RPC
    never comes up."""
    import time as _time
    port = rpc_port(test, node)
    deadline = _time.monotonic() + timeout
    while True:
        try:
            c.exec_("curl", "-sf", "--max-time", "2",
                    f"http://127.0.0.1:{port}/status")
            return
        except c.RemoteError as err:
            if err.exit in (126, 127):
                # missing/unrunnable curl is a node-image problem, not
                # "not ready" — burning the timeout would misdirect
                raise
            if _time.monotonic() > deadline:
                raise TimeoutError(
                    f"tendermint RPC on {node}:{port} not ready after "
                    f"{timeout}s")
            _time.sleep(0.25)


def stop_tendermint(test, node) -> str:
    with c.su():
        cu.stop_daemon(tendermint_pid(test))
    return "stopped"


def stop_merkleeyes(test, node) -> str:
    with c.su():
        cu.stop_daemon(merkleeyes_pid(test))
        c.exec_("rm", "-rf", socket_file(test))
    return "stopped"


def start(test, node):
    """(db.clj:133-136)."""
    start_merkleeyes(test, node)
    start_tendermint(test, node)
    return "started"


def stop(test, node):
    """(db.clj:138-141)."""
    stop_tendermint(test, node)
    stop_merkleeyes(test, node)
    return "stopped"


def reset_validator(test, node) -> None:
    """Wipe identity + data, preserving binaries and genesis
    (db.clj:155-161)."""
    with c.su():
        bd = base_dir(test)
        c.exec_("bash", "-c", c.lit(c.escape(
            f"rm -rf {bd}/data {bd}/jepsen "
            f"{bd}/config/priv_validator_key.json "
            f"{bd}/config/node_key.json")))


class TendermintDB(jdb.DB, jdb.Process, jdb.LogFiles):
    """Full cluster deployment (db.clj:163-219). Options:
    tendermint_url / merkleeyes_url — archives to install (merkleeyes
    defaults to shipping the locally built native binary)."""

    def __init__(self, opts: Optional[dict] = None):
        self.opts = opts or {}
        self._lock = threading.Lock()  # on_nodes runs setup in parallel

    def setup(self, test, node):
        bd = base_dir(test)
        with c.su():
            if self.opts.get("tendermint_url"):
                cu.install_archive(self.opts["tendermint_url"],
                                   bd + "/tendermint-dist")
                c.exec_("cp", bd + "/tendermint-dist/tendermint", bd + "/")
            if self.opts.get("merkleeyes_url"):
                cu.install_archive(self.opts["merkleeyes_url"],
                                   bd + "/merkleeyes")
            else:
                with self._lock:  # make must not run concurrently
                    binary = me.build()
                c.exec_("mkdir", "-p", bd + "/merkleeyes")
                c.upload([str(binary)], bd + "/merkleeyes/merkleeyes")
                c.exec_("chmod", "+x", bd + "/merkleeyes/merkleeyes")
            c.exec_("mkdir", "-p", bd + "/config", bd + "/data",
                    bd + "/jepsen/jepsen.db")
            write_config(test)

        # One node computes the initial validator config; the rest wait
        # on the lock and reuse it — the synchronize-barrier equivalent
        # (db.clj:180-192). on_nodes runs setups in parallel threads.
        with self._lock:
            box = test.setdefault("validator_config", [None])
            if box[0] is None:
                box[0] = tv.initial_config(test)

        vc = box[0]
        write_genesis(test, tv.genesis(vc))
        v = vc["validators"].get(vc["nodes"].get(node))
        if v is not None:
            write_validator(test, node, v)
        write_node_key(test, node, vc["node_keys"].get(node) or {})

        start_merkleeyes(test, node)
        start_tendermint(test, node)
        if test.get("await_rpc_timeout"):
            await_tendermint_rpc(test, node, test["await_rpc_timeout"])
        if test.get("seed_app_valset") and node == consensus_node(test):
            seed_app_valset(test, node)
        with self._lock:
            # /opt/jepsen is per-MACHINE: single-host multi-node
            # deployments would otherwise race N gccs onto one binary;
            # on a real cluster this merely serializes an idempotent
            # per-node compile
            nt.install()

    def teardown(self, test, node):
        try:
            stop(test, node)
        finally:
            with c.su():
                c.exec_("rm", "-rf", base_dir(test))

    # Process protocol: used by the crash nemesis / combined packages.
    def start(self, test, node):
        return start(test, node)

    def kill(self, test, node):
        return stop(test, node)

    def log_files(self, test, node):
        bd = base_dir(test)
        return [tendermint_log(test), merkleeyes_log(test),
                bd + "/config/priv_validator_key.json",
                bd + "/config/node_key.json",
                bd + "/config/genesis.json"]


def db(opts: Optional[dict] = None) -> TendermintDB:
    return TendermintDB(opts)


# ------------------------------------------------------------ local mode


class LocalMerkleeyesDB(jdb.DB):
    """One shared native merkleeyes process standing in for the cluster.
    setup/teardown manage the process; `transport_for` points every
    node at it."""

    def __init__(self, workdir: Optional[str] = None):
        self.workdir = workdir
        self.server: Optional[me.LocalServer] = None
        self._lock = threading.Lock()

    def setup(self, test, node):
        with self._lock:
            self._setup_locked(test)

    def _setup_locked(self, test):
        if self.server is None:
            d = self.workdir or tempfile.mkdtemp(prefix="merkleeyes-")
            self.server = me.LocalServer(
                sock_path=d + "/merkleeyes.sock",
                wal_path=d + "/merkleeyes.wal").start()
            test["merkleeyes_sock"] = self.server.sock_path

    def teardown(self, test, node):
        if self.server is not None:
            self.server.stop()
            self.server = None

    # ---- crash-nemesis surface (local parallel of the cluster kill
    # nemesis): SIGKILL the shared process / restart it on the SAME
    # wal path, so committed txs must come back via WAL replay
    def kill_server(self):
        with self._lock:
            if self.server is not None:
                self.server.kill()

    def restart_server(self):
        with self._lock:
            if self.server is not None and self.server.proc is None:
                self.server.start()


def local_transport_for(test, node):
    """transport factory for local mode: every node reaches the one
    shared server."""
    from jepsen_tpu.tendermint import client as tc
    sock = test.get("merkleeyes_sock")
    assert sock, "local merkleeyes is not running (no :merkleeyes_sock)"
    return tc.SocketTransport(("unix", sock))


def http_transport_for(test, node):
    """transport factory for cluster mode: tendermint RPC on the node,
    at the node's configured port (test["rpc_ports"] honored end to
    end: config.toml, readiness poll, and clients agree)."""
    from jepsen_tpu.tendermint import client as tc
    return tc.HttpTransport(node, port=rpc_port(test, node))


# ------------------------------------------- single-host cluster mode


def seed_app_valset(test, node, timeout: float = 10.0) -> None:
    """InitChain stand-in for stub-tendermint deployments (opt-in via
    test["seed_app_valset"]): push the genesis validators into the
    deployed app's validator set, which the REAL binary does on chain
    start via ABCI InitChain (the reference leaves this to tendermint,
    db.clj:49-56 only writes genesis.json). Without it the app's
    valset is empty and the first refresh_config would reconcile the
    genesis validators away. Polls the daemon's socket: start_daemon
    backgrounds with no readiness wait."""
    import time as _time

    from jepsen_tpu.tendermint import client as tc
    t = tc.SocketTransport(
        ("unix", node_base_dir(test, node) + "/merkleeyes.sock"))
    vc = test["validator_config"][0]
    deadline = _time.monotonic() + timeout
    for pub, v in sorted(vc["validators"].items()):
        while True:
            try:
                tc.validator_set_change(t, pub, v["votes"])
                break
            except OSError:
                if _time.monotonic() > deadline:
                    raise
                _time.sleep(0.05)


def consensus_node(test) -> str:
    """The node whose deployed merkleeyes stands in for the replicated
    state machine under routed_transport_for."""
    return test.get("consensus_node") or (test.get("nodes") or ["n1"])[0]


class _PartitionedTransport:
    """A transport on the wrong side of a grudge: every use times out.
    Raised at USE (not open) so the clients' _map_errors classification
    applies — writes/cas surface as indeterminate :info, reads as
    :fail — exactly how a minority node's RPC behaves in the real
    cluster: the connection opens, the commit never comes."""

    def __init__(self, node, target):
        self.node, self.target = node, target

    def _cut(self):
        raise TimeoutError(
            f"partition: {self.node} cannot reach {self.target}")

    def broadcast_tx(self, tx):
        self._cut()

    def abci_query(self, path, data):
        self._cut()


def routed_transport_for(test, node):
    """Cluster-mode transport for a single-host deployment (Local
    remote): every client routes to the consensus node's DEPLOYED
    merkleeyes socket — consensus collapses to one linearizable app,
    as in local mode, but through the daemon TendermintDB actually
    deployed and manages — and the route honors the test's net: a
    client whose node holds a grudge against the consensus node gets
    the partitioned transport above. The remaining distance to the
    reference's semantics is real replication (the real tendermint
    binary + docker, README.md:19-35)."""
    from jepsen_tpu.tendermint import client as tc
    target = consensus_node(test)
    net = test.get("net")
    if (net is not None and node is not None and node != target
            and not net.reachable(node, target)):
        return _PartitionedTransport(node, target)
    return tc.SocketTransport(
        ("unix", node_base_dir(test, target) + "/merkleeyes.sock"))
