"""Command-line runner (reference: jepsen/src/jepsen/cli.clj).

Subcommands mirror `jepsen.cli/single-test-cmd` + serve
(cli.clj:343-419, 324-341):

    test      build a test from flags and run it
    analyze   re-check the latest (or given) stored history
    serve     browse stored results over HTTP

Exit-code contract (cli.clj:120-130): 0 = valid, 1 = invalid,
2 = unknown validity, 254 = bad arguments, 255 = crash.

A suite supplies `run_cli(test_fn)` where (test_fn options) -> test map;
options include the parsed flags below. The `--concurrency` flag accepts
the reference's "3n" syntax — a multiple of the node count
(cli.clj:55-102 parse-concurrency).
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback
from typing import Callable, Dict, Optional

from jepsen_tpu import core as jcore
from jepsen_tpu import store as jstore
from jepsen_tpu.history import History

EXIT_VALID = 0
EXIT_INVALID = 1
EXIT_UNKNOWN = 2
EXIT_BAD_ARGS = 254
EXIT_CRASH = 255


def parse_concurrency(s: str, n_nodes: int) -> int:
    """'10' -> 10; '3n' -> 3 * node count (cli.clj:132-150)."""
    s = str(s).strip()
    if s.endswith("n"):
        return int(s[:-1] or 1) * max(1, n_nodes)
    return int(s)


def parse_nodes(args) -> list:
    if args.node:
        return list(args.node)
    if args.nodes_file:
        with open(args.nodes_file) as fh:
            return [ln.strip() for ln in fh if ln.strip()]
    return ["n1", "n2", "n3", "n4", "n5"]  # cli.clj default node set


def base_parser(prog: str = "jepsen") -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog=prog)
    sub = p.add_subparsers(dest="command")

    def common(sp):
        sp.add_argument("--node", action="append",
                        help="node name (repeatable)")
        sp.add_argument("--nodes-file", help="file with one node per line")
        sp.add_argument("--username", default="root")
        sp.add_argument("--password", default="root")
        sp.add_argument("--private-key-path")
        sp.add_argument("--ssh-port", type=int, default=22)
        sp.add_argument("--no-ssh", action="store_true",
                        help="use the dummy remote (no cluster needed)")
        sp.add_argument("--concurrency", default="1n",
                        help="worker count; '3n' = 3 per node")
        sp.add_argument("--time-limit", type=float, default=60,
                        help="seconds of main workload")
        sp.add_argument("--test-count", type=int, default=1)
        sp.add_argument("--workload", default=None)
        sp.add_argument("--nemesis", default=None)

    t = sub.add_parser("test", help="run a test")
    common(t)
    a = sub.add_parser("analyze", help="re-check a stored history")
    common(a)
    a.add_argument("--run-dir", help="store/<name>/<timestamp> to re-check")
    s = sub.add_parser(
        "serve",
        help="serve stored results over HTTP; with --checker, run the "
             "streaming checker service instead")
    s.add_argument("--port", type=int, default=8080)
    s.add_argument("--host", default="0.0.0.0")
    s.add_argument("--checker", action="store_true",
                   help="run the streaming checker service: JSONL "
                        "delta requests on stdin, verdict responses "
                        "on stdout (docs/streaming.md)")
    s.add_argument("--model", default="cas-register",
                   choices=sorted(SERVE_MODELS),
                   help="model family for --checker")
    s.add_argument("--wal-dir", default=None,
                   help="delta WAL + checkpoint-store directory for "
                        "--checker (default: JEPSEN_TPU_SERVE_WAL)")
    s.add_argument("--dedupe", default=None,
                   choices=("sort", "hash"),
                   help="frontier dedupe strategy for --checker "
                        "(default: JEPSEN_TPU_DEDUPE)")
    s.add_argument("--ops-port", type=int, default=None,
                   help="with --checker: serve /metrics (Prometheus "
                        "text), /healthz, and /status on this port "
                        "(0 = OS-assigned; default: "
                        "JEPSEN_TPU_OPS_PORT, unset = no ops "
                        "endpoint — docs/observability.md)")
    s.add_argument("--repl-dir", default=None,
                   help="with --checker: WAL segment replication "
                        "target — the successor replica's repl/ "
                        "mirror (e.g. a shared mount); required when "
                        "JEPSEN_TPU_SERVE_REPL is async/sync, "
                        "rejected when it is off (docs/streaming.md "
                        "'Fleet self-healing')")
    s.add_argument("--ingress-port", type=int, default=None,
                   help="with --checker: accept streamed-JSONL delta "
                        "requests over HTTP on this port "
                        "(POST /v1/deltas, GET /v1/result, "
                        "POST /v1/finalize; per-tenant bearer-token "
                        "auth when JEPSEN_TPU_TENANTS is set; 0 = "
                        "OS-assigned; default: "
                        "JEPSEN_TPU_INGRESS_PORT, unset = stdio "
                        "only — docs/streaming.md)")
    # listed for --help discoverability only: run_cli dispatches `lint`
    # to jepsen_tpu.analysis.main BEFORE parsing (its own parser is the
    # single source of truth for lint flags and the 0/1/2 contract;
    # argparse.REMAINDER cannot forward a leading optional)
    li = sub.add_parser(
        "lint", add_help=False,
        help="tracing-safety & concurrency static analysis "
             "(jepsen_tpu.analysis); exit 0 clean / 1 findings / "
             "2 usage error")
    # listed for --help discoverability only, like lint: run_cli
    # dispatches `probe` BEFORE parsing (jepsen_tpu.probe owns its
    # flags and the 0/1/2 healthy/wedged/no-backend exit contract)
    pr = sub.add_parser(
        "probe", add_help=False,
        help="bounded device-runtime health check (subprocess "
             "jax.devices() with timeout + retry); exit 0 healthy / "
             "1 wedged / 2 no-backend")
    # listed for --help discoverability only, like lint/probe: run_cli
    # dispatches `status` BEFORE parsing (jepsen_tpu.obs.httpd owns its
    # flags and the 0/1/2 ready/degraded/unreachable exit contract)
    st = sub.add_parser(
        "status", add_help=False,
        help="fetch /status + /healthz from a running `jepsen serve "
             "--checker --ops-port N` and print the operator summary; "
             "exit 0 ready / 1 degraded / 2 unreachable")
    # listed for --help discoverability only, like lint/probe/status:
    # run_cli dispatches `report` BEFORE parsing (obs.search_report
    # owns its flags; exit 0 written / 1 no stats / 254 usage)
    rp = sub.add_parser(
        "report", add_help=False,
        help="render a stored run's telemetry reports; --search "
             "renders the JEPSEN_TPU_SEARCH_STATS per-key table "
             "(worst keys by load factor / escalations / pad waste); "
             "--slow renders the slow-delta forensics table "
             "(JEPSEN_TPU_SLOW_DELTA_SECS stage breakdowns); --plan "
             "renders the strategy-advisor table (JEPSEN_TPU_LEDGER "
             "decision records joined with perf_ab bench evidence)")
    # listed for --help discoverability only, like lint/probe/status:
    # run_cli dispatches `trace` BEFORE parsing (obs.trace_merge owns
    # its flags and the 0/1/2 merged/invalid/unreachable contract)
    tr = sub.add_parser(
        "trace", add_help=False,
        help="merge a fleet's per-replica trace exports (live /trace "
             "endpoints, run dirs, flight dumps) into one Perfetto "
             "file — one process track per replica, wall-clock "
             "aligned; --validate schema-checks exports")
    ta = sub.add_parser(
        "test-all", help="run a whole suite of tests in one go")
    common(ta)
    ta.add_argument("--workloads",
                    help="comma-separated workload sweep (default: the "
                         "single --workload)")
    ta.add_argument("--nemeses",
                    help="comma-separated nemesis sweep (default: the "
                         "single --nemesis)")
    p._jepsen_subparsers = {"test": t, "analyze": a, "serve": s,
                            "lint": li, "probe": pr, "status": st,
                            "report": rp, "trace": tr,
                            "test-all": ta}
    return p


def options_from_args(args) -> Dict:
    nodes = parse_nodes(args)
    ssh = {
        "username": args.username,
        "password": args.password,
        "port": args.ssh_port,
        "private-key-path": args.private_key_path,
        "dummy": bool(args.no_ssh),
    }
    return {
        "nodes": nodes,
        "ssh": ssh,
        "concurrency": parse_concurrency(args.concurrency, len(nodes)),
        "time-limit": args.time_limit,
        "test-count": args.test_count,
        "workload": args.workload,
        "nemesis": args.nemesis,
        # suite-specific flags as plain data (serializable, no Namespace)
        "args": dict(vars(args)),
        "explicit-nodes": bool(args.node or args.nodes_file),
    }


def validity_exit_code(results: Dict) -> int:
    v = (results or {}).get("valid?")
    if v is True:
        return EXIT_VALID
    if v is False:
        return EXIT_INVALID
    return EXIT_UNKNOWN


def run_test_cmd(test_fn: Callable[[Dict], Dict], args) -> int:
    opts = options_from_args(args)
    for _ in range(opts["test-count"]):  # cli.clj:375-386 loop
        test = test_fn(opts)
        completed = jcore.run(test)
        code = validity_exit_code(completed.get("results"))
        print(json.dumps({"valid?": completed["results"].get("valid?"),
                          "store": completed["store"].dir}, default=str))
        if code != EXIT_VALID:
            # exit on first non-valid run, as the reference does
            return code
    return EXIT_VALID


def run_analyze_cmd(test_fn: Callable[[Dict], Dict], args) -> int:
    """Reload the latest stored run and re-check it against a freshly
    built test map (cli.clj:388-419)."""
    run_dir = args.run_dir or jstore.latest()
    if run_dir is None:
        print("no stored runs to analyze", file=sys.stderr)
        return EXIT_BAD_ARGS
    stored = jstore.load_run(run_dir)
    history = stored.get("history")
    if history is None:
        print(f"no history.npz/history.edn under {run_dir}", file=sys.stderr)
        return EXIT_BAD_ARGS
    opts = options_from_args(args)
    test = test_fn(opts)
    # merge stored test config under the fresh test map (cli.clj:396-400)
    for k, v in (stored.get("test") or {}).items():
        test.setdefault(k, v)
    results = jcore.analyze(test, History.wrap(history))
    print(json.dumps({"valid?": results.get("valid?"), "run": run_dir},
                     default=str))
    return validity_exit_code(results)


def _sweep_tests(args, opts):
    """The default tests-fn for test-all: the cross product of
    --workloads x --nemeses, each repeated --test-count times."""
    workloads = [w.strip() for w in (args.workloads or "").split(",")
                 if w.strip()] or [opts.get("workload")]
    nemeses = [n.strip() for n in (args.nemeses or "").split(",")
               if n.strip()] or [opts.get("nemesis")]
    for w in workloads:
        for n in nemeses:
            for _ in range(max(1, opts.get("test-count") or 1)):
                o = dict(opts)
                o["workload"] = w
                o["nemesis"] = n
                yield f"{w or 'default'}:{n or 'none'}", o


def run_test_all_cmd(test_fn: Callable[[Dict], Dict], args,
                     tests_fn: Optional[Callable] = None) -> int:
    """Run a suite of tests, collate outcomes, print a summary, and exit
    255 if any crashed / 2 if any unknown / 1 if any invalid / 0 if all
    passed (cli.clj:421-503 test-all-cmd + test-all-exit!).

    tests_fn(opts) may yield (name, options) pairs to override the
    default --workloads x --nemeses sweep."""
    opts = options_from_args(args)
    pairs = (tests_fn(opts) if tests_fn is not None
             else _sweep_tests(args, opts))
    outcomes: Dict = {}  # True | False | "unknown" | "crashed" -> [runs]
    for name, o in pairs:
        try:
            completed = jcore.run(test_fn(o))
            v = completed["results"].get("valid?")
            key = v if v in (True, False) else "unknown"
            run_ref = str(getattr(completed.get("store"), "dir", name))
            outcomes.setdefault(key, []).append(run_ref)
            print(json.dumps({"test": name, "valid?": v,
                              "store": run_ref}, default=str))
        except KeyboardInterrupt:
            raise
        except Exception:  # noqa: BLE001 — one crash must not end the sweep
            traceback.print_exc()
            outcomes.setdefault("crashed", []).append(name)
    for title, key in (("Successful tests", True),
                       ("Indeterminate tests", "unknown"),
                       ("Crashed tests", "crashed"),
                       ("Failed tests", False)):
        if outcomes.get(key):
            print(f"\n# {title}\n")
            for r in outcomes[key]:
                print(r)
    print()
    print(len(outcomes.get(True, [])), "successes")
    print(len(outcomes.get("unknown", [])), "unknown")
    print(len(outcomes.get("crashed", [])), "crashed")
    print(len(outcomes.get(False, [])), "failures")
    if outcomes.get("crashed"):
        return EXIT_CRASH
    if outcomes.get("unknown"):
        return EXIT_UNKNOWN
    if outcomes.get(False):
        return EXIT_INVALID
    return EXIT_VALID


# model families the streaming checker service can be started with
# (jepsen serve --checker --model <name>); values are jepsen_tpu.models
# class names, instantiated with their defaults
SERVE_MODELS = {
    "register": "Register",
    "cas-register": "CASRegister",
    "mutex": "Mutex",
    "gset": "GSet",
    "fifo": "FIFOQueue",
    "uqueue": "UnorderedQueue",
}


def run_serve_cmd(args) -> int:
    if getattr(args, "checker", False):
        # the streaming checker service (docs/streaming.md): deltas in,
        # verdicts out, over the JSONL stdio transport — jax imports
        # stay inside this branch so the results browser keeps working
        # against a wedged device runtime
        from jepsen_tpu import models as model_ns
        from jepsen_tpu.obs import httpd as ops_httpd
        from jepsen_tpu.serve import CheckerService, default_wal_dir
        from jepsen_tpu.serve.stdio import run_stdio
        model = getattr(model_ns, SERVE_MODELS[args.model])()
        # WAL segment replication (docs/streaming.md "Fleet
        # self-healing"): --repl-dir names the successor's repl/
        # mirror; the mode comes from JEPSEN_TPU_SERVE_REPL. The
        # service itself rejects a mode with no target; reject the
        # inverse here too — a --repl-dir under mode "off" would be
        # an operator believing replication is on when it is not.
        from jepsen_tpu.serve import fleet as fleet_mod
        wal_dir = args.wal_dir or default_wal_dir()
        repl_mode = fleet_mod.resolve_repl_mode()
        replicator = None
        if repl_mode != "off" and not getattr(args, "repl_dir", None):
            # the service would raise the same complaint — answer it
            # here as a usage error, not a traceback
            print(f"jepsen serve: JEPSEN_TPU_SERVE_REPL={repl_mode!r}"
                  f" but no --repl-dir names the successor's mirror "
                  f"— add --repl-dir PATH or unset the flag "
                  f"(docs/streaming.md 'Fleet self-healing')",
                  file=sys.stderr)
            return 2
        if getattr(args, "repl_dir", None):
            if repl_mode == "off":
                print("jepsen serve: --repl-dir given but "
                      "JEPSEN_TPU_SERVE_REPL is off/unset — set the "
                      "mode (async|sync) or drop the flag",
                      file=sys.stderr)
                return 2
            if not wal_dir:
                print("jepsen serve: --repl-dir needs a WAL-backed "
                      "service (--wal-dir / JEPSEN_TPU_SERVE_WAL)",
                      file=sys.stderr)
                return 2
            from jepsen_tpu.serve.wal import DeltaWAL
            replicator = fleet_mod.SegmentReplicator(
                DeltaWAL(wal_dir),
                fleet_mod.constant_dst(args.repl_dir),
                mode=repl_mode)
        svc = CheckerService(model, wal_dir=wal_dir,
                             dedupe=args.dedupe,
                             replicator=replicator)
        # the live ops surface (docs/observability.md "Ops endpoint"):
        # off unless --ops-port / JEPSEN_TPU_OPS_PORT names a port, so
        # a bare serve is byte-identical to the pre-ops service. The
        # continuous chip watch rides JEPSEN_TPU_PROBE_INTERVAL
        # independently — its gauges also feed flight-recorder dumps.
        from jepsen_tpu import probe as probe_mod
        watch = probe_mod.start_watch_from_env()
        port = ops_httpd.resolve_ops_port(
            getattr(args, "ops_port", None))
        ops = None
        if port is not None:

            def _health():
                doc = svc.health()
                if watch is not None:
                    p = watch.status()
                    doc["checks"]["probe"] = p
                    doc["ok"] = doc["ok"] and p["ok"]
                return doc

            ops = ops_httpd.start_ops_server(
                port, host=args.host, health_fn=_health,
                status_fn=svc.status, refresh_fn=svc.refresh_gauges,
                # POST /adopt: the fleet supervisor's live handoff
                # trigger (WAL-backed services only — adopt_keys
                # raises without one)
                adopt_fn=(svc.adopt_keys if wal_dir else None))
            print(f"ops endpoint: http://{args.host}:{ops.port} "
                  f"(/metrics /healthz /status — `jepsen status "
                  f"--port {ops.port}`)", file=sys.stderr)
        # the HTTP delta ingress (docs/streaming.md "HTTP ingress"):
        # off unless --ingress-port / JEPSEN_TPU_INGRESS_PORT names a
        # port; stdio keeps running either way — both transports feed
        # the same admission layer (tenancy, quotas, backpressure)
        from jepsen_tpu.serve import ingress as ingress_mod
        iport = ingress_mod.resolve_ingress_port(
            getattr(args, "ingress_port", None))
        ing = None
        if iport is not None:
            ing = ingress_mod.start_ingress(svc, iport,
                                            host=args.host)
            print(f"delta ingress: http://{args.host}:{ing.port} "
                  f"(POST /v1/deltas — streamed JSONL)",
                  file=sys.stderr)
        try:
            return run_stdio(svc)
        finally:
            if ing is not None:
                ing.close()
            if ops is not None:
                ops.close()
            if watch is not None:
                watch.stop()
    from jepsen_tpu import web
    web.serve(host=args.host, port=args.port)
    return EXIT_VALID




def run_cli(test_fn: Optional[Callable[[Dict], Dict]] = None,
            argv: Optional[list] = None, prog: str = "jepsen",
            extend_parser: Optional[Callable] = None,
            tests_fn: Optional[Callable] = None) -> int:
    """Main dispatcher (cli.clj:246-322). test_fn builds a test map from
    parsed options; defaults to the noop test. extend_parser(parser)
    may add suite-specific flags (parser._jepsen_subparsers maps
    subcommand names to their subparsers). tests_fn(opts), if given,
    yields (name, options) pairs for the test-all sweep
    (cli.clj:478-503's :tests-fn)."""
    if test_fn is None:
        test_fn = lambda opts: jcore.make_test(  # noqa: E731
            {"nodes": opts["nodes"], "ssh": opts["ssh"],
             "concurrency": opts["concurrency"]})
    raw = list(argv) if argv is not None else sys.argv[1:]
    if raw[:1] == ["lint"]:
        # forwarded BEFORE the main parser: argparse.REMAINDER drops a
        # leading optional (`lint --check` errors), and the analysis
        # package's own parser is the single source of truth for lint
        # flags, help, and the 0/1/2 exit contract
        from jepsen_tpu import analysis
        return analysis.main(raw[1:])
    if raw[:1] == ["probe"]:
        # same pre-parse forwarding as lint: jepsen_tpu.probe owns its
        # flags and the 0/1/2 healthy/wedged/no-backend contract (the
        # runbook's automation hook — see docs/observability.md)
        from jepsen_tpu import probe
        return probe.main(raw[1:])
    if raw[:1] == ["status"]:
        # same pre-parse forwarding: the ops-endpoint client owns its
        # flags and the 0/1/2 ready/degraded/unreachable contract, and
        # importing it never touches jax — `jepsen status` must answer
        # against a wedged runtime
        from jepsen_tpu.obs import httpd as ops_httpd
        return ops_httpd.status_main(raw[1:])
    if raw[:1] == ["report"]:
        # same pre-parse forwarding: the telemetry reports own their
        # flags (`--search` / `--slow`, `--run-dir`), read stored
        # artifacts only, and never touch jax
        from jepsen_tpu.obs import search_report
        return search_report.report_main(raw[1:])
    if raw[:1] == ["trace"]:
        # same pre-parse forwarding: the fleet trace merge owns its
        # flags, talks only to ops endpoints / trace files, and never
        # touches jax — it must run from a coordinator while the
        # fleet's device runtimes are busy or wedged
        from jepsen_tpu.obs import trace_merge
        return trace_merge.trace_main(raw[1:])
    parser = base_parser(prog)
    if extend_parser is not None:
        extend_parser(parser)
    try:
        args = parser.parse_args(argv)
    except SystemExit as e:
        return EXIT_BAD_ARGS if e.code not in (0, None) else 0
    if args.command is None:
        parser.print_help()
        return EXIT_BAD_ARGS
    # test / analyze / serve compile device programs; the subcommands
    # forwarded above never touch jax
    from jepsen_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    try:
        if args.command == "test":
            return run_test_cmd(test_fn, args)
        if args.command == "test-all":
            return run_test_all_cmd(test_fn, args, tests_fn=tests_fn)
        if args.command == "analyze":
            return run_analyze_cmd(test_fn, args)
        if args.command == "serve":
            return run_serve_cmd(args)
        return EXIT_BAD_ARGS
    except KeyboardInterrupt:
        raise
    except Exception:  # noqa: BLE001
        traceback.print_exc()
        return EXIT_CRASH


def main(argv: Optional[list] = None) -> int:
    return run_cli(None, argv)


if __name__ == "__main__":
    sys.exit(main())
