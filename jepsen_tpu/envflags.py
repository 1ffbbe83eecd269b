"""Validated accessors for the ``JEPSEN_TPU_*`` environment flags.

Every read of a ``JEPSEN_TPU_*`` variable anywhere in the tree goes
through this module; the ``env-flag-accessor`` rule in
``jepsen_tpu.analysis`` enforces that mechanically. Why it exists: a
malformed flag value must fail loudly at the read site, not silently
revert a measured default. The motivating incident is the round-5
pallas flip — with the old raw read (``flag == "1"``), a stray
``JEPSEN_TPU_PALLAS=yes`` would have silently disabled the measured
54x win, and nothing would have said so.

Contract:

* ``env_bool`` flags are strict tri-state: unset means "use the code
  default", ``"1"`` means on, ``"0"`` means off, and anything else
  raises :class:`EnvFlagError`.
* ``env_choice`` flags accept exactly the listed strings.
* Names must carry the ``JEPSEN_TPU_`` prefix — the accessor refuses
  to read anything else, so the namespace stays greppable.

This module must stay importable with no JAX and no device runtime:
the static-analysis pass (and its CI gate) runs CPU-only before any
backend exists, and engine modules import it at module scope.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

PREFIX = "JEPSEN_TPU_"


class EnvFlagError(ValueError):
    """A JEPSEN_TPU_* variable is set to a value outside its contract."""


def env_raw(name: str, default: Optional[str] = None) -> Optional[str]:
    """The raw string value of a prefixed flag (no validation beyond
    the namespace check). Prefer the typed accessors below."""
    if not name.startswith(PREFIX):
        raise EnvFlagError(
            f"{name!r} is not a {PREFIX}* flag — the accessor only "
            f"serves the jepsen_tpu namespace")
    return os.environ.get(name, default)


def env_bool(name: str, default: Optional[bool] = None) -> Optional[bool]:
    """Strict tri-state boolean flag.

    Unset -> ``default`` (pass ``None`` to mean "let the code pick a
    platform default"), ``"1"`` -> True, ``"0"`` -> False. Any other
    value raises :class:`EnvFlagError` instead of silently counting as
    an opt-out — the exact failure mode that nearly reverted the
    measured pallas default in round 5.
    """
    raw = env_raw(name)
    if raw is None:
        return default
    if raw == "1":
        return True
    if raw == "0":
        return False
    raise EnvFlagError(
        f"{name}={raw!r}: must be '1' (on) or '0' (off); unset the "
        f"variable to get the default")


def env_choice(name: str, choices: Sequence[str],
               default: Optional[str] = None,
               what: str = "value") -> Optional[str]:
    """A flag restricted to an explicit set of strings. Unset ->
    ``default``; anything outside ``choices`` raises
    :class:`EnvFlagError` (the message names ``what`` so callers'
    error-matching tests read naturally)."""
    raw = env_raw(name)
    if raw is None:
        return default
    if raw in choices:
        return raw
    raise EnvFlagError(
        f"{name}={raw!r}: unknown {what} (expected one of "
        f"{tuple(choices)})")


def env_int(name: str, default: Optional[int] = None,
            min_value: Optional[int] = None,
            what: str = "value") -> Optional[int]:
    """An integer flag. Unset -> ``default``; a non-integer value or
    one below ``min_value`` raises :class:`EnvFlagError` — same
    fail-loud contract as the other accessors (a malformed cache-size
    flag must not silently disable the cache or blow up later in an
    unrelated stack)."""
    raw = env_raw(name)
    if raw is None:
        return default
    try:
        v = int(raw)
    except ValueError:
        raise EnvFlagError(
            f"{name}={raw!r}: must be an integer {what}; unset the "
            f"variable to get the default")
    if min_value is not None and v < min_value:
        raise EnvFlagError(
            f"{name}={raw!r}: {what} must be >= {min_value}")
    return v


def env_float(name: str, default: Optional[float] = None,
              min_value: Optional[float] = None,
              what: str = "value") -> Optional[float]:
    """A float flag (seconds-style knobs). Unset -> ``default``; a
    non-numeric value or one below ``min_value`` raises
    :class:`EnvFlagError` — a malformed watchdog timeout must not
    silently disable the watchdog (the exact no-op failure the whole
    module exists to prevent)."""
    raw = env_raw(name)
    if raw is None:
        return default
    try:
        v = float(raw)
    except ValueError:
        raise EnvFlagError(
            f"{name}={raw!r}: must be a number {what}; unset the "
            f"variable to get the default")
    import math
    if not math.isfinite(v):
        # float() happily parses "inf"/"nan", but a non-finite
        # watchdog/backoff would blow up far from the read site
        # (Thread.join(inf) raises OverflowError per dispatch) — the
        # exact silent-misconfiguration mode this accessor prevents
        raise EnvFlagError(
            f"{name}={raw!r}: {what} must be finite")
    if min_value is not None and v < min_value:
        raise EnvFlagError(
            f"{name}={raw!r}: {what} must be >= {min_value}")
    return v


def env_path(name: str, what: str = "path") -> Optional[str]:
    """A tri-state *destination* flag: unset or ``"0"`` -> ``None``
    (feature off), ``"1"`` -> ``""`` (feature on, caller picks the
    default destination), anything else -> that value as a filesystem
    path (feature on, write there). Whitespace-only values raise —
    a stray ``JEPSEN_TPU_TRACE=" "`` must not silently create a
    directory named after the typo. Used by the telemetry flags
    (``JEPSEN_TPU_TRACE``, ``JEPSEN_TPU_JAX_PROFILE``)."""
    raw = env_raw(name)
    if raw is None or raw == "0":
        return None
    if raw == "1":
        return ""
    if not raw.strip():
        raise EnvFlagError(
            f"{name}={raw!r}: must be '0' (off), '1' (on, default "
            f"destination), or a {what}")
    return raw


# Registry of the JEPSEN_TPU_* flags in circulation — one line per
# flag, naming the accessor and the owning module, so the namespace
# stays auditable in one place (the env-flag-accessor lint rule keeps
# every READ going through this module; this table documents what a
# grep for the prefix should find):
#
#   JEPSEN_TPU_PALLAS        env_bool    parallel.bitdense — closure
#                            kernel default (r5 on-chip verdict)
#   JEPSEN_TPU_CLOSURE       env_choice  parallel.bitdense — XLA loop
#                            shape ("while"/"fori")
#   JEPSEN_TPU_BUCKET        env_choice  parallel.engine — batch
#                            bucketing strategy ("tier"/"exact")
#   JEPSEN_TPU_DEDUPE        env_choice  parallel.engine — sparse
#                            frontier dedupe strategy ("sort"/"hash":
#                            lexsort vs delta-frontier closure over a
#                            device-resident hash visited-set, also
#                            sharded by owner in parallel.sharded);
#                            opt-in until bench records a win
#   JEPSEN_TPU_SPARSE_PALLAS env_bool    parallel.engine — fuse the
#                            hash dedupe path into the VMEM-resident
#                            pallas frontier kernel
#                            (parallel.sparse_kernels; whole-event
#                            closure single-device, per-iteration
#                            insert in parallel.sharded); "1" forces
#                            it on (interpret mode off-TPU, like
#                            JEPSEN_TPU_PALLAS); opt-in until
#                            tools/perf_ab.py's hash-pallas strategy
#                            records the on-chip win
#   JEPSEN_TPU_SEARCH_STATS  env_bool    parallel.engine — device-
#                            resident search telemetry: when on, the
#                            engine jits (sparse XLA + pallas,
#                            bitdense, sharded, streaming-resumable)
#                            additionally return a per-event stats
#                            block computed on device (frontier-width
#                            trajectory, closure iterations, delta
#                            split, hash-table load factor, bucketed
#                            probe-length histogram, pad waste),
#                            threaded into result "stats" dicts, the
#                            engine.search.* registry names (/metrics),
#                            Perfetto counter tracks, and `jepsen
#                            report --search`; default off — results,
#                            bench schema, and trace files byte-
#                            identical to the pre-stats engine
#   JEPSEN_TPU_CONFIG_PACK   env_bool    parallel.engine — pack each
#                            configuration's (state, mask_lo, mask_hi)
#                            triple into the minimal word the event
#                            family needs (state field + C mask bits,
#                            1-2 uint32 lanes instead of 3): shrinks
#                            the frontier, the hash visited-set, the
#                            FrontierCheckpoint carry boundary, and
#                            the sharded all-to-all payloads, and
#                            widens the sparse kernels' width-aware
#                            VMEM gate; families past 64 bits run
#                            unpacked (tagged). Verdicts and counters
#                            are representation-independent
#                            (parity-pinned); opt-in until the chip
#                            A/B (tools/perf_ab.py hash-packed)
#                            records the win
#   JEPSEN_TPU_VMEM_BUDGET   env_int     parallel.sparse_kernels — the
#                            probe-state VMEM budget (bytes) gating
#                            the fused/tiled sparse kernels (default
#                            4 MiB, min 64 KiB): the one knob that
#                            re-gates every sparse kernel for a
#                            different TPU generation without a code
#                            edit
#   JEPSEN_TPU_PROBE_LIMIT   env_int     parallel.engine — bounded
#                            linear-probe length of the hash
#                            visited-set (default 32, min 1); one
#                            knob for the XLA and pallas hash paths;
#                            exhaustion escalates capacity, never
#                            drops a config
#   JEPSEN_TPU_PIPELINE      env_bool    parallel.engine — route
#                            check_batch through the pipelined
#                            executor (parallel.pipeline); opt-in
#                            until bench records a win
#   JEPSEN_TPU_RESHARD       env_bool    parallel.engine/sharded —
#                            re-shard-on-escalation: a sharded search
#                            (incl. the batch overflow escalation
#                            tier) starts on a narrow device slice and
#                            capacity overflow RECRUITS devices along
#                            MeshPlan.ladder's rungs (wider 1-D, then
#                            2-D slice promotion) at flat per-device
#                            capacity before growing tables; overflow
#                            semantics and ceilings unchanged; opt-in
#                            until the perf_ab reshard arm records the
#                            win
#   JEPSEN_TPU_DIST          env_bool    parallel.meshplan — arm the
#                            jax.distributed multi-host handshake
#                            (meshplan.distributed_init): off/unset =
#                            single-host, no initialize call ever;
#                            "1" REQUIRES the three companion flags
#                            below (a half-configured pod plan raises
#                            at the read site instead of hanging in a
#                            collective)
#   JEPSEN_TPU_DIST_COORD    env_raw     parallel.meshplan — the
#                            jax.distributed coordinator address
#                            (host:port), validated for the colon
#   JEPSEN_TPU_DIST_NPROC    env_int     parallel.meshplan — total
#                            process count of the distributed run
#                            (min 1)
#   JEPSEN_TPU_DIST_PROC     env_int     parallel.meshplan — this
#                            process's id (0-based, < NPROC)
#   JEPSEN_TPU_ENCODE_CACHE  env_int     parallel.pipeline — encode
#                            cache capacity in entries (0 disables)
#   JEPSEN_TPU_COMPILE_CACHE env_path    parallel.programs — the
#                            compile-economics master switch
#                            (docs/performance.md "Compile
#                            economics"): unset/"0" off (plain jit
#                            dispatch, byte-identical results and
#                            schemas), "1" arms the in-process program
#                            registry (AOT lower().compile() engine
#                            programs, engine.programs.* counters,
#                            serve.compile_secs histogram, freeze-time
#                            program manifests for warm rehome
#                            handoff), <dir> additionally persists
#                            serialized executables there so a
#                            restarted replica cold-starts warm
#                            (loads are version/fingerprint-guarded:
#                            a mismatch degrades to a fresh compile,
#                            counted load_errors — never a wrong
#                            program)
#   JEPSEN_TPU_CANON_SHAPES  env_bool    parallel.programs — shape
#                            canonicalization: quantize one-shot and
#                            resumable-chunk event-row counts onto the
#                            EVENT_QUANTUM ladder (the streaming
#                            chunk-padding precedent) so the
#                            fleet-wide program population is dozens,
#                            not one per history length; pad rows are
#                            scan no-ops — verdict/counterexample/
#                            max-frontier/configs-stepped identical
#                            (parity-pinned); opt-in until perf_ab's
#                            compile record shows the population win
#                            against the pad-waste telemetry
#   JEPSEN_TPU_PRECOMPILE    env_bool    parallel.programs — ladder
#                            precompile: a background best-effort
#                            thread pre-compiles the next capacity
#                            rung (N doubled, same event shapes)
#                            above every live AOT program so a
#                            mid-incident escalation re-dispatch
#                            finds its program resident (counted
#                            engine.programs.precompiles); requires
#                            JEPSEN_TPU_COMPILE_CACHE armed
#   JEPSEN_TPU_TEST_WEDGE    env_bool    resilience.faults — legacy
#                            alias for the bench child-wedge seam; =1
#                            now injects an implicit `wedge@child`
#                            fault rule (prefer JEPSEN_TPU_FAULTS)
#   JEPSEN_TPU_FAULTS        env_raw     resilience.faults — the
#                            deterministic fault-injection plan:
#                            comma-separated `<kind>@<site>[:<count>]`
#                            specs (`wedge@dispatch:2`,
#                            `raise@transfer:every=3`,
#                            `flaky@search:n=1`); validated by
#                            faults.parse_spec — a malformed spec
#                            raises FaultSpecError (an EnvFlagError),
#                            never a silent no-op
#   JEPSEN_TPU_WATCHDOG      env_float   resilience.supervisor —
#                            bounded wait (seconds) on every
#                            supervised device dispatch; a dispatch
#                            past the bound raises DispatchWedged
#                            instead of hanging the process (the r05
#                            make_c_api_client signature). Unset/0 =
#                            off (the supervised call is a near-zero-
#                            overhead passthrough)
#   JEPSEN_TPU_DISPATCH_RETRIES env_int  resilience.supervisor — extra
#                            attempts after a transient dispatch
#                            failure while the breaker stays closed
#                            (default 1, min 0)
#   JEPSEN_TPU_BREAKER_THRESHOLD env_int resilience.breaker —
#                            consecutive dispatch failures that open a
#                            backend's circuit breaker (default 3,
#                            min 1)
#   JEPSEN_TPU_BREAKER_BACKOFF env_float resilience.breaker — base
#                            open-state backoff seconds (default 1.0;
#                            doubles per re-open, jittered, capped)
#   JEPSEN_TPU_TRACE         env_path    obs — span tracing: "0"/unset
#                            off (a true no-op), "1" on (artifacts land
#                            in the store run dir / bench trace dir),
#                            <path> on + Chrome trace JSON written there
#   JEPSEN_TPU_JAX_PROFILE   env_path    obs — wrap a batched check in
#                            jax.profiler.trace(<dir>) (no Python
#                            tracer) and arm span tracing: each span
#                            is an annotation on the capture's clock
#   JEPSEN_TPU_SERVE_QUEUE   env_int     serve.service — per-key
#                            pending-delta queue bound (default 64,
#                            min 1); a full queue BLOCKS the producer
#                            (backpressure), never buffers unboundedly
#   JEPSEN_TPU_SERVE_GLOBAL  env_int     serve.service — global
#                            pending-ops hard bound across all keys
#                            (default 65536, min 1); the service's
#                            memory ceiling for unapplied deltas
#   JEPSEN_TPU_SERVE_HIGH_WATER env_int  serve.service — pending-ops
#                            level past which NEW deltas are shed with
#                            a structured {shed, reason} response
#                            (default: 3/4 of the global bound; 0
#                            disables shedding — producers then block
#                            at the hard bound instead)
#   JEPSEN_TPU_SERVE_EVICT_SECS env_float serve.service — idle seconds
#                            before a key's frontier is frozen to the
#                            checkpoint store and its in-memory state
#                            dropped (default 300; 0 disables; thaw on
#                            the next delta is transparent and
#                            digest-guarded)
#   JEPSEN_TPU_SERVE_WAL     env_path    serve.service — the delta WAL
#                            + checkpoint-store directory: unset/"0"
#                            no WAL (in-memory service, no eviction),
#                            "1" store/serve_wal, <path> there; every
#                            ADMITTED delta is fsynced here before the
#                            producer sees {"accepted"}
#   JEPSEN_TPU_SERVE_WAL_SEGMENT_BYTES env_int serve.wal — auto-rotate
#                            a key's active WAL segment once it grows
#                            past this many bytes (0/unset = no auto
#                            rotation; DeltaWAL.rotate() always
#                            available): segmented files are what
#                            per-tenant WAL quotas meter and replica
#                            handoff ships
#   JEPSEN_TPU_SERVE_REPL    env_choice  serve.fleet — WAL segment
#                            replication mode: "off" (default) |
#                            "async" (segments ship to the key's ring
#                            successor from a background thread;
#                            serve.repl_lag_keys gauges the lag, which
#                            is also the loss window if the primary's
#                            DISK dies) | "sync" (the producer's ack
#                            waits for successor durability — a dead
#                            node with a dead disk then loses nothing
#                            acknowledged); a non-off mode with no
#                            replication target wired (replicator= /
#                            --repl-dir) raises at service
#                            construction instead of silently
#                            protecting nothing
#   JEPSEN_TPU_FLEET_INTERVAL env_float  serve.fleet — supervisor
#                            heartbeat interval seconds (default 2.0,
#                            min 0.01): how often every replica's
#                            /healthz is polled and breakers advance
#   JEPSEN_TPU_FLEET_THRESHOLD env_int   serve.fleet — consecutive
#                            /healthz misses before a replica is
#                            declared dead and its keys rehomed
#                            (default 3, min 1; the PR-6 breaker
#                            threshold, per replica)
#   JEPSEN_TPU_FLEET_REHOME_RETRIES env_int serve.fleet — bounded
#                            rehome attempts per supervision tick
#                            (default 3, min 1; exponential backoff
#                            between attempts, then the next tick
#                            retries — a rehome is idempotent)
#   JEPSEN_TPU_TENANTS       env_raw     serve.tenancy — the tenant
#                            table: comma-separated
#                            `<name>[:token=T][:weight=W][:ops=N]
#                            [:keys=N][:wal=BYTES]` declarations,
#                            strictly validated (TenantSpecError, an
#                            EnvFlagError, on any malformed field —
#                            a typo'd tenant plan must never silently
#                            run un-isolated); unset = single-tenant
#                            mode, byte-identical to the PR 7/8
#                            service
#   JEPSEN_TPU_TENANT_OPS    env_int     serve.tenancy — default
#                            per-tenant pending-ops quota when a
#                            tenant declares no `ops=` (0/unset =
#                            derive each tenant's bound as its weight
#                            share of the shed high-water)
#   JEPSEN_TPU_TENANT_KEYS   env_int     serve.tenancy — default
#                            per-tenant concurrent-key quota when a
#                            tenant declares no `keys=` (0/unset =
#                            unlimited)
#   JEPSEN_TPU_TENANT_WAL_BYTES env_int  serve.tenancy — default
#                            per-tenant WAL-bytes quota when a tenant
#                            declares no `wal=` (0/unset = unlimited);
#                            a tenant past it sheds new deltas until
#                            the operator rotates/archives its keys
#   JEPSEN_TPU_TENANT_QUANTUM env_int    serve.tenancy — deficit-
#                            round-robin quantum: ops of service
#                            credit one weight unit banks per worker
#                            cycle (default 512, min 1); smaller =
#                            finer-grained fairness, larger = bigger
#                            batched device programs
#   JEPSEN_TPU_INGRESS_PORT  env_int     serve.ingress — the HTTP
#                            delta-ingress port for `jepsen serve
#                            --checker` (streamed-JSONL POST
#                            /v1/deltas + /v1/result + /v1/finalize,
#                            per-tenant bearer-token auth; 0 =
#                            OS-assigned; unset = stdio only);
#                            `--ingress-port` overrides
#   JEPSEN_TPU_OPS_PORT      env_int     obs.httpd — the live ops
#                            endpoint port for `jepsen serve
#                            --checker` (/metrics Prometheus text,
#                            /healthz, /status; 0 = OS-assigned;
#                            unset = no endpoint, serve behavior
#                            byte-identical to the pre-ops service);
#                            `--ops-port` overrides
#   JEPSEN_TPU_PROBE_INTERVAL env_float  probe — continuous chip
#                            watch: re-run probe.check_in_process
#                            every N seconds on a daemon thread and
#                            publish probe.chip_healthy /
#                            probe.last_ok_age_secs gauges (feeding
#                            /healthz + flight dumps); unset/0 = off
#                            (no thread)
#   JEPSEN_TPU_SLOW_DELTA_SECS env_float serve.service — slow-delta
#                            forensics threshold: a delta whose
#                            ingest->verdict latency crosses this many
#                            seconds lands a structured record (stage
#                            breakdown admission/backpressure/wal/
#                            queue/device/publish, verdict, resilience
#                            note, search-stats block when armed) in a
#                            bounded newest-wins ring — surfaced on
#                            /status, drained into slow_deltas.jsonl
#                            by export_run, rendered by `jepsen report
#                            --slow`, and flight-dumped on the worst
#                            offender. Also arms per-delta trace
#                            identity (delta_id minting + WAL id
#                            stamping) like JEPSEN_TPU_TRACE /
#                            JEPSEN_TPU_FLIGHT_RECORDER do. Unset/0 =
#                            off — serve results, WAL bytes, /status
#                            and /metrics schema byte-identical to the
#                            pre-forensics service
#   JEPSEN_TPU_FLIGHT_RECORDER env_int   obs.tracer — crash flight
#                            recorder: retain the last N closed spans
#                            in a bounded ring EVEN WITH TRACING OFF
#                            ("1" = the default 256; N>=2 = that
#                            capacity), dumped as a Chrome-trace file
#                            (+ metric delta) on DispatchWedged,
#                            breaker open, serve shed, or serve
#                            worker error; unset/0 = off — span() is
#                            then the historical no-op singleton
#   JEPSEN_TPU_LEDGER        env_path    obs.ledger — the decision
#                            ledger: append one durable JSONL evidence
#                            record per device dispatch / escalation /
#                            reshard / serve publish (shape
#                            fingerprint, strategy vector, secs,
#                            stats digest, outcome) into bounded
#                            segments under the given dir ("1" =
#                            store/ledger). Aggregated on /ledger,
#                            snapshotted into run dirs as
#                            ledger.jsonl, joined by `jepsen report
#                            --plan`. Unset/"0" = off — no file, no
#                            obs.ledger.* metric, results/bench/
#                            trace/metrics byte-identical
#   JEPSEN_TPU_LEDGER_SEGMENT_BYTES env_int obs.ledger — rotate the
#                            active ledger segment past this many
#                            bytes (default 1048576, min 4096)
#   JEPSEN_TPU_LEDGER_SEGMENTS env_int   obs.ledger — retained
#                            segment count; older segments are
#                            unlinked, bounding the ledger's disk
#                            footprint (default 8, min 2)
#   JEPSEN_TPU_LEDGER_FLOOR  env_int     obs.advisor — `jepsen report
#                            --plan` per-cell sample floor: a
#                            shape×strategy cell with fewer ledger
#                            records recommends nothing
#                            ("insufficient evidence") instead of
#                            guessing (default 3, min 1)
#   JEPSEN_TPU_SLO_ACK_SECS  env_float   obs.slo — the serve ack-
#                            latency SLO target (seconds, objective
#                            99%): arms the two-window burn-rate
#                            gauges serve.slo.ack_burn_rate[window=
#                            fast|slow] derived from serve.ack_secs
#                            histogram deltas on every /metrics
#                            refresh, and the /healthz "slo" check.
#                            Unset/0 = off — /metrics and /healthz
#                            byte-identical
#   JEPSEN_TPU_SLO_BURN_MAX  env_float   obs.slo — degrade /healthz
#                            readiness when the FAST-window burn rate
#                            exceeds this (burn 1.0 = consuming error
#                            budget exactly on schedule); unset/0 =
#                            never degrade, gauges only
#   JEPSEN_TPU_AUTO          env_bool    parallel.planner — the self-
#                            tuning strategy planner: per slot-window
#                            bucket, pick the strategy vector (dedupe,
#                            pallas closure, pack, pipeline)
#                            from a per-shape decision table seeded by
#                            the `jepsen report --plan` advisor join
#                            and updated online (EWMA per
#                            shape×strategy cell) from every
#                            dispatch's measured secs; below the
#                            JEPSEN_TPU_LEDGER_FLOOR sample floor the
#                            static defaults run and the dispatch only
#                            contributes evidence. A plan routes only
#                            between parity-pinned paths — never a
#                            verdict change. Results/"plan" blocks,
#                            /status rows, kind=plan ledger records,
#                            engine.plan.* metrics, /plan endpoint;
#                            table durable beside the ledger segments.
#                            Unset/"0" = off, byte-identical
#                            (docs/performance.md "Auto planner")
#   JEPSEN_TPU_AUTO_EXPLORE  env_int     parallel.planner — run the
#                            least-sampled non-chosen arm every Nth
#                            auto decision per shape group so a stale
#                            seed self-corrects (default 8, min 0;
#                            0 = never explore)
