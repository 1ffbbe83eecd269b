"""`jepsen probe` — bounded device-runtime health check.

A wedged device runtime blocks forever inside backend start-up with no
Python-level signal delivery, so the probe runs ``jax.devices()`` in a
throwaway subprocess under a timeout: the probe process takes the
hang, never the operator's shell. One verdict line per attempt:

    2026-07-31T03:46:32Z probe: HEALTHY — jax.devices() -> ['tpu'] in 2.5s (tpu platform)
    2026-07-31T02:18:07Z probe: hung past 100s (attempt 1/3)
    2026-07-31T02:28:00Z probe: WEDGED — all 3 attempts hung past 100s

Exit contract (the runbook's automation hook):

    0  healthy     jax.devices() answered within the timeout with an
                   accelerator (or with the CPU, when JAX_PLATFORMS
                   asks for it)
    1  wedged      every attempt hung past the timeout (runtime up but
                   unreachable)
    2  no-backend  the child ran but failed (no devices / import error
                   / only the CPU found) — a different failure class:
                   retries won't help, fix the environment

Usage: ``jepsen probe [--timeout 100] [--retries 3] [--interval 30]``
(also ``python -m jepsen_tpu.probe``). The parent never imports jax.

A process that already holds the device cannot probe it from a child:
a chip belongs to one process at a time. The circuit breaker's
recovery check and :class:`ProbeWatch` therefore run
:func:`check_in_process` instead — a tiny op on the backend this
process holds, under a bounded watchdog thread.
"""

from __future__ import annotations

import argparse
import datetime as _dt
import logging
import os
import subprocess
import sys
import threading
import time
from typing import Callable, Optional, Sequence

_log = logging.getLogger(__name__)

EXIT_HEALTHY = 0
EXIT_WEDGED = 1
EXIT_NO_BACKEND = 2

# the child: enumerate devices and print one machine-parseable line
_CHILD_CODE = (
    "import json\n"
    "import jax\n"
    "ds = jax.devices()\n"
    "print('JEPSEN_PROBE ' + json.dumps(sorted({d.platform for d in ds})"
    " + [len(ds)]))\n"
)


def _now() -> str:
    return _dt.datetime.now(_dt.timezone.utc).strftime(
        "%Y-%m-%dT%H:%M:%SZ")


def _emit(msg: str, out=None):
    print(f"{_now()} probe: {msg}", file=out or sys.stdout, flush=True)


def cpu_requested() -> bool:
    """Whether JAX_PLATFORMS asks for the CPU backend. Otherwise a
    process that finds only the CPU has lost its accelerator, and no
    probe calls that healthy."""
    return "cpu" in {p.strip().lower() for p in
                     os.environ.get("JAX_PLATFORMS", "").split(",")}


def _only_cpu(platforms) -> bool:
    return set(platforms) == {"cpu"} and not cpu_requested()


_ONLY_CPU = ("jax.devices() found only the CPU backend "
             "(set JAX_PLATFORMS=cpu to probe the CPU)")


def probe_once(timeout: float) -> dict:
    """One bounded ``jax.devices()`` child. Returns
    {"status": "healthy"|"hung"|"failed", ...}: healthy carries
    platforms/n_devices/secs, failed carries rc + a stderr tail."""
    t0 = time.monotonic()
    try:
        proc = subprocess.run([sys.executable, "-c", _CHILD_CODE],
                              capture_output=True, text=True,
                              timeout=timeout)
    except subprocess.TimeoutExpired:
        # subprocess.run kills the child on timeout (SIGKILL after
        # terminate) — the hang dies with it, as the runbook's manual
        # `kill -9` did
        return {"status": "hung", "secs": time.monotonic() - t0}
    secs = time.monotonic() - t0
    if proc.returncode == 0:
        for ln in proc.stdout.splitlines():
            if ln.startswith("JEPSEN_PROBE "):
                import json
                payload = json.loads(ln[len("JEPSEN_PROBE "):])
                if _only_cpu(payload[:-1]):
                    return {"status": "failed", "secs": secs, "rc": 0,
                            "err": _ONLY_CPU}
                return {"status": "healthy", "secs": secs,
                        "platforms": payload[:-1],
                        "n_devices": payload[-1]}
    return {"status": "failed", "secs": secs, "rc": proc.returncode,
            "err": (proc.stderr or proc.stdout).strip()[-300:]}


def run_probe(timeout: float = 100.0, retries: int = 3,
              interval: float = 0.0, out=None,
              record: Optional[list] = None) -> int:
    """The retry loop: probe until healthy or attempts run out,
    emitting one verdict line per attempt (the module docstring's format) and
    a final summary line. Returns the exit code. `record`, when a
    list, receives each attempt's raw result dict — the structured
    side of the verdict lines (probe_json builds on it)."""
    retries = max(1, retries)
    for attempt in range(1, retries + 1):
        r = probe_once(timeout)
        if record is not None:
            record.append(r)
        if r["status"] == "healthy":
            plats = r["platforms"]
            _emit(f"HEALTHY — jax.devices() -> {plats} in "
                  f"{r['secs']:.1f}s ({'/'.join(plats)} platform, "
                  f"{r['n_devices']} device(s))", out)
            return EXIT_HEALTHY
        if r["status"] == "hung":
            _emit(f"hung past {timeout:.0f}s "
                  f"(attempt {attempt}/{retries})", out)
        else:
            # a child that RAN and failed is not a wedge — retrying
            # cannot help (no plugin, no devices, import error), so
            # don't burn the operator's time on the remaining attempts
            _emit(f"NO BACKEND — jax.devices() failed rc={r['rc']} "
                  f"in {r['secs']:.1f}s ({r['err'].splitlines()[-1] if r['err'] else '?'})",
                  out)
            return EXIT_NO_BACKEND
        if attempt < retries and interval > 0:
            time.sleep(interval)
    _emit(f"WEDGED — all {retries} attempt(s) hung past "
          f"{timeout:.0f}s (a wedged device runtime; see "
          f"docs/observability.md)", out)
    return EXIT_WEDGED


_VERDICTS = {EXIT_HEALTHY: "healthy", EXIT_WEDGED: "wedged",
             EXIT_NO_BACKEND: "no-backend"}


def probe_json(timeout: float = 100.0, retries: int = 3,
               interval: float = 0.0, out=None) -> dict:
    """The probe loop as one machine-readable document — what
    ``jepsen probe --json`` prints. `out` receives the human verdict
    lines (default:
    discarded under --json's stdout-JSON contract; the CLI routes
    them to stderr).

    Schema: verdict (healthy|wedged|no-backend), exit (the 0/1/2
    runbook code), attempts (each raw probe_once result), elapsed_secs,
    timeout, retries; healthy additionally carries platforms and
    n_devices from the answering attempt."""
    import io
    t0 = time.monotonic()
    record: list = []
    code = run_probe(timeout=timeout, retries=retries,
                     interval=interval, out=out or io.StringIO(),
                     record=record)
    doc = {
        "verdict": _VERDICTS.get(code, "unknown"),
        "exit": code,
        "attempts": record,
        "elapsed_secs": round(time.monotonic() - t0, 3),
        "timeout": timeout,
        "retries": retries,
    }
    if code == EXIT_HEALTHY and record:
        doc["platforms"] = record[-1].get("platforms")
        doc["n_devices"] = record[-1].get("n_devices")
    return doc


def check_in_process(timeout: float = 30.0) -> dict:
    """One bounded health check of the backend THIS process holds: a
    tiny op, materialized, on a watchdog thread. Never starts a child.
    Returns {"verdict": "healthy"|"wedged"|"no-backend", ...} —
    healthy carries platforms and n_devices. A wedged runtime leaves
    its daemon thread behind (the supervisor's documented cost)."""
    from jepsen_tpu.resilience import supervisor as sup

    def tiny_op():
        import jax
        import jax.numpy as jnp
        devs = jax.devices()
        ok = int(jnp.asarray(41, jnp.int32) + 1) == 42
        return ok, sorted({d.platform for d in devs}), len(devs)

    t0 = time.monotonic()
    try:
        ok, plats, n = sup.run_watchdogged(tiny_op, timeout, "probe")
    except sup.DispatchWedged:
        return {"verdict": "wedged", "timeout": timeout,
                "elapsed_secs": round(time.monotonic() - t0, 3)}
    except Exception as err:  # noqa: BLE001 — an error is not health
        return {"verdict": "no-backend",
                "error": f"{type(err).__name__}: {err}",
                "elapsed_secs": round(time.monotonic() - t0, 3)}
    doc = {"elapsed_secs": round(time.monotonic() - t0, 3),
           "platforms": plats, "n_devices": n}
    if not ok:
        doc.update(verdict="no-backend", error="1 + 41 != 42")
    elif _only_cpu(plats):
        doc.update(verdict="no-backend", error=_ONLY_CPU)
    else:
        doc["verdict"] = "healthy"
    return doc


# ------------------------------------------------ continuous chip watch


class ProbeWatch:
    """The health check as a background service: re-run
    :func:`check_in_process` every ``interval`` seconds on a daemon
    thread and publish the
    verdict as live gauges —

        probe.chip_healthy       1 healthy / 0 wedged or no-backend
        probe.last_ok_age_secs   seconds since the last healthy verdict

    so ``/healthz`` reflects a device outage the moment the watch sees
    it, instead of at the next dispatch wedge. Off by
    default: armed via ``JEPSEN_TPU_PROBE_INTERVAL`` (seconds; 0/unset
    = no watch, no thread, no gauges) through
    :func:`start_watch_from_env`.

    Staleness contract for readiness: before the first tick completes
    the watch reports ok (a service must not fail readiness while the
    first 100s-timeout probe is still in flight); after that, ok means
    the last verdict was healthy AND its age is within ``max_stale``
    (default ``2*interval + timeout`` — a stuck watch loop is itself a
    health failure). The check runs in this process, on the backend it
    holds; a hang is bounded by the check's watchdog thread.
    """

    def __init__(self, interval: float, timeout: float = 30.0,
                 max_stale: Optional[float] = None,
                 probe: Optional[Callable[[], dict]] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.interval = float(interval)
        self.timeout = float(timeout)
        self.max_stale = (float(max_stale) if max_stale is not None
                          else 2.0 * self.interval + self.timeout)
        self._probe = probe if probe is not None else (
            lambda: check_in_process(self.timeout))
        self._clock = clock
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self.ticks = 0
        self.last: Optional[dict] = None
        self._last_ok: Optional[float] = None
        self._started = self._clock()

    # gauges live in obs (jax-free, same import contract as this
    # module); imported lazily so `jepsen probe` stays as light as the
    # pre-watch subcommand
    @staticmethod
    def _gauges():
        from jepsen_tpu import obs
        return obs.gauge("probe.chip_healthy"), \
            obs.gauge("probe.last_ok_age_secs")

    def _age(self, now: float) -> float:
        with self._lock:
            t0 = self._last_ok if self._last_ok is not None \
                else self._started
        return max(0.0, now - t0)

    def tick(self) -> dict:
        """One probe cycle (the loop body; callable directly in
        tests): run the probe, record, publish gauges. A probe that
        RAISES still counts as a completed
        tick with verdict ``probe-error`` — otherwise ``ticks`` would
        stay 0 and :meth:`status`'s first-probe-in-flight grace would
        report ok forever while chip health is completely unknown."""
        try:
            doc = self._probe()
        except Exception as err:  # noqa: BLE001 — a crashed probe is
            # not health; it must degrade readiness, not kill the loop
            _log.exception("probe watch tick failed")
            doc = {"verdict": "probe-error",
                   "error": f"{type(err).__name__}: {err}"}
        now = self._clock()
        healthy = doc.get("verdict") == "healthy"
        with self._lock:
            self.ticks += 1
            self.last = doc
            if healthy:
                self._last_ok = now
        g_h, g_age = self._gauges()
        g_h.set(1 if healthy else 0)
        g_age.set(round(self._age(now), 3))
        return doc

    def _run(self):
        while not self._stop.is_set():
            try:
                self.tick()
            except Exception:  # noqa: BLE001 — tick() already
                # absorbs probe failures as a probe-error verdict;
                # this guards the bookkeeping itself (gauge/registry
                # errors must not kill the watch loop)
                _log.exception("probe watch bookkeeping failed")
            if self._stop.wait(self.interval):
                return

    def start(self) -> "ProbeWatch":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, daemon=True,
                name="jepsen-probe-watch")
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            # an in-flight check may hold the loop for up to `timeout`
            # seconds; the thread is a daemon, so a bounded join
            # suffices
            self._thread.join(timeout=1.0)

    def status(self) -> dict:
        """The watch as a /healthz check entry (and the live refresh
        of the age gauge for /metrics scrapes)."""
        now = self._clock()
        age = self._age(now)
        with self._lock:
            ticks, last = self.ticks, self.last
        verdict = last.get("verdict") if last else None
        if ticks == 0:
            ok = True        # first probe still in flight: not a failure
        else:
            ok = verdict == "healthy" and age <= self.max_stale
        if ticks:
            _g_h, g_age = self._gauges()
            g_age.set(round(age, 3))
        return {"ok": ok, "verdict": verdict, "ticks": ticks,
                "last_ok_age_secs": round(age, 3),
                "interval": self.interval,
                "max_stale": self.max_stale}


def start_watch_from_env() -> Optional[ProbeWatch]:
    """Arm the continuous chip watch when
    ``JEPSEN_TPU_PROBE_INTERVAL`` names an interval (seconds; 0/unset
    = off — the default, so a bare serve carries no extra thread)."""
    from jepsen_tpu import envflags
    interval = envflags.env_float("JEPSEN_TPU_PROBE_INTERVAL",
                                  default=0.0, min_value=0.0,
                                  what="probe watch interval seconds")
    if not interval:
        return None
    return ProbeWatch(interval).start()


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="jepsen probe",
        description="bounded device-runtime health check: subprocess "
                    "jax.devices() with timeout + retry; exit 0 "
                    "healthy / 1 wedged / 2 no-backend")
    p.add_argument("--timeout", type=float, default=100.0,
                   help="seconds before one attempt counts as hung "
                        "(default: 100)")
    p.add_argument("--retries", type=int, default=3,
                   help="attempts before the WEDGED verdict")
    p.add_argument("--interval", type=float, default=0.0,
                   help="seconds between attempts")
    p.add_argument("--json", action="store_true",
                   help="emit one machine-readable JSON document on "
                        "stdout (verdict lines go to stderr); exit "
                        "code unchanged")
    try:
        args = p.parse_args(list(argv) if argv is not None else None)
    except SystemExit as e:
        # argparse exits 2 on usage errors, which collides with the
        # no-backend code — keep --help at 0 and map misuse to the
        # CLI's bad-args convention via a distinct code
        return 0 if e.code in (0, None) else 254
    if args.json:
        # verdict lines keep flowing (stderr) so an operator tailing
        # the run still sees the runbook format; stdout is exactly one
        # JSON document for automation
        import json
        doc = probe_json(timeout=args.timeout, retries=args.retries,
                         interval=args.interval, out=sys.stderr)
        print(json.dumps(doc))
        return doc["exit"]
    return run_probe(timeout=args.timeout, retries=args.retries,
                     interval=args.interval)


if __name__ == "__main__":
    sys.exit(main())
