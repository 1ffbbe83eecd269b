"""Tests for `jepsen probe` (jepsen_tpu.probe) — the device-health
loop as a first-class subcommand, and the in-process check the
breaker and the probe watch run.

The wedge and no-backend paths are driven by swapping the child code
(the same seam the runbook's real failures exercised: a child that
never answers vs a child that errors), so no TPU — and no actual
100-second wait — is needed. The healthy path runs the REAL child
pinned to the CPU backend."""

import io
import re
from unittest import mock

import pytest

from jepsen_tpu import probe

# one verdict line per attempt (the probe module docstring's format):
# utc timestamp, "probe:", verdict text
_LINE = re.compile(r"^\d{4}-\d{2}-\d{2}T\d{2}:\d{2}:\d{2}Z probe: ")


def _run(out, **kw):
    buf = io.StringIO()
    rc = probe.run_probe(out=buf, **kw)
    lines = buf.getvalue().splitlines()
    assert lines and all(_LINE.match(ln) for ln in lines), lines
    out.extend(lines)
    return rc


def test_probe_healthy_on_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    lines = []
    rc = _run(lines, timeout=120.0, retries=1)
    assert rc == probe.EXIT_HEALTHY == 0
    assert "HEALTHY" in lines[-1] and "jax.devices()" in lines[-1]
    assert "cpu" in lines[-1]


def test_probe_wedged_exhausts_retries_and_exits_1():
    with mock.patch.object(probe, "_CHILD_CODE",
                           "import time; time.sleep(3600)"):
        lines = []
        rc = _run(lines, timeout=0.8, retries=2)
    assert rc == probe.EXIT_WEDGED == 1
    hung = [ln for ln in lines if "(attempt " in ln]
    assert len(hung) == 2                       # one line per attempt
    assert "attempt 1/2" in hung[0] and "attempt 2/2" in hung[1]
    assert "WEDGED" in lines[-1]


def test_probe_no_backend_fails_fast_without_retries():
    """A child that RAN and failed is a different failure class:
    retrying cannot help, so the loop must stop after one attempt."""
    with mock.patch.object(probe, "_CHILD_CODE",
                           "raise RuntimeError('no plugin')") as _, \
            mock.patch.object(probe, "probe_once",
                              wraps=probe.probe_once) as spy:
        lines = []
        rc = _run(lines, timeout=30.0, retries=3)
    assert rc == probe.EXIT_NO_BACKEND == 2
    assert spy.call_count == 1
    assert "NO BACKEND" in lines[-1]
    assert "no plugin" in lines[-1]


def test_probe_recovers_mid_loop():
    """hung-then-healthy (the r05 03:46Z recovery): the loop keeps
    probing and the final verdict is HEALTHY / 0."""
    calls = {"n": 0}
    real = probe.probe_once

    def flaky(timeout):
        calls["n"] += 1
        if calls["n"] == 1:
            return {"status": "hung", "secs": timeout}
        return {"status": "healthy", "secs": 1.2,
                "platforms": ["tpu"], "n_devices": 4}

    with mock.patch.object(probe, "probe_once", flaky):
        lines = []
        rc = _run(lines, timeout=5.0, retries=3)
    assert rc == 0
    assert "hung past" in lines[0] and "HEALTHY" in lines[-1]
    assert "4 device(s)" in lines[-1]
    assert real is probe.probe_once is not flaky or True


def test_probe_cli_dispatch(monkeypatch):
    """`jepsen probe ...` forwards pre-parse like lint, honoring the
    probe module's own flags and exit contract."""
    from jepsen_tpu import cli

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert cli.main(["probe", "--timeout", "120", "--retries", "1"]) == 0
    # usage errors map to the CLI's bad-args convention, not exit 2
    # (which means no-backend here)
    assert probe.main(["--not-a-flag"]) == 254


def test_probe_json_healthy_schema(monkeypatch):
    """probe_json is the machine-readable side of the verdict lines —
    the SAME contract the circuit breaker's half-open recovery check
    consumes (resilience.breaker), pinned here."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    doc = probe.probe_json(timeout=120.0, retries=1)
    assert doc["verdict"] == "healthy" and doc["exit"] == 0
    assert doc["retries"] == 1 and doc["timeout"] == 120.0
    assert doc["elapsed_secs"] >= 0
    assert doc["attempts"][-1]["status"] == "healthy"
    assert "cpu" in doc["platforms"] and doc["n_devices"] >= 1


def test_probe_json_wedged_and_no_backend():
    with mock.patch.object(probe, "_CHILD_CODE",
                           "import time; time.sleep(3600)"):
        doc = probe.probe_json(timeout=0.5, retries=2)
    assert (doc["verdict"], doc["exit"]) == ("wedged", 1)
    assert [a["status"] for a in doc["attempts"]] == ["hung", "hung"]
    with mock.patch.object(probe, "_CHILD_CODE",
                           "raise RuntimeError('no plugin')"):
        doc = probe.probe_json(timeout=30.0, retries=3)
    assert (doc["verdict"], doc["exit"]) == ("no-backend", 2)
    assert len(doc["attempts"]) == 1      # fail-fast, no retries


def test_probe_json_cli(monkeypatch, capsys):
    """`jepsen probe --json`: exactly one JSON document on stdout,
    verdict lines on stderr, exit code unchanged."""
    import json

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = probe.main(["--json", "--timeout", "120", "--retries", "1"])
    cap = capsys.readouterr()
    doc = json.loads(cap.out)
    assert rc == doc["exit"] == 0 and doc["verdict"] == "healthy"
    # the runbook's verdict-line format still flows, on stderr
    assert any(_LINE.match(ln) for ln in cap.err.splitlines())


@pytest.mark.parametrize("argv,expect", [
    (["--timeout", "7.5", "--retries", "2", "--interval", "1"],
     (7.5, 2, 1.0)),
    ([], (100.0, 3, 0.0)),
])
def test_probe_flag_parsing(argv, expect, monkeypatch):
    seen = {}

    def fake(timeout, retries, interval):
        seen.update(timeout=timeout, retries=retries, interval=interval)
        return 0

    monkeypatch.setattr(probe, "run_probe", fake)
    assert probe.main(argv) == 0
    assert (seen["timeout"], seen["retries"], seen["interval"]) == expect


def test_probe_cpu_only_is_not_healthy(monkeypatch):
    """A child that finds only the CPU backend is no-backend unless
    JAX_PLATFORMS asked for the CPU: a TPU host whose chip is gone
    must not read healthy. (The child is stubbed: it reports what
    jax.devices() would, without starting a backend.)"""
    cpu_only = """print('JEPSEN_PROBE ["cpu", 8]')"""
    with mock.patch.object(probe, "_CHILD_CODE", cpu_only):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        doc = probe.probe_json(timeout=30.0, retries=3)
        assert (doc["verdict"], doc["exit"]) == ("no-backend", 2)
        assert "only the CPU" in doc["attempts"][-1]["err"]
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        doc = probe.probe_json(timeout=30.0, retries=1)
        assert doc["verdict"] == "healthy" and doc["n_devices"] == 8


def test_check_in_process_verdicts(monkeypatch):
    """The in-process check answers on the backend this process holds
    (the suite's CPU), with the same CPU rule as the subprocess
    probe."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    doc = probe.check_in_process(60.0)
    assert doc["verdict"] == "healthy", doc
    assert doc["platforms"] == ["cpu"] and doc["n_devices"] >= 1
    monkeypatch.delenv("JAX_PLATFORMS")
    doc = probe.check_in_process(60.0)
    assert doc["verdict"] == "no-backend" and "only the CPU" in doc["error"]


def test_check_in_process_wedge_is_bounded(monkeypatch):
    """A runtime that never answers is a bounded `wedged` verdict: the
    watchdog thread takes the hang, not the caller."""
    import threading
    import time

    import jax

    release = threading.Event()
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: release.wait(30))
    t0 = time.monotonic()
    try:
        doc = probe.check_in_process(0.2)
    finally:
        release.set()
    assert doc["verdict"] == "wedged", doc
    assert time.monotonic() - t0 < 5.0


def test_breaker_and_watch_checks_start_no_child(monkeypatch):
    """The breaker's half-open probe and the probe watch run in this
    process: a chip belongs to one process at a time, so a child could
    never open the device this process holds."""
    import subprocess

    from jepsen_tpu.resilience import breaker

    def no_child(*a, **k):
        raise AssertionError("started a child process")

    monkeypatch.setattr(subprocess, "run", no_child)
    monkeypatch.setattr(subprocess, "Popen", no_child)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert breaker._default_probe() is True
    assert probe.ProbeWatch(interval=60.0).tick()["verdict"] == "healthy"
