"""Tests for the `jepsen-tpu lint` static-analysis pass
(jepsen_tpu.analysis).

Three layers:
  * fixture files with known violations per rule family, asserting
    exact file:line anchors (tests/data/lint_fixtures/ — parsed, never
    imported);
  * the suppression contract: comments are honored, still REPORTED
    (marked suppressed), and must carry a known rule name;
  * the repo-wide gate: `python -m jepsen_tpu.analysis --check` exits
    0 on this tree (every finding fixed or suppressed-with-rule) —
    the tier-1 entry for the lint pass.

The pass is pure-AST: no JAX import, no device init — the subprocess
test below pins that too (it must be fast even where a device runtime
would hang).
"""

import json
import os
import subprocess
import sys

from jepsen_tpu import analysis
from jepsen_tpu.analysis import core as lint_core

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "data", "lint_fixtures")


def _lint(name):
    return analysis.lint_file(os.path.join(FIXTURES, name), REPO)


def _anchors(findings, rule):
    return sorted((f.line, f.suppressed) for f in findings
                  if f.rule == rule)


# ------------------------------------------------------------- purity


def test_purity_fixture_findings_with_anchors():
    fs = _lint("purity_viol.py")
    assert all(not f.suppressed for f in fs)
    host = [f.line for f in fs if f.rule == "purity-host-call"]
    # time.time in a reachable helper; random/os.environ in the root;
    # open/print inside a lax.scan body
    assert host == sorted(host)
    assert set(host) == {18, 25, 26, 38, 40}
    assert [f.line for f in fs if f.rule == "purity-numpy-call"] == [27]
    assert [f.line for f in fs
            if f.rule == "purity-tracer-branch"] == [28, 30, 32]
    # host-side code after the roots is untouched
    assert not any(f.line > 45 for f in fs)
    # file:line anchors are repo-relative and clickable
    assert all(f.path == "tests/data/lint_fixtures/purity_viol.py"
               for f in fs)


def test_obs_in_trace_fixture_findings_with_anchors():
    """The telemetry-purity rule: obs.span()/registry calls inside
    traced code flag (module-attribute, bare-import, and nested-scan
    forms), carry the usual suppression escape, and never fire on the
    host-side instrumentation pattern."""
    fs = _lint("obs_viol.py")
    assert _anchors(fs, "purity-obs-in-trace") == [
        (15, False), (21, False), (23, False), (25, False),
        (31, False), (40, True)]
    # the host-side span/counter block at the bottom stays clean
    assert not any(f.line > 45 and f.rule == "purity-obs-in-trace"
                   for f in fs)


def test_obs_in_trace_repo_sweep_green():
    """The instrumented engine files carry obs calls on the HOST side
    only — the new rule must not fire on the production tree (that is
    the PR's own acceptance: instrumentation never leaked into a
    trace)."""
    for rel in ("jepsen_tpu/parallel/engine.py",
                "jepsen_tpu/parallel/bitdense.py",
                "jepsen_tpu/parallel/sharded.py",
                "jepsen_tpu/parallel/pipeline.py"):
        fs = analysis.lint_file(os.path.join(REPO, rel), REPO)
        bad = [f for f in fs if f.rule == "purity-obs-in-trace"
               and not f.suppressed]
        assert bad == [], "\n".join(f.format() for f in bad)


# ---------------------------------------------------------- recompile


def test_recompile_fixture_findings_with_anchors():
    fs = _lint("recompile_viol.py")
    assert _anchors(fs, "recompile-closure-capture") == [(14, False),
                                                         (22, False)]
    assert _anchors(fs, "recompile-nonliteral-static-args") == \
        [(25, False)]


def test_donate_rule_satisfied_by_explicit_decisions():
    """The donate rule is scoped to the frontier-buffer engines. The
    in-tree jits now all DECIDE donation explicitly — donate_argnames
    on the resumable frontier carries, donate_argnums=() recording
    the nothing-donatable cases — so the rule documents decisions
    instead of being suppressed: zero findings, zero suppressions."""
    for rel in ("jepsen_tpu/parallel/bitdense.py",
                "jepsen_tpu/parallel/engine.py",
                "jepsen_tpu/parallel/dense.py",
                "jepsen_tpu/parallel/sharded.py"):
        fs = analysis.lint_file(os.path.join(REPO, rel), REPO)
        donate = [f for f in fs if f.rule == "recompile-donate-argnums"]
        assert donate == [], (rel, donate)


def test_donate_rule_still_fires_on_undecided_jit(tmp_path):
    """The rule itself stays live: a frontier-engine jit with NO
    donate kwarg (the undecided state this PR eliminated in-tree)
    must still flag."""
    d = tmp_path / "jepsen_tpu" / "parallel"
    d.mkdir(parents=True)
    f = d / "engine.py"
    f.write_text(
        "import jax\n\n\n"
        "def _impl(xs):\n    return xs\n\n\n"
        "_check = jax.jit(_impl, static_argnames=())\n")
    fs = analysis.lint_file(str(f), str(tmp_path))
    donate = [x for x in fs if x.rule == "recompile-donate-argnums"]
    assert donate and not donate[0].suppressed, fs


# -------------------------------------------------------- concurrency


def test_concurrency_fixture_findings_with_anchors():
    fs = _lint("concurrency_viol.py")
    races = _anchors(fs, "concurrency-unlocked-shared-write")
    # unlocked closure write, unlocked global, and an unlocked global
    # write in a BOUND-METHOD thread target (the membership-nemesis
    # shape); the locked variant and main-thread writes stay clean
    assert races == [(17, False), (41, False), (71, False)]


def test_unsupervised_dispatch_fixture_findings_with_anchors():
    """Device-dispatch entry calls outside a supervisor.dispatch thunk
    flag; thunks (lambda, named, via a reachable helper) and the
    rule-named suppression stay clean."""
    fs = _lint("dispatch_viol.py")
    hits = _anchors(fs, "concurrency-unsupervised-dispatch")
    assert hits == [(20, False), (26, False), (52, True)]


def test_env_hygiene_catches_reintroduced_pallas_read():
    """The acceptance regression: a raw JEPSEN_TPU_PALLAS read (what
    bitdense did before the accessor) must be caught with a correct
    anchor; foreign-namespace env reads stay clean."""
    fs = _lint("concurrency_viol.py")
    env = [f for f in fs if f.rule == "env-flag-accessor"]
    assert [(f.line, f.suppressed) for f in env] == \
        [(49, False), (50, False), (51, False)]
    assert "JEPSEN_TPU_PALLAS" in env[0].message
    assert "envflags" in env[0].message
    assert not any("NOT_OURS" in f.message for f in fs)


def test_env_hygiene_allows_the_accessor_module():
    fs = analysis.lint_file(
        os.path.join(REPO, "jepsen_tpu", "envflags.py"), REPO)
    assert not [f for f in fs if f.rule == "env-flag-accessor"]


# ------------------------------------------------------- suppressions


def test_suppressions_honored_and_reported():
    fs = _lint("suppressed_ok.py")
    sup = [f for f in fs if f.suppressed]
    act = [f for f in fs if not f.suppressed]
    # line-level, statement-level (comment above), def-line, and
    # file-level suppressions all honored — and all still REPORTED
    assert {(f.rule, f.line) for f in sup} == {
        ("purity-numpy-call", 15),
        ("purity-host-call", 17),
        ("purity-tracer-branch", 18),
        ("purity-numpy-call", 26),
        ("purity-numpy-call", 27),
        # own-line comment above a DECORATED def (lands on the
        # decorator line) still covers the body
        ("purity-host-call", 48),
        # blank/comment lines between directive and statement don't
        # void the suppression
        ("purity-numpy-call", 69),
    }
    # a bare disable and an unknown rule are findings themselves, and
    # the violations they failed to name stay active; line 59 is the
    # decorated `# jepsen-lint: device` pragma registering its root
    assert _anchors(act, "bad-suppression") == [(33, False), (39, False)]
    assert _anchors(act, "purity-host-call") == [(33, False), (39, False),
                                                 (59, False)]


def test_every_rule_name_documented():
    for rule in lint_core.RULES:
        assert lint_core.RULES[rule], rule


# ------------------------------------------------- repo gate + CLI


def test_repo_lint_is_clean():
    """Zero unsuppressed findings over the production tree, and every
    suppression carries a rule name (bad-suppression is itself a
    finding, so one assert covers both)."""
    findings = analysis.run_lint(root=REPO)
    active = [f for f in findings if not f.suppressed]
    assert active == [], "\n".join(f.format() for f in active)
    # the sweep left a real suppression inventory (donate decisions,
    # trace-constant numpy) — if this drops to zero the rules broke
    assert len(findings) > 10


def test_check_gate_subprocess_no_jax():
    """The tier-1 entry: `python -m jepsen_tpu.analysis --check` exits
    0 on this repo WITHOUT importing jax (pure AST; must stay safe
    under a wedged device runtime)."""
    probe = ("import sys, runpy; sys.argv=['jepsen_tpu.analysis',"
             "'--check']\n"
             "try:\n"
             "    runpy.run_module('jepsen_tpu.analysis',"
             " run_name='__main__')\n"
             "except SystemExit as e:\n"
             "    assert e.code == 0, e.code\n"
             "assert 'jax' not in sys.modules, 'lint imported jax'\n"
             "print('LINT-GATE-OK')\n")
    proc = subprocess.run([sys.executable, "-c", probe],
                          capture_output=True, text=True, timeout=120,
                          cwd=REPO)
    assert proc.returncode == 0, proc.stderr
    assert "LINT-GATE-OK" in proc.stdout


def test_cli_exit_code_contract_and_json():
    """0 clean / 1 findings, both via the library main and the
    `jepsen lint` subcommand; --json emits the stable report shape."""
    import contextlib
    import io

    dirty = os.path.join(FIXTURES, "purity_viol.py")
    clean = os.path.join(REPO, "jepsen_tpu", "envflags.py")
    with contextlib.redirect_stdout(io.StringIO()):
        assert analysis.main([dirty]) == 1
        assert analysis.main([clean]) == 0
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert analysis.main([dirty, "--json"]) == 1
    report = json.loads(buf.getvalue())
    assert report["clean"] is False
    assert report["counts"]["active"] == report["counts"]["total"]
    assert set(report["by_rule"]) == {"purity-host-call",
                                      "purity-numpy-call",
                                      "purity-tracer-branch"}

    from jepsen_tpu import cli
    assert cli.main(["lint", dirty]) == 1
    assert cli.main(["lint", clean]) == 0


def test_usage_errors_exit_2_not_1():
    """A typo'd path or unparseable file is a USAGE error (2) — CI
    must not misread it as 'lint found issues' (1)."""
    import contextlib
    import io

    with contextlib.redirect_stderr(io.StringIO()) as err:
        assert analysis.main(["definitely/not/a/file.py"]) == 2
    assert "lint:" in err.getvalue()

    from jepsen_tpu import cli
    with contextlib.redirect_stderr(io.StringIO()):
        assert cli.main(["lint", "definitely/not/a/file.py"]) == 2


def test_undecodable_target_is_a_usage_error(tmp_path):
    """Non-UTF8 bytes in a target file are a usage error (2), not a
    lint verdict (1)."""
    import contextlib
    import io

    bad = tmp_path / "bad_enc.py"
    bad.write_bytes(b'x = "caf\xe9"\n')
    with contextlib.redirect_stderr(io.StringIO()) as err:
        assert analysis.main([str(bad)]) == 2
    assert "lint:" in err.getvalue()


def test_json_stdout_stays_machine_parseable_with_save_store(tmp_path,
                                                             monkeypatch):
    """--json --save-store: stdout is EXACTLY the JSON document; the
    save notice goes to stderr."""
    import contextlib
    import io

    monkeypatch.chdir(tmp_path)   # Store writes ./store relative cwd
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = analysis.main([os.path.join(FIXTURES, "purity_viol.py"),
                            "--json", "--save-store"])
    assert rc == 1
    report = json.loads(out.getvalue())   # whole stdout parses
    assert report["clean"] is False
    assert "report saved under" in err.getvalue()


# ------------------------------------------------- envflags accessor


def test_envflags_bool_strict_tristate(monkeypatch):
    from jepsen_tpu import envflags

    monkeypatch.delenv("JEPSEN_TPU_PALLAS", raising=False)
    assert envflags.env_bool("JEPSEN_TPU_PALLAS") is None
    assert envflags.env_bool("JEPSEN_TPU_PALLAS", default=True) is True
    monkeypatch.setenv("JEPSEN_TPU_PALLAS", "1")
    assert envflags.env_bool("JEPSEN_TPU_PALLAS") is True
    monkeypatch.setenv("JEPSEN_TPU_PALLAS", "0")
    assert envflags.env_bool("JEPSEN_TPU_PALLAS") is False
    # anything else raises instead of silently counting as opt-out
    for bad in ("yes", "2", "true", ""):
        monkeypatch.setenv("JEPSEN_TPU_PALLAS", bad)
        try:
            envflags.env_bool("JEPSEN_TPU_PALLAS")
            raise AssertionError(f"{bad!r} did not raise")
        except envflags.EnvFlagError as e:
            assert "JEPSEN_TPU_PALLAS" in str(e)


def test_envflags_choice_and_namespace_guard(monkeypatch):
    from jepsen_tpu import envflags

    monkeypatch.delenv("JEPSEN_TPU_BUCKET", raising=False)
    assert envflags.env_choice("JEPSEN_TPU_BUCKET", ("tier", "exact"),
                               default="tier") == "tier"
    monkeypatch.setenv("JEPSEN_TPU_BUCKET", "exact")
    assert envflags.env_choice("JEPSEN_TPU_BUCKET",
                               ("tier", "exact")) == "exact"
    monkeypatch.setenv("JEPSEN_TPU_BUCKET", "bogus")
    try:
        envflags.env_choice("JEPSEN_TPU_BUCKET", ("tier", "exact"),
                            what="bucket strategy")
        raise AssertionError("bogus did not raise")
    except envflags.EnvFlagError as e:
        assert "bucket strategy" in str(e)
    # EnvFlagError is a ValueError: existing pytest.raises(ValueError)
    # call sites keep working
    assert issubclass(envflags.EnvFlagError, ValueError)
    # the accessor refuses foreign namespaces
    try:
        envflags.env_raw("HOME")
        raise AssertionError("foreign namespace did not raise")
    except envflags.EnvFlagError:
        pass


def test_envflags_int_accessor(monkeypatch):
    from jepsen_tpu import envflags

    monkeypatch.delenv("JEPSEN_TPU_ENCODE_CACHE", raising=False)
    assert envflags.env_int("JEPSEN_TPU_ENCODE_CACHE") is None
    assert envflags.env_int("JEPSEN_TPU_ENCODE_CACHE",
                            default=256) == 256
    monkeypatch.setenv("JEPSEN_TPU_ENCODE_CACHE", "0")
    assert envflags.env_int("JEPSEN_TPU_ENCODE_CACHE",
                            default=256, min_value=0) == 0
    monkeypatch.setenv("JEPSEN_TPU_ENCODE_CACHE", "1024")
    assert envflags.env_int("JEPSEN_TPU_ENCODE_CACHE") == 1024
    # malformed or below-floor values fail loudly, never silently
    # revert to the default (the envflags contract)
    for bad in ("many", "1.5", ""):
        monkeypatch.setenv("JEPSEN_TPU_ENCODE_CACHE", bad)
        try:
            envflags.env_int("JEPSEN_TPU_ENCODE_CACHE")
            raise AssertionError(f"{bad!r} did not raise")
        except envflags.EnvFlagError as e:
            assert "JEPSEN_TPU_ENCODE_CACHE" in str(e)
    monkeypatch.setenv("JEPSEN_TPU_ENCODE_CACHE", "-3")
    try:
        envflags.env_int("JEPSEN_TPU_ENCODE_CACHE", min_value=0)
        raise AssertionError("below-floor did not raise")
    except envflags.EnvFlagError as e:
        assert ">= 0" in str(e)


def test_resolve_use_pallas_rejects_malformed_flag(monkeypatch):
    """The satellite regression: JEPSEN_TPU_PALLAS outside {'0','1'}
    must raise at resolve time, not silently disable the measured
    pallas default."""
    import pytest

    from jepsen_tpu import envflags
    from jepsen_tpu.parallel import bitdense

    monkeypatch.setenv("JEPSEN_TPU_PALLAS", "yes")
    with pytest.raises(envflags.EnvFlagError, match="JEPSEN_TPU_PALLAS"):
        bitdense._resolve_use_pallas(None, 17, 12, "tpu")
    # an explicit argument bypasses the env read entirely
    assert bitdense._resolve_use_pallas(False, 17, 12, "tpu") \
        == (False, False)


def test_lint_report_saves_into_store_run_dir(tmp_path):
    """JSON + human reports ride the store.py run-dir lifecycle."""
    from jepsen_tpu import store as jstore

    findings = analysis.lint_file(
        os.path.join(FIXTURES, "suppressed_ok.py"), REPO)
    st = jstore.Store("lint-test", base_dir=str(tmp_path))
    d = analysis.save_to_store(findings, st)
    data = json.loads(open(os.path.join(d, "lint.json")).read())
    assert data["counts"]["total"] == len(findings)
    txt = open(os.path.join(d, "lint.txt")).read()
    assert "[suppressed]" in txt and "bad-suppression" in txt


# ------------------------------------------- lock discipline (PR 16)


def test_lock_order_cycle_fixture():
    fs = _lint("locks_viol.py")
    assert _anchors(fs, "concurrency-lock-order") == [(29, False)]
    msg = [f.message for f in fs
           if f.rule == "concurrency-lock-order"][0]
    assert "Cycle._a" in msg and "Cycle._b" in msg


def test_blocking_under_lock_fixture_and_pr8_regression():
    fs = _lint("locks_viol.py")
    assert _anchors(fs, "concurrency-blocking-under-lock") == [
        (49, False), (54, False), (55, False), (56, False),
        (61, False), (68, False)]
    by_line = {f.line: f.message for f in fs
               if f.rule == "concurrency-blocking-under-lock"}
    # the PR-8 regression shape: a flight dump (file I/O) inside the
    # service condition
    assert "flight_dump" in by_line[49]
    assert "Dumper._cond" in by_line[49]
    # the one-level self.method() inlining names the calling context
    assert "inlined from `Dumper.outer`" in by_line[68]
    # wait() on the condition the function HOLDS (line 50) is the
    # sanctioned idiom — wait releases it
    assert 50 not in by_line


def test_unguarded_field_fixture_pr11_regression():
    """The PR-11 shape: a worker-thread write to a field every other
    writer touches under the lock."""
    fs = _lint("locks_viol.py")
    assert _anchors(fs, "concurrency-unguarded-field") == [(96, False)]
    msg = [f.message for f in fs
           if f.rule == "concurrency-unguarded-field"][0]
    assert "9/10" in msg and "read-modify-write" in msg
    assert "Tally._lock" in msg


def test_lock_rules_silent_on_clean_twin():
    """Consistent order, I/O outside locks, wait-on-held-cond, fully
    guarded field, explicit acquire/release: zero findings of ANY
    rule."""
    assert _lint("locks_ok.py") == []


def test_cross_module_pair_cycle():
    from jepsen_tpu.analysis import locks
    sa = lint_core.SourceFile(
        os.path.join(FIXTURES, "pair_svc.py"), REPO)
    sb = lint_core.SourceFile(
        os.path.join(FIXTURES, "pair_wal.py"), REPO)
    fs = locks.pair_findings(sa, sb, r"wal", r"svc")
    assert len(fs) == 1 and fs[0].rule == "concurrency-lock-order"
    assert "closes across" in fs[0].message
    assert "Service._lock" in fs[0].message
    assert "Wal._mu" in fs[0].message
    # each side alone is clean — the cycle exists only in the pair
    # graph, which is exactly why the sweep runs the pair pass
    assert _anchors(_lint("pair_svc.py"),
                    "concurrency-lock-order") == []
    assert _anchors(_lint("pair_wal.py"),
                    "concurrency-lock-order") == []


def test_stale_suppression_fixture():
    fs = _lint("stale_viol.py")
    # the dead directive is a finding anchored at ITS OWN line, and
    # it is not suppressible
    assert _anchors(fs, "lint-stale-suppression") == [(16, False)]
    # the used directive is NOT stale — its finding stays reported,
    # marked suppressed
    assert _anchors(fs, "env-flag-accessor") == [(12, True)]


def test_repo_suppression_inventory_is_live():
    """The audited WAL suppressions are real: the repo sweep carries
    SUPPRESSED blocking-under-lock findings (fsync under the per-key
    handoff lock), and zero stale directives anywhere."""
    findings = analysis.run_lint(root=REPO)
    assert any(f.rule == "concurrency-blocking-under-lock"
               and f.suppressed and f.path.endswith("serve/wal.py")
               for f in findings)
    assert not any(f.rule == "lint-stale-suppression"
                   for f in findings)


# ------------------------------------------------------- drift gates


def test_flag_drift_fixture():
    from jepsen_tpu.analysis import drift
    root = os.path.join(FIXTURES, "driftrepo")
    fs = drift.flag_findings(root, "envflags.py", ("docs/flags.md",))
    assert sorted((f.path, f.line) for f in fs) == [
        ("docs/flags.md", 6), ("envflags.py", 8)]
    msgs = " ".join(f.message for f in fs)
    assert "JEPSEN_TPU_BETA" in msgs and "JEPSEN_TPU_GAMMA" in msgs
    # the clean flag never shows up
    assert "JEPSEN_TPU_ALPHA" not in msgs


def test_metric_drift_fixture():
    from jepsen_tpu.analysis import drift
    root = os.path.join(FIXTURES, "driftrepo")
    fs = drift.metric_findings(root,
                               [os.path.join(root, "mints.py")],
                               "docs/obs.md")
    assert sorted((f.path, f.line) for f in fs) == [
        ("docs/obs.md", 12), ("mints.py", 13)]
    msgs = " ".join(f.message for f in fs)
    assert "app.orphan" in msgs and "app.ghost" in msgs
    # shorthand/label/wildcard rows all matched their mints: the
    # leading-dot pair, the [tenant=<t>] base, the f-string pattern
    for clean in ("app.hits", "app.misses", "app.depth",
                  "app.latency", "app.dyn"):
        assert clean not in msgs


def test_drift_gates_pass_against_live_docs():
    """The acceptance pin: drift found during the PR was FIXED in the
    docs, not suppressed — both gates are empty on the live tree."""
    from jepsen_tpu.analysis import drift
    assert drift.flag_findings(REPO) == []
    assert drift.metric_findings(
        REPO, lint_core.default_targets(REPO)) == []


def test_drift_gates_skipped_for_explicit_paths():
    """Linting one file never drags in the repo-wide doc checks."""
    fs = analysis.run_lint([os.path.join(FIXTURES, "locks_ok.py")],
                           root=REPO)
    assert fs == []


# ----------------------------------------------------- --changed mode


def test_changed_mode_contract():
    import contextlib
    import io
    buf, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(buf), \
            contextlib.redirect_stderr(err):
        # a bad base ref and mixing --changed with explicit paths are
        # USAGE errors (2), never lint verdicts
        assert analysis.main(["--changed", "no-such-ref-xyz"]) == 2
        assert analysis.main(["jepsen_tpu", "--changed"]) == 2
        # the fast path itself: a clean tree (or clean changed files)
        # exits 0, same contract as the full gate
        assert analysis.main(["--changed"]) == 0


def test_changed_files_shape():
    files = analysis.changed_files(root=REPO)
    assert isinstance(files, list)
    for p in files:
        assert p.endswith(".py") and os.path.isfile(p)
        rel = os.path.relpath(p, REPO)
        top = rel.split(os.sep, 1)[0]
        assert top in ("jepsen_tpu", "tools") \
            or rel in ("bench.py", "__graft_entry__.py")
