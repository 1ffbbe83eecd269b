"""CPU rehearsal of chip_smoke.py: every phase at smoke size.

The phases run exactly as on the chip, with the pallas kernel forced on
(interpret mode here, JEPSEN_TPU_PALLAS=1) so the `closure == "pallas"`
checks hold on the CPU too. The platform check is steered here, in the
test: the script itself has no CPU mode.
"""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def pallas_on(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_PALLAS", "1")


def test_multikey_phase(pallas_on, capsys):
    r = chip_smoke.phase_multikey(n_keys=8, ops_per_key=40,
                                  sampled=(0, 3))
    assert r["failures"] == [7]
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["phase"] == "multikey" and line["keys"] == 8
    assert line["first_secs"] > 0 and line["warm_secs"] > 0


def test_multikey_corrupted_key_is_the_only_failure():
    """The generator's corrupted key is invalid by construction (a read
    of a value no write produced) and every other key is valid — the
    host packed engine agrees, so the phase's assertions are about the
    device, not the data."""
    from jepsen_tpu.checker import linear_packed
    from jepsen_tpu.models import CASRegister
    per_key, keyed, bad = chip_smoke.multikey_histories(6, 40)
    assert bad == 5 and len(keyed) == sum(len(h) for h in per_key.values())
    verdicts = [linear_packed.analysis(CASRegister(), per_key[k])["valid?"]
                for k in range(6)]
    assert verdicts == [True] * 5 + [False]


def test_adversarial_phase(pallas_on, capsys):
    # k=11: the kernel's support floor is C=12 slots (one lane tile);
    # 120 ops keep the interpret-mode kernel inside the suite's budget
    out = chip_smoke.phase_adversarial(n_ops=120, k=11)
    assert out["valid"]["valid?"] is True
    assert out["invalid"]["valid?"] is False
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["phase"] == "adversarial" and line["crashed"] == 11


def test_served_phase(capsys):
    finals = chip_smoke.phase_served(n_keys=2, n_ops=24, deltas=3,
                                     capacity=256)
    assert finals["k0"]["valid?"] is True
    assert finals["k1"]["valid?"] is False
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["phase"] == "served" and line["deltas"] == 3


def test_four_chip_phase_on_virtual_devices(pallas_on, capsys):
    """The --chips 4 path on four of the suite's eight virtual CPU
    devices: the key-sharded pallas batch (shard_map, one kernel per
    device) and the frontier-sharded engine, each against device 0."""
    import jax
    out = chip_smoke.phase_four_chips(jax.devices()[:4], n_keys=8,
                                      ops_per_key=40, adv_ops=60,
                                      adv_k=4)
    assert len(out["batch"]) == 8
    assert out["sharded"]["devices"] == 4
    phases = [json.loads(ln)["phase"]
              for ln in capsys.readouterr().out.splitlines()]
    assert phases == ["batch-4chip", "sharded-4chip"]


def test_require_tpu_refuses_the_cpu(capsys):
    with pytest.raises(SystemExit, match="no TPU found"):
        chip_smoke.require_tpu(1)
    assert capsys.readouterr().out == ""


def test_script_on_cpu_exits_nonzero_without_a_result():
    """Run as the driver runs it, on a machine without a TPU: non-zero,
    a message saying so, and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                       capture_output=True, text=True, env=env,
                       timeout=300)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout
