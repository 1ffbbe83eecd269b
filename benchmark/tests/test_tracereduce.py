"""The reduction from trace and spans to numbers: on hand-made events,
and on a recorded chip trace cut down, where it must give what it gave
when the trace was recorded."""

import glob
import json
import os

import pytest

import harness
import tracereduce
from conftest import HERE, ROOT


class FakeRun(harness.Run):
    def __init__(self, d):
        super().__init__()
        self.traced = tuple(d["traced"])
        self.device = [tuple(e) for e in d["device"]]
        self.spans = d["spans"]
        self.checks = [tuple(c) for c in d["checks"]]


def read(name, run):
    return harness.load_reader(ROOT, name)(run)


K2 = ('%k.2 = u32[8]{0} custom-call(u32[8]{0} %a), '
      'custom_call_target="tpu_custom_call", backend_config={}')


def test_hand_made_trace():
    d = {"traced": [0.0, 5.0],
         "device": [["%w.1 = (u32[8]) while((u32[8]) %t), body=%b", 0.0, 1.0],
                    [K2, 0.5, 0.9], [K2, 1.2, 2.0],
                    ["%f.3 = u32[8]{0} fusion(u32[8]{0} %a)", 3.0, 4.0],
                    ["%c.4 = u32[8]{0} custom-call(u32[8]{0} %a), "
                     'custom_call_target="Other"', 4.0, 4.0]],
         "spans": [{"name": "engine.encode_batch", "id": 1, "parent": None,
                    "t0": 1.5, "t1": 3.5},
                   {"name": "inner", "id": 2, "parent": 1,
                    "t0": 2.5, "t1": 2.75}],
         "checks": [[0, 0.0, 4.5, 10]]}
    run = FakeRun(d)
    assert tracereduce.busy_seconds(run.device) == pytest.approx(2.8)
    assert tracereduce.idle_gaps(run.device, run.traced) == [
        (1.0, 1.2), (2.0, 3.0), (4.0, 5.0)]
    assert read("device_idle_share", run) == pytest.approx(2.2 / 5)
    assert read("closure_kernel_share", run) == pytest.approx(1.2 / 5)
    assert read("encode_share", run) == pytest.approx(1.75 / 5)
    b = tracereduce.breakdown(run.device, run.traced, run.spans, run.checks)
    assert dict(b["device_ops"]) == pytest.approx(
        {"%k.2 custom-call:tpu_custom_call": 1.2, "%w.1 while": 0.6,
         "%f.3 fusion": 1.0, "%c.4 custom-call:Other": 0.0})
    idle = dict(b["idle_gaps"])
    assert idle == pytest.approx({"engine.encode_batch": 0.75,
                                  "inner": 0.25, "harness.check": 0.7,
                                  "harness.between_checks": 0.5})


RECORDED = sorted(glob.glob(os.path.join(HERE, "data", "trace_*.json")))


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_trace_reduces_as_recorded(path):
    with open(path) as f:
        d = json.load(f)
    run = FakeRun(d)
    want = d["expected"]
    for name in ("device_idle_share", "closure_kernel_share"):
        assert read(name, run) == pytest.approx(want[name], rel=1e-9)
    got = tracereduce.breakdown(run.device, run.traced, run.spans,
                                run.checks)
    for key in ("device_ops", "idle_gaps"):
        assert [n for n, _ in got[key]] == [n for n, _ in want[key]]
        assert [s for _, s in got[key]] == pytest.approx(
            [s for _, s in want[key]], rel=1e-9)


def test_a_recorded_trace_is_committed():
    assert RECORDED, "no recorded chip trace under tests/data"
