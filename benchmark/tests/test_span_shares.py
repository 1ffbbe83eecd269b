"""The readers of the program's stage spans (split, issue, place, fetch,
assembly, collector pauses) on a hand-built span and check list."""

import pytest

import harness
from conftest import ROOT


class FakeRun(harness.Run):
    def __init__(self, d):
        super().__init__()
        self.traced = tuple(d["traced"])
        self.device = [tuple(e) for e in d["device"]]
        self.spans = d["spans"]
        self.checks = [tuple(c) for c in d["checks"]]


def read(name, run):
    return harness.load_reader(ROOT, name)(run)


def _span(sid, name, parent, t0, t1):
    return {"name": name, "id": sid, "parent": parent, "t0": t0, "t1": t1}


# A keyed check (0-4 s) and a single-key check (4.9-9 s) in a 10 s
# window, with three collector pauses: one inside encode, one in the
# single-key root's own time, one between checks and past the window.
SPANS = [
    _span(1, "independent.check", None, 0.0, 4.0),
    _span(2, "independent.split", 1, 0.0, 0.5),
    _span(3, "independent.device_batch", 1, 0.5, 3.5),
    _span(4, "engine.encode_batch", 3, 0.5, 1.5),
    _span(5, "bitdense.pad_place", 3, 1.5, 2.0),
    _span(6, "transfer.place", 5, 1.8, 2.0),
    _span(7, "bitdense.issue", 3, 2.0, 2.1),
    _span(8, "bitdense.finalize", 3, 2.1, 3.0),
    _span(9, "bitdense.fetch", 8, 2.9, 3.0),
    _span(10, "bitdense.results", 3, 3.0, 3.2),
    _span(11, "independent.assemble", 1, 3.5, 3.9),
    _span(12, "python.gc", None, 1.0, 1.1),
    _span(13, "linearizable.check", None, 5.0, 9.0),
    _span(14, "engine.encode", 13, 5.0, 7.0),
    _span(15, "bitdense.check", 13, 7.0, 8.0),
    _span(16, "bitdense.issue", 15, 7.0, 7.2),
    _span(17, "bitdense.fetch", 15, 7.9, 8.0),
    _span(18, "bitdense.results", 13, 8.0, 8.5),
    _span(19, "python.gc", None, 8.6, 8.8),
    _span(20, "python.gc", None, 9.9, 10.5),
]

# seconds / 10 s: the roots' own time is 3.9-4.0 (independent.check),
# 4.9-5.0 (harness.check alone), 8.5-8.6 and 8.8-9.0
# (linearizable.check)
SHARES = {"unattributed_share": 0.05, "split_share": 0.05,
          "assemble_share": 0.11, "dispatch_share": 0.03,
          "place_share": 0.02, "device_fetch_share": 0.02,
          "gc_share": 0.04}


def _hand_run(spans):
    return FakeRun({"traced": [0.0, 10.0], "device": [], "spans": spans,
                    "checks": [[0, 0.0, 4.0, 10], [1, 4.9, 9.0, 10]]})


@pytest.mark.parametrize("name", sorted(SHARES))
def test_span_share_on_hand_built_spans(name):
    assert read(name, _hand_run(SPANS)) == pytest.approx(SHARES[name])


def test_new_spans_leave_the_older_readers_as_they_were():
    """A collector pause inside encode is not taken out of encode's self
    time, and the spans nested in pad/place and the wait do not change
    what those readers sum."""
    run = _hand_run(SPANS)
    assert read("encode_share", run) == pytest.approx(0.3)
    assert read("pad_place_share", run) == pytest.approx(0.05)
    assert read("device_wait_share", run) == pytest.approx(0.19)


@pytest.mark.parametrize("name", sorted(set(SHARES) - {
    "unattributed_share"}))
def test_span_share_reports_nothing_without_its_spans(name):
    """A program without the spans (an older commit) reports nothing."""
    old = [s for s in SPANS if s["name"] not in (
        "independent.split", "independent.assemble", "bitdense.results",
        "bitdense.issue", "transfer.place", "bitdense.fetch",
        "python.gc")]
    assert read(name, _hand_run(old)) is None
