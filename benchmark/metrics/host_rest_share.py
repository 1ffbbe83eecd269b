"""Share of the traced window spent inside checks but outside encode,
pad/place and the device wait: the checker entry, jepsen.independent's
split and assembly, and counterexample extraction."""

import tracereduce

PROGRAM = (("engine.encode_batch", "engine.encode"),
           ("bitdense.pad_place",),
           ("bitdense.finalize", "bitdense.check"))


def read(run):
    if run.traced is None or not run.checks:
        return None
    rest = tracereduce.check_seconds(run.checks, run.traced)
    for i, names in enumerate(PROGRAM):
        rest -= tracereduce.span_seconds(run.spans, names, run.traced,
                                         self_time=(i == 0))
    return rest / (run.traced[1] - run.traced[0])
