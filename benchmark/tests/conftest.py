"""The benchmark's own tests run on the CPU: JAX is steered there before
it is imported, and the benchmark's modules and the program are put on
the path as the command finds them."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def shrink(cfg, mix):
    """A cell cut to a size the CPU runs in seconds, at the same
    shapes' kinds: fewer ops a key, fewer keys, fewer crashed writes."""
    if cfg["generator"] == "register":
        cfg["ops_per_key"] = 40
        mix["keys"] = min(mix["keys"], 6)
    else:
        cfg["ops"] = 400
        cfg["crashed_writes"] = 6
    return cfg, mix


CELLS = ("tendermint-cas.10k-ops", "crashed-writes-register.10k",
         "tendermint-cas.short-run")
