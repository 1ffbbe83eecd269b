"""The device SCC closure's share of its roofline, in percent: the
least time the chip's matrix units could take for the closures of the
traced window, over the device's busy time inside them.

A closure at n padded nodes is ceil(log2 n) squarings of an n x n
bfloat16 matrix, 2 n^3 flops each (`closure_flops`, kept here). A
squaring moves at least 4 n^2 bytes (one matrix read, one written):
n / 2 flops a byte, against the chip's 240 (197 TFLOP/s over 819
GB/s), so compute bounds it. n is the program's counters' padded nodes per
closure call; the closures are the `elle.sccs.device` spans wholly
inside the traced window; busy time is the union of the device's ops
inside those spans. Padding counts, as it runs."""

import math

import tracereduce

# Peak dense bf16 FLOP/s by `device_kind` (Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM).
PEAK_FLOPS = {"TPU v5 lite": 197e12}
NAME = "elle.sccs.device"


def closure_flops(n: int) -> float:
    return math.ceil(math.log2(max(2, n))) * 2.0 * n ** 3


def counters() -> dict:
    """The program's SCC counters, summed over the run; {} where the
    program has none."""
    from jepsen_tpu import obs
    snap = obs.registry().snapshot()
    return {k: snap[k]["value"] for k in ("elle.scc.padded_nodes",
                                          "elle.scc.closure_calls")
            if k in snap}


def device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind


def busy_inside(device: list, spans: list) -> float:
    """Seconds of the device's busy union inside the spans."""
    busy = tracereduce.merged(device)
    return sum(max(0.0, min(b, s["t1"]) - max(a, s["t0"]))
               for s in spans for a, b in busy)


def read(run, counts=None, kind=None):
    if run.traced is None or not run.device:
        return None
    c = counters() if counts is None else counts
    calls = c.get("elle.scc.closure_calls", 0)
    spans = [s for s in run.spans if s["name"] == NAME
             and run.traced[0] <= s["t0"] and s["t1"] <= run.traced[1]]
    if not calls or not spans:
        return None
    busy = busy_inside(run.device, spans)
    if busy <= 0:
        return None
    n = c["elle.scc.padded_nodes"] / calls
    peak = PEAK_FLOPS[kind or device_kind()]
    return 100.0 * len(spans) * closure_flops(round(n)) / (peak * busy)
