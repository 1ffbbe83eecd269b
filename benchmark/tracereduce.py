"""From a profiler trace and the program's spans to per-layer numbers.

The JAX profiler writes `<dir>/plugins/profile/<time>/*.xplane.pb`.
`device_events` reads the ops that ran on each device (the `XLA Ops`
line of every `/device:` plane) and puts them on the harness's clock:
the harness opens the annotation `bench.window` in the trace at the
moment it reads `time.perf_counter()`, so one offset maps the trace's
nanoseconds onto the clock of the program's spans.

Everything below works on plain lists, so that a recorded trace cut
down to a few hundred events (`tests/data/`) checks the reduction:

- device events: [(short op name, start, end)] in seconds on that
  clock;
- spans: dicts with `name`, `id`, `parent`, `t0`, `t1`;
- checks: (pool index, start, end, ops), one per check of the window.
"""

from __future__ import annotations

import glob
import os
import re

OP_LINE = "XLA Ops"
DEVICE_PLANE = "/device:"
# The bitset closure's Pallas kernel is the only Mosaic kernel the
# bitdense programs compile. XLA names its op after the traced function
# (`%branch_1_fun.1 = u32[18,256]{...} custom-call(...),
# custom_call_target="tpu_custom_call"`), so it is found by its call
# target. An op's events nest on the `XLA Ops` line: a `while` covers
# the ops of its body.
CLOSURE_KIND = "custom-call:tpu_custom_call"
_KIND = re.compile(r"\s([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(op: str) -> str:
    """`%while.6 while`, or `%k.1 custom-call:<target>`, from an op's
    full HLO text (a short name is left as it is)."""
    lhs, _, rhs = op.partition(" = ")
    m = _KIND.search(" " + rhs)
    if not m:
        return lhs
    kind = m.group(1)
    t = _TARGET.search(rhs) if kind == "custom-call" else None
    return f"{lhs} {kind}:{t.group(1)}" if t else f"{lhs} {kind}"


def is_closure(op: str) -> bool:
    return short_name(op).endswith(" " + CLOSURE_KIND)


def extract(path: str, annotation: str) -> dict:
    """{"annotation": [start_ns, end_ns], "device": [[short op name,
    start_ns, end_ns], ...]} from one `.xplane.pb` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ann = None
    device = []
    names: dict = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for ev in line.events:
                    n = names.get(ev.name)
                    if n is None:
                        n = names[ev.name] = short_name(ev.name)
                    device.append([n, ev.start_ns, ev.end_ns])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == annotation and ann is None:
                        ann = [ev.start_ns, ev.end_ns]
    return {"annotation": ann, "device": device}


def device_events(trace_dir: str, traced: tuple, annotation: str) -> list:
    """Device ops inside the traced window, on the harness's clock."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for path in paths:
        x = extract(path, annotation)
        if x["annotation"] is None:
            continue
        out += to_clock(x, traced)
    return sorted(out, key=lambda e: e[1])


def to_clock(x: dict, traced: tuple) -> list:
    a0 = x["annotation"][0]
    t0, t1 = traced
    out = []
    for name, s, e in x["device"]:
        s, e = t0 + (s - a0) / 1e9, t0 + (e - a0) / 1e9
        s, e = max(s, t0), min(e, t1)
        if e > s:
            out.append((name, s, e))
    return out


def merged(device: list) -> list:
    """The union of the device ops' intervals, as sorted (start, end)."""
    out: list = []
    for _, s, e in sorted(device, key=lambda d: d[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(device: list) -> float:
    return sum(e - s for s, e in merged(device))


def idle_gaps(device: list, traced: tuple) -> list:
    """(start, end) of every stretch of the traced window in which no
    op ran on the device."""
    gaps = []
    t = traced[0]
    for s, e in merged(device):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if traced[1] > t:
        gaps.append((t, traced[1]))
    return gaps


def host_segments(spans: list, checks: list, traced: tuple) -> list:
    """The traced window cut into (start, end, name) pieces, each named
    by the innermost span open on the host: a program span, else
    `harness.check` inside a check, else `harness.between_checks`."""
    items = [(c[1], c[2], "harness.check", -1) for c in checks]
    depth = {}
    by_id = {s["id"]: s for s in spans}

    def d(s):
        if s["id"] not in depth:
            p = by_id.get(s["parent"])
            depth[s["id"]] = 0 if p is None else d(p) + 1
        return depth[s["id"]]

    items += [(s["t0"], s["t1"], s["name"], d(s)) for s in spans]
    # cut points: every span edge inside the window
    points = sorted({traced[0], traced[1]}
                    | {t for a, b, _, _ in items for t in (a, b)
                       if traced[0] < t < traced[1]})
    items.sort(key=lambda it: it[0])
    out = []
    j = 0
    active: list = []
    for a, b in zip(points, points[1:]):
        mid = (a + b) / 2
        while j < len(items) and items[j][0] <= mid:
            active.append(items[j])
            j += 1
        active = [it for it in active if it[1] > mid]
        inner = max(active, key=lambda it: (it[3], it[0]), default=None)
        out.append((a, b, inner[2] if inner else "harness.between_checks"))
    return out


def idle_by_host(device: list, spans: list, checks: list,
                 traced: tuple) -> dict:
    """Idle device seconds by what the host was doing meanwhile."""
    segs = host_segments(spans, checks, traced)
    out: dict = {}
    j = 0
    for g0, g1 in idle_gaps(device, traced):
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < g1:
            a, b, name = segs[k]
            overlap = min(b, g1) - max(a, g0)
            if overlap > 0:
                out[name] = out.get(name, 0.0) + overlap
            k += 1
    return out


def op_self_seconds(device: list) -> dict:
    """Device seconds by short op name, each op's own time: less what
    the ops nested inside it (a loop's body) cover."""
    out: dict = {}
    stack: list = []            # [name, start, end, seconds of children]

    def close(item):
        name, s, e, kids = item
        out[name] = out.get(name, 0.0) + (e - s) - kids
        if stack:
            stack[-1][3] += e - s

    for name, s, e in sorted(device, key=lambda d: (d[1], -d[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        stack.append([short_name(name), s, e, 0.0])
    while stack:
        close(stack.pop())
    return out


def breakdown(device: list, traced: tuple, spans: list,
              checks: list) -> dict:
    """The contract's breakdown: the ten device ops that took most of
    their own time, and idle device time by the host span open
    meanwhile."""
    ops = sorted(op_self_seconds(device).items(), key=lambda x: -x[1])[:10]
    idle = sorted(idle_by_host(device, spans, checks, traced).items(),
                  key=lambda x: -x[1])[:10]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle]}


def span_seconds(spans: list, names: tuple, traced: tuple,
                 self_time: bool = False) -> float:
    """Seconds the named spans cover inside the traced window; with
    `self_time`, less what their child spans cover."""
    def clip(s):
        return max(0.0, min(s["t1"], traced[1]) - max(s["t0"], traced[0]))

    total = 0.0
    picked = [s for s in spans if s["name"] in names]
    ids = {s["id"] for s in picked}
    for s in picked:
        total += clip(s)
    if self_time:
        total -= sum(clip(s) for s in spans if s["parent"] in ids)
    return total


def check_seconds(checks: list, traced: tuple) -> float:
    return sum(max(0.0, min(e, traced[1]) - max(s, traced[0]))
               for _, s, e, _ in checks)


def cut(device: list, spans: list, checks: list, traced: tuple,
        start: float, seconds: float) -> dict:
    """`seconds` of a traced window from `start`, with everything in
    it: the recorded form the reduction's test reads."""
    t0 = max(traced[0], start)
    t1 = min(traced[1], t0 + seconds)

    def inside(a, b):
        return a < t1 and b > t0

    return {"traced": [t0, t1],
            "device": [[n, max(s, t0), min(e, t1)] for n, s, e in device
                       if inside(s, e)],
            "spans": [dict(s) for s in spans if inside(s["t0"], s["t1"])],
            "checks": [list(c) for c in checks if inside(c[1], c[2])]}
