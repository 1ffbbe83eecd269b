"""First-decisive-verdict-wins race between the device and host engines.

The reference's default analyzer races knossos `linear` and `wgl` in
parallel futures; the first decisive result wins and the loser's future
is cancelled (jepsen/src/jepsen/checker.clj:199,
knossos/src/knossos/competition.clj). Here the arms are:

    jax     the TPU engine (jepsen_tpu.parallel.engine) — normally the
            winner by orders of magnitude, but it can WEDGE when the
            device runtime dies mid-call (a wedged runtime blocks
            forever with no Python-level signal delivery);
    packed  the int-config host frontier — fastest host arm, the hedge
            that keeps a dead device runtime from turning a check into
            a hang;
    wgl     the host depth-first search — decisive where the frontier
            arms go "unknown" (config-budget blowups), and the only arm
            for models that don't pack.

Cancellation is cooperative for the host arms (a threading.Event they
poll at their deadline stride). The device arm cannot be interrupted
mid-dispatch — the same is true of a JVM future blocked in native code,
which `future-cancel` also cannot stop — so its thread is a daemon and
the race simply stops waiting for it once another arm is decisive.

A decisive verdict is `valid?` in {True, False}; "unknown" and crashes
are indecisive, and the race returns the best indecisive result only
when every arm failed to decide.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Optional

# Grace period once only device arms remain undecided: host arms have
# all reported indecisive, so the race is waiting on an arm that may be
# wedged in PJRT — wait this long, then report rather than hang.
DEVICE_ARM_GRACE_SECS = 60.0

# Device arms their race has given up on: thread -> the instant the
# race returned without them. An ORPHANED arm that stays silent long
# past any sane dispatch is the mid-process wedge signature (the
# runtime wedged AFTER the availability probe cached healthy);
# device_engine_suspect() lets the dispatcher stop adding device arms —
# and leaking one stuck thread — to further checks. Orphans that do
# eventually report are removed, so a merely-slow (but healthy) device
# clears the suspicion and rejoins later races: suspicion is
# RECOVERABLE, unlike the process-wide availability cache.
_device_arms: dict = {}      # running device arms: thread -> start
_orphaned: dict = {}         # given-up device arms: thread -> orphaned-at
_device_arms_lock = threading.Lock()
DEVICE_WEDGE_SUSPECT_SECS = 120.0


def device_engine_suspect() -> bool:
    """True while some device arm whose race already gave up on it has
    stayed silent for DEVICE_WEDGE_SUSPECT_SECS past the give-up — the
    mid-process device-runtime wedge signature. Self-clearing: the arm
    reporting (however late) removes it."""
    now = time.monotonic()
    with _device_arms_lock:
        return any(now - t0 > DEVICE_WEDGE_SUSPECT_SECS
                   for t0 in _orphaned.values())


def analysis(model, history, arms=("jax", "packed", "wgl"),
             timeout: Optional[float] = None) -> dict:
    """Race the given arms over (model, history); first decisive verdict
    wins. Returns the winner's result with "analyzer" set to the winning
    arm and a "competition" field naming winner and arms. `timeout`
    bounds the WHOLE race (one monotonic deadline, seconds); on expiry
    the best indecisive result so far is returned with valid?
    "unknown". Even without a timeout the race cannot hang on a wedged
    device arm: once every host arm has reported, the wait for the
    remaining device arm(s) is bounded by DEVICE_ARM_GRACE_SECS."""
    cancel = threading.Event()
    results: queue.Queue = queue.Queue()

    def run_arm(name):
        try:
            if name == "jax":
                from jepsen_tpu.parallel import engine
                me = threading.current_thread()
                try:
                    with _device_arms_lock:
                        _device_arms[me] = time.monotonic()
                    r = engine.analysis(model, history)
                finally:
                    with _device_arms_lock:
                        _device_arms.pop(me, None)
                        _orphaned.pop(me, None)
            elif name == "packed":
                from jepsen_tpu.checker import linear_packed
                r = linear_packed.analysis(model, history, cancel=cancel)
            elif name == "linear":
                from jepsen_tpu.checker import linear
                r = linear.analysis(model, history, cancel=cancel)
            elif name == "wgl":
                from jepsen_tpu.checker import wgl
                r = wgl.analysis(model, history, cancel=cancel)
            else:
                raise ValueError(f"unknown competition arm {name!r}")
        except Exception as err:  # noqa: BLE001 — a crashed arm loses;
            # the race decides from the survivors (crash kept for the
            # all-indecisive report)
            r = {"valid?": "unknown", "error": repr(err)}
        results.put((name, r))

    threads = []
    for name in arms:
        # daemon: a wedged device arm must never block process exit
        t = threading.Thread(target=run_arm, args=(name,), daemon=True,
                             name=f"competition-{name}")
        t.start()
        threads.append((name, t))

    deadline = None if timeout is None else time.monotonic() + timeout
    grace_deadline = None
    indecisive = {}
    pending = set(arms)

    def handle(name, r):
        """Absorb one arm result; the winner's dict when decisive."""
        pending.discard(name)
        if r.get("valid?") in (True, False):
            cancel.set()
            out = dict(r)
            out["analyzer"] = name
            out["competition"] = {"winner": name, "arms": list(arms)}
            return out
        indecisive[name] = r
        return None

    try:
        while pending:
            now = time.monotonic()
            limits = []
            if deadline is not None:
                limits.append(deadline)
            if pending <= {"jax"}:
                # only wedge-prone device arms are left: bound the wait
                # instead of trusting PJRT to return — even when an
                # explicit (possibly large) race timeout is set
                if grace_deadline is None:
                    grace_deadline = now + DEVICE_ARM_GRACE_SECS
                limits.append(grace_deadline)
            wait = min(limits) - now if limits else None
            if wait is not None and wait <= 0:
                # expiry: drain anything already posted — an arm may
                # have delivered a decisive verdict just before the
                # deadline, and "unknown" must not beat it
                while True:
                    try:
                        name, r = results.get_nowait()
                    except queue.Empty:
                        break
                    win = handle(name, r)
                    if win:
                        return win
                break
            try:
                name, r = results.get(timeout=wait)
            except queue.Empty:
                continue  # re-check deadlines; expiry handled above
            win = handle(name, r)
            if win:
                return win

        cancel.set()
        return {"valid?": "unknown",
                "error": "no competition arm produced a decisive verdict"
                         + ("" if not pending
                            else f" in time ({sorted(pending)} still "
                                 f"running)"),
                "analyzer": "competition",
                "competition": {"winner": None, "arms": list(arms),
                                "results": indecisive}}
    finally:
        # any device arm we stop waiting for becomes an orphan — the
        # input to the mid-process wedge detection above
        if "jax" in pending:
            now = time.monotonic()
            with _device_arms_lock:
                for name, t in threads:
                    if name == "jax" and t in _device_arms:
                        _orphaned[t] = now
