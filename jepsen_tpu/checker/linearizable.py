"""Linearizability checker — the `checker/linearizable` dispatcher.

Mirrors the reference's algorithm dispatch (jepsen/src/jepsen/checker.clj:182-213):
`:algorithm` selects the engine —

    "wgl"          host depth-first WGL search (jepsen_tpu.checker.wgl)
    "linear"       host JIT-linearization frontier (jepsen_tpu.checker.linear)
    "packed"       host frontier over int configs — the device encoding
                   run on CPU (jepsen_tpu.checker.linear_packed); the
                   fastest host engine for packable models, and the
                   bench's baseline. Falls back to wgl when the model
                   can't pack.
    "jax"          the TPU engine (jepsen_tpu.parallel.engine) — batched,
                   device-sharded frontier expansion; the north star
    "competition"  a REAL first-decisive-wins race (checker.competition),
                   mirroring the reference's parallel linear-vs-wgl race
                   (checker.clj:199, knossos.competition): packable
                   models race jax + packed + wgl, others race
                   linear + wgl. The host arms hedge a wedged device
                   runtime; the losers are cooperatively cancelled.

Results mirror knossos: {"valid?", "op", "final-paths", "configs",
"analyzer"}. Like the reference, final-paths/configs are truncated to 10
(checker.clj:210-213 — "Writing these can take *hours*").
"""

from __future__ import annotations

from typing import Optional

from jepsen_tpu import models as model_ns
from jepsen_tpu import obs
from jepsen_tpu.checker.core import Checker
from jepsen_tpu.history import History, Intern


def _truncate(result: dict, n: int = 10) -> dict:
    for k in ("final-paths", "configs"):
        if isinstance(result.get(k), list):
            result[k] = result[k][:n]
    return result


class Linearizable(Checker):
    def __init__(self, model=None, algorithm: str = "competition"):
        self.model = model
        self.algorithm = algorithm

    def check(self, test, history, opts=None):
        # the root span of a single-key check (under independent.key on
        # the per-key host path)
        with obs.span("linearizable.check"):
            return self._check(test, history, opts)

    def _check(self, test, history, opts):
        model = self.model or (test or {}).get("model")
        if model is None:
            raise ValueError("The linearizable checker requires a model")
        algo = self.algorithm or "competition"
        h = history if isinstance(history, History) else History.wrap(history)

        # Guard against mis-parsed histories (e.g. raw EDN keyword keys):
        # a non-empty history in which NO op has a recognizable :type
        # would otherwise sail through as trivially linearizable.
        if len(h) and not any(
                o.get("type") in ("invoke", "ok", "fail", "info")
                for o in h):
            raise ValueError(
                "history has no ops with a recognizable :type — was it "
                "parsed with History.from_edn / op_from_edn?")

        if algo == "competition":
            from jepsen_tpu.checker import competition
            packable = model_ns.pack_spec(model, Intern()) is not None
            if packable:
                # host arms always race; the device arm only joins when
                # the bounded probe says the runtime is usable (a wedged
                # runtime would leak one stuck thread per check). A
                # device arm orphaned by an EARLIER race and silent ever
                # since is the mid-process wedge signature — skip the
                # device arm while the suspicion lasts. It self-clears
                # if the arm ever reports (a slow-but-healthy device
                # rejoins later races; a wedged one never does).
                suspect = competition.device_engine_suspect()
                global _wedge_warned
                if suspect and not _wedge_warned:
                    import logging
                    logging.getLogger(__name__).warning(
                        "a device competition arm from an earlier check "
                        "has been silent for >%.0fs — racing host arms "
                        "only until it reports",
                        competition.DEVICE_WEDGE_SUSPECT_SECS)
                _wedge_warned = suspect
                arms = (("jax", "packed", "wgl")
                        if _engine_available() and not suspect
                        else ("packed", "wgl"))
            else:
                arms = ("linear", "wgl")   # the reference's exact race
            r = competition.analysis(
                model, h, arms=arms,
                timeout=(test or {}).get("competition-timeout"))
            algo = r.get("analyzer", "competition")

        elif algo == "wgl":
            from jepsen_tpu.checker import wgl
            r = wgl.analysis(model, h)
        elif algo == "linear":
            from jepsen_tpu.checker import linear
            r = linear.analysis(model, h)
        elif algo == "packed":
            from jepsen_tpu.checker import linear_packed, wgl
            from jepsen_tpu.parallel.encode import EncodeError
            try:
                r = linear_packed.analysis(model, h)
            except EncodeError as err:
                r = wgl.analysis(model, h)
                r["fallback"] = str(err)
                algo = "wgl"
        elif algo == "jax":
            from jepsen_tpu.parallel import engine
            r = engine.analysis(model, h)
        else:
            raise ValueError(f"unknown linearizability algorithm {algo!r}")
        r["analyzer"] = algo
        if (r.get("valid?") is False and not r.get("final-paths")
                and algo in ("linear", "packed") and len(h) <= 1000):
            # the frontier engines localize the failure but keep no
            # breadcrumbs; knossos's linear analysis always produces
            # final-paths (they feed linear.svg, checker.clj:203-207) —
            # attach them via a state-bounded WGL re-search
            from jepsen_tpu.checker import wgl as _wgl
            rw = _wgl.analysis(model, h, max_states=1_000_000)
            if rw.get("valid?") is False:
                # take wgl's whole failure report so op / final-paths /
                # configs describe the SAME stuck point (the frontier
                # engine may localize a different window)
                r["final-paths"] = rw.get("final-paths", [])
                r["configs"] = rw.get("configs", [])
                if rw.get("op"):
                    r["op"] = rw["op"]
            elif rw.get("valid?") is True:
                # the oracle contradicts the engine: surface it loudly —
                # a silent wrong verdict would hide an engine bug
                import logging
                logging.getLogger(__name__).warning(
                    "%s said invalid but the WGL oracle says valid — "
                    "engine disagreement", algo)
                r["oracle-disagreement"] = True
        r = _truncate(r)

        # On failure, render the counterexample SVG into the store, as
        # the reference does via knossos.linear.report
        # (checker.clj:203-207). Rendered from the truncated analysis:
        # thousands of final-paths would take hours, just like writing
        # them would (checker.clj:210-213).
        if r.get("valid?") is False and (test or {}).get("store"):
            try:
                from jepsen_tpu.checker import linear_report
                linear_report.render_analysis_file(h, r, test, opts)
            except Exception:  # noqa: BLE001 - plots must never fail a check
                pass
        return r


_engine_probe_result: Optional[bool] = None
_engine_probe: dict = {}   # in-flight probe: {"thread": t, "out": {...}}
_wedge_warned = False   # one warning per suspicion episode, not per check


def _engine_available(timeout: float = 15.0) -> bool:
    """Whether the device engine can run — probed with a BOUNDED wait.

    jax.devices() blocks forever inside backend start-up when the
    device runtime is wedged, and it ignores Python signals — probing it inline would hang the check
    before the competition race could hedge anything. The probe runs in
    a daemon thread with a timeout instead; while it has not answered,
    the engine is treated as unavailable (so races run host arms only).
    Only an actual ANSWER is cached: a merely-slow first init (cold jax
    import on a loaded host) that finishes after the timeout flips
    later checks back to the device engine. One probe thread total —
    later calls re-join the same thread briefly rather than piling a
    new wedged thread onto every check."""
    global _engine_probe_result
    if _engine_probe_result is not None:
        return _engine_probe_result
    if not _engine_probe:
        out: dict = {}

        def probe():
            try:
                import jax
                from jepsen_tpu.parallel import engine  # noqa: F401
                out["ok"] = len(jax.devices()) > 0
            except Exception:  # noqa: BLE001
                out["ok"] = False

        import threading
        t = threading.Thread(target=probe, daemon=True,
                             name="engine-availability-probe")
        t.start()
        _engine_probe.update(thread=t, out=out)
        _engine_probe["thread"].join(timeout)
    else:
        # an earlier call already paid the full wait; just peek
        _engine_probe["thread"].join(0.1)
    out = _engine_probe["out"]
    if "ok" in out:
        _engine_probe_result = bool(out["ok"])
        return _engine_probe_result
    return False


def linearizable(model=None, algorithm: str = "competition") -> Linearizable:
    return Linearizable(model, algorithm)
