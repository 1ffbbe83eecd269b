"""Unified telemetry for the checker pipeline: span tracing + metrics.

One subsystem, three pieces (each documented in its module;
docs/observability.md is the operator guide):

  tracer    ``span("name", key=...)`` context managers — nested,
            contextvar-propagated (incl. across the pipeline's worker
            pool via ``ctx_runner``), wall + process CPU time, gated by
            ``JEPSEN_TPU_TRACE`` and compiled to a no-op singleton when
            off. ``timer`` is the always-measuring variant whose
            recorded span and returned wall time are the same clock
            reads — bench split lines and trace spans cannot disagree.
  metrics   counters / gauges / histograms under stable dotted names
            (``pipeline.cache.hits``, ``engine.configs_stepped``, ...)
            — always on, the home for every one-off counter the
            checker used to carry in private dicts.
  export    Chrome trace-event JSON (opens in Perfetto, one track per
            host thread + one per device bucket), JSONL into the store
            run dir, an end-of-run summary table, the
            ``JEPSEN_TPU_JAX_PROFILE`` capture (the spans are its
            annotations, on the device ops' clock), and the flight
            recorder's crash dump (``flight_dump``).
  httpd     the live ops surface (import ``jepsen_tpu.obs.httpd``
            explicitly): ``/metrics`` Prometheus text + ``/healthz`` +
            ``/status`` + ``/ledger`` on a stdlib HTTP daemon thread
            behind ``jepsen serve --ops-port``, plus the ``jepsen
            status`` client.

Two sibling modules ride the same contract (import them explicitly —
they are consumers, not core): ``ledger`` (JEPSEN_TPU_LEDGER — the
durable per-dispatch decision ledger; ``advisor`` joins it with bench
evidence into ``jepsen report --plan``) and ``slo``
(JEPSEN_TPU_SLO_ACK_SECS — two-window ack burn-rate gauges over the
serve histograms).

Import-safe by construction: no JAX at import time, no device init —
engine modules import this at module scope and must survive a wedged
PJRT runtime (the same contract as envflags).

NEVER call ``obs.span(...)`` or registry methods inside jit-traced
code: the side effect fires at trace time, once, not per execution —
the ``purity-obs-in-trace`` lint rule enforces this mechanically.
"""

from jepsen_tpu.obs.export import (  # noqa: F401
    chrome_trace, drain_search_stats, drain_slow_deltas, export_run,
    flight_dump, flight_reset, jsonl_events, record_search_stats,
    record_slow_delta, search_stats_records, set_flight_dir,
    slow_delta_records, summary, write_chrome_trace, write_jsonl,
    write_search_stats, write_slow_deltas,
)
from jepsen_tpu.obs.metrics import (  # noqa: F401
    BUCKET_LADDER, Registry, counter, gauge, hist_quantile, histogram,
    labeled, registry, split_labels,
)
from jepsen_tpu.obs.tracer import (  # noqa: F401
    Span, Tracer, configure, counter_sample, ctx_runner, current_span,
    enabled, flight_active, jax_profile_dir, maybe_jax_profile, reset,
    span, timer, tracer,
)
