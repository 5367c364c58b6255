"""Observability: metrics registry (Prometheus exposition) + request tracing.

Three pillars (ISSUE 2):

- ``obs.metrics`` — typed Counter/Gauge/Histogram primitives in a
  :class:`~rag_llm_k8s_tpu.obs.metrics.MetricsRegistry`, rendered in
  Prometheus text exposition format (and a flat JSON snapshot for the
  legacy ``/metrics`` consumers);
- ``obs.tracing`` — contextvar-propagated per-request span trees, kept in
  an in-memory ring buffer (``/debug/traces``) and returned inline for
  ``{"trace": true}`` queries; spans wrap device work in
  ``jax.profiler.TraceAnnotation`` so xprof captures show named stages;
- engine instrumentation (TTFT / inter-token / occupancy / compile time)
  lives at the call sites in ``engine/`` and ``server/`` and reports into
  the registry.

The decision layer on top (ISSUE 3):

- ``obs.slo`` — declarative SLOs evaluated over sliding windows of the
  registry's histograms/counters, multi-window burn-rate alerting
  (``GET /slo`` + ``rag_slo_*`` gauges);
- ``obs.logging`` — W3C ``traceparent`` parse/emit and trace-correlated
  structured JSON logs;
- ``obs.devices`` — per-device HBM / prefix-cache residency gauges.
  (Whether a change made the program slower is not judged in here: the
  driver runs ``benchmark/`` on the chip against ``BENCHMARK.json``'s bounds.)

The causal layer (ISSUE 11):

- ``obs.flight`` — the engine flight recorder: a bounded in-process
  journal of typed scheduler/substrate decision events, per-request
  lifecycle timelines (``/debug/timeline/<id>``), and trigger-driven
  incident bundles (``/debug/incidents``; rendered offline by
  ``scripts/flightview.py``).

The efficiency layer (ISSUE 14):

- ``obs.goodput`` — the goodput ledger: per-device-sync-window chip-time
  attribution into a closed category set, an analytic FLOPs/bytes
  roofline (per-executable MFU / bandwidth utilization), per-request
  chip-second + cost figures in ``/generate`` timings, and the
  ``GET /debug/goodput`` capacity report (``flightview --goodput``
  renders the same report offline). Stdlib-only by contract — the
  offline renderer loads it by file path with no jax present.

The quality layer (ISSUE 15):

- ``obs.shadow`` — the shadow-traffic quality auditor: a sampled
  fraction of completed requests re-runs on the EXACT serving path
  (``InferenceEngine.score_exact``) and every divergence from the
  delivered stream is measured and attributed to the approximation that
  served it (warm tier / chunk splice / re-rotation / boundary fixup /
  speculation); ``rag_quality_*`` metrics, the ``quality_p99_logit_err``
  SLO's SLI, ``quality_divergence`` incident bundles, and the
  ``GET /debug/quality`` report (``flightview --quality`` renders the
  same report offline; stdlib-only by the same contract as goodput).
"""

from rag_llm_k8s_tpu.obs.metrics import MetricsRegistry, default_registry  # noqa: F401
from rag_llm_k8s_tpu.obs.tracing import TraceBuffer, span, start_trace  # noqa: F401
