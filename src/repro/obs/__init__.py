"""repro.obs — the protocol observability layer.

Six pieces, all wired through the session's RoundHook seam:

* **Phase tracing** (:mod:`repro.obs.trace`): ``jax.named_scope``
  annotations on the round phases and the model blocks (metadata-only),
  ``span()`` host spans that the api and engine layers open around their
  host work (one ``HOST_SPANS`` vocabulary, on the profiler's clock), the
  process-wide compile counter behind ``RunReport.counts``, and the
  profiling join that turns a ``jax.profiler`` trace into a per-phase
  device-time breakdown (:meth:`repro.api.Session.profile`).
* **Metrics/event bus** (:mod:`repro.obs.metrics`): one timestamped
  :class:`Event` schema, counter/gauge/histogram aggregates, and the
  ``repro.obs`` logger that the hooks' warn/print sinks route through.
* **Exporters** (:mod:`repro.obs.export`): JSONL event stream +
  Prometheus text exposition.
* **Health watchdogs** (:mod:`repro.obs.watchdog`): in-scan traced
  diagnostics (NaN/Inf wire guard, push-sum mass drift, consensus
  residual) surfaced as structured :class:`Alert` events at segment
  boundaries, with warn/abort policies mirroring ``BudgetHook.strict``.
* **Run timeline** (:mod:`repro.obs.timeline`): per-run span/event
  record — host segment spans, device phase slices, async message
  lifecycle — exported as Chrome-trace-event JSON (Perfetto-loadable)
  via :class:`TimelineHook` / :class:`Timeline`.
* **Cross-run registry** (:mod:`repro.obs.registry`): schema-versioned
  :class:`RunRecord` history (``BENCH_history.jsonl``, append-only) with
  rolling-median regression gates (``python -m repro.obs.registry
  check``).

Import discipline: this package imports only jax + stdlib, so the core
protocol (:mod:`repro.core.dpps`) can annotate phases without an import
cycle. The watchdog and timeline hooks subclass
:class:`repro.api.hooks.RoundHook`, so they load lazily (module
``__getattr__``) — ``repro.obs`` stays importable before/without
``repro.api``.
"""
from __future__ import annotations

from repro.obs.export import JsonlExporter, prometheus_text, write_prometheus
from repro.obs.metrics import (
    Event,
    MetricsBus,
    default_bus,
    get_logger,
    log_sink,
)
from repro.obs.trace import HOST_SPANS, KNOWN_PHASES, ProfileReport, phase, span

__all__ = [
    "Alert",
    "Event",
    "HOST_SPANS",
    "JsonlExporter",
    "KNOWN_PHASES",
    "MetricGate",
    "MetricsBus",
    "ProfileReport",
    "RunRecord",
    "Timeline",
    "TimelineHook",
    "WatchdogAbort",
    "WatchdogHook",
    "default_bus",
    "get_logger",
    "log_sink",
    "phase",
    "prometheus_text",
    "span",
    "validate_chrome_trace",
    "write_prometheus",
]

# Lazily resolved (module __getattr__): the watchdog/timeline hooks
# subclass repro.api.hooks.RoundHook, and the registry is pure-stdlib but
# only needed by record/check consumers.
_LAZY = {
    "Alert": "repro.obs.watchdog",
    "WatchdogAbort": "repro.obs.watchdog",
    "WatchdogHook": "repro.obs.watchdog",
    "Timeline": "repro.obs.timeline",
    "TimelineHook": "repro.obs.timeline",
    "validate_chrome_trace": "repro.obs.timeline",
    "RunRecord": "repro.obs.registry",
    "MetricGate": "repro.obs.registry",
}


def __getattr__(name: str):
    if name in _LAZY:
        import importlib

        return getattr(importlib.import_module(_LAZY[name]), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
