"""``repro.observe`` — trace-driven attribution + anomaly-triggered
re-planning.

Until this package, the online loop saw whole-step wall times only:
per-leaf backward budgets came from the FLOPs-share heuristic
(``profiler.apportion_backward``), wire samples from an injectable
micro-benchmark probe, and ``ReplanController`` re-planned on a blind
fixed cadence.  ``repro.observe`` turns that controller from
cadence-driven into evidence-driven, in four pieces:

  * :mod:`~repro.observe.trace` — annotation primitives (the train
    step's phase scopes, the ``core.lags`` collectives and the
    ``Session.run`` host spans follow the :mod:`~repro.observe.names`
    grammar, so a ``jax.profiler`` trace carries it) and a
    **deterministic fake-trace backend** for CPU/CI.
  * :mod:`~repro.observe.attribution` — trace events → per-bucket
    ``CommSample``\\ s (consumed by ``costfit``/``tier_hardware``) and
    **measured** per-leaf backward times (consumed by
    ``planner.plan_schedule`` / ``profiler.profile_model``), with the
    FLOPs-share heuristic demoted to explicit fallback.
  * :mod:`~repro.observe.anomaly` — robust median/MAD change-point
    detector over the telemetry step window (warmup/compile-spike
    masking, fire-exactly-once, checkpointable).
  * :mod:`~repro.observe.triggers` — the ``ReplanTrigger`` protocol and
    the built-ins (cadence / anomaly / hardware-fingerprint drift) the
    controller ORs together; the default set reproduces the old
    ``replan_every`` semantics bit-for-bit.
  * :mod:`~repro.observe.health` — the convergence-health plane: the
    paper's theory quantities (Assumption-1 delta, EF residual energy,
    async1 staleness) computed online from what the live exchange
    already returns, plus the :class:`HealthMonitor` that turns the
    delta stream into ``health_alarm`` events and
    :class:`~repro.observe.triggers.HealthTrigger` re-plans.
  * :mod:`~repro.observe.metrics` / :mod:`~repro.observe.events` — the
    process-wide metrics registry (counters/gauges/histograms over the
    ``names`` grammar, Prometheus text + JSONL snapshot exporters) and
    the versioned event bus (replan swaps, trigger firings, publishes,
    guard trips, resyncs, per-request serve records) that every
    subsystem — ``api.Session.run``, ``runtime.ReplanController``,
    ``repro.stream`` — reports into; :mod:`~repro.observe.check` is the
    CI validator over exported snapshots.

Import is lazy (PEP 562): ``repro.core`` annotates collectives via the
leaf module ``repro.observe.names`` without dragging the autotune stack
into its import graph.
"""
from __future__ import annotations

_LAZY = {
    "names": "repro.observe.names",
    "trace": "repro.observe.trace",
    "attribution": "repro.observe.attribution",
    "anomaly": "repro.observe.anomaly",
    "triggers": "repro.observe.triggers",
    "metrics": "repro.observe.metrics",
    "events": "repro.observe.events",
    "check": "repro.observe.check",
    "health": "repro.observe.health",
    "HealthMonitor": ("repro.observe.health", "HealthMonitor"),
    "MetricsRegistry": ("repro.observe.metrics", "MetricsRegistry"),
    "save_snapshot": ("repro.observe.metrics", "save_snapshot"),
    "load_snapshot": ("repro.observe.metrics", "load_snapshot"),
    "EventLog": ("repro.observe.events", "EventLog"),
    "Event": ("repro.observe.events", "Event"),
    "Trace": ("repro.observe.trace", "Trace"),
    "TraceEvent": ("repro.observe.trace", "TraceEvent"),
    "FakeTraceBackend": ("repro.observe.trace", "FakeTraceBackend"),
    "AnomalyConfig": ("repro.observe.anomaly", "AnomalyConfig"),
    "StepTimeAnomalyDetector": ("repro.observe.anomaly",
                                "StepTimeAnomalyDetector"),
    "ReplanTrigger": ("repro.observe.triggers", "ReplanTrigger"),
    "TriggerContext": ("repro.observe.triggers", "TriggerContext"),
    "CadenceTrigger": ("repro.observe.triggers", "CadenceTrigger"),
    "AnomalyTrigger": ("repro.observe.triggers", "AnomalyTrigger"),
    "FingerprintTrigger": ("repro.observe.triggers", "FingerprintTrigger"),
    "HealthTrigger": ("repro.observe.triggers", "HealthTrigger"),
    "default_triggers": ("repro.observe.triggers", "default_triggers"),
}

__all__ = sorted(_LAZY)


def __getattr__(name: str):
    import importlib
    target = _LAZY.get(name)
    if target is None:
        raise AttributeError(f"module 'repro.observe' has no attribute "
                             f"{name!r}")
    if isinstance(target, str):
        return importlib.import_module(target)
    mod, attr = target
    return getattr(importlib.import_module(mod), attr)
