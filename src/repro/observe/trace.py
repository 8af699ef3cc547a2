"""Trace instrumentation and the deterministic fake-trace backend.

``annotation(name)`` (host-side ``TraceAnnotation``) and
``device_annotation(name)`` (``jax.named_scope``, usable inside jit)
are the two instrumentation primitives.  Real captures need no code
here: a ``jax.profiler`` trace of ``api.Session.run`` carries the
``repro.observe.names`` grammar in the compiled ops' ``op_name``
(``lags/fwd``, ``lags/exchange``, ``lags/select/l<i>``, ...) and in the
host plane (``lags/step``, ``lags/host/...``), and
``benchmarks/chip/lagsbench/xplane.py`` reduces the ``.xplane.pb`` with
``jax.profiler.ProfileData``.

:class:`FakeTraceBackend` synthesizes a :class:`Trace` of named
:class:`TraceEvent`\\ s from the α–β cost model: per-leaf backward
events from measured budgets, per-leaf collective events priced on the
live wire, and a step event from the pipelined LAGS timeline
(``cm.iteration_time_lags``).  It is **deterministic**, so CPU/CI runs
of :mod:`repro.observe.attribution`, the runtime controller and the
benchmarks see an injectable wire with no wall-clock noise.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
from typing import Any, Callable, Sequence

from repro.core import comm_model as cm
from repro.observe import names


@dataclasses.dataclass(frozen=True)
class TraceEvent:
    """One named span: ``t_start``/``dur`` in seconds on a common clock."""
    name: str
    t_start: float
    dur: float


@dataclasses.dataclass(frozen=True)
class Trace:
    """A bag of events plus provenance; JSON round-trippable."""
    events: tuple[TraceEvent, ...]
    meta: dict = dataclasses.field(default_factory=dict)

    def named(self, prefix: str) -> list[TraceEvent]:
        return [e for e in self.events if e.name.startswith(prefix)]

    def to_json(self) -> str:
        return json.dumps({"meta": self.meta,
                           "events": [dataclasses.asdict(e)
                                      for e in self.events]},
                          indent=1, sort_keys=True)

    @staticmethod
    def from_json(text: str) -> "Trace":
        obj = json.loads(text)
        return Trace(events=tuple(TraceEvent(**e) for e in obj["events"]),
                     meta=dict(obj.get("meta", {})))


def annotation(name: str):
    """Host-side profiler annotation (no-op when jax lacks the API)."""
    import jax
    cls = getattr(jax.profiler, "TraceAnnotation", None)
    return cls(name) if cls is not None else contextlib.nullcontext()


def device_annotation(name: str):
    """In-jit annotation: names the HLO ops traced under it, so real
    device profiles carry the ``repro.observe.names`` grammar."""
    import jax
    return jax.named_scope(name)


def phase_scope(phase: str, label: str = ""):
    """In-jit ``lags/<phase>[/<label>]`` scope over one phase of the
    train step: every op traced under it carries the phase in its
    ``op_name``, where ``names.phase_of`` reads it back."""
    return device_annotation(names.phase_name(phase, label))


# ---------------------------------------------------------------------------
# deterministic fake backend (CPU / CI)
# ---------------------------------------------------------------------------

class FakeTraceBackend:
    """Synthesizes the trace an annotated step *would* produce.

    Deterministic by construction — durations come from the α–β model of
    the **live** wires, so CI can inject a mid-run bandwidth regression
    by mutating ``wires`` and every downstream consumer (attribution →
    costfit → planner, the anomaly detector) sees exactly the physics
    the injection implies, with zero wall-clock noise.

    Args:
      leaves: backprop-ordered objects with ``name``/``d``/``t_backward``
        (``profiler.LeafSample`` — budgets are the per-leaf backward
        durations emitted as ``bwd`` events).
      wires: ``{tier: cm.Hardware}`` — a LIVE mapping; callers mutate it
        to shift a tier's wire mid-run.
      tier_workers: ``{tier: worker count}`` for the same tiers.
      t_forward: forward-pass duration (seconds) for the ``fwd`` event.
      schedule_fn: ``() -> Schedule | HierSchedule | None`` — the live
        plan; per-leaf ratios price each tier's collective (a flat
        schedule prices the ``flat``/``outer`` tier; ``None`` falls back
        to ``static_ratio``, today's uniform ``cfg.compression_ratio``).
      static_ratio: ratio used when no schedule is live (1.0 = dense).
      wave_fn: optional ``() -> repro.pipeline.WaveSchedule | None`` —
        when it returns a schedule, :meth:`capture` synthesizes the
        *wave-pipelined* step instead: one aggregated collective per
        (wave, tier) — allreduce for the wave's dense leaves, allgather
        for its sparse ones — starting at ``max(wave readiness, wire
        free)`` (``pipeline="async1"`` drops the readiness gate: the
        payload is the previous step's), and the step event ends at
        ``max(compute end, last wire end)``.  ``None`` (the default, and
        a ``wave_fn`` returning None) keeps the classic per-leaf
        synthesis byte-for-byte.
    """

    def __init__(self, leaves: Sequence, wires: dict,
                 tier_workers: dict, *, t_forward: float,
                 schedule_fn: Callable[[], Any] | None = None,
                 static_ratio: float = 1.0,
                 wave_fn: Callable[[], Any] | None = None):
        self.leaves = tuple(leaves)
        self.wires = wires
        self.tier_workers = dict(tier_workers)
        self.t_forward = float(t_forward)
        self.schedule_fn = schedule_fn or (lambda: None)
        self.static_ratio = float(static_ratio)
        self.wave_fn = wave_fn or (lambda: None)

    def _tier_ratios(self) -> dict[str, dict[str, float]]:
        sched = self.schedule_fn()
        fallback = {l.name: self.static_ratio for l in self.leaves}
        if sched is None:
            return {t: fallback for t in self.wires}
        tiers = getattr(sched, "tiers", None)
        if tiers is not None:
            by_tier = {t: {lp.name: lp.ratio for lp in s.leaves}
                       for t, s in tiers.items()}
            # the inner tier of a HierSchedule prices "inner"; anything
            # else (flat/outer wires) prices on the sparse outer tier
            return {t: by_tier.get("inner" if t == "inner" else "outer",
                                   fallback)
                    for t in self.wires}
        flat = {lp.name: lp.ratio for lp in sched.leaves}
        # a flat schedule plans the sparse exchange: price the flat/outer
        # wires with it; an intra-pod tier it never planned stays static
        return {t: (fallback if t == "inner" else flat) for t in self.wires}

    def _comm_event(self, leaf, tier: str, ratio: float,
                    t_start: float) -> TraceEvent | None:
        p = int(self.tier_workers.get(tier, 1))
        if p <= 1:
            return None
        hw = self.wires[tier]
        if ratio <= 1.0:
            kind, nbytes = "allreduce", 4.0 * leaf.d
            t = cm.allreduce_time(nbytes, p, hw)
        else:
            k = max(1, int(round(leaf.d / ratio)))
            kind, nbytes = "allgather", 8.0 * k   # fp32 values + int32 idx
            t = cm.allgather_time(nbytes, p, hw)
        return TraceEvent(
            name=names.comm_name(tier, kind, leaf.name, nbytes=nbytes, p=p),
            t_start=t_start, dur=t)

    def _capture_waves(self, waves, ratios, step: int) -> Trace:
        """Wave-pipelined synthesis (see ``wave_fn``): collectives start
        when their wave's last gradient lands AND the tier's wire is
        free; exposed comm is whatever sticks out past compute."""
        by_name = {l.name: l for l in self.leaves}
        events = [TraceEvent(names.FWD, 0.0, self.t_forward)]
        clock = self.t_forward
        ready: dict[str, float] = {}
        for leaf in self.leaves:
            events.append(TraceEvent(names.bwd_name(leaf.name), clock,
                                     leaf.t_backward))
            clock += leaf.t_backward
            ready[leaf.name] = clock
        comp_end = clock
        asynchronous = getattr(waves, "pipeline", "wave") == "async1"
        wire_clock = {t: 0.0 for t in self.wires}
        for w_no, wave in enumerate(waves.waves):
            wleaves = [by_name[nm] for nm in wave.names if nm in by_name]
            if not wleaves:
                continue
            # async1 ships the PREVIOUS step's payload: nothing to wait on
            t_ready = (0.0 if asynchronous
                       else max(ready[l.name] for l in wleaves))
            label = f"wave{w_no}"
            for tier in self.wires:
                p = int(self.tier_workers.get(tier, 1))
                if p <= 1:
                    continue
                hw = self.wires[tier]
                dense_d = sparse_k = 0
                for l in wleaves:
                    r = ratios[tier].get(l.name, 1.0)
                    if r <= 1.0:
                        dense_d += l.d
                    else:
                        sparse_k += max(1, int(round(l.d / r)))
                start = max(t_ready, wire_clock[tier])
                if dense_d:
                    nbytes = 4.0 * dense_d
                    t = cm.allreduce_time(nbytes, p, hw)
                    events.append(TraceEvent(
                        names.comm_name(tier, "allreduce", label,
                                        nbytes=nbytes, p=p), start, t))
                    start += t
                if sparse_k:
                    nbytes = 8.0 * sparse_k   # fp32 values + int32 idx
                    t = cm.allgather_time(nbytes, p, hw)
                    events.append(TraceEvent(
                        names.comm_name(tier, "allgather", label,
                                        nbytes=nbytes, p=p), start, t))
                    start += t
                wire_clock[tier] = start
        t_step = max(comp_end, max(wire_clock.values(), default=comp_end))
        events.insert(0, TraceEvent(names.STEP, 0.0, t_step))
        return Trace(events=tuple(events),
                     meta={"backend": "fake", "step": int(step),
                           "pipeline": getattr(waves, "pipeline", "wave")})

    def capture(self, step: int = 0) -> Trace:
        """One instrumented step's worth of events (pure function of the
        live wires/schedule — the ``step`` argument is provenance only)."""
        ratios = self._tier_ratios()
        waves = self.wave_fn()
        if waves is not None:
            return self._capture_waves(waves, ratios, step)
        events = [TraceEvent(names.FWD, 0.0, self.t_forward)]
        clock = self.t_forward
        t_b, t_c = [], []
        for leaf in self.leaves:
            events.append(TraceEvent(names.bwd_name(leaf.name), clock,
                                     leaf.t_backward))
            clock += leaf.t_backward
            leaf_comm = 0.0
            for tier in self.wires:
                ev = self._comm_event(leaf, tier,
                                      ratios[tier].get(leaf.name, 1.0),
                                      clock)
                if ev is not None:
                    events.append(ev)
                    leaf_comm += ev.dur
            t_b.append(leaf.t_backward)
            t_c.append(leaf_comm)
        t_step = cm.iteration_time_lags(self.t_forward, t_b, t_c)
        events.insert(0, TraceEvent(names.STEP, 0.0, t_step))
        return Trace(events=tuple(events),
                     meta={"backend": "fake", "step": int(step)})
