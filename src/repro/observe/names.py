"""Annotation-name vocabulary shared by every trace producer/consumer.

A trace event is attributed purely from its *name*, so the exchange code
(`core.lags` named scopes), the deterministic fake backend
(:class:`~repro.observe.trace.FakeTraceBackend`) and real
``jax.profiler`` captures all speak one string grammar:

  * ``lags/step``                         — one whole train step
                                            (a ``StepTraceAnnotation``
                                            per ``Session.run`` step)
  * ``lags/fwd``                          — the forward pass; in a
                                            compiled step the scope
                                            around autodiff, so its
                                            backward ops sit inside
                                            ``transpose(``
  * ``lags/<phase>[/<label>]``            — one phase of the compiled
                                            step (:data:`PHASES`:
                                            ``exchange``, ``select/l<i>``,
                                            ``scatter_mean/l<i>``,
                                            ``apply``, ``health``);
                                            :func:`phase_of` reads it
                                            back from an op's ``op_name``
  * ``lags/host/<span>``                  — one host phase of a
                                            ``Session.run`` step
                                            (:data:`HOST_SPANS`)
  * ``lags/bwd/<leaf path>``              — one leaf's backward compute
  * ``lags/comm/<tier>/<kind>/<label>?nbytes=<B>&p=<P>``
                                          — one collective (per bucket /
                                            per leaf / per wave); ``tier``
                                            is ``flat`` | ``inner`` |
                                            ``outer``, ``kind`` is
                                            ``allgather`` | ``allreduce``
  * ``lags/overlap/<label>``              — overlap-attribution span
                                            labels: the ``span`` label
                                            value of the
                                            ``train_overlap_comm_seconds``
                                            gauge family
                                            (``repro.pipeline.overlap``)
  * ``lags/health/<kind>/<label>``        — convergence-health quantity
                                            (``repro.observe.health``);
                                            ``kind`` is one of
                                            :data:`HEALTH_KINDS` and
                                            ``label`` is a leaf path or a
                                            ``<tier>/<leaf path>`` pair
  * ``serve/<kind>/<label>?version=<V>``  — serving-path work
                                            (``repro.stream``); ``kind``
                                            is one of :data:`SERVE_KINDS`

Leaf paths may themselves contain ``/`` (``layers/0/attn/wq``): the
``bwd`` payload is everything after the prefix, and the ``comm`` label
is everything after the third slash-separated field.  ``nbytes``/``p``
ride in the name because a device annotation has no other side channel
for metadata — :func:`parse` recovers them for
``repro.observe.attribution``.

This module is import-leaf (stdlib only) so ``repro.core`` can annotate
collectives without pulling the rest of the observe package — or any
cycle — into its import graph.
"""
from __future__ import annotations

import re

STEP = "lags/step"
FWD = "lags/fwd"
BWD_PREFIX = "lags/bwd/"
COMM_PREFIX = "lags/comm/"
OVERLAP_PREFIX = "lags/overlap/"
HEALTH_PREFIX = "lags/health/"
SERVE_PREFIX = "serve/"
HOST_PREFIX = "lags/host/"

#: Phases of the compiled train step, each a ``jax.named_scope``
#: ``lags/<phase>[/<label>]``.  ``select`` and ``scatter_mean`` sit inside
#: ``exchange`` and carry the leaf label the comm scopes use (``l<i>``);
#: ``bwd`` is never a scope: it is ``fwd`` under autodiff's
#: ``transpose(``.
EXCHANGE = "exchange"
SELECT = "select"
SCATTER_MEAN = "scatter_mean"
APPLY = "apply"
HEALTH = "health"
PHASES = ("fwd", EXCHANGE, SELECT, SCATTER_MEAN, APPLY, HEALTH)
#: Phases that together make up the step, each op in exactly one.
STEP_PHASES = ("fwd", "bwd", EXCHANGE, APPLY, HEALTH)
#: Phases that are parts of ``exchange``.
EXCHANGE_PARTS = (SELECT, SCATTER_MEAN)

#: Host spans of one ``Session.run`` step: the ``data_fn`` call, the
#: step's dispatch, the ``float(loss)`` sync, and the rest of the
#: iteration (metrics, logging, checkpoints).
HOST_SPANS = ("data", "dispatch", "loss_sync", "bookkeeping")

_PHASE_RE = re.compile(r"lags/(" + "|".join(PHASES) + r")(?=[/)]|$)")

#: Tier vocabulary: flat data-parallel wire, intra-pod ICI, cross-pod DCN.
TIERS = ("flat", "inner", "outer")

#: Serve-side work kinds (``repro.stream`` subscriber): prompt prefill,
#: one-token decode, a delta-packet apply, a full-checkpoint resync, and
#: a rollout-guard quality eval.
SERVE_KINDS = ("prefill", "decode", "apply", "resync", "eval")

#: Convergence-health kinds (``repro.observe.health``): the online
#: per-leaf Assumption-1 ratio (Eq. 20), EF-residual energy retention,
#: and the async1 one-step staleness gap.
HEALTH_KINDS = ("delta", "ef_energy", "staleness")


def phase_name(phase: str, label: str = "") -> str:
    """``lags/<phase>[/<label>]`` — the named scope of one step phase."""
    if phase not in PHASES:
        raise ValueError(f"unknown phase {phase!r}; expected one of "
                         f"{PHASES}")
    return f"lags/{phase}/{label}" if label else f"lags/{phase}"


def host_name(span: str) -> str:
    """``lags/host/<span>`` — one host phase of a ``Session.run`` step."""
    if span not in HOST_SPANS:
        raise ValueError(f"unknown host span {span!r}; expected one of "
                         f"{HOST_SPANS}")
    return HOST_PREFIX + span


def phase_of(op_name: str) -> str | None:
    """The step phase of a compiled op, from its ``op_name`` metadata.

    The last ``lags/<phase>`` component is the phase: a wave exchange
    inside the backward pass reads ``exchange`` (or ``select`` /
    ``scatter_mean``), not ``bwd``.  ``fwd`` reads ``bwd`` where the op
    sits under autodiff's ``transpose(`` (remat recompute included).
    ``None``: no phase scope reaches the op.
    """
    found = _PHASE_RE.findall(op_name)
    if not found:
        return None
    phase = found[-1]
    if phase == "fwd" and "transpose(" in op_name:
        return "bwd"
    return phase


def step_phase(phase: str | None) -> str | None:
    """The :data:`STEP_PHASES` entry a :func:`phase_of` result falls in
    (``select`` and ``scatter_mean`` are parts of ``exchange``)."""
    return EXCHANGE if phase in EXCHANGE_PARTS else phase


def bwd_name(leaf: str) -> str:
    return BWD_PREFIX + leaf


def overlap_name(label: str) -> str:
    """``lags/overlap/<label>`` — metric-label spelling for one
    collective's overlap attribution (``label`` is the same string the
    ``comm`` event carried)."""
    return OVERLAP_PREFIX + label


def health_name(kind: str, label: str = "") -> str:
    """``lags/health/<kind>/<label>`` — one convergence-health quantity.
    ``label`` is a leaf path (``layers/0/attn/wq``) or, for tiered
    quantities, ``<tier>/<leaf path>``."""
    return f"{HEALTH_PREFIX}{kind}/{label}"


def serve_name(kind: str, label: str = "", *,
               version: int | None = None) -> str:
    """``serve/<kind>/<label>[?version=<v>]`` — the serving-path analogue
    of the ``lags/`` training grammar.  ``version`` rides in the name for
    the same reason ``nbytes`` does on ``comm``: a device annotation has
    no other metadata side channel."""
    name = f"{SERVE_PREFIX}{kind}/{label}"
    if version is not None:
        name += f"?version={int(version)}"
    return name


def comm_name(tier: str, kind: str, label: str, *, nbytes: float,
              p: int) -> str:
    return (f"{COMM_PREFIX}{tier}/{kind}/{label}"
            f"?nbytes={float(nbytes):.6g}&p={int(p)}")


def parse(name: str) -> dict | None:
    """Structured view of an annotation name, or None for foreign names.

    Returns ``{"type": "step" | "fwd"}``, ``{"type": "bwd", "leaf": ...}``,
    ``{"type": "comm", "tier", "kind", "label", "nbytes", "p"}``,
    ``{"type": "overlap", "label": ...}``,
    ``{"type": "health", "kind", "label"}``,
    ``{"type": "phase", "phase", "label"}`` or
    ``{"type": "host", "span"}``.
    Malformed ``comm`` metadata parses as ``nbytes=0.0 / p=1`` rather
    than raising — a real profiler run may mangle suffixes, and a sample
    with no payload is simply dropped downstream.
    """
    if name == STEP:
        return {"type": "step"}
    if name == FWD:
        return {"type": "fwd"}
    if name.startswith(BWD_PREFIX):
        return {"type": "bwd", "leaf": name[len(BWD_PREFIX):]}
    if name.startswith(COMM_PREFIX):
        rest = name[len(COMM_PREFIX):]
        parts = rest.split("/", 2)
        if len(parts) != 3:
            return None
        tier, kind, tail = parts
        label, _, query = tail.partition("?")
        nbytes, p = 0.0, 1
        for field in query.split("&"):
            key, _, val = field.partition("=")
            try:
                if key == "nbytes":
                    nbytes = float(val)
                elif key == "p":
                    p = int(val)
            except ValueError:
                pass
        return {"type": "comm", "tier": tier, "kind": kind, "label": label,
                "nbytes": nbytes, "p": p}
    if name.startswith(HOST_PREFIX):
        span = name[len(HOST_PREFIX):]
        return {"type": "host", "span": span} if span in HOST_SPANS else None
    phase, _, label = name.partition("/")[2].partition("/")
    # ``lags/health/...`` is a health quantity; the scope is bare
    if (name.startswith("lags/") and phase in PHASES[1:]
            and (phase != HEALTH or name == "lags/" + HEALTH)):
        return {"type": "phase", "phase": phase, "label": label}
    if name.startswith(OVERLAP_PREFIX):
        return {"type": "overlap", "label": name[len(OVERLAP_PREFIX):]}
    if name.startswith(HEALTH_PREFIX):
        rest = name[len(HEALTH_PREFIX):]
        kind, _, label = rest.partition("/")
        if not kind:
            return None
        return {"type": "health", "kind": kind, "label": label}
    if name.startswith(SERVE_PREFIX):
        rest = name[len(SERVE_PREFIX):]
        parts = rest.split("/", 1)
        if len(parts) != 2:
            return None
        kind, tail = parts
        label, _, query = tail.partition("?")
        version = None
        for field in query.split("&"):
            key, _, val = field.partition("=")
            if key == "version":
                try:
                    version = int(val)
                except ValueError:
                    pass
        return {"type": "serve", "kind": kind, "label": label,
                "version": version}
    return None
