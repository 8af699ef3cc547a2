"""Device time of each phase of the train step, from the op names.

The program runs each phase of its step under a ``lags/<phase>`` named
scope, so the compiled step's ``op_name`` of an op carries its phase
(``repro.observe.names.phase_of``): ``fwd``, ``bwd`` (``fwd`` under
autodiff's ``transpose(``), ``exchange`` with its parts ``select`` and
``scatter_mean``, ``apply`` and ``health``.  An op XLA made itself
(a layout copy, an async copy, some fusions) has no ``op_name``; it
takes the phase of the first of its operands that has one, whose result
it moves or finishes, or else of the first op that consumes it, whose
input it prepares.  What stays without a phase is the unattributed
remainder.

Each op is clipped to the traced window and counted by its self time
(``xplane.self_times``), so the step phases and the remainder add up to
the chip's busy time.  Times are milliseconds per step, mean over chips.
A program without the scopes (no op carries a phase) gives ``None``.
"""
from __future__ import annotations

import collections
import functools
import re

from lagsbench import xplane

#: Ops that no phase scope reaches.
UNATTRIBUTED = "unattributed"

#: An operand in an ``XLA Ops`` event's text: ``... fusion(bf16[..] %x.1)``.
_OPERAND = re.compile(r" %([\w.\-]+)")


def _names(trace):
    """The program's name grammar, or None where it has no phase scopes
    or the trace has nothing to read."""
    from repro.observe import names
    if not hasattr(names, "phase_of") or not trace.device_ops \
            or trace.steps == 0:
        return None
    return names


def _attributed(trace, names, ops) -> list:
    """(instruction, self seconds, phase or None) of one chip's ops,
    clipped to the window, in time order (producers before consumers)."""
    ws, we = trace.window
    timed = [(xplane.instruction(n), n, st) for s, e, n, st in
             xplane.self_times([(max(s, ws), min(e, we), n)
                                for s, e, n in ops if e > ws and s < we])]
    phase: dict = {}
    consumers = collections.defaultdict(list)
    for inst, event_name, _ in timed:
        if inst in phase:
            continue
        operands = _OPERAND.findall(event_name.split(" = ", 1)[-1])
        for o in operands:
            consumers[o].append(inst)
        if inst in trace.op_names:
            phase[inst] = names.phase_of(trace.op_names[inst])
        else:
            phase[inst] = next((phase[o] for o in operands
                                if phase.get(o) is not None), None)
    # latest first, so a chain (copy-start -> copy-done -> op) resolves
    for inst in reversed(list(phase)):
        if phase[inst] is None and inst not in trace.op_names:
            phase[inst] = next((phase[c] for c in consumers[inst]
                                if phase[c] is not None), None)
    return [(inst, st, phase[inst]) for inst, _, st in timed]


@functools.lru_cache(maxsize=1)
def per_step(trace) -> dict | None:
    """{phase: ms per step} over ``names.STEP_PHASES`` (which add up to
    busy time with :data:`UNATTRIBUTED`) and ``names.EXCHANGE_PARTS``;
    None where no op carries a phase.  Cached for the last trace: every
    phase's reader asks for it."""
    names = _names(trace)
    if names is None:
        return None
    total: collections.Counter = collections.Counter()
    found = False
    for ops in trace.device_ops.values():
        for _, st, phase in _attributed(trace, names, ops):
            found = found or phase is not None
            total[names.step_phase(phase) or UNATTRIBUTED] += st
            if phase in names.EXCHANGE_PARTS:
                total[phase] += st
    if not found:
        return None
    scale = 1e3 / (len(trace.device_ops) * trace.steps)
    keys = names.STEP_PHASES + names.EXCHANGE_PARTS + (UNATTRIBUTED,)
    return {k: total[k] * scale for k in keys}


def unattributed_ops(trace, n: int = 5) -> list | None:
    """The ``n`` largest unattributed ops on the first chip, as
    ``[opcode, op_name or "", ms per step]``; None as :func:`per_step`."""
    if per_step(trace) is None:
        return None
    names = _names(trace)
    acc: collections.Counter = collections.Counter()
    ops = trace.device_ops[min(trace.device_ops)]
    for inst, st, phase in _attributed(trace, names, ops):
        if phase is None:
            acc[(xplane.opcode(inst), trace.op_names.get(inst, ""))] += st
    return [[op, name, 1e3 * s / trace.steps]
            for (op, name), s in acc.most_common(n)]


def _gaps(trace, ops) -> list:
    """Idle gaps of one chip inside the window, as (start, end)."""
    ws, we = trace.window
    gaps, cur = [], ws
    for s, e in xplane.merge([(s, e) for s, e, _ in ops]):
        if s > cur:
            gaps.append((cur, min(s, we)))
        cur = max(cur, e)
    if cur < we:
        gaps.append((cur, we))
    return [(s, e) for s, e in gaps if e > s]


def sync_idle_per_step(trace) -> float | None:
    """Milliseconds per step, mean over chips, of the device idle the
    program's loss read leaves: for each ``lags/host/loss_sync`` host
    span, the longest idle gap that meets it, which runs from the
    step's last op through the host's return from the read, its
    bookkeeping and the next dispatch.  The longest, not the last: the
    host and device clocks of a trace differ by up to a millisecond, so
    the next step's first ops can appear before the read returns.
    None where the trace has no such span."""
    names = _names(trace)
    if names is None:
        return None
    span = names.host_name("loss_sync")
    syncs = sorted((s, e) for s, e, n in trace.host_events if n == span)
    if not syncs:
        return None
    per_chip = []
    for ops in trace.device_ops.values():
        gaps = _gaps(trace, ops)
        hit = set()
        for a, b in syncs:
            meets = [g for g in gaps if g[0] < b and g[1] > a]
            if meets:
                hit.add(max(meets, key=lambda g: g[1] - g[0]))
        per_chip.append(sum(e - s for s, e in hit))
    return 1e3 * sum(per_chip) / len(per_chip) / trace.steps
