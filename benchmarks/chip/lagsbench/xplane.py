"""Capture of a profiler trace around the window, and its reduction.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` alone.  What it carries on a TPU v5e:

- one plane per chip, ``/device:TPU:<id>``, whose ``XLA Ops`` line holds
  one event per executed HLO instruction, named by the instruction's
  text (``%fusion.12 = bf16[...] fusion(...), ...``); a loop's ``while``
  event contains the events of its body;
- the host plane ``/host:CPU``, whose lines hold the host threads'
  annotations (the benchmark's ``bench/...`` spans among them), on the
  same clock as the device events.

Busy time of a chip is the union of its op intervals; idle gaps are the
holes in that union inside the window.  Each gap is labelled by the
innermost host event that covers its middle, so a gap reads as what the
host was doing meanwhile.
"""
from __future__ import annotations

import collections
import glob
import os
import re
import shutil
import tempfile

COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all)")


def merge(intervals: list) -> list:
    """Union of [start, end) intervals, as sorted disjoint intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def measure(intervals: list) -> float:
    return sum(e - s for s, e in merge(intervals))


def subtract(a: list, b: list) -> float:
    """Measure of the union of ``a`` minus the union of ``b``."""
    a, b = merge(a), merge(b)
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def instruction(event_name: str) -> str:
    """``%fusion.12 = ...`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def opcode(inst: str) -> str:
    """``all-gather-start.3`` -> ``all-gather-start``."""
    return re.sub(r"\.\d+$", "", inst)


def leaves(events: list) -> list:
    """Events that contain no other event (loop bodies' ops, not the
    loop).  ``events``: (start, end, name) sorted by start."""
    evs = sorted(events, key=lambda x: (x[0], -x[1]))
    out = []
    for i, (s, e, n) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[0] < e and nxt[1] <= e:
            continue
        out.append((s, e, n))
    return out


def self_times(events: list) -> list:
    """(start, end, name, self seconds) with nested time taken out."""
    evs = sorted(events, key=lambda x: (x[0], -x[1]))
    stack: list = []
    recs = []
    for s, e, n in evs:
        while stack and stack[-1][1] <= s:
            stack.pop()
        rec = [s, e, n, e - s]
        if stack:
            stack[-1][3] -= min(e, stack[-1][1]) - s
        stack.append(rec)
        recs.append(rec)
    return [tuple(r) for r in recs]


class Reduced:
    """Reduction of one trace; times in seconds on the trace's clock."""

    def __init__(self, device_ops: dict, host_events: list, window: tuple,
                 steps: int, op_names: dict | None = None):
        self.device_ops = device_ops        # device id -> [(s, e, name)]
        self.host_events = host_events      # [(s, e, name)]
        self.window = window                # (start, end)
        self.steps = steps
        self.op_names = op_names or {}      # instruction -> op_name
        ws, we = window
        self.window_s = we - ws
        busy = [measure([(max(s, ws), min(e, we)) for s, e, _ in ops
                         if e > ws and s < we])
                for ops in device_ops.values()]
        self.busy_s = sum(busy) / len(busy) if busy else 0.0

    def idle_gaps(self, device=None) -> list:
        dev = device if device is not None else min(self.device_ops)
        ws, we = self.window
        spans = merge([(s, e) for s, e, _ in self.device_ops[dev]])
        gaps, cur = [], ws
        for s, e in spans:
            if s > cur:
                gaps.append((cur, min(s, we)))
            cur = max(cur, e)
        if cur < we:
            gaps.append((cur, we))
        return [(s, e, self.host_label((s + e) / 2)) for s, e in gaps
                if e > s]

    def host_label(self, t: float) -> str:
        covering = [(e - s, n) for s, e, n in self.host_events if s <= t < e]
        return min(covering)[1] if covering else "no host span"

    def collective_exposed_s(self) -> float:
        """Per chip, mean over chips: time in collective ops during which
        no other op runs on that chip."""
        per = []
        for ops in self.device_ops.values():
            leaf = leaves(ops)
            coll = [(s, e) for s, e, n in leaf
                    if COLLECTIVE.match(opcode(instruction(n)))]
            rest = [(s, e) for s, e, n in leaf
                    if not COLLECTIVE.match(opcode(instruction(n)))]
            per.append(subtract(coll, rest))
        return sum(per) / len(per) if per else 0.0

    def top_ops(self, n: int = 10) -> list:
        """Device ops by self time on the first chip, keyed by the HLO
        op_name where the compiled program gives one."""
        dev = min(self.device_ops)
        acc: collections.Counter = collections.Counter()
        for s, e, name, st in self_times(self.device_ops[dev]):
            inst = instruction(name)
            acc[self.op_names.get(inst) or opcode(inst)] += st
        return [[k, v] for k, v in acc.most_common(n)]

    def breakdown(self) -> dict:
        if not self.device_ops:
            return {"device_ops": [], "idle_gaps": []}
        gaps = sorted(((e - s, lab) for s, e, lab in self.idle_gaps()),
                      reverse=True)[:10]
        return {"device_ops": self.top_ops(10),
                "idle_gaps": [[lab, d] for d, lab in gaps]}


def op_names_from_hlo(hlo_text: str) -> dict:
    """instruction name -> op_name metadata of a compiled HLO module."""
    out = {}
    for m in re.finditer(r'^\s*(?:ROOT )?%(\S+) = [^\n]*?op_name="([^"]*)"',
                         hlo_text, re.M):
        out[m.group(1)] = m.group(2)
    return out


def read(path: str, device_ids: list, span_prefix: str, steps: int = 0,
         op_names=None) -> Reduced:
    """Reduce an ``.xplane.pb``.  The window runs from the first to the
    last host span whose name starts with ``span_prefix``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops: dict = {}
    host: list = []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m and int(m.group(1)) in device_ids:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_ops[int(m.group(1))] = [
                        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                         e.name) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9, e.name)
                            for e in line.events)
    ours = [(s, e) for s, e, n in host if n.startswith(span_prefix)]
    window = (min(s for s, _ in ours), max(e for _, e in ours))
    return Reduced(device_ops, host, window, steps, op_names)


class Capture:
    """A profiler trace of the window, kept in a temporary directory."""

    def __init__(self):
        self.dir = None

    def start(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="lagsbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def reduce(self, prog, batch, span_prefix: str, steps: int) -> Reduced:
        """Reduce the captured trace; the window spans the benchmark's
        ``span_prefix`` host spans.  Deletes the trace."""
        try:
            path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                             recursive=True)[0]
            ids = [d.id for d in prog.devices]
            hlo = prog.compiled_text(batch)
            return read(path, ids, span_prefix, steps=steps,
                        op_names=op_names_from_hlo(hlo))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
