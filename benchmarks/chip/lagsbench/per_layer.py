"""Per-layer metrics: one reader file per metric, ``metrics/<name>.py``,
found by the metric's name in ``BENCHMARK.json``.

A reader is ``read(ctx) -> float | None``.  ``None`` means it found
nothing to read, and the metric is left out of the line.
"""
from __future__ import annotations

import dataclasses

from lagsbench.reference import load_module
from lagsbench.spec import BENCH_DIR


@dataclasses.dataclass
class Context:
    cell: dict                  # spec.load_cell(...)
    trace: object               # xplane.Reduced of the traced window
    peaks: dict | None          # peaks.lookup(device_kind)
    tokens_per_step: int


def read_all(cell: dict, ctx: Context) -> dict:
    out = {}
    for m in cell["per_layer"]:
        value = load_module(BENCH_DIR / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
