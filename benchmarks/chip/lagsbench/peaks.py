"""Published per-chip peaks, keyed by ``device_kind`` (``peaks.json``)."""
from __future__ import annotations

import json

from lagsbench.spec import BENCH_DIR


def lookup(device_kind: str, path=BENCH_DIR / "peaks.json") -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{path}; known: {sorted(table['devices'])}") from None
