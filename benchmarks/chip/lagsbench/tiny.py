"""Small cells for the CPU tests: a configuration file's keys with tiny
widths, and a traffic mix of short sequences.

A configuration file may hold a ``tiny`` block: its own keys at test
widths, merged over the file (a nested dict such as ``program`` key by
key), so that an architecture brings its test widths with it.  A file
without the block gets ``WIDTHS`` and the dense or mixture-of-experts
widths below."""
from __future__ import annotations

import json
import pathlib

from lagsbench.spec import BENCH_DIR

WIDTHS = {"hidden_size": 64, "num_attention_heads": 4, "head_dim": 16,
          "num_hidden_layers": 2, "vocab_size": 256}
DENSE = {"num_key_value_heads": 2, "intermediate_size": 96}
MOE = {"num_key_value_heads": 4, "intermediate_size": 32, "num_experts": 8,
       "num_experts_per_tok": 2}


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over``'s keys in place, dicts merged key by key."""
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            v = merged(out[k], v)
        out[k] = v
    return out


def cell(config: str = "mistral-7b", traffic: str = "lags-c1000-b1x4096",
         chips: int = 1, dtype: str = "float32", limits: str | None = None,
         ratio: float = 20.0,
         configs_dir: pathlib.Path = BENCH_DIR / "configs") -> dict:
    """A cell with ``config``'s keys at tiny widths.  ``dtype`` sets the
    program's compute and parameter type; ``limits`` names the cell whose
    limits it is held to (default: zero on each of check's three numbers);
    ``configs_dir`` holds ``<config>.json``."""
    file = configs_dir / f"{config}.json"
    with open(file) as f:
        cfg = json.load(f)
    widths = cfg.pop("tiny", None)
    if widths is None:
        widths = {**WIDTHS,
                  **(MOE if int(cfg.get("num_experts", 0)) else DENSE)}
    cfg = merged(cfg, widths)
    cfg["program"] = dict(cfg["program"], dtype=dtype, param_dtype=dtype)
    with open(BENCH_DIR / "traffic" / f"{traffic}.json") as f:
        tr = json.load(f)
    tr["run"] = dict(tr["run"], block_size=128)
    if "ratio" in tr["run"]:
        tr["run"]["ratio"] = ratio
    tr.update(seq_len=128, pool_steps=4)
    lim = {"loss_gap": 0.0, "grad_norm_gap": 0.0, "change_norm_gap": 0.0}
    if limits is not None:
        with open(BENCH_DIR / "limits" / f"{limits}.json") as f:
            lim = json.load(f)["limits"]
    return {"name": f"tiny-{config}", "chips": chips, "config": cfg,
            "config_file": str(file), "traffic": tr, "limits": lim,
            "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}
