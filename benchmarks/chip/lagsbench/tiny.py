"""Small cells for the CPU tests: a configuration file's keys with tiny
widths, and a traffic mix of short sequences."""
from __future__ import annotations

import json

from lagsbench.spec import BENCH_DIR

WIDTHS = {"hidden_size": 64, "num_attention_heads": 4, "head_dim": 16,
          "num_hidden_layers": 2, "vocab_size": 256}


def cell(config: str = "mistral-7b", traffic: str = "lags-c1000-b1x4096",
         chips: int = 1, dtype: str = "float32", limits: str | None = None,
         ratio: float = 20.0) -> dict:
    """A cell with ``config``'s keys at tiny widths.  ``dtype`` sets the
    program's compute and parameter type; ``limits`` names the cell whose
    limits it is held to (default: a zero limit on every number)."""
    with open(BENCH_DIR / "configs" / f"{config}.json") as f:
        cfg = json.load(f)
    cfg.update(WIDTHS)
    if int(cfg.get("num_experts", 0)):
        cfg.update(num_key_value_heads=4, intermediate_size=32,
                   num_experts=8, num_experts_per_tok=2)
    else:
        cfg.update(num_key_value_heads=2, intermediate_size=96)
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
            "traffic": tr, "limits": lim,
            "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}
