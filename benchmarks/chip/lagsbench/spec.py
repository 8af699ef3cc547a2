"""The data files of a cell, found by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix.
The configuration's file holds the model's sizes under the source's own
keys, as run; the traffic file holds the ``RunConfig`` of the job, its
batch and sequence shape; ``limits/<cell>.json`` holds the limits of the
numbers that decide ``correct``.  Adding a cell adds files, never edits
one of these loaders.
"""
from __future__ import annotations

import json
import pathlib

#: ``benchmarks/chip``: the directory that holds the benchmark.
BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
#: Root of the checkout (``BENCHMARK.json`` and ``src/`` live here).
REPO_DIR = BENCH_DIR.parents[1]


def _read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(repo_dir: pathlib.Path = REPO_DIR) -> dict:
    return _read_json(repo_dir / "BENCHMARK.json")


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    known = ", ".join(e["name"] for e in entries)
    raise KeyError(f"no {what} named {name!r}; known: {known}")


def load_cell(name: str, repo_dir: pathlib.Path = REPO_DIR) -> dict:
    """Everything a run of cell ``name`` reads from data files."""
    bench = load_benchmark(repo_dir)
    cell = find(bench["workloads"], name, "workload")
    cfg_entry = find(bench["configs"], cell["config"], "config")
    config = _read_json(repo_dir / cfg_entry["file"])
    traffic = _read_json(BENCH_DIR / "traffic" / f"{cell['traffic']}.json")
    limits = _read_json(BENCH_DIR / "limits" / f"{name}.json")
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    return {"name": name, "chips": int(cell["chips"]), "config": config,
            "traffic": traffic, "limits": limits["limits"],
            "end_to_end": end_to_end, "per_layer": per_layer}


def as_run(config: dict) -> dict:
    """The configuration as the program computes it: the source's keys,
    with the file's ``as_run`` values (departures the program has no
    option for) in their place.  The reference computes this."""
    return {**config, **config.get("as_run", {})}


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file.

    Every width comes from the file's source keys; the ``program`` block
    gives what the source has no key for (family, compute types)."""
    from repro.configs.base import ModelConfig
    prog = config["program"]
    n_experts = int(config.get("num_experts", 0))
    return ModelConfig(
        name=config["name"], family=prog["family"],
        n_layers=int(config["num_hidden_layers"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        d_ff=int(config["intermediate_size"]),
        vocab=int(config["vocab_size"]),
        head_dim=int(config["head_dim"]),
        activation=config["hidden_act"], gated_ffn=True, norm="rmsnorm",
        rope_theta=float(config["rope_theta"]),
        sliding_window=config.get("sliding_window"),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        n_experts=n_experts,
        moe_top_k=int(config.get("num_experts_per_tok", 0)),
        moe_period=1, moe_shard=prog.get("moe_shard", "ffn"),
        dtype=prog["dtype"], param_dtype=prog["param_dtype"],
        source=config["source"])
