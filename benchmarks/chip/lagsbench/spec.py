"""The data files of a cell, found by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix.
The configuration's file holds the model's sizes under the source's own
keys, as run; the traffic file holds the ``RunConfig`` of the job, its
batch and sequence shape; ``limits/<cell>.json`` holds the limits of the
numbers that decide ``correct``.  Adding a cell adds files, never edits
one of these loaders.
"""
from __future__ import annotations

import dataclasses
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
            "config_file": cfg_entry["file"],
            "traffic": traffic, "limits": limits["limits"],
            "end_to_end": end_to_end, "per_layer": per_layer}


def as_run(config: dict) -> dict:
    """The configuration as the program computes it: the source's keys,
    with the file's ``as_run`` values (departures the program has no
    option for) in their place.  The reference computes this."""
    return {**config, **config.get("as_run", {})}


#: The program's ``ModelConfig`` fields a configuration file's source
#: keys give, each worked out from the file only where ``program.model``
#: does not state the field.
DERIVED = {
    "n_layers": lambda c: int(c["num_hidden_layers"]),
    "d_model": lambda c: int(c["hidden_size"]),
    "n_heads": lambda c: int(c["num_attention_heads"]),
    "n_kv_heads": lambda c: int(c["num_key_value_heads"]),
    "d_ff": lambda c: int(c["intermediate_size"]),
    "vocab": lambda c: int(c["vocab_size"]),
    "head_dim": lambda c: int(c["head_dim"]),
    "activation": lambda c: c["hidden_act"],
    "gated_ffn": lambda c: True,
    "norm": lambda c: "rmsnorm",
    "rope_theta": lambda c: float(c["rope_theta"]),
    "sliding_window": lambda c: c.get("sliding_window"),
    "tie_embeddings": lambda c: bool(c["tie_word_embeddings"]),
    "n_experts": lambda c: int(c.get("num_experts", 0)),
    "moe_top_k": lambda c: int(c.get("num_experts_per_tok", 0)),
    "moe_period": lambda c: 1,
    "moe_shard": lambda c: c["program"].get("moe_shard", "ffn"),
    "dtype": lambda c: c["program"]["dtype"],
    "param_dtype": lambda c: c["program"]["param_dtype"],
    "source": lambda c: c["source"],
}


def model_config(config: dict, file: str | None = None):
    """The program's ``ModelConfig`` for a configuration file.

    Every width comes from the file's source keys (``DERIVED``); the
    ``program`` block gives what the source has no key for (family,
    compute types), and its optional ``model`` dict sets ``ModelConfig``
    fields by name over what is derived: the fields of an architecture
    whose source keys the derivation does not know.  ``file`` names the
    file in errors."""
    from repro.configs.base import ModelConfig
    where = file or f"configuration {config['name']!r}"
    prog = config["program"]
    stated = prog.get("model", {})
    known = {f.name for f in dataclasses.fields(ModelConfig)}
    for field in stated:
        if field not in known:
            raise ValueError(f"{where}: program.model field {field!r} is "
                             f"no field of the program's ModelConfig")
    fields = {"name": config["name"], "family": prog["family"]}
    for field, derive in DERIVED.items():
        if field in stated:
            continue
        try:
            fields[field] = derive(config)
        except KeyError as e:
            raise KeyError(f"{where}: no source key {e.args[0]!r} for the "
                           f"program's {field!r}, and program.model does "
                           f"not state {field!r}") from None
    for field, value in stated.items():
        # JSON has no tuples; a ModelConfig is hashed, so no lists
        fields[field] = tuple(value) if isinstance(value, list) else value
    return ModelConfig(**fields)
