"""Configuration files against their sources, and the harness's view of
the program's parameters."""
import dataclasses
import json

import jax
import pytest

from lagsbench import spec, weights
from lagsbench.spec import BENCH_DIR, REPO_DIR



def _is_width(key: str) -> bool:
    return (key.endswith(("_dim", "_rank")) or "expansion" in key
            or (key.endswith("_size") and key != "vocab_size")
            or key == "num_experts_per_tok")


def _bench():
    with open(REPO_DIR / "BENCHMARK.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["mistral-7b", "olmoe-1b-7b"])
def test_model_config_has_the_files_widths(name):
    with open(BENCH_DIR / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    mc = spec.model_config(cfg)
    assert mc.d_model == cfg["hidden_size"] == cfg["published"]["hidden_size"]
    assert mc.d_ff == cfg["intermediate_size"] \
        == cfg["published"]["intermediate_size"]
    assert mc.n_heads == cfg["num_attention_heads"] \
        == cfg["published"]["num_attention_heads"]
    assert mc.n_kv_heads == cfg["num_key_value_heads"] \
        == cfg["published"]["num_key_value_heads"]
    assert mc.hd == cfg["head_dim"] == cfg["hidden_size"] \
        // cfg["num_attention_heads"]
    assert mc.vocab == cfg["vocab_size"] == cfg["published"]["vocab_size"]
    assert mc.rope_theta == cfg["rope_theta"] == cfg["published"]["rope_theta"]
    assert mc.n_layers == cfg["num_hidden_layers"]
    assert mc.tie_embeddings is False
    assert mc.n_experts == cfg.get("num_experts", 0) \
        == cfg["published"].get("num_experts", 0)
    assert mc.moe_top_k == cfg.get("num_experts_per_tok", 0) \
        == cfg["published"].get("num_experts_per_tok", 0)


@pytest.mark.parametrize("name", ["mistral-7b", "olmoe-1b-7b"])
def test_every_changed_key_is_listed_as_reduced(name):
    with open(BENCH_DIR / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"])
    for k, why in cfg["reduced"].items():
        assert why["published"] == cfg["published"][k]
        assert not _is_width(k), k
    for k in cfg.get("as_run", {}):
        assert any(d.startswith(f"{k}:") for d in cfg["departures"]), k
    for entry in _bench()["configs"]:
        if entry["name"] == name:
            assert set(entry["reduced"]) == changed
            assert entry["source"] == cfg["source"]
            assert entry["file"] == f"benchmarks/chip/configs/{name}.json"


@pytest.mark.parametrize("name", ["mistral-7b", "olmoe-1b-7b"])
def test_layer_blocks_match_the_programs_stacked_blocks(name):
    """The reference selects per layer leaf where a layer is a whole
    number of blocks (else across the stacked layers, as the program
    does); there the per-block budget and the blocks must agree."""
    from repro.launch import train
    with open(BENCH_DIR / "configs" / f"{name}.json") as f:
        cfg = spec.model_config(json.load(f))
    sds, _ = train.model_shapes_and_axes(cfg)
    stacked = {weights._path(p): x.shape for p, x in
               jax.tree_util.tree_flatten_with_path(sds)[0]}
    for n, shape, _, parts, slot in weights.layout(sds):
        size = 1
        for s in shape:
            size *= s
        full = size * (stacked[parts][0] if slot is not None else 1)
        if slot is not None and size % 4096:
            continue
        bs = min(4096, size)
        per_layer = -(-max(1, round(size / 1000)) * bs // size)
        prog_bs = min(4096, full)
        prog = -(-max(1, round(full / 1000)) * prog_bs // full)
        assert (bs, per_layer) == (prog_bs, prog), n


def test_layout_round_trips_the_program_tree():
    import jax.numpy as jnp
    tree = {"embed": {"embedding": jnp.arange(6.).reshape(3, 2)},
            "decoder": {"blocks": [{"attn": {"wq": jnp.arange(8.)
                                             .reshape(2, 2, 2)}}],
                        "tail": []},
            "final_norm": {"scale": jnp.ones(2)}}
    lay = weights.layout(tree)
    assert [n for n, *_ in lay] == [
        "embed.embedding", "final_norm.scale", "layer0.attn.wq",
        "layer1.attn.wq"]
    named = weights.from_program(tree, lay)
    back = weights.to_program(named, lay, tree)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        assert (a == b).all()


def _parent_model_config(config: dict):
    """The derivation before configuration files could state fields."""
    from repro.configs.base import ModelConfig
    prog = config["program"]
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
        n_experts=int(config.get("num_experts", 0)),
        moe_top_k=int(config.get("num_experts_per_tok", 0)),
        moe_period=1, moe_shard=prog.get("moe_shard", "ffn"),
        dtype=prog["dtype"], param_dtype=prog["param_dtype"],
        source=config["source"])


def _config(name):
    with open(BENCH_DIR / "configs" / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["mistral-7b", "olmoe-1b-7b"])
def test_model_config_is_the_derivation_field_for_field(name):
    cfg = _config(name)
    assert dataclasses.asdict(spec.model_config(cfg)) == \
        dataclasses.asdict(_parent_model_config(cfg))


def test_program_model_fields_reach_the_program():
    cfg = _config("mistral-7b")
    cfg["program"]["model"] = {"moe_period": 2, "d_ff": 1408,
                               "xlstm_pattern": ["m", "s"]}
    mc = spec.model_config(cfg)
    assert (mc.moe_period, mc.d_ff, mc.xlstm_pattern) == (2, 1408, ("m", "s"))
    assert mc.d_model == cfg["hidden_size"]
    hash(mc)                    # the program keys a shape cache on it


def test_unknown_program_model_field_raises_and_names_it():
    cfg = _config("mistral-7b")
    cfg["program"]["model"] = {"n_routed_expert": 64}
    with pytest.raises(ValueError, match="n_routed_expert") as e:
        spec.model_config(cfg, "benchmarks/chip/configs/x.json")
    assert "benchmarks/chip/configs/x.json" in str(e.value)


def test_missing_head_dim_raises_unless_program_model_states_it():
    cfg = _config("mistral-7b")
    del cfg["head_dim"]
    with pytest.raises(KeyError, match="head_dim"):
        spec.model_config(cfg)
    cfg["program"]["model"] = {"head_dim": 192}
    assert spec.model_config(cfg).head_dim == 192
