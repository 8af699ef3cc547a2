"""The benchmark's operation and byte counts against hand counts."""
import json

import pytest

from lagsbench import flops
from lagsbench.spec import BENCH_DIR


def _config(name):
    with open(BENCH_DIR / "configs" / f"{name}.json") as f:
        return json.load(f)


def test_mistral_flops_per_token_hand_count():
    # per layer, forward: q,k,v,o projections 2*4096*(4096+2*1024) +
    # 2*4096*4096; causal attention 2*4096*32*128; FFN 3*2*4096*14336
    proj = 2 * 4096 * 6144 + 2 * 4096 * 4096
    attn = 2 * 4096 * 32 * 128
    ffn = 6 * 4096 * 14336
    head = 2 * 4096 * 32000
    hand = 3 * (4 * (proj + attn + ffn) + head)
    assert flops.model_flops_per_token(_config("mistral-7b"), 4096) == hand
    assert hand == pytest.approx(6.42e9, rel=2e-3)


def test_olmoe_flops_per_token_hand_count():
    # per layer: projections 4*2*2048*2048; attention 2*4096*16*128;
    # router 2*2048*64; 8 of 64 experts, 3 matrices 2048x1024 each
    proj = 8 * 2048 * 2048
    attn = 2 * 4096 * 16 * 128
    moe = 2 * 2048 * 64 + 8 * 6 * 2048 * 1024
    head = 2 * 2048 * 50304
    hand = 3 * (3 * (proj + attn + moe) + head)
    assert flops.model_flops_per_token(_config("olmoe-1b-7b"), 4096) == hand
    assert hand == pytest.approx(1.98e9, rel=5e-3)


def test_flops_per_step_scales_with_tokens():
    cfg = _config("mistral-7b")
    assert flops.model_flops_per_step(cfg, 4096, 4 * 4096) == \
        4 * 4096 * flops.model_flops_per_token(cfg, 4096)


def test_exchange_bytes_one_leaf_hand_count():
    # one 4096 x 14336 bf16 FFN matrix, ratio 1000, blocks of 4096, 4
    # workers: 14336 blocks, k = 58720, k_b = ceil(58720*4096/58720256) = 5
    size = 4096 * 14336
    kept = 14336 * 5
    hand = size * (2 + 4 + 4) + 8 * kept + 4 * 8 * kept + 4 * 4 * kept
    assert flops.kept_per_leaf(size, 1000.0, 4096) == kept
    assert flops.exchange_min_bytes([size], 2, 1000.0, 4096, 4) == hand


def test_kept_per_leaf_small_leaf_keeps_its_share():
    # a 4096-wide norm is one block: k = round(4.096) = 4 entries
    assert flops.kept_per_leaf(4096, 1000.0, 4096) == 4
    assert flops.kept_per_leaf(100, 1000.0, 4096) == 1


def test_reference_module_without_flops_per_token_raises(monkeypatch):
    import types

    from lagsbench import reference
    monkeypatch.setattr(reference, "model_module",
                        lambda config: types.SimpleNamespace())
    with pytest.raises(AttributeError, match="flops_per_token"):
        flops.model_flops_per_token(_config("mistral-7b"), 4096)
