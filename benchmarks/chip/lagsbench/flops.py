"""Operations and bytes the work needs, computed from the configuration's
shapes alone (never from the compiled program).

``model_flops_per_token``: matrix-multiply FLOP of the forward and
backward passes (3x the forward) per trained token.  Forward per layer:
the q/k/v/o projections, causal attention (QK^T and PV over the
``S/2`` earlier positions on average: ``2 * S * H * hd``), and the FFN
(three matrices) or, for a mixture of experts, the router and the
``top_k`` experts a token is sent to; then the output head.  The
embedding lookup, norms, softmax and recomputation are not counted.

``exchange_min_bytes``: the least HBM traffic of one worker's LAGS
exchange per step, whatever implements it: read the gradient (parameter
type) and the float32 residual, write the residual, write the payload
(float32 value + int32 index per kept entry), read every worker's
payload, and write each gathered entry into the float32 update.
"""
from __future__ import annotations


def model_flops_per_token(config: dict, seq_len: int) -> float:
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    kv = int(config["num_key_value_heads"])
    hd = int(config["head_dim"])
    f = int(config["intermediate_size"])
    v = int(config["vocab_size"])
    n_layers = int(config["num_hidden_layers"])
    proj = 2 * d * hd * (h + 2 * kv) + 2 * h * hd * d
    attn = 2 * seq_len * h * hd
    e = int(config.get("num_experts", 0))
    if e:
        ffn = 2 * d * e + int(config["num_experts_per_tok"]) * 3 * 2 * d * f
    else:
        ffn = 3 * 2 * d * f
    head = 2 * d * v
    return 3.0 * (n_layers * (proj + attn + ffn) + head)


def model_flops_per_step(config: dict, seq_len: int, tokens: int) -> float:
    return model_flops_per_token(config, seq_len) * tokens


def kept_per_leaf(size: int, ratio: float, block_size: int) -> int:
    """Entries a worker keeps of one leaf: ``k_b`` in each block."""
    bs = min(block_size, size)
    n_blocks = -(-size // bs)
    k = max(1, int(round(size / float(ratio))))
    return n_blocks * max(1, min(bs, -(-k * bs // size)))


def exchange_min_bytes(leaf_sizes, grad_bytes: int, ratio: float,
                       block_size: int, n_workers: int) -> float:
    total = 0.0
    for size in leaf_sizes:
        kept = kept_per_leaf(size, ratio, block_size)
        total += size * (grad_bytes + 4 + 4)      # g, residual in and out
        total += 8 * kept                         # own payload out
        total += n_workers * 8 * kept             # all payloads in
        total += n_workers * 4 * kept             # scatter into the update
    return total
