"""Operations and bytes the work needs, computed from the configuration's
shapes alone (never from the compiled program).

``model_flops_per_token``: matrix-multiply FLOP of the forward and
backward passes per trained token, as the configuration's reference
module (``configs/<reference>.py``) counts them in its
``flops_per_token(config, seq_len)``; a module without one is an error,
since a wrong ``step_mfu`` is worse than none.

``exchange_min_bytes``: the least HBM traffic of one worker's LAGS
exchange per step, whatever implements it: read the gradient (parameter
type) and the float32 residual, write the residual, write the payload
(float32 value + int32 index per kept entry), read every worker's
payload, and write each gathered entry into the float32 update.
"""
from __future__ import annotations

from lagsbench import reference


def model_flops_per_token(config: dict, seq_len: int) -> float:
    module = reference.model_module(config)
    count = getattr(module, "flops_per_token", None)
    if count is None:
        raise AttributeError(
            f"configs/{config['reference']}.py has no flops_per_token("
            f"config, seq_len): the configuration has no FLOP count")
    return count(config, seq_len)


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
