"""Plain reference of the ``lags_dp`` exchange (LAGS-SGD, Algorithm 1).

Per layer leaf and worker: accumulate the learning-rate-scaled gradient
onto the error-feedback residual, keep the largest ``k_b`` magnitudes of
every block of ``block_size`` consecutive elements (row-major order),
leave the rest as the new residual, and average the kept entries of all
workers into the update.  ``k = max(1, round(d / ratio))`` and ``k_b =
ceil(k * bs / d)``, so each block keeps at least its share ``1/ratio``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

PIPELINES = ("off", "wave")
NEEDS_EF = True
#: Selection works on blocks, so leaves blocked across layers are
#: exchanged as one vector in layer order.
BLOCKED = True


def geometry(size: int, ratio: float, block_size: int):
    bs = min(block_size, size)
    n_blocks = -(-size // bs)
    k = max(1, int(round(size / float(ratio))))
    k_b = max(1, min(bs, -(-k * bs // size)))
    return n_blocks, bs, k_b


def select(u, e, ratio: float, block_size: int):
    """(values, block-local indices, new residual) of one worker."""
    size = u.size
    n_blocks, bs, k_b = geometry(size, ratio, block_size)
    acc = (e + u).reshape(-1)
    rows = jnp.pad(acc, (0, n_blocks * bs - size)).reshape(n_blocks, bs)
    _, idx = lax.top_k(jnp.abs(rows), k_b)
    vals = jnp.take_along_axis(rows, idx, axis=1)
    kept = jnp.zeros_like(rows).at[jnp.arange(n_blocks)[:, None], idx].set(
        vals)
    resid = (rows - kept).reshape(-1)[:size].reshape(u.shape)
    return (vals, idx), resid


def combine(payloads, shape, ratio: float, block_size: int):
    """Mean over workers of their kept entries, as a dense update."""
    size = 1
    for s in shape:
        size *= int(s)
    n_blocks, bs, _ = geometry(size, ratio, block_size)
    rows = jnp.zeros((n_blocks, bs), jnp.float32)
    row_ids = jnp.arange(n_blocks)[:, None]
    for vals, idx in payloads:
        rows = rows.at[row_ids, idx].add(vals)
    return (rows.reshape(-1)[:size] / len(payloads)).reshape(shape)
