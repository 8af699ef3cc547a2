"""Token feed from the seed: the traffic mix's batches, made on the
device during set-up.

Tokens are uniform over the vocabulary (as ``data.synthetic.lm_input_
batch`` draws them), every row of every step its own draw, so no two
rows of the run are alike.  ``pool_steps`` batches are made; a window
longer than that cycles through them again.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def global_batch(traffic: dict, chips: int) -> int:
    return int(traffic["batch_per_chip"]) * chips


def make(traffic: dict, vocab: int, chips: int, seed: int, sharding) -> list:
    """``pool_steps`` batches ``{"tokens", "labels"}`` of (global batch,
    seq_len) int32, each placed with ``sharding``."""
    n = int(traffic["pool_steps"])
    b = global_batch(traffic, chips)
    s = int(traffic["seq_len"])
    seed = int(seed)

    def build(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        key = jax.random.fold_in(key, 0x7E9D)        # apart from the weights
        toks = jax.random.randint(key, (n, b, s + 1), 0, vocab, jnp.int32)
        return [{"tokens": toks[i, :, :-1], "labels": toks[i, :, 1:]}
                for i in range(n)]

    return jax.jit(build, out_shardings=sharding)(
        jnp.uint32(seed % 2**32), jnp.uint32(seed // 2**32))


def worker_rows(batch: dict, chips: int, w: int) -> dict:
    """The rows of ``batch`` that worker ``w`` trains on (as numpy)."""
    import numpy as np
    toks, labs = np.asarray(batch["tokens"]), np.asarray(batch["labels"])
    per = toks.shape[0] // chips
    return {"tokens": toks[w * per:(w + 1) * per],
            "labels": labs[w * per:(w + 1) * per]}
