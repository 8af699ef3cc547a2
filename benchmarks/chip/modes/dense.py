"""Plain reference of the ``dense`` exchange (S-SGD): the update is the
mean over workers of their learning-rate-scaled gradients."""
from __future__ import annotations

PIPELINES = ("off", "wave")
NEEDS_EF = False


def select(u, e, ratio, block_size):
    del e, ratio, block_size
    return u, None


def combine(payloads, shape, ratio, block_size):
    del ratio, block_size
    total = payloads[0]
    for u in payloads[1:]:
        total = total + u
    return (total / len(payloads)).reshape(shape)
