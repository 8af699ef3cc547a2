"""The program's readings of its own state, by the reference's formulas.

``grad_norms`` after the first step: per named leaf, the norm of the
gradient as the optimizer got it, ``(mean over workers of the residual +
(w0 - w1)) / lr``.  ``change_norms`` after the last checked step: the
norm of ``w - w0``.  ``w0`` is made again from the seed by the same
compiled call that made the program's weights, so it is bitwise the
program's start.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from lagsbench import weights as W

F32 = jnp.float32


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x)))


def grad_norms(state, w0: dict, lay: tuple, lr: float) -> dict:
    @jax.jit
    def fn(params, ef, w0):
        w1 = W.from_program(params, lay)
        e = W.from_program(ef, lay, lead=1) if ef != () else None
        out = {}
        for n in w1:
            g = w0[n].astype(F32) - w1[n].astype(F32)
            if e is not None:
                g = g + jnp.mean(e[n], axis=0)
            out[n] = _norm(g / lr)
        return out
    return {k: float(v) for k, v in fn(state["params"], state["ef"],
                                       w0).items()}


def change_norms(state, w0: dict, lay: tuple) -> dict:
    @jax.jit
    def fn(params, w0):
        w = W.from_program(params, lay)
        return {n: _norm(w[n].astype(F32) - w0[n].astype(F32)) for n in w}
    return {k: float(v) for k, v in fn(state["params"], w0).items()}
