"""In-backprop wave exchange via ``jax.custom_vjp`` taps.

``wave_backward`` differentiates the loss through one identity *tap*
per wave: the tap forwards the wave's parameter leaves unchanged, and
its custom VJP intercepts the arriving cotangents — exactly that wave's
gradients, at the moment backprop produces them — and runs
``exchange_bucket`` on them right there, inside the backward pass.  The
exchanged means and the new error-feedback residuals ride out of the
autodiff as the cotangent of a dummy ``z`` input (one per wave), while
the parameter cotangent passes through untouched.  Each wave's
collectives therefore depend ONLY on that wave's backward ops, so XLA's
latency-hiding scheduler can run them under the remaining backward
compute — the paper's Fig. 1(c) overlap, physically.

Because ``exchange_bucket`` keys PRNG streams and EF updates off global
leaf ids, the result is bitwise identical to the monolithic
post-backward ``exchange`` — parity the pipeline test battery asserts
step-for-step for every registered strategy.

``waved_exchange`` is the no-tap variant (same regrouping, run after
backprop) used by ``pipeline="async1"`` double-buffering and by the
pure-auto (vmap-over-pod) path where taps cannot reach inside the
per-pod vmap.

State-shape convention (matches ``ExchangeStrategy.ef_tiers``):
``()`` (dense, stateless), a tree of residuals (single-tier EF), or a
``{"inner": tree, "outer": tree}`` dict (two-tier EF, lags_hier2).
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.observe.trace import phase_scope


# -- flat-state plumbing (handles the three EF layouts uniformly) -----------

def flatten_state(state, treedef, tiers: Sequence[str] = ()):
    """Flat-list view of an EF state.  ``tiers`` comes from the exchange
    registration (``ExchangeStrategy.ef_tiers``): non-empty means the
    state is a tier-keyed dict of residual trees — the params tree may
    itself be a dict, so tier-ness must be declared, not sniffed."""
    if tiers:
        return {t: treedef.flatten_up_to(state[t]) for t in tiers}
    if state == () or state is None:
        return ()
    return treedef.flatten_up_to(state)


def unflatten_state(flat_state, treedef):
    if isinstance(flat_state, dict):
        return {t: treedef.unflatten(flat_state[t]) for t in flat_state}
    if flat_state == () or flat_state is None:
        return ()
    return treedef.unflatten(flat_state)


def _slice_state(flat_state, ids):
    if flat_state == () or flat_state is None:
        return ()
    if isinstance(flat_state, dict):
        return {t: [v[i] for i in ids] for t, v in flat_state.items()}
    return [flat_state[i] for i in ids]


def _scatter_state(out_flat, wave_state, ids):
    if out_flat == () or out_flat is None:
        return
    if isinstance(out_flat, dict):
        for t in out_flat:
            for j, i in enumerate(ids):
                out_flat[t][i] = wave_state[t][j]
        return
    for j, i in enumerate(ids):
        out_flat[i] = wave_state[j]


def _zeros_like_state(sl):
    if sl == () or sl is None:
        return ()
    if isinstance(sl, dict):
        return {t: [jnp.zeros_like(x) for x in v] for t, v in sl.items()}
    return [jnp.zeros_like(x) for x in sl]


def _empty_like(flat_state):
    if flat_state == () or flat_state is None:
        return ()
    if isinstance(flat_state, dict):
        return {t: [None] * len(v) for t, v in flat_state.items()}
    return [None] * len(flat_state)


# -- the tap ----------------------------------------------------------------

def _make_tap(exch, wave, axis_names):
    """Identity on the wave's param leaves; VJP runs the wave exchange.

    ``lr`` and ``key`` are explicit primal inputs (they are tracers under
    jit — a custom_vjp must not close over them); ``key``'s cotangent is
    the float0 zero its integer dtype requires."""
    ids = tuple(int(i) for i in wave.leaf_ids)

    @jax.custom_vjp
    def tap(ps, efs, z, lr, key):
        del efs, z, lr, key
        return ps

    def tap_fwd(ps, efs, z, lr, key):
        del z
        return ps, (efs, lr, key)

    def tap_bwd(res, g):
        efs, lr, key = res
        # EXACTLY the monolithic worker's update law: lr * grad in fp32;
        # under lags/exchange, so a profile counts it as exchange, not bwd
        with phase_scope("exchange"):
            updates = [lr * gi.astype(jnp.float32) for gi in g]
            means, new_efs = exch.exchange_bucket(ids, updates, efs,
                                                  axis_names, key=key)
        key_ct = np.zeros(key.shape, jax.dtypes.float0)
        return (list(g), _zeros_like_state(efs), (new_efs, means),
                jnp.zeros_like(lr), key_ct)

    tap.defvjp(tap_fwd, tap_bwd)
    return tap


def wave_backward(loss_fn: Callable, exch, waves: Sequence, params,
                  state, axis_names, *, lr, key, has_aux: bool = False,
                  tiers: Sequence[str] = ()):
    """Loss + in-backprop waved exchange.

    ``loss_fn(params) -> loss`` (or ``(loss, aux)`` with ``has_aux``).
    Returns ``(loss_out, mean_updates_tree, new_state_tree)`` where
    ``mean_updates_tree`` is the exchanged fp32 mean update (apply as
    ``p - mean``) and ``new_state_tree`` the post-exchange EF state.
    """
    flat_p, treedef = jax.tree.flatten(params)
    flat_state = flatten_state(state, treedef, tiers)
    taps = [_make_tap(exch, w, axis_names) for w in waves]
    zs = [(
        _zeros_like_state(_slice_state(flat_state, w.leaf_ids)),
        [jnp.zeros(flat_p[i].shape, jnp.float32) for i in w.leaf_ids],
    ) for w in waves]

    def tapped(zs_in):
        tp = list(flat_p)
        for w, tap, z in zip(waves, taps, zs_in):
            sub_p = [tp[i] for i in w.leaf_ids]
            sub_e = _slice_state(flat_state, w.leaf_ids)
            out = tap(sub_p, sub_e, z, lr, key)
            for j, i in enumerate(w.leaf_ids):
                tp[i] = out[j]
        return loss_fn(treedef.unflatten(tp))

    loss_out, g_z = jax.value_and_grad(tapped, has_aux=has_aux)(zs)

    flat_means: list = [None] * len(flat_p)
    new_flat_state = _empty_like(flat_state)
    for w, (new_efs, means) in zip(waves, g_z):
        for j, i in enumerate(w.leaf_ids):
            flat_means[i] = means[j]
        _scatter_state(new_flat_state, new_efs, w.leaf_ids)
    return (loss_out, treedef.unflatten(flat_means),
            unflatten_state(new_flat_state, treedef))


def waved_exchange(exch, waves: Sequence, updates, state, axis_names, *,
                   key=None, tiers: Sequence[str] = ()):
    """Post-backward per-wave exchange — the same regrouping without the
    taps.  Bitwise equal to ``exch.exchange(updates, state, ...)``; used
    by async1 double-buffering and the pure-auto (vmap-over-pod) path."""
    flat_u, treedef = jax.tree.flatten(updates)
    flat_state = flatten_state(state, treedef, tiers)
    flat_means: list = [None] * len(flat_u)
    new_flat_state = _empty_like(flat_state)
    for w in waves:
        ids = tuple(int(i) for i in w.leaf_ids)
        with phase_scope("exchange"):
            means, new_sub = exch.exchange_bucket(
                ids, [flat_u[i] for i in ids], _slice_state(flat_state, ids),
                axis_names, key=key)
        for j, i in enumerate(ids):
            flat_means[i] = means[j]
        _scatter_state(new_flat_state, new_sub, ids)
    return (treedef.unflatten(flat_means),
            unflatten_state(new_flat_state, treedef))
