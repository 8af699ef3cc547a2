"""Weights made from the seed, and the map between the program's
parameter tree and named per-layer leaves.

The benchmark makes the weights itself, on the device, in one jitted call
from ``--seed``, in the stored parameter type.  The program gets them
packed into its own state layout; the reference gets the same named
leaves from the same call, so it takes nothing the program made.

A named leaf is one matrix of one layer: ``layer3.attn.wq``,
``layer0.moe.w_up``, ``embed.embedding``.  The program stacks the layers
of a period along a leading axis (``decoder/blocks/<j>``) and keeps a
tail list (``decoder/tail/<i>``); :func:`layout` splits both.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

#: Standard deviation of the random offset of RMSNorm weights (the
#: program stores an RMSNorm weight as its offset from 1).
NORM_STD = 0.1


def _key_str(k) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def _path(path) -> tuple:
    return tuple(_key_str(k) for k in path)


def layout(params_like) -> tuple:
    """Named leaves of a program parameter tree (shapes or arrays).

    Returns a tuple of ``(name, shape, dtype name, program path, layer
    slot)``; ``layer slot`` is the index along the stacked leading axis,
    or ``None`` for an unstacked leaf."""
    flat = jax.tree_util.tree_flatten_with_path(params_like)[0]
    blocks = [p for p, _ in flat if _path(p)[:2] == ("decoder", "blocks")]
    period = 1 + max((int(_path(p)[2]) for p in blocks), default=0)
    n_periods = next((x.shape[0] for p, x in flat
                      if _path(p)[:2] == ("decoder", "blocks")), 0)
    out = []
    for p, x in flat:
        parts = _path(p)
        dt = jnp.dtype(x.dtype).name
        if parts[:2] == ("decoder", "blocks"):
            j, rest = int(parts[2]), ".".join(parts[3:])
            for t in range(x.shape[0]):
                out.append((f"layer{t * period + j}.{rest}",
                            tuple(x.shape[1:]), dt, parts, t))
        elif parts[:2] == ("decoder", "tail"):
            layer = n_periods * period + int(parts[2])
            out.append((f"layer{layer}.{'.'.join(parts[3:])}",
                        tuple(x.shape), dt, parts, None))
        else:
            out.append((".".join(parts), tuple(x.shape), dt, parts, None))
    return tuple(sorted(out, key=lambda e: e[0]))


def init_std(name: str, shape: tuple) -> float:
    """Standard deviation of a leaf's random init: 1/sqrt(fan-in)."""
    leaf = name.rsplit(".", 1)[-1]
    if leaf == "scale":
        return NORM_STD
    if leaf == "embedding":                    # (V, d): a row is the input
        return 1.0 / math.sqrt(shape[-1])
    if leaf == "wo":                           # (H, hd, d)
        return 1.0 / math.sqrt(shape[0] * shape[1])
    if leaf in ("w_up", "w_gate", "w_down") and len(shape) == 3:
        return 1.0 / math.sqrt(shape[1])       # (E, in, out)
    return 1.0 / math.sqrt(shape[0])           # (in, ...)


def make_named(seed: int, lay: tuple, sharding) -> dict:
    """All named leaves, made on the device in one jitted call.

    The same ``(layout, sharding)`` always runs the same compiled
    program, so a second call returns the same bits."""
    names = tuple((n, s, d) for n, s, d, _, _ in lay)
    seed = int(seed)
    return _named_fn(names, sharding)(jnp.uint32(seed % 2**32),
                                      jnp.uint32(seed // 2**32))


_NAMED_FNS: dict = {}


def _named_fn(names: tuple, sharding):
    if (names, sharding) not in _NAMED_FNS:
        def build(lo, hi):
            base = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
            out = {}
            for n, s, d in names:
                k = jax.random.fold_in(base, zlib.crc32(n.encode())
                                       & 0x7FFFFFFF)
                w = jax.random.normal(k, s, jnp.float32) * init_std(n, s)
                out[n] = w.astype(d)
            return out
        _NAMED_FNS[names, sharding] = jax.jit(build,
                                              out_shardings=sharding)
    return _NAMED_FNS[names, sharding]


def to_program(named: dict, lay: tuple, params_like):
    """Pack named leaves into the program's parameter tree (traceable)."""
    by_path: dict = {}
    for n, _, _, parts, slot in lay:
        by_path.setdefault(parts, []).append((slot, named[n]))
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_like)
    leaves = []
    for p, _ in flat:
        got = by_path[_path(p)]
        if got[0][0] is None:
            leaves.append(got[0][1])
        else:
            leaves.append(jnp.stack([a for _, a in sorted(got,
                                                          key=lambda e: e[0])]))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def from_program(tree, lay: tuple, lead: int = 0) -> dict:
    """Named leaves of a program tree (traceable).  ``lead`` leading axes
    (the EF residual's worker axis) are kept in front."""
    flat = {_path(p): x for p, x in
            jax.tree_util.tree_flatten_with_path(tree)[0]}
    out = {}
    for n, _, _, parts, slot in lay:
        x = flat[parts]
        out[n] = x if slot is None else jnp.take(x, slot, axis=lead)
    return out
