"""Public wrappers around the Pallas kernels.

On a TPU backend the kernels compile through Mosaic; on any other
backend (CPU tests) they run under ``interpret=True`` — the kernel body
runs in Python per grid step, validating the same program.

Mosaic kernels cannot be partitioned by GSPMD, so every wrapper runs its
kernel per device (:func:`_per_device`): under a mesh with auto axes the
call is wrapped in a ``shard_map`` that is manual over every mesh axis.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from repro.kernels import block_topk as _bt
from repro.kernels import ef_sparsify as _ef


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _per_device(kernel, rows, scalars=(), row_axes=()):
    """``kernel(*rows, *scalars)`` on each device's share of ``rows``.

    Outside a mesh, or where every mesh axis is already manual, this is
    the plain call.  Otherwise the call runs in a ``shard_map`` manual
    over all mesh axes: the leading (row) dim of ``rows`` and of every
    output is split over those of ``row_axes`` that are still auto
    (padded to a multiple of their size), and everything else is
    replicated.
    """
    mesh = jax.sharding.get_abstract_mesh()
    auto = [a for a, t in zip(mesh.axis_names, mesh.axis_types)
            if t != AxisType.Manual]
    if not auto:
        return kernel(*rows, *scalars)
    split = tuple(a for a in row_axes if a in auto)
    m = math.prod(mesh.shape[a] for a in split)
    n = rows[0].shape[0]
    pad = -n % m
    if pad:
        rows = [jnp.pad(r, ((0, pad),) + ((0, 0),) * (r.ndim - 1))
                for r in rows]
    spec = P(split or None)
    out = jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(spec,) * len(rows) + (P(),) * len(scalars),
        out_specs=spec, axis_names=set(mesh.axis_names),
        check_vma=False)(*rows, *scalars)
    return jax.tree.map(lambda o: o[:n], out) if pad else out


def block_topk(blocks: jax.Array, r: int, *, tm: int = 8):
    """Per-row top-r by magnitude: (values, local int32 indices)."""
    interpret = _interpret()
    return _per_device(
        lambda x: _bt.block_topk_pallas(x, r, tm=tm, interpret=interpret),
        (blocks,))


def ef_accum_sparsify(g: jax.Array, e: jax.Array, lr, thr, *, tm: int = 64):
    """Fused acc = e + lr*g; selected = acc·[|acc|≥thr]; residual = acc−sel."""
    interpret = _interpret()
    return _per_device(
        lambda gg, ee, lr_, thr_: _ef.ef_accum_sparsify_pallas(
            gg, ee, lr_, thr_, tm=tm, interpret=interpret),
        (g, e), (jnp.asarray(lr, jnp.float32), jnp.asarray(thr, jnp.float32)))


def hier_topk_threshold(x: jax.Array, k: int, *, block_size: int = 4096,
                        r: int = 4, tm: int = 8):
    """Stage 1+2 of hierarchical top-k, returning the selection THRESHOLD
    (the k-th candidate magnitude) for use by the fused EF kernel.

    Returns (thr, (cand_vals, cand_idx)).  Exact whenever no block holds
    more than r of the true top-k; otherwise a slightly-high threshold —
    the resulting under-selection stays in the error-feedback residual,
    covered by the paper's framework.
    """
    d = x.shape[0]
    n_blocks = -(-d // block_size)
    pad = n_blocks * block_size - d
    xp = jnp.pad(x, (0, pad))
    blocks = xp.reshape(n_blocks, block_size)
    r_eff = min(r, block_size)
    cand_vals, cand_local = block_topk(blocks, r_eff, tm=tm)
    base = jnp.arange(n_blocks, dtype=jnp.int32)[:, None] * block_size
    # a short tail block pads with zeros whose global index lands >= d;
    # they carry value 0, so clamping into range keeps the scatter-ADD
    # no-op contract AND the values+int32 wire payload in-contract
    cand_idx = jnp.minimum((base + cand_local).reshape(-1), d - 1)
    cand_flat = cand_vals.reshape(-1)
    kk = min(k, cand_flat.shape[0])
    top_mag = jax.lax.top_k(jnp.abs(cand_flat), kk)[0]
    thr = top_mag[-1]
    return thr, (cand_flat, cand_idx)


def ef_select_pack_rows(g_rows: jax.Array, e_rows: jax.Array, lr, thr,
                        k: int, *, tm: int = 8, row_axes: tuple = ()):
    """Fused EF accumulate + per-block top-k + payload pack on a block view.

    g_rows: (n_blocks, bs) any float; e_rows: (n_blocks, bs) f32.
    ``thr=None`` disables the threshold gate (pure per-block budget —
    bitwise equal selection/residual to the XLA block top-k path).
    Returns (vals (n_blocks, k) f32, local idx (n_blocks, k) int32,
    residual (n_blocks, bs) f32); ``acc = e + lr·g`` never touches HBM.
    ``row_axes``: mesh axes the block rows are split over under a mesh.
    """
    thr_v = jnp.float32(-jnp.inf) if thr is None else thr
    interpret = _interpret()
    return _per_device(
        lambda g, e, lr_, thr_: _ef.ef_select_pack_pallas(
            g, e, lr_, thr_, k=k, tm=tm, interpret=interpret),
        (g_rows, e_rows),
        (jnp.asarray(lr, jnp.float32), jnp.asarray(thr_v, jnp.float32)),
        row_axes)


def _block_view(x: jax.Array, n_blocks: int, bs: int) -> jax.Array:
    d = x.shape[0]
    return jnp.pad(x, (0, n_blocks * bs - d)).reshape(n_blocks, bs)


def ef_block_pack(g: jax.Array, e: jax.Array, lr, k: int, *,
                  block_size: int = 4096, tm: int = 8):
    """Flat fused block-budget EF: compressors.topk_block geometry
    (k_b = ceil(k·bs/d) kept per block) in one HBM pass.

    g: (d,) any float; e: (d,) f32.  Returns (vals (n_blocks·k_b,) f32,
    global idx int32 clamped into [0, d), residual (d,) f32) with the
    decompress scatter-ADD padding contract (pad entries carry value 0).
    """
    d = g.shape[0]
    bs = min(block_size, d)
    n_blocks = -(-d // bs)
    k_b = max(1, min(bs, -(-k * bs // d)))
    vals, local, res = ef_select_pack_rows(
        _block_view(g, n_blocks, bs), _block_view(e, n_blocks, bs),
        lr, None, k_b, tm=tm)
    base = jnp.arange(n_blocks, dtype=jnp.int32)[:, None] * bs
    idx = jnp.minimum((base + local).reshape(-1), d - 1)
    return vals.reshape(-1), idx, res.reshape(-1)[:d]


def ef_hier_pack(g: jax.Array, e: jax.Array, lr, k: int, *,
                 block_size: int = 4096, r: int = 4, tm: int = 8):
    """Flat fused hierarchical EF: candidate kernel -> threshold ->
    threshold-gated pack kernel, two HBM reads of (g, e) and one write of
    (payload, residual) — ``acc`` never materializes.

    Selection = every per-block top-``r`` candidate of ``acc = e + lr·g``
    whose magnitude reaches the k-th candidate magnitude; at most r per
    block, payload size n_blocks·r (zero-padded beyond the threshold).
    Threshold ties may keep slightly more than k entries — the bias
    either way stays inside the error-feedback residual.  For
    ``d <= block_size`` the single block degenerates to an EXACT fused
    top-k (threshold gate off, k passes).

    Returns (vals f32, global idx int32 clamped into [0, d),
    residual (d,) f32).
    """
    d = g.shape[0]
    if d <= block_size or k >= d:
        kk = min(k, d)
        vals, local, res = ef_select_pack_rows(
            g.reshape(1, d), e.reshape(1, d), lr, None, kk, tm=tm)
        return vals.reshape(-1), local.reshape(-1), res.reshape(-1)
    bs = block_size
    n_blocks = -(-d // bs)
    r_eff = min(r, bs)
    g_rows = _block_view(g, n_blocks, bs)
    e_rows = _block_view(e, n_blocks, bs)
    interpret = _interpret()
    cand_vals, _ = _per_device(
        lambda gg, ee, lr_: _ef.ef_block_candidates_pallas(
            gg, ee, lr_, r=r_eff, tm=tm, interpret=interpret),
        (g_rows, e_rows), (jnp.asarray(lr, jnp.float32),))
    cand_flat = cand_vals.reshape(-1)
    kk = min(k, cand_flat.shape[0])
    thr = jax.lax.top_k(jnp.abs(cand_flat), kk)[0][-1]
    vals, local, res = ef_select_pack_rows(g_rows, e_rows, lr, thr, r_eff,
                                           tm=tm)
    base = jnp.arange(n_blocks, dtype=jnp.int32)[:, None] * bs
    idx = jnp.minimum((base + local).reshape(-1), d - 1)
    return vals.reshape(-1), idx, res.reshape(-1)[:d]
