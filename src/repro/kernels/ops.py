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


def on_tpu() -> bool:
    """Whether the kernels compile through Mosaic (else they run in
    interpret mode); callers that pick between a kernel and plain XLA by
    the backend ask this too."""
    return jax.default_backend() == "tpu"


def _auto_axes(mesh) -> list:
    return [a for a, t in zip(mesh.axis_names, mesh.axis_types)
            if t != AxisType.Manual]


def _row_split(row_axes: tuple) -> int:
    """Devices :func:`_per_device` splits the leading dim over under the
    current mesh (1 outside a mesh or where those axes are manual)."""
    mesh = jax.sharding.get_abstract_mesh()
    return math.prod(mesh.shape[a] for a in _auto_axes(mesh)
                     if a in row_axes)


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
    auto = _auto_axes(mesh)
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
    interpret = not on_tpu()
    return _per_device(
        lambda x: _bt.block_topk_pallas(x, r, tm=tm, interpret=interpret),
        (blocks,))


def ef_accum_sparsify(g: jax.Array, e: jax.Array, lr, thr, *, tm: int = 64):
    """Fused acc = e + lr*g; selected = acc·[|acc|≥thr]; residual = acc−sel."""
    interpret = not on_tpu()
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


def _scaled(g: jax.Array, lr) -> jax.Array:
    """``lr·g`` in f32, rounded before the kernel adds it to the residual
    (as the XLA path and ``ref`` round it); the exchanges pass updates
    already scaled, ``lr=1``, and then nothing runs."""
    if isinstance(lr, (int, float)) and lr == 1:
        return g
    return jnp.asarray(lr, jnp.float32) * g.astype(jnp.float32)


def ef_select_pack_rows(g_rows: jax.Array, e_rows: jax.Array, lr, thr,
                        k: int, *, block_size: int | None = None,
                        row_axes: tuple = ()):
    """Fused EF accumulate + per-block top-k + payload pack, in place.

    g_rows: (rows, cols) any float; e_rows: (rows, cols) f32, whose
    row-major order is cut into blocks of ``block_size`` (default
    ``cols``: the (n_blocks, bs) block view).  ``thr=None`` disables the
    threshold gate (pure per-block budget — bitwise equal selection and
    residual to the XLA block top-k path).  Returns (vals (n_blocks, k)
    f32, local idx (n_blocks, k) int32, residual (rows, cols) f32);
    ``acc = e + lr·g`` never touches HBM (``lr·g`` does where ``lr`` is
    not 1; the exchanges pass it already scaled).  ``row_axes``: mesh
    axes the rows are split over under a mesh (each device's share must
    be a view ``select_geometry`` can read).
    """
    interpret = not on_tpu()
    scalars = () if thr is None else (jnp.asarray(thr, jnp.float32),)

    def kernel(u, e, thr_=None):
        return _ef.ef_select_pack_pallas(u, e, thr_, k=k,
                                         block_size=block_size,
                                         interpret=interpret)

    return _per_device(kernel, (_scaled(g_rows, lr), e_rows), scalars,
                       row_axes)


def ef_select_pack_leaf(u: jax.Array, e: jax.Array, k: int,
                        block_size: int, *, row_axes: tuple = ()):
    """:func:`ef_select_pack_rows` of one leaf of any shape, cut into
    blocks of ``block_size`` consecutive (row-major) elements, with the
    residual in the leaf's shape.

    The kernel reads the leaf in place, as (leading dims merged, last
    dim), where each device's share of those rows is a view
    ``select_geometry`` can read: merging the leading dims is a bitcast
    in the TPU's tiled layout.  Otherwise it reads the padded
    (n_blocks, block_size) block view, which costs a relayout of u and e
    into it and of the residual back.  Returns (vals (n_blocks, k) f32,
    local idx (n_blocks, k) int32, residual f32 shaped like ``u``).
    """
    shape, size = u.shape, u.size
    cols = int(shape[-1]) if shape else 1
    rows = size // cols
    split = _row_split(row_axes)
    if shape and rows % split == 0 and _ef.select_geometry(
            rows // split, cols, block_size) is not None:
        vals, idx, res = ef_select_pack_rows(
            u.reshape(rows, cols), e.reshape(rows, cols), 1, None, k,
            block_size=block_size, row_axes=row_axes)
        return vals, idx, res.reshape(shape)
    n_blocks = -(-size // block_size)
    vals, idx, res = ef_select_pack_rows(
        _block_view(u.reshape(-1), n_blocks, block_size),
        _block_view(e.reshape(-1), n_blocks, block_size), 1, None, k,
        row_axes=row_axes)
    return vals, idx, res.reshape(-1)[:size].reshape(shape)


def _block_view(x: jax.Array, n_blocks: int, bs: int) -> jax.Array:
    d = x.shape[0]
    return jnp.pad(x, (0, n_blocks * bs - d)).reshape(n_blocks, bs)


def ef_block_pack(g: jax.Array, e: jax.Array, lr, k: int, *,
                  block_size: int = 4096):
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
        lr, None, k_b)
    base = jnp.arange(n_blocks, dtype=jnp.int32)[:, None] * bs
    idx = jnp.minimum((base + local).reshape(-1), d - 1)
    return vals.reshape(-1), idx, res.reshape(-1)[:d]


def ef_hier_pack(g: jax.Array, e: jax.Array, lr, k: int, *,
                 block_size: int = 4096, r: int = 4):
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
            g.reshape(1, d), e.reshape(1, d), lr, None, kk)
        return vals.reshape(-1), local.reshape(-1), res.reshape(-1)
    bs = block_size
    n_blocks = -(-d // bs)
    r_eff = min(r, bs)
    u_rows = _scaled(_block_view(g, n_blocks, bs), lr)
    e_rows = _block_view(e, n_blocks, bs)
    interpret = not on_tpu()
    cand_vals, _ = _per_device(
        lambda uu, ee: _ef.ef_block_candidates_pallas(
            uu, ee, r=r_eff, interpret=interpret),
        (u_rows, e_rows))
    cand_flat = cand_vals.reshape(-1)
    kk = min(k, cand_flat.shape[0])
    thr = jax.lax.top_k(jnp.abs(cand_flat), kk)[0][-1]
    vals, local, res = ef_select_pack_rows(u_rows, e_rows, 1, thr, r_eff)
    base = jnp.arange(n_blocks, dtype=jnp.int32)[:, None] * bs
    idx = jnp.minimum((base + local).reshape(-1), d - 1)
    return vals.reshape(-1), idx, res.reshape(-1)[:d]
