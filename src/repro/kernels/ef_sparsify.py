"""Pallas TPU kernel: fused error-feedback accumulate + threshold sparsify.

Algorithm 1 lines 7–8 touch each gradient element three times when written
naively (read g + read e → write acc; read acc → write selected; read acc,
selected → write residual).  Fused, each element is read once (g, e) and
written once (selected, residual) — a single HBM stream at exactly the
4-array bandwidth floor:

    acc      = e + lr · g
    selected = acc · [|acc| ≥ thr]          # TopK-as-threshold (Eq. 4)
    residual = acc − selected               # error feedback

``thr`` is the k-th magnitude produced by the (cheap, candidate-sized)
stage-2 selection of `block_topk`, so the fused pass realizes the whole
per-layer sparsify-with-memory update in one pass over the layer.

Tiling: the flat vector is viewed as (rows, 1024) f32 — 1024 = 8·128 fills
one VREG row naturally; grid over row-tiles of ``tm`` rows.  lr and thr
ride in SMEM as (1, 1) scalars via PrefetchScalarGridSpec-free plain
inputs with a (1, 1) BlockSpec.

This module also holds the fused select → residual-update → payload-pack
kernel (``ef_select_pack_pallas``): instead of a dense ``selected``
output it emits the sparse wire form directly — per-block top-k values
(f32) + local int32 indices, the ``bucketing.payload_bytes_per_elem``
layout — plus the residual, so the accumulated ``acc = e + u`` of the
scaled update ``u = lr·g`` never round-trips through HBM between
selection and error feedback.  It reads the leaf in its own 2-D layout
where it can (``select_geometry``), so no relayout copy precedes it.  Its
candidate-stage sibling (``ef_block_candidates_pallas``) computes the
same inline accumulate but emits only the per-block top-r candidates,
for the hierarchical threshold estimate (stage 2 runs on the tiny
candidate set in plain XLA).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 1024  # 8 sublanes * 128 lanes of f32


def _ef_kernel(lr_ref, thr_ref, g_ref, e_ref, sel_ref, res_ref):
    lr = lr_ref[0, 0]
    thr = thr_ref[0, 0]
    acc = e_ref[...] + lr * g_ref[...].astype(jnp.float32)
    keep = jnp.abs(acc) >= thr
    sel = jnp.where(keep, acc, 0.0)
    sel_ref[...] = sel
    res_ref[...] = acc - sel


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def ef_accum_sparsify_pallas(g: jax.Array, e: jax.Array, lr, thr, *,
                             tm: int = 64, interpret: bool = True):
    """Fused EF update on flat vectors.

    g: (d,) any float dtype; e: (d,) f32; lr, thr: scalars.
    Returns (selected (d,) f32, residual (d,) f32).
    """
    d = g.shape[0]
    rows = -(-d // LANE)
    rows_pad = -(-rows // tm) * tm
    dp = rows_pad * LANE
    gp = jnp.pad(g, (0, dp - d)).reshape(rows_pad, LANE)
    # pad e with +inf magnitude guard? zeros are fine: 0 never selected
    ep = jnp.pad(e.astype(jnp.float32), (0, dp - d)).reshape(rows_pad, LANE)
    lr2 = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    thr2 = jnp.asarray(thr, jnp.float32).reshape(1, 1)
    grid = (rows_pad // tm,)
    sel, res = pl.pallas_call(
        _ef_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0)),
                  pl.BlockSpec((tm, LANE), lambda i: (i, 0)),
                  pl.BlockSpec((tm, LANE), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((tm, LANE), lambda i: (i, 0)),
                   pl.BlockSpec((tm, LANE), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows_pad, LANE), jnp.float32),
                   jax.ShapeDtypeStruct((rows_pad, LANE), jnp.float32)],
        interpret=interpret,
    )(lr2, thr2, gp, ep)
    return sel.reshape(-1)[:d], res.reshape(-1)[:d]




# ---------------------------------------------------------------------------
# Fused select -> residual-update -> payload-pack
# ---------------------------------------------------------------------------

#: Bytes of one input block a grid step aims for: each step costs a fixed
#: ~0.35 us, small against the DMA of 2 MiB per array (2.6 us at 819 GB/s).
_STEP_BYTES = 2 << 20
#: Scoped VMEM the kernel may use: two inputs and the residual, double
#: buffered at ``_STEP_BYTES`` each, with room for the body's values.
_VMEM_LIMIT = 32 << 20
#: Sets of eight blocks a body selects, so that the lane reductions of
#: independent blocks hide each other's latency.
_INTERLEAVE = 4
#: Most windows the reads of one body may take: rows that wrap blocks
#: split the chunks into more runs, the windows of each run are code in
#: the body, and Mosaic's compile time grows with them (Mistral-7B's
#: 14336-wide rows take 224 windows in 2 runs; its 32000-wide
#: ``lm_head`` would take 4000 in 16, and takes the block view instead).
_MAX_READS = 512
_LANES = 128
_NO_KEY = jnp.iinfo(jnp.int32).max


@dataclasses.dataclass(frozen=True)
class _Read:
    """One window of a chunk: ``n`` rows from ``row`` of the body (every
    ``stride``-th), lanes ``[col, col + width)``, rolled by ``shift``
    sublanes, for the set's sublanes in ``mask`` (None: all of them)."""
    row: int
    col: int
    stride: int = 1
    shift: int = 0
    mask: tuple | None = None


@dataclasses.dataclass(frozen=True)
class _Set:
    """Up to eight blocks selected together, one per sublane: ``reads[c]``
    gathers their chunk c; their payload goes to rows ``out + sublane``
    of the body's payload."""
    n: int
    out: int
    reads: tuple


@dataclasses.dataclass(frozen=True)
class SelectGeometry:
    """How the fused select reads the blocks of ``bs`` consecutive
    (row-major) elements of a ``(rows, cols)`` array in place.

    A grid step runs ``loops`` bodies; a body covers ``body_rows`` rows
    and writes ``body_out`` payload rows, set by set.  The payload comes
    out in block order, or, where a row holds several blocks, in the
    bodies' set order, and ``order`` gives each block's row there.
    """
    rows: int
    cols: int
    bs: int
    width: int           # lanes of one chunk: 128, or bs if not lane-aligned
    sets: tuple
    body_rows: int
    body_out: int
    loops: int
    order: tuple | None
    runs: tuple          # (first, end) chunks that one loop covers
    step: tuple          # (rows, lanes) a read moves from chunk to chunk

    @property
    def n_blocks(self) -> int:
        return self.rows * self.cols // self.bs

    @property
    def n_out(self) -> int:
        return -(-self.rows // self.body_rows) * self.body_out


def _lanes_sets(n_blocks: int, bs: int):
    """Rows of one 128-lane chunk each, ``bs / 128`` rows to a block:
    chunk c of eight blocks is one strided read (Mosaic strides only
    128-lane rows).  Returns (sets, body rows)."""
    block_rows, n = bs // _LANES, min(8, n_blocks)
    n_sets = max(1, min(_INTERLEAVE, n_blocks // 8))
    sets = tuple(_Set(n, 8 * s, tuple(
        (_Read(8 * s * block_rows + c, 0, block_rows),)
        for c in range(block_rows))) for s in range(n_sets))
    return sets, n_sets * n * block_rows


def _row_sets(rows: int, cols: int, bs: int, width: int):
    """Rows of any width, read with contiguous windows: a unit of rows
    (whole row periods, whole eight-row groups) hosts each block at the
    row where it starts; set (g, s) takes the s-th block hosted by each
    row of group g, whose chunk c may lie at another column of each row,
    or in a later row (a window rolled by the rows between).  Returns
    (sets, body rows, body payload rows, block -> payload row within the
    body) or None."""
    nch, cpb = cols // width, bs // width
    period = bs // math.gcd(cols, bs)
    unit = period * 8 // math.gcd(period, 8)
    if rows < unit:
        if period > 1:
            return None
        unit = rows
    n = min(8, unit)
    hosted = [list(range(-(-r * nch // cpb), -(-(r + 1) * nch // cpb)))
              for r in range(unit)]
    n_slots = max(len(h) for h in hosted)
    units = max(1, min(_INTERLEAVE * n // (unit * n_slots), rows // unit))
    sets, where = [], {}
    for u in range(units):
        for g in range(unit // n):
            for s in range(n_slots):
                out = ((u * unit // n + g) * n_slots + s) * n
                chunks = []
                for c in range(cpb):
                    windows: dict = {}
                    for i in range(n):
                        r = g * n + i
                        if s >= len(hosted[r]):
                            continue
                        b = hosted[r][s]
                        rr, col = divmod(b * cpb + c, nch)
                        key = (u * unit + rr // n * n, col * width,
                               (i - rr % n) % n)
                        windows.setdefault(key, []).append(i)
                    chunks.append(tuple(
                        _Read(row, col, 1, shift,
                              None if len(m) == n else tuple(m))
                        for (row, col, shift), m in sorted(
                            windows.items(), key=lambda kv: -len(kv[1]))))
                if any(chunks):
                    sets.append(_Set(n, out, tuple(chunks)))
                    for i in range(n):
                        r = g * n + i
                        if s < len(hosted[r]):
                            where[u * unit * nch // cpb + hosted[r][s]] = \
                                out + i
    if sum(len(rd) for st in sets for rd in st.reads) > _MAX_READS:
        return None
    body_out = units * unit // n * n_slots * n
    order = tuple(where[b] for b in range(len(where)))
    return tuple(sets), units * unit, body_out, order


def _runs(sets: tuple, chunks: int, step: tuple) -> tuple:
    """Split the chunks into runs in which every read of every set moves
    by ``step`` from one chunk to the next: a run is one loop over its
    chunks, with the reads of its first chunk offset by the loop index."""
    def moves(a: _Read, b: _Read) -> bool:
        return b == dataclasses.replace(a, row=a.row + step[0],
                                        col=a.col + step[1])

    cuts = [0] + [c for c in range(1, chunks) if not all(
        len(st.reads[c]) == len(st.reads[c - 1])
        and all(map(moves, st.reads[c - 1], st.reads[c])) for st in sets)]
    return tuple(zip(cuts, cuts[1:] + [chunks]))


@functools.lru_cache(maxsize=None)
def select_geometry(rows: int, cols: int, bs: int) -> SelectGeometry | None:
    """The grid of the fused select over a ``(rows, cols)`` view whose
    row-major order is cut into blocks of ``bs``; None where that view
    cannot be read in place (the caller then passes the ``(n_blocks, bs)``
    block view, ``cols == bs``, which always can).  The grid depends on
    the view alone: bodies of about four sets of eight blocks, and steps
    of about ``_STEP_BYTES`` per array."""
    if (rows * cols) % bs:
        return None
    if cols % _LANES == 0 and bs % _LANES == 0:
        width = _LANES
    elif cols == bs:
        width = bs
    else:
        return None
    if cols == _LANES and bs > _LANES:
        n_blocks = rows * cols // bs
        sets, body_rows = _lanes_sets(n_blocks, bs)
        body_out, order = sum(st.n for st in sets), None
    else:
        plan = _row_sets(rows, cols, bs, width)
        if plan is None:
            return None
        sets, body_rows, body_out, order = plan
        if order == tuple(range(len(order))):
            order = None
    loops = max(1, min(_STEP_BYTES // (body_rows * cols * 4),
                       rows // body_rows))
    step = (1, 0) if cols == _LANES and bs > _LANES else (0, width)
    return SelectGeometry(rows, cols, bs, width, sets, body_rows, body_out,
                          loops, order, _runs(sets, bs // width, step), step)


def _select_kernel(*refs, geo: SelectGeometry, k: int, gated: bool,
                   residual: bool):
    """Exact per-block top-k of ``acc = e + u`` by magnitude, lowest
    column first among equal magnitudes (``lax.top_k``'s order), with the
    wire payload and, if ``residual``, ``acc`` less the kept entries.

    For ``k`` below the chunks of a block it is two-stage: one
    elementwise pass keeps each lane's top-k (magnitude, key) over the
    block's 128-lane chunks in increasing column order, where a strict
    ``>`` keeps the lower column on ties; then ``k`` masked-argmax passes
    run over those ``k`` candidates per lane.  A block's top-k lies within
    the union of its lanes' top-k, so this is exact.  Otherwise the
    passes run over every chunk.  The key is ``2·column + sign bit``:
    unique, ordered as the columns, and it restores the value exactly as
    ``±magnitude``.  The residual keeps ``x`` except at the top-k, which
    are exactly the entries ordered at or before the k-th winner; a kept
    ``x`` leaves ``x - (0 + x)``, bit for bit what subtracting the
    scattered payload from ``acc`` leaves.  Under ``gated`` a winner
    below ``thr`` is sent as value 0 and stays in the residual.

    The chunks are visited in loops, one per run of ``geo.runs``, and the
    passes in one loop, each step over all sets side by side: the traced
    body stays small, so the kernel lowers in a fraction of a second.
    """
    if gated:
        thr_ref, *refs = refs
    u_ref, e_ref, vals_ref, idx_ref, *res = refs
    thr = thr_ref[0, 0] if gated else None
    width, chunks = geo.width, geo.bs // geo.width
    d_row, d_col = geo.step

    def iota(n, w, axis):
        return jax.lax.broadcasted_iota(jnp.int32, (n, w), axis)

    def sublanes(n, mask):
        return functools.reduce(jnp.logical_or,
                                [iota(n, width, 0) == i for i in mask])

    def window(ref, base, n, rd, off):
        """Read ``rd`` moved ``off`` chunks on (an int, or a loop index)."""
        row = base + rd.row + (off * d_row if d_row else 0)
        col = rd.col
        if d_col:
            col = col + off * d_col
            if not isinstance(col, int):
                col = pl.multiple_of(col, _LANES)
        rows = pl.ds(row, n, stride=rd.stride) if rd.stride > 1 \
            else pl.ds(row, n)
        return ref.at[rows, pl.ds(col, width)]

    def acc(base, st, lo, off):
        """``e + u`` of chunk ``lo + off`` of the set's blocks, one per
        sublane; ``lo`` starts a run."""
        out = None
        for rd in st.reads[lo]:
            v = window(e_ref, base, st.n, rd, off)[...] + window(
                u_ref, base, st.n, rd, off)[...]
            if rd.shift:
                v = pltpu.roll(v, rd.shift, 0)
            out = v if out is None else jnp.where(
                sublanes(st.n, rd.mask), v, out)
        return out

    def scatter(ref, base, st, lo, off, x):
        for rd in st.reads[lo]:
            v = pltpu.roll(x, st.n - rd.shift, 0) if rd.shift else x
            if rd.mask is None:
                window(ref, base, st.n, rd, off)[...] = v
            else:
                pltpu.store(window(ref, base, st.n, rd, off), v,
                            mask=sublanes(st.n, tuple(
                                (i - rd.shift) % st.n for i in rd.mask)))

    def over_chunks(fn, carry, unrolled=False):
        """``carry = fn(lo, off, carry)`` for every chunk ``lo + off``, a
        loop per run (unrolled where each chunk must stay a value)."""
        for lo, end in geo.runs:
            if unrolled or end - lo == 1:
                for off in range(end - lo):
                    carry = fn(lo, off, carry)
            else:
                carry = jax.lax.fori_loop(
                    0, end - lo, functools.partial(fn, lo), carry)
        return carry

    def candidates(base):
        """Per set, each lane's (magnitude, key) candidates: its top-k
        over the chunks where ``k`` is below them, else every chunk."""
        def fn(lo, off, carry):
            out = []
            for st, (mags, keys) in zip(geo.sets, carry):
                x = acc(base, st, lo, off)
                sign = jax.lax.shift_right_logical(
                    jax.lax.bitcast_convert_type(x, jnp.int32), 31)
                a = jnp.abs(x)
                key = (iota(st.n, width, 1) + (lo + off) * width) * 2 + sign
                mags, keys = list(mags), list(keys)
                if k < chunks:
                    for j in range(k):
                        gt = a > mags[j]
                        mags[j], a = jnp.where(gt, a, mags[j]), jnp.where(
                            gt, mags[j], a)
                        keys[j], key = jnp.where(gt, key, keys[j]), \
                            jnp.where(gt, keys[j], key)
                else:
                    mags.append(a)
                    keys.append(key)
                out.append((tuple(mags), tuple(keys)))
            return tuple(out)

        if k >= chunks:
            return over_chunks(fn, (((), ()),) * len(geo.sets), True)
        # every lane sees more than k chunks, so no -1 stays a candidate
        return over_chunks(fn, tuple(
            ((jnp.full((st.n, width), -1.0, jnp.float32),) * k,
             (jnp.full((st.n, width), _NO_KEY, jnp.int32),) * k)
            for st in geo.sets))

    def select(cands):
        """``k`` masked-argmax passes over each set's candidates, the sets
        side by side.  Per set: the payload (values, columns) and the
        k-th winner's magnitude and column."""
        def one(p, carry):
            out = []
            for st, (_, keys), (mags, vals, idx, _, _) in zip(
                    geo.sets, cands, carry):
                m = jnp.max(functools.reduce(jnp.maximum, mags), axis=1,
                            keepdims=True)
                w = jnp.min(functools.reduce(jnp.minimum, [
                    jnp.where(a == m, key, _NO_KEY)
                    for a, key in zip(mags, keys)]), axis=1, keepdims=True)
                v = jnp.where((w & 1) == 1, -m, m)
                if gated:
                    v = jnp.where(m >= thr, v, 0.0)
                slot = iota(st.n, k, 1) == p
                out.append((tuple(jnp.where(key == w, -1.0, a)
                                  for a, key in zip(mags, keys)),
                            jnp.where(slot, v, vals),
                            jnp.where(slot, w >> 1, idx), m, w >> 1))
            return tuple(out)

        init = tuple((mags, jnp.zeros((st.n, k), jnp.float32),
                      jnp.zeros((st.n, k), jnp.int32),
                      jnp.zeros((st.n, 1), jnp.float32),
                      jnp.zeros((st.n, 1), jnp.int32))
                     for st, (mags, _) in zip(geo.sets, cands))
        return [c[1:] for c in jax.lax.fori_loop(0, k, one, init)]

    def body(it, carry):
        base, out = it * geo.body_rows, it * geo.body_out
        winners = []
        for st, (vals, idx, m_k, col_k) in zip(geo.sets,
                                               select(candidates(base))):
            vals_ref[pl.ds(out + st.out, st.n), :] = vals
            idx_ref[pl.ds(out + st.out, st.n), :] = idx
            winners.append((m_k, col_k))
        if not residual:
            return carry

        def fn(lo, off, _):
            for st, (m_k, col_k) in zip(geo.sets, winners):
                x = acc(base, st, lo, off)
                a = jnp.abs(x)
                kept = (a > m_k) | ((a == m_k) & (
                    iota(st.n, width, 1) <= col_k - (lo + off) * width))
                if gated:
                    kept = kept & (a >= thr)
                scatter(res[0], base, st, lo, off,
                        x - jnp.where(kept, x + 0.0, 0.0))
            return 0

        over_chunks(fn, 0)
        return carry

    if geo.loops == 1:
        body(0, 0)
    else:
        jax.lax.fori_loop(0, geo.loops, body, 0)


def _select_call(u, e, thr, *, k: int, block_size: int | None,
                 residual: bool, interpret: bool):
    rows, cols = u.shape
    bs = block_size or cols
    geo = select_geometry(rows, cols, bs)
    if geo is None:
        raise ValueError(f"a ({rows}, {cols}) array cannot be read as blocks "
                         f"of {bs} in place; pass the (n_blocks, {bs}) view")
    gated = thr is not None
    scalars = [jnp.asarray(thr, jnp.float32).reshape(1, 1)] if gated else []
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tile = pl.BlockSpec((geo.loops * geo.body_rows, cols), lambda i: (i, 0))
    pack = pl.BlockSpec((geo.loops * geo.body_out, k), lambda i: (i, 0))
    out_specs = [pack, pack]
    out_shape = [jax.ShapeDtypeStruct((geo.n_out, k), jnp.float32),
                 jax.ShapeDtypeStruct((geo.n_out, k), jnp.int32)]
    if residual:
        out_specs.append(tile)
        out_shape.append(jax.ShapeDtypeStruct((rows, cols), jnp.float32))
    vals, idx, *res = pl.pallas_call(
        functools.partial(_select_kernel, geo=geo, k=k, gated=gated,
                          residual=residual),
        grid=(pl.cdiv(rows, geo.loops * geo.body_rows),),
        in_specs=[smem] * len(scalars) + [tile, tile],
        out_specs=out_specs,
        out_shape=out_shape,
        # the residual overwrites e in place: each tile of e is read
        # before its residual is written, and no other tile reads it
        input_output_aliases={len(scalars) + 1: 2} if residual else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*scalars, u.astype(jnp.float32), e.astype(jnp.float32))
    if geo.order is None:
        return (vals[:geo.n_blocks], idx[:geo.n_blocks], *res)
    # blocks from the bodies' set order back to block order
    blocks = np.arange(geo.n_blocks)
    per = len(geo.order)
    at = blocks // per * geo.body_out + np.asarray(geo.order)[blocks % per]
    return (vals[at], idx[at], *res)


@functools.partial(jax.jit,
                   static_argnames=("k", "block_size", "interpret"))
def ef_select_pack_pallas(u: jax.Array, e: jax.Array, thr=None, *,
                          k: int, block_size: int | None = None,
                          interpret: bool = True):
    """Fused EF accumulate + per-block top-k select + payload pack.

    u: (rows, cols) the scaled update, any float dtype; e: (rows, cols)
    f32; the row-major
    order is cut into blocks of ``block_size`` (default ``cols``: each row
    is a block).  ``thr``: None, or a scalar f32 below which a winner is
    sent as value 0 (and kept in the residual).

    One pass over the layer, in place (``select_geometry``): reads u and
    e once, writes the wire payload (values (n_blocks, k) f32 + block-local
    indices (n_blocks, k) int32 — the ``bucketing.payload_bytes_per_elem``
    value+int32 layout) and the residual (rows, cols) f32 once; ``acc = e
    + u`` exists only in VMEM.  Bitwise equal to
    ``ref.ef_select_pack_ref`` on the block view.
    """
    return _select_call(u, e, thr, k=k, block_size=block_size,
                        residual=True, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("r", "interpret"))
def ef_block_candidates_pallas(u_rows: jax.Array, e_rows: jax.Array, *,
                               r: int, interpret: bool = True):
    """Per-block top-r candidates of ``acc = e + u``, accumulate fused.

    The hierarchical-selection stage 1 run directly on (u, e) — the only
    HBM traffic is one read of each plus the r·n_blocks candidate write;
    ``acc`` itself is never materialized.  Stage 2 (threshold from the
    candidates) is candidate-sized and runs in plain XLA.
    """
    return _select_call(u_rows, e_rows, None, k=r, block_size=None,
                        residual=False, interpret=interpret)
