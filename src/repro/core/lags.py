"""LAGS-SGD — layer-wise adaptive gradient sparsification (Algorithm 1).

Three gradient-exchange strategies share one interface:

  * ``DenseExchange``  — Dense-SGD baseline: plain mean over workers.
  * ``SLGSExchange``   — single-layer (whole-model-vector) Top-k baseline:
    one global Top-k after the full backward pass.  Structurally this
    serializes communication after computation (no pipelining), which in
    XLA terms is a single collective depending on every layer's gradient.
  * ``LAGSExchange``   — the paper: per-layer Top-k with per-layer error
    feedback and per-layer (bucketed) sparse collectives, each depending
    only on its own layer's backward op — XLA's latency-hiding scheduler
    can overlap them with the remaining backward computation.

Each strategy exposes the **bucket-stream interface**:

    init(updates_like)                       -> state (residual pytree)
    exchange(updates, state, axis_names)     -> (mean_update, new_state)
    exchange_bucket(wave, updates, state, axis_names)
                                             -> (means, new_state)

``exchange`` is the monolithic entry point: it flattens the update tree
and delegates to ``exchange_bucket`` with the single wave covering every
leaf — the degenerate case of the wave-pipelined step
(``repro.pipeline``), which calls ``exchange_bucket`` once per wave as
that wave's gradients materialise in backprop.  ``wave`` is anything
with a ``leaf_ids`` tuple (``repro.pipeline.buckets.Wave``) or a plain
sequence of **global** leaf indices into the flattened update tree;
``updates``/``state`` are flat lists of just the wave's leaves, in
``leaf_ids`` order.  Per-leaf PRNG streams fold the *global* leaf index,
so how leaves are grouped into waves never changes a selection — wave
and monolithic execution are bitwise identical.

``updates`` are **learning-rate-scaled** gradients (alpha * G), matching the
paper's Algorithm 1 where the residual accumulates parameter-deltas.

``axis_names`` selects the distributed path (inside ``jax.shard_map`` manual
axes); ``axis_names=None`` selects the P-leading-axis simulation path used
for CPU convergence experiments (updates leaves shaped ``(P, ...)``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compressors as C
from repro.kernels import ops as kops


# ---------------------------------------------------------------------------
# k^(l) bookkeeping
# ---------------------------------------------------------------------------

def _size(x) -> int:
    import math
    return int(math.prod(x.shape))


def leaf_dims(tree) -> Any:
    return jax.tree.map(_size, tree)


def ks_from_ratio(tree, ratio: float) -> Any:
    """k^(l) = max(1, d^(l) / c) for a scalar compression ratio c."""
    c = float(ratio)
    return jax.tree.map(lambda x: max(1, int(round(_size(x) / c))), tree)


def ks_from_ratios_tree(tree, ratios_tree) -> Any:
    return jax.tree.map(lambda x, c: max(1, int(round(_size(x) / float(c)))),
                        tree, ratios_tree)


# ---------------------------------------------------------------------------
# Local per-leaf sparsification (Algorithm 1, lines 7-9 local part)
# ---------------------------------------------------------------------------

def _compress_flat(acc_flat: jax.Array, k: int, compressor: C.Compressor,
                   key=None, **kw):
    if compressor.needs_key:
        # thread kwargs too: sampled compressors (topk_sampled) take both
        # a key and tuning knobs
        key = key if key is not None else jax.random.PRNGKey(0)
        return compressor(acc_flat, k, key=key, **kw)
    return compressor(acc_flat, k, **kw)


def local_select(acc_leaf: jax.Array, k: int, compressor: C.Compressor,
                 key=None, **kw):
    """Per-leaf: select top-k of the accumulated update.

    Returns (values, indices, residual_leaf).  residual = acc - TopK(acc).
    """
    flat = acc_leaf.reshape(-1)
    vals, idx = _compress_flat(flat, k, compressor, key=key, **kw)
    dense_sel = C.decompress(vals, idx, flat.shape[0])
    residual = (flat - dense_sel).reshape(acc_leaf.shape)
    return vals, idx, residual


def local_select_ef(u_leaf: jax.Array, e_leaf: jax.Array, k: int,
                    compressor: C.Compressor, key=None, *, label: str = "",
                    **kw):
    """Per-leaf EF accumulate + select, fused when the compressor can.

    The one selection entry point the exchanges call: a compressor with a
    ``fused_select`` kernel runs accumulate -> select -> residual ->
    payload pack in one HBM pass (``acc = e + u`` never materializes);
    otherwise this is exactly ``local_select(e + u, ...)``.  Same
    contract either way:

        e + u == scatter(values, indices) + residual

    Parity note: with materialized ``u``/``e`` operands the kernel and
    XLA backends agree **bitwise** (eager or jitted — the parity battery
    pins this).  Inside a *larger* jitted program XLA may contract u's
    producer into the accumulate (``lr*g + e`` -> one fma, no
    intermediate rounding; LLVM-level on CPU, so not suppressible with
    an optimization barrier) — a 1-ulp drift that makes even the XLA
    path disagree with its own eager execution.  It lands in the
    residual and the selected values, so end-to-end training agrees to
    1-ulp tolerance rather than bitwise; EF absorbs the difference.

    Runs under the ``lags/select/<label>`` phase scope.
    """
    with _phase_scope("select", label):
        if compressor.fused_select is not None and not compressor.needs_key:
            vals, idx, resid = compressor.fused_select(
                u_leaf.reshape(-1), e_leaf.reshape(-1), k, **kw)
            return vals, idx, resid.reshape(e_leaf.shape)
        acc = e_leaf + u_leaf.astype(e_leaf.dtype)
        return local_select(acc, k, compressor, key=key, **kw)


# ---------------------------------------------------------------------------
# Exchange strategies
# ---------------------------------------------------------------------------

def _psum_mean(x, axis_names):
    s = jax.lax.psum(x, axis_names)
    n = 1
    for a in axis_names:
        n *= jax.lax.axis_size(a)
    return s / n


def _axis_prod(axis_names) -> jax.Array:
    n = 1
    for a in axis_names:
        n *= jax.lax.axis_size(a)
    return n


def _worker_index(axis_names) -> jax.Array:
    """Linearized worker index over the manual axes (0 outside shard_map)."""
    idx = jnp.int32(0)
    for a in axis_names:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _leaf_key(key, leaf_no: int, worker=None):
    """Per-(step, leaf, worker) PRNG stream for key-needing compressors.

    ``key=None`` (callers that predate key threading) degrades to the old
    fixed stream — still distinct per leaf/worker, but identical every
    step.  Train loops pass a per-step key (fold_in of the step counter)
    so sampled selection (randk) draws fresh indices each step.

    ``worker`` must be the FULL linearized worker coordinate of whoever
    runs the selection (``_worker_index`` over every axis the selected
    data varies across).  Hierarchical exchanges fold the (outer, inner)
    coordinate for the intra-pod tier — where each worker selects on its
    own gradient — but only the outer (pod) coordinate for the cross-pod
    tier, where the accumulator is replicated within the pod and every
    inner worker must draw the SAME selection.
    """
    base = key if key is not None else jax.random.PRNGKey(0)
    k = jax.random.fold_in(base, leaf_no)
    if worker is not None:
        k = jax.random.fold_in(k, worker)
    return k


def _worker_keys(key, leaf_no: int, p):
    """(p,) stacked keys: ``fold_in(leaf_key, w)`` for ``w in range(p)``.

    The simulation (leading-P) paths use this so worker ``w`` draws the
    SAME stream the distributed path derives via
    ``_leaf_key(key, leaf_no, _worker_index(axes))`` — sim and
    distributed randk selections match coordinate for coordinate.
    """
    lk = _leaf_key(key, leaf_no)
    return jax.vmap(lambda w: jax.random.fold_in(lk, w))(jnp.arange(p))


def _wave_ids(wave) -> tuple[int, ...]:
    """Global flatten-order leaf indices of a wave.

    Accepts a ``repro.pipeline.buckets.Wave`` (anything with a
    ``leaf_ids`` attribute) or a plain sequence of ints.  Strategies key
    their per-leaf PRNG streams and comm labels off these GLOBAL
    indices, which is what makes wave grouping invisible to the math.
    """
    ids = getattr(wave, "leaf_ids", wave)
    return tuple(int(i) for i in ids)


def _comm_scope(tier: str, kind: str, label: str, nbytes: float, p: int):
    """In-jit annotation carrying the ``repro.observe.names`` grammar,
    so a real device profile attributes each collective per leaf/tier.
    Lazy function-scope imports: observe's modules import nothing from
    ``repro.core.lags``, so no cycle — and tracing only pays them once
    per compile."""
    from repro.observe import names as _obs_names
    from repro.observe.trace import device_annotation
    return device_annotation(
        _obs_names.comm_name(tier, kind, label, nbytes=nbytes, p=p))


def _phase_scope(phase: str, label: str = ""):
    """``observe.trace.phase_scope``, imported lazily as in
    :func:`_comm_scope`."""
    from repro.observe.trace import phase_scope
    return phase_scope(phase, label)


def _sparse_mean_over(vals, idx, d: int, axes, *, tier: str = "flat",
                      label: str = "leaf") -> jax.Array:
    """All-gather each worker's sparse (vals, idx) over the manual
    ``axes`` and scatter-mean into a dense d-vector; ``axes=()`` is the
    single-worker degeneracy (plain decompress).  The gather runs under
    an observe-grammar named scope (``tier``/``label``) so device traces
    attribute it per collective."""
    if axes:
        # 2*k scalars per worker: fp32 values + int32 indices
        with _comm_scope(tier, "allgather", label, 8.0 * vals.size,
                         _axis_prod(axes)):
            vals_all = jax.lax.all_gather(vals, axes, tiled=False)
            idx_all = jax.lax.all_gather(idx, axes, tiled=False)
            return _gathered_scatter_mean(vals_all, idx_all, d,
                                          _axis_prod(axes), label=label)
    with _phase_scope("scatter_mean", label):
        return C.decompress(vals, idx, d)


@dataclasses.dataclass(frozen=True)
class DenseExchange:
    """Vanilla S-SGD: mean of dense updates across workers."""
    name: str = "dense"
    wave_granularity = "leaf"

    def init(self, updates_like):
        return ()

    def exchange_bucket(self, wave, updates, state,
                        axis_names: Sequence[str] | None, *, key=None):
        """Dense mean over one wave's flat leaf list; state is ()."""
        del key
        ids = _wave_ids(wave)
        if axis_names is None:  # simulation: leading P axis
            means = [u.mean(0) for u in updates]
        else:
            axes = tuple(axis_names)
            means = []
            for i, u in zip(ids, updates):
                with _comm_scope("flat", "allreduce", f"l{i}",
                                 4.0 * u.size, _axis_prod(axes)):
                    means.append(_psum_mean(u, axes))
        return means, state

    def exchange(self, updates, state, axis_names: Sequence[str] | None,
                 *, key=None):
        flat_u, treedef = jax.tree.flatten(updates)
        means, state = self.exchange_bucket(
            tuple(range(len(flat_u))), flat_u, state, axis_names, key=key)
        return treedef.unflatten(means), state


def _gathered_scatter_mean(vals_all, idx_all, d: int, p, *,
                           label: str = "") -> jax.Array:
    """Sum every worker's sparse contribution into a dense vector, / P,
    under the ``lags/scatter_mean/<label>`` phase scope.

    vals_all/idx_all: (P, k) or flattened (P*k,)."""
    with _phase_scope("scatter_mean", label):
        dense = jnp.zeros((d,), vals_all.dtype)
        dense = dense.at[idx_all.reshape(-1)].add(vals_all.reshape(-1))
        return dense / p


@dataclasses.dataclass(frozen=True)
class LAGSExchange:
    """Layer-wise adaptive gradient sparsification (the paper).

    ``ks`` is a pytree (matching the update pytree) of per-leaf k^(l).
    """
    ks: Any
    compressor_name: str = "topk_exact"
    residual_dtype: Any = jnp.float32
    name: str = "lags"
    compressor_kwargs: tuple = ()
    wave_granularity = "leaf"

    @property
    def compressor(self) -> C.Compressor:
        return C.get_compressor(self.compressor_name)

    def init(self, updates_like):
        # In simulation, ``updates_like`` leaves carry a leading P axis and
        # so do the residuals (one residual vector per simulated worker).
        return jax.tree.map(
            lambda u: jnp.zeros(u.shape, self.residual_dtype), updates_like)

    def exchange_bucket(self, wave, updates, state,
                        axis_names: Sequence[str] | None, *, key=None):
        """One wave: flat lists of the wave's leaves, global-id keyed."""
        kw = dict(self.compressor_kwargs)
        needs_key = self.compressor.needs_key
        ids = _wave_ids(wave)
        flat_k = jax.tree.leaves(self.ks)

        if axis_names is None:
            # --- simulation path: leaves have leading P axis ---------------
            def leaf_fn(i, u, e, k):
                d = u[0].size
                p = u.shape[0]
                if needs_key:
                    wkeys = _worker_keys(key, i, p)
                    vals, idx, resid = jax.vmap(
                        lambda uu, ee, kk: local_select_ef(
                            uu, ee, k, self.compressor, key=kk,
                            label=f"l{i}", **kw)
                    )(u, e, wkeys)
                else:
                    vals, idx, resid = jax.vmap(
                        lambda uu, ee: local_select_ef(
                            uu, ee, k, self.compressor, label=f"l{i}", **kw)
                    )(u, e)
                mean = _gathered_scatter_mean(vals, idx, d, p,
                                              label=f"l{i}")
                return mean.reshape(u.shape[1:]), resid
        else:
            # --- distributed path (inside shard_map manual axes) ----------
            axes = tuple(axis_names)

            def leaf_fn(i, u, e, k):
                wk = (_leaf_key(key, i, _worker_index(axes)) if needs_key
                      else None)
                vals, idx, resid = local_select_ef(u, e, k, self.compressor,
                                                   key=wk, label=f"l{i}",
                                                   **kw)
                # layer-wise sparse all-gather: ships 2*k scalars per worker
                mean = _sparse_mean_over(vals, idx, u.size, axes,
                                         label=f"l{i}")
                return mean.reshape(u.shape).astype(u.dtype), resid

        out = [leaf_fn(i, u, e, flat_k[i])
               for i, u, e in zip(ids, updates, state)]
        return [o[0] for o in out], [o[1] for o in out]

    def exchange(self, updates, state, axis_names: Sequence[str] | None,
                 *, key=None):
        flat_u, treedef = jax.tree.flatten(updates)
        means, resids = self.exchange_bucket(
            tuple(range(len(flat_u))), flat_u, treedef.flatten_up_to(state),
            axis_names, key=key)
        return treedef.unflatten(means), treedef.unflatten(resids)


@dataclasses.dataclass(frozen=True)
class SLGSExchange:
    """Single-layer gradient sparsification baseline: global Top-k over the
    concatenation of ALL layers (k_total = sum over the per-layer budget),
    selected only after the entire backward pass."""
    k_total: int
    compressor_name: str = "topk_exact"
    residual_dtype: Any = jnp.float32
    name: str = "slgs"
    compressor_kwargs: tuple = ()
    # Global top-k over the whole-model vector: the selection is only
    # defined once every leaf's gradient exists, so the pipeline layer
    # must schedule exactly one wave (``repro.pipeline.waves`` honours
    # this marker and degenerates to a single post-backward wave).
    wave_granularity = "model"

    @property
    def compressor(self) -> C.Compressor:
        return C.get_compressor(self.compressor_name)

    def init(self, updates_like):
        return jax.tree.map(
            lambda u: jnp.zeros(u.shape, self.residual_dtype), updates_like)

    def exchange_bucket(self, wave, updates, state,
                        axis_names: Sequence[str] | None, *, key=None):
        ids = _wave_ids(wave)
        if ids != tuple(range(len(ids))):
            raise ValueError(
                "slgs selects over the whole-model vector: its single wave "
                "must cover every leaf in flatten order "
                f"(wave_granularity='model'), got leaf_ids={ids}")
        kw = dict(self.compressor_kwargs)
        needs_key = self.compressor.needs_key
        flat_u, flat_e = list(updates), list(state)

        def pack(us, es):
            # concatenate u and e separately (elementwise add commutes with
            # concat) so a fused compressor can run accumulate+select in
            # one kernel pass over the whole-model vector
            u_vec = jnp.concatenate([u.reshape(-1) for u in us])
            e_vec = jnp.concatenate([e.reshape(-1).astype(jnp.float32)
                                     for e in es])
            return u_vec, e_vec

        if axis_names is None:
            p = flat_u[0].shape[0]
            d = sum(int(u[0].size) for u in flat_u)

            def worker(us, es, wk):
                u_vec, e_vec = pack(us, es)
                vals, idx, resid_vec = local_select_ef(
                    u_vec, e_vec, self.k_total, self.compressor,
                    key=(wk if needs_key else None), label="packed", **kw)
                return vals, idx, resid_vec

            wkeys = _worker_keys(key, 0, p)
            vals, idx, resid_vec = jax.vmap(worker)(flat_u, flat_e, wkeys)
            mean_vec = _gathered_scatter_mean(vals, idx, d, p,
                                              label="packed")
            means, resids, off = [], [], 0
            for u in flat_u:
                n = int(u[0].size)
                means.append(mean_vec[off:off + n].reshape(u.shape[1:]).astype(u.dtype))
                resids.append(resid_vec[:, off:off + n].reshape(u.shape))
                off += n
            return means, resids

        axes = tuple(axis_names)
        u_vec, e_vec = pack(flat_u, flat_e)
        wk = _leaf_key(key, 0, _worker_index(axes)) if needs_key else None
        vals, idx, resid_vec = local_select_ef(u_vec, e_vec, self.k_total,
                                               self.compressor, key=wk,
                                               label="packed", **kw)
        mean_vec = _sparse_mean_over(vals, idx, u_vec.shape[0], axes,
                                     label="packed")
        means, resids, off = [], [], 0
        for u in flat_u:
            n = u.size
            means.append(mean_vec[off:off + n].reshape(u.shape).astype(u.dtype))
            resids.append(resid_vec[off:off + n].reshape(u.shape))
            off += n
        return means, resids

    def exchange(self, updates, state, axis_names: Sequence[str] | None,
                 *, key=None):
        flat_u, treedef = jax.tree.flatten(updates)
        means, resids = self.exchange_bucket(
            tuple(range(len(flat_u))), flat_u, treedef.flatten_up_to(state),
            axis_names, key=key)
        return treedef.unflatten(means), treedef.unflatten(resids)




# ---------------------------------------------------------------------------
# Block-LAGS: the production distributed path.
# ---------------------------------------------------------------------------

#: Largest per-block budget the TPU's manual block path selects in the
#: fused pass: the largest measured.  On a v5e, over one Mistral-7B
#: layer's leaves and its embedding (350 M f32 parameters), the whole
#: exchange took 24.8 / 85.8 / 107.2 / 158.1 / 304.0 / 608.5 ms fused
#: against 46.0 / 156.3 / 347.7 / 363.9 / 512.7 / 808.2 ms in XLA at k_b
#: 5 / 32 / 41 / 64 / 128 / 256; both grow about linearly in k_b there.
FUSED_MAX_KB = 256


@dataclasses.dataclass(frozen=True)
class BlockLAGSExchange:
    """LAGS with the block-budget compressor, keeping the (n_blocks,
    block_size) layout through selection -> all-gather -> scatter so every
    stage is embarrassingly block-parallel (shards over any mesh axis with
    zero resharding, and never runs a global sort over a 10^8..10^11-element
    layer).

    Exactly k_b = ceil(k^(l) / n_blocks) elements are kept per block.
    Covered by the paper's Lemma 1 with the partition pieces = blocks
    (c_max = block_size / k_b); the same error-feedback residual semantics
    as `LAGSExchange` (Algorithm 1 lines 7-9) apply per leaf.
    """
    ks: Any
    block_size: int = 4096
    residual_dtype: Any = jnp.float32
    name: str = "lags_block"
    use_kernel: bool = False
    # Auto mesh axes to shard the (n_blocks, bs) row view over.  Pinning the
    # layout makes the top-k_b selection and the scatter-back fully local
    # per device (block-parallel), and avoids SPMD-partitioner pathologies
    # for gathers/scatters on reshaped views inside partial-manual shard_map.
    row_axes: tuple = ()
    # Per-leaf tuple of SHARDED dim indices (same pytree structure as ``ks``;
    # () / None = unsharded).  When set, the block view is built by
    # transposing the sharded dims to the FRONT before flattening, so the
    # row dim of the (n_blocks, bs) view is sharded exactly like the leaf —
    # the reshape is then a local relabeling and XLA inserts NO collective
    # for selection/scatter.  Without it, flattening a tensor sharded on an
    # inner dim interleaves elements across shards and the partitioner
    # materializes a FULL all-gather of the leaf (measured: 29.6 GiB/dev of
    # the 57.9 GiB/dev collective traffic on llama3-8b train_4k).
    shard_dims: Any = None

    def init(self, updates_like):
        return jax.tree.map(
            lambda u: jnp.zeros(u.shape, self.residual_dtype), updates_like)

    def _pin_rows(self, rows: jax.Array) -> jax.Array:
        if not self.row_axes:
            return rows
        from jax.sharding import PartitionSpec as P
        ax = self.row_axes if len(self.row_axes) > 1 else self.row_axes[0]
        return jax.lax.with_sharding_constraint(rows, P(ax, None))

    # -- per-leaf geometry --------------------------------------------------
    def _geom(self, size: int, k: int):
        bs = min(self.block_size, size)
        n_blocks = -(-size // bs)
        # ratio-preserving per-block budget: k_b/bs >= k/d, so c=1 (k=d)
        # keeps every element even when d is not block-divisible
        k_b = max(1, min(bs, -(-k * bs // size)))
        return n_blocks, bs, k_b

    def _select_rows(self, rows: jax.Array, k_b: int):
        """(n_blocks, bs) -> (vals, local idx) each (n_blocks, k_b).

        For small k_b this runs k_b masked-argmax passes (the same program
        as the Pallas block_topk kernel) instead of ``lax.top_k``:
        ``top_k`` lowers to an opaque TopK custom-call that GSPMD cannot
        partition, so the partitioner ALL-GATHERS the full row matrix
        (measured 27 GiB/dev on llama3-8b).  Max/argmax/where are
        elementwise/reduce ops along the unsharded dim -> fully local."""
        if k_b > 32:
            _, local = jax.lax.top_k(jnp.abs(rows), k_b)
            vals = jnp.take_along_axis(rows, local, axis=1)
            return vals, local.astype(jnp.int32)
        n, bs = rows.shape
        mag = jnp.abs(rows.astype(jnp.float32))
        col = jax.lax.broadcasted_iota(jnp.int32, (n, bs), 1)
        vals, idx = [], []
        for _ in range(k_b):
            i = jnp.argmax(mag, axis=1).astype(jnp.int32)       # (n,)
            hit = col == i[:, None]
            v = jnp.sum(jnp.where(hit, rows, 0), axis=1)
            vals.append(v)
            idx.append(i)
            mag = jnp.where(hit, -1.0, mag)
        return (jnp.stack(vals, axis=1).astype(rows.dtype),
                jnp.stack(idx, axis=1))

    def _fused(self, k_b: int, manual: bool) -> bool:
        """Whether the select runs as the fused Pallas pass
        (``kops.ef_select_pack_leaf``): always under ``use_kernel``; on a
        TPU also on the manual path for k_b <= ``FUSED_MAX_KB``, where it
        is bitwise equal to the XLA branch and reads the leaf once
        instead of making k_b passes over it in HBM (or a
        ``lax.top_k``)."""
        return self.use_kernel or (manual and k_b <= FUSED_MAX_KB
                                   and kops.on_tpu())

    def _local_rows(self, u_flat, e_flat, n_blocks, bs, k_b):
        """Accumulate + select on the padded block view, in XLA.

        Returns (vals, local, residual_rows)."""
        pad = n_blocks * bs - u_flat.shape[0]
        acc = e_flat + u_flat.astype(e_flat.dtype)
        rows = self._pin_rows(jnp.pad(acc, (0, pad)).reshape(n_blocks, bs))
        vals, local = self._select_rows(rows, k_b)
        row_ids = jnp.arange(n_blocks, dtype=jnp.int32)[:, None]
        sel_rows = jnp.zeros_like(rows).at[row_ids, local].set(vals)
        resid_rows = rows - sel_rows
        return vals, local, resid_rows

    wave_granularity = "leaf"

    def exchange_bucket(self, wave, updates, state,
                        axis_names: Sequence[str] | None, *, key=None):
        # block-Top-k selection is deterministic; ``key`` is accepted for
        # interface uniformity (every strategy takes the per-step stream)
        del key
        ids = _wave_ids(wave)
        flat_k = jax.tree.leaves(self.ks)
        if self.shard_dims is None:
            flat_s = None
        else:
            flat_s = jax.tree.structure(self.ks).flatten_up_to(
                self.shard_dims)
        outs = [self._leaf(u, e, flat_k[i],
                           (flat_s[i] if flat_s is not None else None),
                           axis_names, f"l{i}")
                for i, u, e in zip(ids, updates, state)]
        return [o[0] for o in outs], [o[1] for o in outs]

    def exchange(self, updates, state, axis_names: Sequence[str] | None,
                 *, key=None):
        flat_u, treedef = jax.tree.flatten(updates)
        means, resids = self.exchange_bucket(
            tuple(range(len(flat_u))), flat_u, treedef.flatten_up_to(state),
            axis_names, key=key)
        return treedef.unflatten(means), treedef.unflatten(resids)

    @staticmethod
    def _perm(ndim: int, sdims) -> tuple[int, ...] | None:
        """Permutation putting the sharded dims first (None = identity)."""
        sd = tuple(d for d in (sdims or ()) if 0 <= d < ndim)
        if not sd:
            return None
        return sd + tuple(i for i in range(ndim) if i not in sd)

    def _leaf(self, u, e, k, sdims, axis_names, label: str = ""):
        """One leaf: select (``lags/select/<label>``), the sparse
        all-gather, and the scatter-mean (``lags/scatter_mean/<label>``)."""
        param_shape = u.shape if axis_names is not None else u.shape[1:]
        size = 1
        for s in param_shape:
            size *= int(s)
        n_blocks, bs, k_b = self._geom(size, int(k))
        row_ids = jnp.arange(n_blocks, dtype=jnp.int32)[:, None]
        perm = self._perm(len(param_shape), sdims)
        inv_perm = tuple(int(i) for i in np.argsort(perm)) if perm else None
        perm_shape = tuple(param_shape[i] for i in perm) if perm else None

        def to_flat(x):
            return (x.transpose(perm) if perm else x).reshape(-1)

        def from_flat(flat):
            if perm is None:
                return flat.reshape(param_shape)
            return flat.reshape(perm_shape).transpose(inv_perm)

        fused = self._fused(k_b, manual=axis_names is not None)

        def select(uu, ee):
            """(vals, local, residual) of one worker's leaf."""
            if fused:
                # accumulate + select + payload pack + residual in one
                # pass; acc never materializes in HBM
                def lead(x):
                    return x.transpose(perm) if perm else x
                vals, local, resid = kops.ef_select_pack_leaf(
                    lead(uu), lead(ee), k_b, bs, row_axes=self.row_axes)
                return vals, local, (resid.transpose(inv_perm) if perm
                                     else resid)
            vals, local, resid_rows = self._local_rows(
                to_flat(uu), to_flat(ee), n_blocks, bs, k_b)
            return vals, local, from_flat(resid_rows.reshape(-1)[:size])

        if axis_names is None:
            # simulation path: leading (P,) axis
            p = u.shape[0]
            with _phase_scope("select", label):
                vals, local, resid = jax.vmap(select)(u, e)
            # aggregate: (P, n_blocks, k_b) -> per-row scatter-add
            with _phase_scope("scatter_mean", label):
                idx_cat = jnp.moveaxis(local, 0, 1).reshape(n_blocks,
                                                            p * k_b)
                val_cat = jnp.moveaxis(vals, 0, 1).reshape(n_blocks, p * k_b)
                mean_rows = self._pin_rows(
                    jnp.zeros((n_blocks, bs), vals.dtype)) \
                    .at[row_ids, idx_cat].add(val_cat) / p
                mean = from_flat(mean_rows.reshape(-1)[:size])
            return mean.astype(u.dtype), resid

        axes = tuple(axis_names)
        with _phase_scope("select", label):
            vals, local, resid = select(u, e)
        if axes:
            # layer-wise sparse all-gather: 2*k_b scalars per block per worker
            with _comm_scope("flat", "allgather", "blocks",
                             8.0 * vals.size, _axis_prod(axes)):
                vals_all = jax.lax.all_gather(vals, axes, tiled=False)
                local_all = jax.lax.all_gather(local, axes, tiled=False)
            p = _axis_prod(axes)
            pk = vals_all.shape[0] * k_b
            idx_cat = jnp.moveaxis(local_all, 0, 1).reshape(n_blocks, pk)
            val_cat = jnp.moveaxis(vals_all, 0, 1).reshape(n_blocks, pk)
        else:
            p = 1
            idx_cat, val_cat = local, vals
        with _phase_scope("scatter_mean", label):
            mean_rows = self._pin_rows(
                jnp.zeros((n_blocks, bs), vals.dtype)) \
                .at[row_ids, idx_cat].add(val_cat) / p
            mean = from_flat(mean_rows.reshape(-1)[:size])
        return mean.astype(u.dtype), resid


# ---------------------------------------------------------------------------
# Hierarchical LAGS (beyond-paper, multi-pod): dense reduce-scatter within
# the fast intra-pod ICI, sparse LAGS exchange across pods on the owned
# gradient slice.  Covered by the paper's theory because Lemma 1 holds for
# ANY partition of the gradient vector into pieces (shards are pieces).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HierLAGSExchange:
    """``inner_axes``: dense-mean axes (fast links). ``outer_axes``: LAGS
    sparse-exchange axes (slow links).  Residuals live on the per-device
    gradient shard (already sharded by GSPMD over auto axes)."""
    ks: Any
    inner_axes: tuple
    outer_axes: tuple
    compressor_name: str = "topk_exact"
    residual_dtype: Any = jnp.float32
    name: str = "lags_hier"
    compressor_kwargs: tuple = ()

    @property
    def compressor(self) -> C.Compressor:
        return C.get_compressor(self.compressor_name)

    def init(self, updates_like):
        return jax.tree.map(
            lambda u: jnp.zeros(u.shape, self.residual_dtype), updates_like)

    wave_granularity = "leaf"

    def exchange_bucket(self, wave, updates, state, axis_names=None,
                        *, key=None):
        kw = dict(self.compressor_kwargs)
        needs_key = self.compressor.needs_key
        ids = _wave_ids(wave)
        flat_k = jax.tree.leaves(self.ks)

        def leaf_fn(i, u, e, k):
            if self.inner_axes:
                u = _psum_mean(u, self.inner_axes)
            # the dense inner mean replicates the accumulator within the
            # pod, so the key folds ONLY the outer (pod) coordinate —
            # every inner worker must draw the same selection (_leaf_key)
            wk = (_leaf_key(key, i, _worker_index(self.outer_axes))
                  if needs_key else None)
            vals, idx, resid = local_select_ef(u, e, k, self.compressor,
                                               key=wk, label=f"l{i}", **kw)
            mean = _sparse_mean_over(vals, idx, u.size, self.outer_axes,
                                     tier="outer", label=f"l{i}")
            return mean.reshape(u.shape).astype(u.dtype), resid

        out = [leaf_fn(i, u, e, flat_k[i])
               for i, u, e in zip(ids, updates, state)]
        return [o[0] for o in out], [o[1] for o in out]

    def exchange(self, updates, state, axis_names=None, *, key=None):
        flat_u, treedef = jax.tree.flatten(updates)
        means, resids = self.exchange_bucket(
            tuple(range(len(flat_u))), flat_u, treedef.flatten_up_to(state),
            axis_names, key=key)
        return treedef.unflatten(means), treedef.unflatten(resids)


# ---------------------------------------------------------------------------
# Two-level sparse hierarchy ("lags_hier2"): BOTH tiers sparse.  The inner
# (intra-pod ICI) tier runs a per-worker LAGS selection with its own
# per-leaf budget ks_inner and its own error-feedback residual; the outer
# (cross-pod DCN) tier runs the sparse all-gather on the inner-tier mean
# with a second residual.  Covered by Lemma 1 twice over: the partition
# pieces are the leaves at each tier, and the k-contraction argument of
# Alistarh et al. (arXiv 1809.10505) composes across the two EF levels.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SparseHierLAGSExchange:
    """Sparse-intra-pod hierarchical LAGS ("lags_hier2").

    Per leaf, per step:

      1. inner tier — each worker accumulates its inner residual
         (``acc_in = e_in + u``), selects ``ks_inner`` entries, and the
         selections are scatter-meaned within the pod (``inner`` axes);
      2. outer tier — the pod-level mean lands on a second accumulator
         (``acc_out = e_out + m``, replicated across the pod), ``ks``
         entries are selected and scatter-meaned across pods (``outer``
         axes).

    Per-tier invariant: ``acc == selected + residual`` (Algorithm 1
    lines 7-9, applied once per tier).  State is a two-tree dict
    ``{"inner": resid, "outer": resid}``; the outer residual is
    replicated across the inner workers of a pod (same data, same key,
    deterministic ops), which keeps the distributed manual path and the
    leading-P simulation path bit-identical.

    Degeneracies (pinned by tests): inner ratio 1 (ks_inner = dims)
    reduces tier 1 to the dense intra-pod mean — the existing
    ``lags_hier`` semantics; a single pod (no outer axes) with outer
    ratio 1 reduces to ``lags_dp`` with ``ks = ks_inner``.

    Distributed, the exchange runs inside shard_map-MANUAL axes and
    splits ``axis_names`` itself: ``outer_axis`` (default 'pod') carries
    the cross-pod tier, every other manual axis is intra-pod.  In
    simulation (``axis_names=None``) the leading ``P`` axis factors as
    ``(n_outer, n_inner)``, outer-major — the same linearization
    ``_worker_index`` produces for ('pod', 'data')."""
    ks: Any                        # outer-tier per-leaf k (cross-pod DCN)
    ks_inner: Any                  # inner-tier per-leaf k (intra-pod ICI)
    n_inner: int = 1               # leading-P factorization (sim path only)
    outer_axis: str = "pod"
    compressor_name: str = "topk_exact"
    residual_dtype: Any = jnp.float32
    name: str = "lags_hier2"
    compressor_kwargs: tuple = ()
    # Inner-tier compressor override (None = same as compressor_name).
    # The inner tier selects on every worker's own full-size gradient —
    # the hot, per-device selection — so it is where the block-parallel
    # (BlockLAGS-style) compressors pay off: inner "topk_block" /
    # "topk_block_ef_kernel" keeps inner selection block-local and
    # GSPMD-partitionable while the (candidate-sized) outer tier can stay
    # exact.
    inner_compressor_name: str | None = None
    inner_compressor_kwargs: tuple = ()

    @property
    def compressor(self) -> C.Compressor:
        return C.get_compressor(self.compressor_name)

    @property
    def inner_compressor(self) -> C.Compressor:
        return C.get_compressor(self.inner_compressor_name
                                or self.compressor_name)

    def init(self, updates_like):
        def zeros(u):
            return jax.tree.map(
                lambda x: jnp.zeros(x.shape, self.residual_dtype), u)
        return {"inner": zeros(updates_like), "outer": zeros(updates_like)}

    wave_granularity = "leaf"

    def exchange_bucket(self, wave, updates, state,
                        axis_names: Sequence[str] | None, *, key=None):
        """One wave; ``state`` is ``{"inner": [...], "outer": [...]}`` flat
        lists of the wave's two-tier residual leaves."""
        kw = dict(self.compressor_kwargs)
        comp = self.compressor
        needs_key = comp.needs_key
        ikw = dict(self.inner_compressor_kwargs) \
            if self.inner_compressor_name else kw
        icomp = self.inner_compressor
        needs_key_in = icomp.needs_key

        ids = _wave_ids(wave)
        flat_u = list(updates)
        flat_ei = list(state["inner"])
        flat_eo = list(state["outer"])
        all_ki = jax.tree.leaves(self.ks_inner)
        all_ko = jax.tree.leaves(self.ks)
        flat_ki = [all_ki[i] for i in ids]
        flat_ko = [all_ko[i] for i in ids]

        if axis_names is None:
            # --- simulation path: leading P = n_outer * n_inner ------------
            n_in = max(1, int(self.n_inner))

            def leaf_fn(i, u, e_in, e_out, k_in, k_out):
                p = u.shape[0]
                if p % n_in:
                    raise ValueError(
                        f"P={p} workers do not factor into n_inner={n_in} "
                        f"per pod (leaf {i})")
                n_out = p // n_in
                d = u[0].size
                # inner tier: per-worker selection, full-coordinate keys
                if needs_key_in:
                    wkeys = _worker_keys(key, i, p)
                    vals, idx, resid_in = jax.vmap(
                        lambda uu, ee, kk: local_select_ef(
                            uu, ee, k_in, icomp, key=kk, label=f"l{i}",
                            **ikw)
                    )(u, e_in, wkeys)
                else:
                    vals, idx, resid_in = jax.vmap(
                        lambda uu, ee: local_select_ef(
                            uu, ee, k_in, icomp, label=f"l{i}", **ikw)
                    )(u, e_in)
                # intra-pod scatter-mean: group the (P, k) selections by pod
                m = jax.vmap(
                    lambda v, ix: _gathered_scatter_mean(
                        v, ix, d, n_in, label=f"l{i}"))(
                        vals.reshape(n_out, n_in, -1),
                        idx.reshape(n_out, n_in, -1))       # (n_out, d)
                # outer tier: one accumulator per pod (e_out is replicated
                # within the pod — take the pod's first copy), outer-only
                # keys.  When this leaf's inner tier is dense (k_in >= d)
                # the exchange degenerates to lags_hier and the outer
                # stream must be LAGSExchange's fold_in(leaf_key, o)
                # exactly; when the inner tier is SPARSE, shift the outer
                # stream past the inner worker-index space (p + o) so the
                # two tiers draw independent randk samples instead of pod
                # o's outer selection colliding with worker o's inner one
                e_pod = e_out.reshape((n_out, n_in) + e_out.shape[1:])[:, 0]
                m_pod = m.reshape((n_out,) + u.shape[1:])
                o_base = 0 if int(k_in) >= d else p
                if needs_key:
                    lk = _leaf_key(key, i)
                    okeys = jax.vmap(lambda o: jax.random.fold_in(lk, o))(
                        jnp.arange(o_base, o_base + n_out))
                    vals2, idx2, resid_out = jax.vmap(
                        lambda mm, ee, kk: local_select_ef(
                            mm, ee, k_out, comp, key=kk, label=f"l{i}", **kw)
                    )(m_pod, e_pod, okeys)
                else:
                    vals2, idx2, resid_out = jax.vmap(
                        lambda mm, ee: local_select_ef(
                            mm, ee, k_out, comp, label=f"l{i}", **kw)
                    )(m_pod, e_pod)
                mean = _gathered_scatter_mean(vals2, idx2, d, n_out,
                                              label=f"l{i}")
                resid_out_full = jnp.broadcast_to(
                    resid_out[:, None],
                    (n_out, n_in) + resid_out.shape[1:]).reshape(e_out.shape)
                return (mean.reshape(u.shape[1:]).astype(u.dtype),
                        resid_in, resid_out_full)

            out = [leaf_fn(i, u, ei, eo, ki, ko)
                   for i, u, ei, eo, ki, ko in zip(
                       ids, flat_u, flat_ei, flat_eo, flat_ki, flat_ko)]
        else:
            # --- distributed path (shard_map manual axes) ------------------
            axes = tuple(axis_names)
            outer = tuple(a for a in axes if a == self.outer_axis)
            inner = tuple(a for a in axes if a != self.outer_axis)

            def leaf_fn(i, u, e_in, e_out, k_in, k_out):
                # inner selection runs on per-worker data: fold the FULL
                # (outer, inner) worker coordinate into the key stream
                wk_in = (_leaf_key(key, i, _worker_index(axes))
                         if needs_key_in else None)
                vals, idx, resid_in = local_select_ef(u, e_in, k_in, icomp,
                                                      key=wk_in,
                                                      label=f"l{i}", **ikw)
                m = _sparse_mean_over(vals, idx, u.size, inner,
                                      tier="inner", label=f"l{i}")
                # outer accumulator is pod-replicated: outer-only key so
                # every inner worker draws the SAME cross-pod selection.
                # Sparse inner tier -> shift the outer stream past the
                # inner worker-index space (see the sim path above)
                o_base = 0 if int(k_in) >= u.size else _axis_prod(axes)
                wk_out = (_leaf_key(key, i, o_base + _worker_index(outer))
                          if needs_key else None)
                vals2, idx2, resid_out = local_select_ef(
                    m.reshape(u.shape), e_out, k_out, comp, key=wk_out,
                    label=f"l{i}", **kw)
                mean = _sparse_mean_over(vals2, idx2, u.size, outer,
                                         tier="outer", label=f"l{i}")
                return (mean.reshape(u.shape).astype(u.dtype),
                        resid_in, resid_out)

            out = [leaf_fn(i, u, ei, eo, ki, ko)
                   for i, u, ei, eo, ki, ko in zip(
                       ids, flat_u, flat_ei, flat_eo, flat_ki, flat_ko)]

        return ([o[0] for o in out],
                {"inner": [o[1] for o in out],
                 "outer": [o[2] for o in out]})

    def exchange(self, updates, state, axis_names: Sequence[str] | None,
                 *, key=None):
        flat_u, treedef = jax.tree.flatten(updates)
        means, ns = self.exchange_bucket(
            tuple(range(len(flat_u))), flat_u,
            {"inner": treedef.flatten_up_to(state["inner"]),
             "outer": treedef.flatten_up_to(state["outer"])},
            axis_names, key=key)
        return (treedef.unflatten(means),
                {"inner": treedef.unflatten(ns["inner"]),
                 "outer": treedef.unflatten(ns["outer"])})
