"""String -> factory registries for exchange strategies and compressors.

The paper's contribution is a *family* of gradient-exchange strategies
(Dense, single-layer Top-k, layer-wise adaptive LAGS) meant to be
compared behind one interface.  Before this module, adding a strategy
meant editing two hard-wired ``if/elif`` chains (``launch.train._mode``
and ``training.make_exchange``); now a strategy is a named entry:

    from repro import api

    @api.register_exchange("my_exchange")
    def _build(spec: api.ExchangeSpec):
        return MyExchange(ks=spec.ks, ...)

and ``RunConfig(mode="my_exchange")`` reaches it from both the
distributed and the simulation surface.  The :class:`ExchangeSpec` a
factory receives is the SAME object on both surfaces — only ``sim``
differs — which is what keeps the two numerically comparable.

Compressors (the per-vector Top-k operators the strategies call) have
their own registry, backed by ``core.compressors.REGISTRY`` so existing
names keep working; :func:`register_compressor` adds new ones.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax

from repro.api.config import canonical_mode
from repro.core import compressors as C
from repro.core import lags


# ---------------------------------------------------------------------------
# exchange-strategy registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TieredKs:
    """Two-tier per-leaf budget container (deliberately NOT a pytree).

    ``resolve_schedule_ks`` packs a ``HierSchedule``'s two ks trees into
    one of these for strategies that consume both tiers (``ef_tiers``
    registrations, e.g. ``lags_hier2``); either tree may be ``None``,
    meaning that tier falls back to the spec's scalar ratio.
    """
    inner: Any = None
    outer: Any = None


@dataclasses.dataclass(frozen=True)
class ExchangeSpec:
    """Everything a strategy factory may need to build an exchange.

    Both surfaces construct one: the distributed step builder fills
    ``row_axes`` / ``shard_dims`` from the mesh and sets ``sim=False``;
    ``SimTrainer`` sets ``sim=True``.  ``ks`` (from an autotuned
    ``Schedule``) overrides the scalar ``ratio`` when present; two-tier
    strategies additionally read ``ks.inner`` / ``ratio_inner`` for the
    intra-pod tier and ``n_inner`` for the sim-path pod factorization.
    """
    mode: str
    params_like: Any                 # pytree of arrays / ShapeDtypeStructs
    ratio: float = 250.0
    ks: Any = None                   # per-leaf k^(l) override (schedule),
                                     # or a TieredKs for two-tier modes
    block_size: int = 4096
    compressor: str = "topk_exact"
    # "xla" | "kernel": which implementation the exchange selects with
    # (kernel = the Pallas kernels, resolved via compressors.KERNEL_BACKED)
    selection_backend: str = "xla"
    # lags_hier2 inner-tier compressor override (None = ``compressor``)
    inner_compressor: str | None = None
    sim: bool = False                # leading-P simulation vs distributed
    n_workers: int = 1
    # two-tier (lags_hier2) knobs: intra-pod ratio fallback + how many of
    # the n_workers are intra-pod (sim path; distributed reads the mesh)
    ratio_inner: float = 1.0
    n_inner: int = 1
    # distributed-only layout hints (see lags.BlockLAGSExchange)
    row_axes: tuple = ()
    shard_dims: Any = None
    # DGC-style momentum correction factor (velocity accumulates BEFORE
    # sparsification); > 0 turns on the per-worker "mom" extra state
    momentum_correction: float = 0.0

    def init_extra_state(self, updates_like=None):
        """Per-worker auxiliary exchange state beyond the EF residual.

        The hook through which strategy-adjacent state (today: the DGC
        momentum-correction velocity) reaches BOTH surfaces — the
        distributed step builder and ``SimTrainer`` each call this once
        and thread the result through their worker step, so adding a
        stateful knob never means editing two state-spec builders.

        Returns ``{name: zero-initialised f32 tree}`` in the per-worker
        layout (leading axis = ``n_workers``, matching the EF residual);
        empty when no knob is enabled.  ``updates_like`` defaults to
        ``params_like``.  Shape-only callers (state-spec builders) wrap
        the call in ``jax.eval_shape``.
        """
        like = self.params_like if updates_like is None else updates_like
        extra: dict[str, Any] = {}
        if self.momentum_correction > 0.0:
            import jax.numpy as jnp
            n_w = max(1, int(self.n_workers))
            extra["mom"] = jax.tree.map(
                lambda x: jnp.zeros((n_w,) + tuple(x.shape), jnp.float32),
                like)
        return extra

    def resolved_ks(self):
        """The per-leaf budget tree of the (outer) sparse exchange:
        schedule override or scalar ratio."""
        ks = self.ks.outer if isinstance(self.ks, TieredKs) else self.ks
        if ks is not None:
            return ks
        return lags.ks_from_ratio(self.params_like, self.ratio)

    def resolved_ks_inner(self):
        """Intra-pod tier budget tree (two-tier modes): schedule override
        or the scalar ``ratio_inner`` (default 1.0 = dense inner)."""
        if isinstance(self.ks, TieredKs) and self.ks.inner is not None:
            return self.ks.inner
        return lags.ks_from_ratio(self.params_like, self.ratio_inner)

    def resolved_compressor(self, *, inner: bool = False) -> str:
        """The compressor name the exchange should actually run, after
        ``selection_backend`` resolution: under the "kernel" backend each
        XLA-path name maps to its Pallas variant
        (``compressors.KERNEL_BACKED``); names with no kernel variant
        (randk, topk_sampled) raise there.  ``inner=True`` resolves the
        lags_hier2 intra-pod tier (``inner_compressor`` override)."""
        name = (self.inner_compressor or self.compressor) if inner \
            else self.compressor
        if self.selection_backend == "kernel":
            return C.kernel_backed(name)
        return name


#: Compressors that take the spec's ``block_size`` as a kwarg.
_BLOCK_SIZED = frozenset({
    "topk_hier", "topk_hier_kernel", "topk_hier_ef_kernel",
    "topk_block", "topk_block_kernel", "topk_block_ef_kernel",
})


def _sel_kwargs(name: str, spec: "ExchangeSpec") -> tuple:
    """compressor_kwargs threading the spec's block geometry into the
    block/hier compressor family (other names take no kwargs)."""
    if name in _BLOCK_SIZED:
        return (("block_size", spec.block_size),)
    return ()


@dataclasses.dataclass(frozen=True)
class ExchangeStrategy:
    """A registered strategy: factory + how it maps onto mesh axes.

    ``axes`` tells the distributed step builder which mesh axes carry the
    exchange ("worker" axes) and which run shard_map-MANUAL:

      * ``"data_manual"`` — manual over the data-parallel axes
        ('pod', 'data'); workers = those axes (lags_dp / dense / slgs).
      * ``"pod_auto"``    — pure-auto GSPMD with a leading vmap'd 'pod'
        worker dim; nothing manual (lags_hier: FSDP intra-pod, sparse
        cross-pod).
      * ``"none"``        — single worker, no exchange axes.
    """
    name: str
    factory: Callable[[ExchangeSpec], Any]
    axes: str = "data_manual"
    # EF-state layout: () = one residual tree (classic); a non-empty tuple
    # of tier names means the exchange's state is {tier: residual_tree},
    # and the state-spec builders (launch.train / SimTrainer) replicate
    # the per-worker residual layout once per tier.  Two-tier schedule
    # ingestion (resolve_schedule_ks -> TieredKs) also keys off this.
    ef_tiers: tuple = ()


_EXCHANGES: dict[str, ExchangeStrategy] = {}


def register_exchange(name: str, *, axes: str = "data_manual",
                      ef_tiers: tuple = ()):
    """Decorator: register ``factory(spec) -> exchange`` under ``name``."""
    if axes not in ("data_manual", "pod_auto", "none"):
        raise ValueError(f"unknown axes plan {axes!r}")

    def deco(factory):
        _EXCHANGES[name] = ExchangeStrategy(name=name, factory=factory,
                                            axes=axes,
                                            ef_tiers=tuple(ef_tiers))
        return factory
    return deco


def get_exchange(name: str) -> ExchangeStrategy:
    """Look up a strategy by (canonicalized) name.

    Raises ``KeyError`` whose message lists the registered names, so a
    typo'd ``RunConfig.mode`` is self-diagnosing.
    """
    key = canonical_mode(name)
    if key not in _EXCHANGES:
        raise KeyError(f"unknown exchange strategy {name!r}; registered: "
                       f"{sorted(_EXCHANGES)}")
    return _EXCHANGES[key]


def exchange_names() -> list[str]:
    return sorted(_EXCHANGES)


def build_exchange(spec: ExchangeSpec):
    """``spec`` -> exchange object, through the registry."""
    return get_exchange(spec.mode).factory(spec)


def resolve_schedule_ks(schedule, mode: str, params_like, *,
                        n_workers: int | None = None):
    """Validate + ingest an autotuned schedule: the ONE sequence both
    surfaces run (``validate_for`` then ``ks_tree``).  Returns the
    per-leaf k tree — or, for strategies registered with ``ef_tiers``
    (two-tier modes), a :class:`TieredKs` carrying BOTH tiers' k trees —
    or None when there is nothing to ingest (no schedule, or a dense
    mode)."""
    if schedule is None or mode == "dense":
        return None
    # lazy: repro.autotune.__init__ pulls in the profiler, which imports
    # the train-step builder back
    from repro.autotune import schedule as SCH
    SCH.validate_for(schedule, mode, n_workers=n_workers)
    strat = _EXCHANGES.get(canonical_mode(mode))
    if strat is not None and strat.ef_tiers:
        tiers = getattr(schedule, "tiers", None)
        if tiers is not None:        # HierSchedule: both tiers consumed
            return TieredKs(inner=tiers["inner"].ks_tree(params_like),
                            outer=tiers["outer"].ks_tree(params_like))
        if getattr(schedule, "tier", "") == "inner":
            # a lone inner-tier plan budgets the intra-pod exchange only;
            # the outer tier falls back to the spec's scalar ratio
            return TieredKs(inner=schedule.ks_tree(params_like))
        return TieredKs(outer=schedule.ks_tree(params_like))
    return schedule.ks_tree(params_like)


# ---------------------------------------------------------------------------
# built-in strategies (the paper's family + the beyond-paper hier mode)
# ---------------------------------------------------------------------------

@register_exchange("dense")
def _dense_factory(spec: ExchangeSpec):
    """Vanilla S-SGD baseline: dense mean over workers."""
    return lags.DenseExchange()


@register_exchange("slgs")
def _slgs_factory(spec: ExchangeSpec):
    """Single-layer (whole-model-vector) global Top-k baseline."""
    d_total = sum(lags._size(x) for x in jax.tree.leaves(spec.params_like))
    name = spec.resolved_compressor()
    return lags.SLGSExchange(
        k_total=max(1, int(round(d_total / spec.ratio))),
        compressor_name=name, compressor_kwargs=_sel_kwargs(name, spec))


def _lags_factory(spec: ExchangeSpec):
    """Layer-wise adaptive sparsification (the paper).

    Simulation uses the per-leaf compressor (``LAGSExchange``, the
    semantics reference); the distributed step uses the shard-aligned
    block layout (``BlockLAGSExchange``) so selection/scatter stay
    collective-free under GSPMD.  ``selection_backend="kernel"`` swaps
    the Pallas kernels in on BOTH surfaces: the sim compressor resolves
    through ``compressors.KERNEL_BACKED`` and the distributed block
    exchange runs the fused select+EF+pack kernel (``use_kernel``).  On a
    TPU the distributed block exchange runs that kernel for k_b <= 256
    under "xla" too, with the same result bit for bit.
    """
    ks = spec.resolved_ks()
    if spec.sim:
        name = spec.resolved_compressor()
        return lags.LAGSExchange(ks=ks, compressor_name=name,
                                 compressor_kwargs=_sel_kwargs(name, spec))
    if spec.compressor not in ("topk_exact", "topk_block",
                               "topk_block_kernel", "topk_block_ef_kernel"):
        # BlockLAGSExchange's selection operator IS block top-k (that is
        # what makes it collective-free); a run validated in simulation
        # under another compressor deploys with a different operator
        import warnings
        warnings.warn(
            f"distributed lags ignores compressor={spec.compressor!r}: "
            f"the production exchange selects via block top-k "
            f"(BlockLAGSExchange); simulate with compressor='topk_exact' "
            f"for the closest semantics match", stacklevel=3)
    return lags.BlockLAGSExchange(ks=ks, block_size=spec.block_size,
                                  row_axes=spec.row_axes,
                                  shard_dims=spec.shard_dims,
                                  use_kernel=(
                                      spec.selection_backend == "kernel"))


register_exchange("lags_dp")(_lags_factory)
# lags_hier shares the exchange object (the sparse cross-pod stage runs
# the leading-P path over the vmap'd pod dim); what differs is the axis
# plan: pure-auto GSPMD with 'pod' as the worker dim.  The intra-pod
# reduction is GSPMD's dense all-reduce; when contended ICI should go
# sparse too, use "lags_hier2" below.
register_exchange("lags_hier", axes="pod_auto")(_lags_factory)


@register_exchange("lags_hier2", axes="data_manual",
                   ef_tiers=("inner", "outer"))
def _hier2_factory(spec: ExchangeSpec):
    """Two-level sparse hierarchy: sparse intra-pod (ICI) LAGS exchange
    with its own per-leaf ``ks_inner`` + residual, then the sparse
    cross-pod (DCN) all-gather on the pod mean with a second residual.

    Registered with the ``data_manual`` axis plan: every (pod, data)
    coordinate is a worker with its own gradient (params replicated over
    the data axes, sharded over 'model' only) — the memory/traffic
    tradeoff vs ``lags_hier``'s FSDP is sparse ICI traffic instead of
    param sharding.  One exchange class serves both surfaces, so a run
    validated in simulation deploys with identical selection semantics.

    The two tiers can run different compressors:
    ``spec.inner_compressor`` (default = ``spec.compressor``) selects on
    each worker's own full-size gradient — the hot path, where the
    block-parallel (BlockLAGS-style) compressors and their Pallas
    kernels belong — while the outer cross-pod tier selects on the
    already-sparse pod mean.  Both resolve through
    ``selection_backend``.
    """
    outer_name = spec.resolved_compressor()
    inner_name = spec.resolved_compressor(inner=True)
    return lags.SparseHierLAGSExchange(
        ks=spec.resolved_ks(), ks_inner=spec.resolved_ks_inner(),
        n_inner=max(1, int(spec.n_inner)),
        compressor_name=outer_name,
        compressor_kwargs=_sel_kwargs(outer_name, spec),
        inner_compressor_name=(
            inner_name if inner_name != outer_name else None),
        inner_compressor_kwargs=_sel_kwargs(inner_name, spec))


# ---------------------------------------------------------------------------
# compressor registry (backed by core.compressors)
# ---------------------------------------------------------------------------

def register_compressor(name: str, compress=None, *, needs_key: bool = False,
                        fused_select=None):
    """Register a compressor ``compress(x, k, **kw) -> (values, indices)``.

    Usable as a decorator (``@register_compressor("name")``) or a plain
    call.  Entries land in ``core.compressors.REGISTRY`` so every
    strategy (and ``compressor_name=`` field) can name them.

    ``fused_select`` optionally provides the one-pass kernel variant
    ``(u_flat, e_flat, k, **kw) -> (values, indices, residual_flat)``
    fusing EF accumulate + select + payload pack; exchanges prefer it
    over compress-then-scatter (see ``lags.local_select_ef``).
    """
    def add(fn):
        C.REGISTRY[name] = C.Compressor(name, fn, needs_key=needs_key,
                                        fused_select=fused_select)
        return fn
    if compress is None:
        return add
    return add(compress)


get_compressor = C.get_compressor


def compressor_names() -> list[str]:
    return sorted(C.REGISTRY)
