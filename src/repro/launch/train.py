"""Distributed train step: partial-auto ``shard_map`` wrapping the LAGS
exchange (the production analogue of ``training.SimTrainer``).

Build steps through ``repro.api`` (``Session.train_step`` /
``build_train_step(cfg, mesh, RunConfig)``); the exchange strategy and
its mesh-axis plan come from the ``repro.api.registry`` string->factory
registry, so new strategies never edit this file.  (The pre-``repro.api``
``make_train_step``/``make_exchange`` kwarg shims are gone — RunConfig
is the only knob surface.)

Built-in train modes (``cfg.train_mode`` / ``RunConfig.mode``):

  * ``lags_dp``   — paper-faithful. ``shard_map`` MANUAL over the data-
    parallel axes ('pod', 'data'): each worker computes its own gradient,
    runs per-leaf block-Top-k with error feedback, and ships the sparse
    (values, indices) via layer-wise ``all_gather`` collectives that
    depend only on their own leaf's backward op — XLA's latency-hiding
    scheduler overlaps them with backward compute (the pipelining of
    Fig. 1(c)).  'model' stays AUTO: tensor parallelism is GSPMD's job.
    Params are replicated over data (sharded over model only).
  * ``lags_hier`` — beyond-paper hierarchical mode for archs whose
    replicated-over-data state can't fit (nemotron-340b, jamba-52b):
    'data' is AUTO too (GSPMD FSDP shards params over data×model and
    dense-reduces gradients within the pod over the fast ICI), while the
    across-pod exchange — the slow links — is sparse LAGS, manual over
    'pod' only.  Covered by Lemma 1: partition pieces = gradient shards.
    On a single-pod mesh this degenerates to FSDP + single-worker
    compression (no sparse comm; the compressor and EF still run).
  * ``lags_hier2`` — two-level SPARSE hierarchy for contended ICI: manual
    over ('pod', 'data'); each worker runs a per-leaf sparse exchange
    with its own inner budget within the pod, then the pod mean goes
    through the sparse cross-pod exchange (separate EF residual per
    tier).  Registered purely through the exchange registry — this file
    has no lags_hier2-specific code.
  * ``dense``     — vanilla S-SGD baseline (psum mean), manual over data.

State pytree: {"params", "ef", "step"}.  ``ef`` carries one residual per
LAGS worker: leading axis = n_workers, sharded over the manual axes, inner
dims sharded like the parameters (auto axes).  The optimizer is the
paper's plain SGD on pre-scaled deltas (Algorithm 1 line 10).

``RunConfig.pipeline`` selects how the exchange meets backprop
(``repro.pipeline``): ``"off"`` is the monolithic post-backward exchange
above; ``"wave"`` runs each wave's exchange inside the backward pass via
custom_vjp taps (bitwise equal to ``"off"``); ``"async1"`` double-buffers
— step N exchanges step N-1's updates (state gains a per-worker
``"pending"`` entry; one step of bounded staleness).  ``RunConfig.
momentum_correction`` adds the DGC velocity through the
``ExchangeSpec.init_extra_state`` hook (state gains ``"extra"``).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.api import registry as R
from repro.api.config import RunConfig, canonical_mode
from repro.configs import base
from repro.core import lags
from repro.launch import mesh as M
from repro.models import transformer as T
from repro.observe import health as OH
from repro.observe.trace import phase_scope
from repro.pipeline import buckets as WB
from repro.pipeline import step as WS
from repro.pipeline import waves as WW
from repro.sharding import rules


# ---------------------------------------------------------------------------
# shapes / shardings
# ---------------------------------------------------------------------------

def model_shapes_and_axes(cfg):
    """(params ShapeDtypeStruct tree, logical axes tree) — no allocation."""
    box = {}

    def initf(k):
        p, a = T.init_model(k, cfg)
        box["axes"] = a  # static python structure, captured at trace time
        return p

    sds = jax.eval_shape(initf, jax.random.PRNGKey(0))
    return sds, box["axes"]


def _mode(cfg, mesh, method: str | None):
    """Returns (mode, manual_axes, worker_axes).

    manual_axes: shard_map-manual mesh axes (lags_dp / dense / slgs).
    worker_axes: axes whose product = number of LAGS workers.  In hier mode
    the per-pod gradients are expressed as a vmap over a leading pod dim in
    pure-auto GSPMD (no shard_map): worker_axes=('pod',), manual=().

    The axis plan comes from the exchange registry (``ExchangeStrategy.
    axes``), so registering a new strategy never touches this file; an
    unknown mode raises with the list of registered names.
    """
    mode = canonical_mode(method or cfg.train_mode)
    strat = R.get_exchange(mode)
    if strat.axes == "pod_auto":
        worker = tuple(a for a in mesh.axis_names if a == "pod")
        manual = ()
    elif strat.axes == "data_manual":
        manual = M.data_axis_names(mesh)
        worker = manual
    else:  # "none": single worker, no exchange axes
        manual = ()
        worker = ()
    return mode, manual, worker


def _tp_priority(cfg):
    if getattr(cfg, "moe_shard", "ffn") == "experts":
        return rules.TP_PRIORITY_EXPERTS
    return rules.TP_PRIORITY


def param_pspecs(cfg, mesh, mode: str, params_sds=None, axes=None):
    if params_sds is None:
        params_sds, axes = model_shapes_and_axes(cfg)
    fsdp = "data" if mode == "lags_hier" else None
    return rules.tree_specs(params_sds, axes, mesh, tp_axis="model",
                            fsdp_axis=fsdp, tp_priority=_tp_priority(cfg))


def _strip_manual(spec: P, manual: tuple[str, ...]) -> P:
    """PartitionSpec with the manual axes removed (shard_map in_specs must
    mention manual axes only via the explicit leading worker dim)."""
    def keep(e):
        if e is None:
            return None
        es = e if isinstance(e, tuple) else (e,)
        es = tuple(a for a in es if a not in manual)
        return None if not es else (es if len(es) > 1 else es[0])
    return P(*[keep(e) for e in spec])


def _auto_only(spec: P, manual: tuple[str, ...]) -> P:
    return _strip_manual(spec, manual)


def make_state_specs(cfg, mesh, *, method: str | None = None,
                     pipeline: str = "off",
                     momentum_correction: float = 0.0):
    """ShapeDtypeStructs (with shardings) for the full train state.

    ``pipeline="async1"`` adds a ``"pending"`` entry (the previous step's
    lr-scaled updates, per worker, awaiting exchange); ``momentum_
    correction > 0`` adds ``"extra"`` — whatever auxiliary trees
    ``ExchangeSpec.init_extra_state`` declares (today the DGC ``"mom"``
    velocity).  Keys exist only when their feature is on, so existing
    checkpoints and donation layouts are untouched.
    """
    mode, manual, worker = _mode(cfg, mesh, method)
    params_sds, axes = model_shapes_and_axes(cfg)
    pspecs = param_pspecs(cfg, mesh, mode, params_sds, axes)
    n_w = M.n_workers(mesh, worker) if worker else 1
    _is_sds = lambda x: isinstance(x, jax.ShapeDtypeStruct)

    def with_sh(sd, spec):
        return jax.ShapeDtypeStruct(sd.shape, sd.dtype,
                                    sharding=NamedSharding(mesh, spec))

    params = jax.tree.map(with_sh, params_sds, pspecs, is_leaf=_is_sds)
    lead = worker if len(worker) > 1 else (worker[0] if worker else None)

    def wstate_sd(sd, spec):
        # per-worker fp32 state (EF residual / pending update / DGC
        # velocity): leading axis = n_workers, sharded over the worker
        # axes; inner dims keep the params' auto sharding ('model', and
        # 'data' in hier mode)
        sp = P(lead, *spec)
        return jax.ShapeDtypeStruct((n_w,) + sd.shape, jnp.float32,
                                    sharding=NamedSharding(mesh, sp))

    if mode == "dense":
        ef = ()
        ef_pspecs = ()
    else:
        ef = jax.tree.map(wstate_sd, params_sds, pspecs, is_leaf=_is_sds)
        # strategies registered with ef_tiers (two-level exchanges) carry
        # one residual tree per tier — same per-worker layout, tier-keyed
        ef_tiers = R.get_exchange(mode).ef_tiers
        if ef_tiers:
            ef = {t: ef for t in ef_tiers}
        ef_pspecs = jax.tree.map(lambda s: s.sharding.spec, ef,
                                 is_leaf=_is_sds)
    step = jax.ShapeDtypeStruct((), jnp.int32,
                                sharding=NamedSharding(mesh, P()))
    state = {"params": params, "ef": ef, "step": step}
    meta = {"mode": mode, "manual": manual, "worker_axes": worker,
            "n_workers": n_w, "pspecs": pspecs, "ef_pspecs": ef_pspecs,
            "axes": axes, "pipeline": pipeline}
    if pipeline == "async1":
        pending = jax.tree.map(wstate_sd, params_sds, pspecs,
                               is_leaf=_is_sds)
        state["pending"] = pending
        meta["pending_pspecs"] = jax.tree.map(
            lambda s: s.sharding.spec, pending, is_leaf=_is_sds)
    # the init_extra_state hook declares which auxiliary per-worker trees
    # the exchange needs (eval_shape: structure only, no allocation)
    extra_sds = jax.eval_shape(R.ExchangeSpec(
        mode=mode, params_like=params_sds, n_workers=n_w,
        momentum_correction=momentum_correction).init_extra_state)
    if extra_sds:
        state["extra"] = {
            name: jax.tree.map(
                lambda sd, spec: with_sh(sd, P(lead, *spec)),
                tree, pspecs, is_leaf=_is_sds)
            for name, tree in extra_sds.items()}
        meta["extra_pspecs"] = jax.tree.map(
            lambda s: s.sharding.spec, state["extra"], is_leaf=_is_sds)
    return state, meta


def batch_pspec(batch_specs, mesh, manual_or_data) -> Any:
    """Shard the global batch dim over the data axes (manual or auto)."""
    axes = tuple(manual_or_data)
    lead = axes if len(axes) > 1 else (axes[0] if axes else None)

    def spec_for(sd):
        return P(lead, *([None] * (len(sd.shape) - 1)))

    return jax.tree.map(spec_for, batch_specs,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def shard_dims_tree(pspecs, row_axes: tuple):
    """Per-leaf tuple of dims sharded over ``row_axes`` (order follows
    row_axes, matching the row-pin spec P(row_axes, None))."""
    def leaf(spec: P):
        out = []
        for ax in row_axes:
            for i, e in enumerate(spec):
                es = e if isinstance(e, tuple) else (e,)
                if ax in es:
                    out.append(i)
        return tuple(dict.fromkeys(out))  # dedupe, keep order

    return jax.tree.map(leaf, pspecs, is_leaf=lambda s: isinstance(s, P))


def build_train_step(cfg, mesh, run: RunConfig):
    """Builds (step_fn, state_specs, meta) from one ``RunConfig``.
    step_fn: (state, batch) -> (state, metrics), jit'd; lower with the
    returned specs for the dry-run.

    ``run.schedule``: optional ``repro.autotune.Schedule`` /
    ``repro.autotune.HierSchedule`` (or anything with a
    ``ks_tree(params_like)`` method).  When given, its planned per-leaf
    k^(l) replace the static ``cfg.compression_ratio`` at the same
    ingestion point ``lags.ks_from_ratios_tree`` feeds; validation
    (leaf structure, tier/provenance/worker-count) is
    ``autotune.schedule.validate_for`` — the same contract the sim path
    enforces.
    """
    state_specs, meta = make_state_specs(
        cfg, mesh, method=run.mode, pipeline=run.pipeline,
        momentum_correction=run.momentum_correction)
    mode, manual = meta["mode"], meta["manual"]
    schedule = run.schedule
    ks_override = R.resolve_schedule_ks(schedule, mode,
                                        state_specs["params"],
                                        n_workers=meta["n_workers"])
    # auto axes available for block-parallel row sharding inside the exchange
    row_axes = tuple(a for a in mesh.axis_names if a not in manual
                     and a in ("data", "model"))
    # shard-aligned block layout: the exchange transposes each leaf's
    # sharded dims to the front so selection/scatter stay collective-free
    sdims = shard_dims_tree(meta["pspecs"], row_axes)
    spec = R.ExchangeSpec(
        mode=mode, params_like=state_specs["params"],
        ratio=run.resolved_ratio(cfg), ks=ks_override,
        block_size=run.block_size, compressor=run.compressor,
        selection_backend=run.selection_backend,
        inner_compressor=run.inner_compressor, sim=False,
        n_workers=meta["n_workers"],
        ratio_inner=run.resolved_ratio_inner(),
        n_inner=max(1, M.n_workers(mesh, M.inner_axis_names(mesh))),
        row_axes=row_axes, shard_dims=sdims,
        momentum_correction=run.momentum_correction)
    exch = R.build_exchange(spec)
    meta["ks"] = getattr(exch, "ks", None)
    meta["schedule"] = schedule
    meta["run"] = dataclasses.replace(run, mode=mode)

    # online convergence health (repro.observe.health), build-time gated:
    # zero graph cost when health_every == 0.  Needs per-leaf budgets, so
    # slgs (whole-model k_total) and dense are skipped.  On this manual
    # surface the delta numerator ||sum_w e_new||^2 costs one dense psum
    # per leaf — cross terms are not recoverable from per-worker scalars.
    health = (run.health_every > 0 and mode != "dense"
              and getattr(exch, "ks", None) is not None)
    outer_axis_h = getattr(exch, "outer_axis", "pod")
    outer_axes_h = tuple(a for a in manual if a == outer_axis_h)
    n_out_h = (int(math.prod(mesh.shape[a] for a in outer_axes_h))
               if outer_axes_h else 1)
    n_w_h = meta["n_workers"]

    # wave partition for the pipelined modes: a user-supplied schedule is
    # re-bound by leaf name against THIS params tree; otherwise a
    # geometry-default partition at the exchange's declared granularity
    # (slgs selects over the whole-model vector -> one wave)
    pipeline = run.pipeline
    ef_tiers = R.get_exchange(mode).ef_tiers
    mc = float(run.momentum_correction)
    waves_sched = None
    if pipeline != "off":
        if run.waves is not None:
            waves_sched = WB.bind(run.waves, state_specs["params"])
        else:
            waves_sched = WW.default_waves(
                state_specs["params"], meta["ks"],
                granularity=getattr(exch, "wave_granularity", "leaf"),
                target_bytes=run.wave_target_bytes, pipeline=pipeline)
    meta["waves"] = waves_sched

    def loss_fn(params, batch):
        return T.loss_fn(params, cfg, batch, chunk=run.chunk,
                         loss_chunk=run.loss_chunk)

    def lr_at(step_no):
        # scheduled LR follows the SAME hook as SimTrainer._lr, so a
        # decayed run no longer silently diverges between surfaces
        return jnp.asarray(run.lr_at(step_no), jnp.float32)

    step_key = run.key_at

    def worker(params, ef, pending, extra, batch, step_no):
        # per-worker state (ef / pending / extra) arrives (1, ...) under
        # the manual axes; the residual's unpacking and repacking are the
        # exchange's (a full pass over the f32 residual each)
        with phase_scope("exchange"):
            ef_local = (jax.tree.map(lambda e: e[0], ef) if mode != "dense"
                        else ())
        lr_f = lr_at(step_no)
        axis_names = manual if manual else ()

        if pipeline == "wave":
            # in-backprop waved exchange: each wave's select+pack+
            # collective fires via a custom_vjp tap the moment backprop
            # produces that wave's cotangents (bitwise equal to "off");
            # the taps open their own lags/exchange scope
            with phase_scope("fwd"):
                (loss, _aux), mean_upd, new_ef_local = WS.wave_backward(
                    lambda p: loss_fn(p, batch), exch, waves_sched.waves,
                    params, ef_local, axis_names, lr=lr_f,
                    key=step_key(step_no), has_aux=True, tiers=ef_tiers)
            new_pending, new_extra = pending, extra
        else:
            with phase_scope("fwd"):
                (loss, _aux), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch)
            with phase_scope("exchange"):
                if mc > 0.0:
                    # DGC momentum correction: the velocity accumulates
                    # BEFORE sparsification, per worker
                    mom = jax.tree.map(lambda m: m[0], extra["mom"])
                    new_mom = jax.tree.map(
                        lambda m, g: mc * m + lr_f * g.astype(jnp.float32),
                        mom, grads)
                    updates = new_mom
                    new_extra = {"mom": jax.tree.map(lambda m: m[None],
                                                     new_mom)}
                else:
                    updates = jax.tree.map(
                        lambda g: lr_f * g.astype(jnp.float32), grads)
                    new_extra = extra
                if pipeline == "async1":
                    # double-buffer: exchange the PREVIOUS step's updates
                    # (zeros at step 0, hence that step's key) while this
                    # step's compute runs; the fresh updates become the next
                    # step's pending payload — one step of bounded staleness
                    pend = jax.tree.map(lambda x: x[0], pending)
                    mean_upd, new_ef_local = WS.waved_exchange(
                        exch, waves_sched.waves, pend, ef_local, axis_names,
                        key=step_key(step_no - 1), tiers=ef_tiers)
                    new_pending = jax.tree.map(lambda u: u[None], updates)
                else:
                    new_pending = pending
                    if mode == "dense":
                        if manual:
                            mean_upd, _ = exch.exchange(updates, (), manual)
                        else:
                            mean_upd = updates
                        new_ef_local = ()
                    else:
                        mean_upd, new_ef_local = exch.exchange(
                            updates, ef_local, axis_names,
                            key=step_key(step_no))
        with phase_scope("exchange"):
            new_ef = (jax.tree.map(lambda e: e[None], new_ef_local)
                      if mode != "dense" else ())
        with phase_scope("apply"):
            new_params = jax.tree.map(
                lambda p, d: (p.astype(jnp.float32) - d).astype(p.dtype),
                params, mean_upd)
            if manual:
                loss = lags._psum_mean(loss, manual)
        metrics = {"loss": loss}
        if health:
            with phase_scope("health"):
                if ef_tiers:
                    # two-tier: delta gates the slow cross-pod (outer) wire.
                    # The outer residual is pod-replicated, so the psum over
                    # the pod axis alone is exactly sum-over-pods.
                    e_sum = (jax.lax.psum(new_ef_local["outer"], outer_axes_h)
                             if outer_axes_h else new_ef_local["outer"])
                    delta = OH.delta_leaves_from_mean(
                        e_sum, mean_upd, exch.ks, n_out_h)
                    agg = jax.tree.map(lambda e, m: e + n_out_h * m,
                                       e_sum, mean_upd)
                    metrics["health_ef_energy_outer"] = OH.safe_ratio(
                        OH.sq_leaves(e_sum), OH.sq_leaves(agg))
                    if pipeline != "wave":
                        src = pend if pipeline == "async1" else updates
                        acc_in = jax.tree.map(lambda e, u: e + u,
                                              ef_local["inner"], src)
                        metrics["health_ef_energy_inner"] = OH.safe_ratio(
                            jax.lax.psum(OH.sq_leaves(new_ef_local["inner"]),
                                         manual),
                            jax.lax.psum(OH.sq_leaves(acc_in), manual))
                else:
                    e_sum = jax.lax.psum(new_ef_local, manual)
                    delta = OH.delta_leaves_from_mean(
                        e_sum, mean_upd, exch.ks, n_w_h)
                    if pipeline == "wave":
                        # the wave taps consume the updates inside backprop:
                        # fall back to the aggregate energy form
                        agg = jax.tree.map(lambda e, m: e + n_w_h * m,
                                           e_sum, mean_upd)
                        metrics["health_ef_energy_flat"] = OH.safe_ratio(
                            OH.sq_leaves(e_sum), OH.sq_leaves(agg))
                    else:
                        src = pend if pipeline == "async1" else updates
                        acc = jax.tree.map(lambda e, u: e + u, ef_local, src)
                        metrics["health_ef_energy_flat"] = OH.safe_ratio(
                            jax.lax.psum(OH.sq_leaves(new_ef_local), manual),
                            jax.lax.psum(OH.sq_leaves(acc), manual))
                metrics["health_delta"] = delta
                metrics["health_delta_max"] = delta.max()
                if pipeline == "async1":
                    u_sq = sum(OH.sq_norm(x) for x in jax.tree.leaves(updates))
                    d_sq = sum(OH.sq_norm(u - q)
                               for u, q in zip(jax.tree.leaves(updates),
                                               jax.tree.leaves(pend)))
                    metrics["health_staleness"] = OH.staleness_gap(
                        jax.lax.psum(u_sq, manual), jax.lax.psum(d_sq, manual))
        return new_params, new_ef, new_pending, new_extra, metrics

    if manual:
        # shard_map in_specs mention manual axes only; auto ('model', and
        # 'data' in hier mode) sharding is GSPMD's job.
        _is_p = lambda s: isinstance(s, P)

        def wstate_spec(s: P) -> P:
            lead = manual if len(manual) > 1 else manual[0]
            return P(lead, *[None] * (len(s) - 1))

        ef_in = (jax.tree.map(wstate_spec, meta["ef_pspecs"], is_leaf=_is_p)
                 if mode != "dense" else ())
        pending_in = (jax.tree.map(wstate_spec, meta["pending_pspecs"],
                                   is_leaf=_is_p)
                      if "pending" in state_specs else ())
        extra_in = (jax.tree.map(wstate_spec, meta["extra_pspecs"],
                                 is_leaf=_is_p)
                    if "extra" in state_specs else {})
        # params enter replicated over manual axes
        params_in = jax.tree.map(lambda s: P(*[None] * len(s)), meta["pspecs"],
                                 is_leaf=_is_p)
        # metrics leave the manual region replicated (every entry is a
        # psum'd reduction); the key set must mirror worker() exactly
        metrics_spec: dict[str, P] = {"loss": P()}
        if health:
            metrics_spec["health_delta"] = P()
            metrics_spec["health_delta_max"] = P()
            if ef_tiers:
                metrics_spec["health_ef_energy_outer"] = P()
                if pipeline != "wave":
                    metrics_spec["health_ef_energy_inner"] = P()
            else:
                metrics_spec["health_ef_energy_flat"] = P()
            if pipeline == "async1":
                metrics_spec["health_staleness"] = P()

        def step(state, batch):
            bspecs = batch_pspec(batch, mesh, manual)
            sm = compat.shard_map(
                worker, mesh=mesh,
                in_specs=(params_in, ef_in, pending_in, extra_in, bspecs,
                          P()),
                out_specs=(params_in, ef_in, pending_in, extra_in,
                           metrics_spec),
                axis_names=set(manual), check_vma=False)
            new_params, new_ef, new_pending, new_extra, metrics = sm(
                state["params"], state["ef"], state.get("pending", ()),
                state.get("extra", {}), batch, state["step"])
            out = {"params": new_params, "ef": new_ef,
                   "step": state["step"] + 1}
            if "pending" in state:
                out["pending"] = new_pending
            if "extra" in state:
                out["extra"] = new_extra
            return out, metrics
    else:
        # pure-auto path (lags_hier, or dense without data axes): per-pod
        # gradients via vmap over a leading pod dim; the exchange's
        # leading-P "simulation" path runs distributed under GSPMD with the
        # leading dim sharded over 'pod'.
        n_w = meta["n_workers"]
        worker_axes = meta["worker_axes"]

        def step(state, batch):
            params, ef = state["params"], state["ef"]
            with phase_scope("fwd"):
                if n_w > 1:
                    lead = (worker_axes if len(worker_axes) > 1
                            else worker_axes[0])

                    def resh(x):
                        y = x.reshape((n_w, x.shape[0] // n_w) + x.shape[1:])
                        return jax.lax.with_sharding_constraint(
                            y, P(lead, "data",
                                 *([None] * (len(x.shape) - 1))))
                    vb = jax.tree.map(resh, batch)
                    (losses, _aux), grads = jax.vmap(
                        lambda b: jax.value_and_grad(loss_fn, has_aux=True)(
                            params, b))(vb)
                    loss = losses.mean()
                else:
                    (loss, _aux), g1 = jax.value_and_grad(
                        loss_fn, has_aux=True)(params, batch)
                    grads = jax.tree.map(lambda g: g[None], g1)
            with phase_scope("exchange"):
                lr_f = lr_at(state["step"])
                if mc > 0.0:
                    # DGC velocity, leading-P layout (no manual slicing here)
                    new_mom = jax.tree.map(
                        lambda m, g: mc * m + lr_f * g.astype(jnp.float32),
                        state["extra"]["mom"], grads)
                    updates = new_mom
                else:
                    updates = jax.tree.map(
                        lambda g: lr_f * g.astype(jnp.float32), grads)
                # async1 exchanges the PREVIOUS step's updates (that step's
                # key); "wave" on this pure-auto path is post-backward
                # regrouping only — taps cannot reach inside the per-pod
                # vmap, so it buys semantics parity, not overlap (use
                # lags_dp / lags_hier2 for in-backprop waves)
                src = state["pending"] if pipeline == "async1" else updates
                if mode == "dense":
                    mean_upd = jax.tree.map(lambda u: u.mean(0), src)
                    new_ef = ()
                elif pipeline == "off":
                    mean_upd, new_ef = exch.exchange(
                        updates, ef, None, key=step_key(state["step"]))
                else:
                    key = (step_key(state["step"] - 1) if pipeline == "async1"
                           else step_key(state["step"]))
                    mean_upd, new_ef = WS.waved_exchange(
                        exch, waves_sched.waves, src, ef, None, key=key,
                        tiers=ef_tiers)
            with phase_scope("apply"):
                new_params = jax.tree.map(
                    lambda p, d: (p.astype(jnp.float32) - d).astype(p.dtype),
                    params, mean_upd)
            metrics = {"loss": loss}
            if health and not ef_tiers:
                with phase_scope("health"):
                    # leading-P layout under GSPMD: same form as the sim
                    # surface (the lags_hier factory builds the flat leading-P
                    # exchange; dict EF never reaches this path)
                    e_sum = jax.tree.map(lambda e: e.sum(0), new_ef)
                    delta = OH.delta_leaves_from_mean(
                        e_sum, mean_upd, exch.ks, n_w)
                    acc = jax.tree.map(lambda e, u: e + u, ef, src)
                    metrics["health_ef_energy_flat"] = OH.energy_leaves(
                        new_ef, acc)
                    metrics["health_delta"] = delta
                    metrics["health_delta_max"] = delta.max()
                    if pipeline == "async1":
                        u_sq = sum(OH.sq_norm(x)
                                   for x in jax.tree.leaves(updates))
                        d_sq = sum(OH.sq_norm(u - q)
                                   for u, q in zip(jax.tree.leaves(updates),
                                                   jax.tree.leaves(src)))
                        metrics["health_staleness"] = OH.staleness_gap(
                            u_sq, d_sq)
            out = {"params": new_params, "ef": new_ef,
                   "step": state["step"] + 1}
            if pipeline == "async1":
                out["pending"] = updates
            if mc > 0.0:
                out["extra"] = {"mom": new_mom}
            return out, metrics

    donate_args = (0,) if run.donate else ()
    return jax.jit(step, donate_argnums=donate_args), state_specs, meta


def init_state(cfg, mesh, *, method: str | None = None, seed: int = 0,
               pipeline: str = "off", momentum_correction: float = 0.0):
    """Materialize a real train state with the dry-run shardings (for
    examples / integration tests on a host mesh)."""
    state_specs, meta = make_state_specs(
        cfg, mesh, method=method, pipeline=pipeline,
        momentum_correction=momentum_correction)
    shardings = jax.tree.map(lambda s: s.sharding, state_specs,
                             is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    def build(k):
        params, _ = T.init_model(k, cfg)
        nw = meta["n_workers"]
        if meta["mode"] == "dense":
            ef = ()
        else:
            ef = jax.tree.map(
                lambda p: jnp.zeros((nw,) + p.shape, jnp.float32), params)
            ef_tiers = R.get_exchange(meta["mode"]).ef_tiers
            if ef_tiers:
                ef = {t: ef for t in ef_tiers}
        state = {"params": params, "ef": ef,
                 "step": jnp.zeros((), jnp.int32)}
        if "pending" in state_specs:
            # async1 double-buffer starts empty: step 0 applies a zero
            # update while its own exchange fills the buffer
            state["pending"] = jax.tree.map(
                lambda p: jnp.zeros((nw,) + p.shape, jnp.float32), params)
        if "extra" in state_specs:
            state["extra"] = R.ExchangeSpec(
                mode=meta["mode"], params_like=params, n_workers=nw,
                momentum_correction=momentum_correction).init_extra_state()
        return state

    return jax.jit(build, out_shardings=shardings)(
        jax.random.PRNGKey(seed)), meta
