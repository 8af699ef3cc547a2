"""``Session`` — config -> mesh -> exchange -> schedule -> controller.

One object composes the pieces that used to be hand-wired at every call
site: the model config, a :class:`~repro.api.config.RunConfig`, a mesh
(for the distributed surface), an optional autotuned schedule, and an
optional online re-planning controller.  Both execution surfaces hang
off it and share the same exchange registry + ``validate_for`` contract:

    from repro import api

    cfg = base.get_smoke_config("tinyllama_1_1b")
    run = api.RunConfig(mode="lags_dp", ratio=100.0, lr=0.25)

    # simulation (P workers on one device; convergence experiments)
    sim = api.Session(cfg, run).simulator(loss_fn, params, n_workers=4)

    # distributed (partial-auto shard_map production step)
    sess = api.Session(cfg, run, mesh=M.make_host_mesh(data=4, model=2))
    step_fn, state_specs, meta = sess.train_step()
    state, _ = sess.init_state()

    # online re-planning (repro.runtime) instead of a static schedule
    ctl = sess.controller(rcfg=RuntimeConfig(replan_every=50))

All heavyweight imports (launch, training, runtime) are lazy so this
module — and therefore ``repro.api`` — is cheap to import and free of
cycles.
"""
from __future__ import annotations

import dataclasses
from typing import Any

from repro.api.config import RunConfig


def build_train_step(cfg, mesh, run: RunConfig | None = None):
    """(step_fn, state_specs, meta) for the distributed step.

    Functional core of :meth:`Session.train_step`; the one non-deprecated
    path to a production train step.
    """
    from repro.launch import train as TR
    return TR.build_train_step(cfg, mesh, run or RunConfig())


class Session:
    """Composable façade over the sim and distributed training surfaces.

    ``mesh`` is only required for the distributed members
    (:meth:`train_step`, :meth:`init_state`, :meth:`controller`);
    :meth:`simulator` works without one.  The config's ``train_mode`` is
    reconciled with ``run.mode`` once, here, so every downstream consumer
    (step builder, controller, checkpoint provenance) sees one canonical
    mode.
    """

    def __init__(self, cfg, run: RunConfig | None = None, mesh=None):
        self.run_config = run or RunConfig()
        mode = self.run_config.resolved_mode(cfg)
        # one source of truth: cfg.train_mode == run.mode == canonical
        self.cfg = (cfg if cfg.train_mode == mode
                    else dataclasses.replace(cfg, train_mode=mode))
        self.run_config = dataclasses.replace(self.run_config, mode=mode)
        self.mesh = mesh
        self._built = None

    @property
    def mode(self) -> str:
        return self.run_config.mode

    def _need_mesh(self, what: str):
        if self.mesh is None:
            raise ValueError(f"Session.{what} needs a mesh — pass one to "
                             f"Session(cfg, run, mesh=...)")
        return self.mesh

    # -- distributed surface ------------------------------------------------
    def train_step(self):
        """(step_fn, state_specs, meta), built once and cached."""
        if self._built is None:
            self._built = build_train_step(self.cfg,
                                           self._need_mesh("train_step"),
                                           self.run_config)
        return self._built

    @property
    def step_fn(self):
        return self.train_step()[0]

    @property
    def state_specs(self):
        return self.train_step()[1]

    @property
    def meta(self):
        return self.train_step()[2]

    def init_state(self, seed: int = 0):
        """Materialized train state with the production shardings (incl.
        the ``pending``/``extra`` entries the run's pipeline/momentum
        knobs require)."""
        from repro.launch import train as TR
        state, _meta = TR.init_state(
            self.cfg, self._need_mesh("init_state"), method=self.mode,
            seed=seed, pipeline=self.run_config.pipeline,
            momentum_correction=self.run_config.momentum_correction)
        return state, _meta

    # -- simulation surface -------------------------------------------------
    def simulator(self, loss_fn, params, n_workers: int):
        """``SimTrainer`` for this run: P simulated workers, leading-P
        batches, the SAME ``ExchangeSpec``/registry the distributed step
        builds from."""
        from repro.training import train_loop as TL
        run = self.run_config
        if run.ratio is None:
            run = dataclasses.replace(run, ratio=run.resolved_ratio(self.cfg))
        return TL.SimTrainer(loss_fn, params, run, n_workers=n_workers)

    # -- online re-planning -------------------------------------------------
    def controller(self, rcfg=None, comm_probe=None, triggers=None,
                   trace_source=None, metrics=None, events=None):
        """``runtime.ReplanController`` owning this session's train step
        (re-fits/re-plans the schedule online; see ``repro.runtime``).

        ``triggers``: optional ``repro.observe.triggers`` sequence (OR
        composition; default = the ``rcfg.replan_every`` cadence).
        ``trace_source``: optional ``step -> repro.observe.Trace`` that
        makes telemetry trace-driven (measured per-leaf backward times,
        per-bucket collective samples).  ``metrics``/``events``: the
        observe plane to report into (default: process-wide)."""
        from repro.runtime import controller as RC
        return RC.ReplanController(self.cfg,
                                   self._need_mesh("controller"),
                                   rcfg=rcfg, run=self.run_config,
                                   comm_probe=comm_probe,
                                   triggers=triggers,
                                   trace_source=trace_source,
                                   metrics=metrics, events=events)

    # -- convenience loop ----------------------------------------------------
    def run(self, data_fn, n_steps: int, *, controller=None, state=None,
            log_path: str | None = None, log_every: int = 10,
            ckpt_every: int = 0, out_dir: str | None = None,
            publisher=None, metrics=None, events=None,
            health_every: int | None = None, health_monitor=None,
            print_fn=print):
        """The whole distributed training loop in one call.

        ``data_fn(step) -> batch`` supplies global batches;  the loop
        runs inside ``compat.set_mesh``, logs one JSONL row per step to
        ``log_path``, and — when ``ckpt_every``/``out_dir`` are set —
        checkpoints the train state (and controller state) periodically
        plus a final ``ckpt_final``/``runtime_final`` pair.

        Each JSONL row is a thin view over the metrics plane
        (``repro.observe.metrics``): the documented subset is ``step``,
        ``loss``, ``elapsed_s`` (cumulative wall seconds, rounded to
        0.1 s — the historical field) and ``step_s`` (this step's
        **unrounded** ``time.perf_counter`` duration, including the
        device sync that materializes the loss), plus the optional
        ``publish`` / ``replan`` sub-dicts.  The same quantities land in
        the registry as ``train_step_seconds`` (histogram),
        ``train_loss`` (gauge), ``train_steps_total`` and
        ``train_comm_bytes_total`` (the live schedule's predicted
        exchange payload — counters), all labelled ``mode=``.  When
        ``out_dir`` is set the loop exports a final snapshot artifact
        ``<out_dir>/metrics_snapshot.{jsonl,json,prom}``.

        ``controller``: a ``ReplanController`` from :meth:`controller`
        (its :meth:`~repro.runtime.ReplanController.step` replaces the
        static step function, and its re-plan decisions — including
        which *trigger* fired — are logged trigger-aware as they
        happen).  ``state=None`` initializes via :meth:`init_state`.

        ``publisher``: a ``repro.stream.StreamPublisher`` — after every
        step it is offered the live params
        (``publisher.maybe_publish(t, params)``) and any emitted
        ``DeltaPacket`` is logged as a ``publish`` row field, so a
        serving fleet can follow this run at delta-bandwidth.

        ``metrics`` / ``events``: an ``observe.metrics.MetricsRegistry``
        and ``observe.events.EventLog`` (default: the process-wide
        plane) — benches pass isolated instances.

        ``health_every`` (default: ``run.health_every``): every N steps
        the convergence-health quantities the step computed in-graph
        (``repro.observe.health`` — per-leaf Assumption-1 delta, EF
        energy retention, async1 staleness) are read host-side
        (piggybacking the existing loss sync) and set as
        ``train_health_*`` gauges whose ``leaf`` label carries the
        ``lags/health/...`` grammar.  ``health_monitor``: an optional
        ``observe.health.HealthMonitor`` fed the delta_max stream — an
        alarm emits a ``health_alarm`` event, bumps
        ``train_health_alarms_total`` and (when the controller's trigger
        set contains a ``HealthTrigger`` over the same monitor) re-plans
        at the next step boundary.  Note the step must have been BUILT
        with ``run.health_every > 0`` for the in-graph quantities to
        exist at all.

        Each step runs under ``jax.profiler.StepTraceAnnotation("lags/
        step", step_num=t)`` with host spans ``lags/host/data`` (the
        ``data_fn`` call), ``lags/host/dispatch`` (the step call),
        ``lags/host/loss_sync`` (the ``float(loss)``) and ``lags/host/
        bookkeeping`` (the rest), so a profiler trace of the loop says
        what the host did in each of the device's idle gaps.

        Returns ``(state, history)`` where ``history`` is the list of
        logged row dicts.
        """
        import json
        import os
        import time

        from repro import compat
        from repro.checkpoint import io as ckpt
        from repro.observe import events as OE
        from repro.observe import metrics as OM
        from repro.observe import names as ON
        from repro.observe.trace import annotation

        mesh = self._need_mesh("run")
        step_fn = controller.step if controller is not None else self.step_fn
        if state is None:
            state, _ = self.init_state()
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        reg = metrics if metrics is not None else OM.default_registry()
        evs = events if events is not None else OE.default_events()
        mode = self.mode
        m_steps = reg.counter("train_steps_total", "Train steps run.",
                              ("mode",))
        m_step_s = reg.histogram(
            "train_step_seconds",
            "Per-step wall time (perf_counter, incl. the loss sync).",
            ("mode",))
        m_loss = reg.gauge("train_loss", "Last step's training loss.",
                           ("mode",))
        m_comm = reg.counter(
            "train_comm_bytes_total",
            "Predicted sparse-exchange payload bytes under the live "
            "schedule (values + int32 indices per kept element).",
            ("mode",))
        m_overlap = reg.gauge(
            "train_overlap_frac",
            "Fraction of exchange comm hidden under compute "
            "(source=predicted: the live wave plan's timeline; "
            "source=achieved: trace attribution via repro.pipeline).",
            ("mode", "source"))
        if health_every is None:
            health_every = self.run_config.health_every
        health_every = int(health_every)
        health_leaves: list[str] = []
        if health_every > 0:
            from repro.observe import health as OH
            health_leaves = OH.leaf_names(state["params"])
            m_h_delta = reg.gauge(
                "train_health_delta",
                "Online per-leaf Assumption-1 delta (Eq. 20, closed-form "
                "RandK denominator); leaf label = lags/health/delta/...",
                ("leaf", "mode"))
            m_h_dmax = reg.gauge(
                "train_health_delta_max",
                "Max online delta over leaves at the last health fence.",
                ("mode",))
            m_h_ef = reg.gauge(
                "train_health_ef_energy",
                "Per-leaf EF residual energy retention ||e||^2/||acc||^2 "
                "per tier; leaf label = lags/health/ef_energy/...",
                ("leaf", "mode", "tier"))
            m_h_stale = reg.gauge(
                "train_health_staleness",
                "async1 one-step staleness gap ||u_t - u_{t-1}||/||u_t||.",
                ("mode",))
            m_h_alarms = reg.counter(
                "train_health_alarms_total",
                "Convergence-health alarms fired (threshold or drift).",
                ("mode", "reason"))

        def save_ckpt(tag: str):
            if not out_dir:
                return
            ckpt.save(os.path.join(out_dir, f"ckpt_{tag}"),
                      {"params": state["params"], "step": state["step"]})
            if controller is not None:
                controller.save_state(os.path.join(out_dir,
                                                   f"runtime_{tag}"))

        history: list[dict] = []
        n_events = 0
        # the predicted payload changes only when the controller swaps
        # the plan: worked out per plan, not per step
        n_plans = len(controller.history) if controller is not None else 0
        comm_bytes = _step_comm_bytes(
            controller.meta if controller is not None else self.meta,
            state["params"])
        span_data = ON.host_name("data")
        span_dispatch = ON.host_name("dispatch")
        span_sync = ON.host_name("loss_sync")
        span_books = ON.host_name("bookkeeping")
        t_start = time.time()
        log = open(log_path, "a") if log_path else None
        try:
            with compat.set_mesh(mesh):
                for t, spans in _step_spans(n_steps):
                    t0 = time.perf_counter()
                    with annotation(span_data):
                        batch = data_fn(t)
                    with annotation(span_dispatch):
                        state, metrics_out = step_fn(state, batch)
                    with annotation(span_sync):
                        loss = float(metrics_out["loss"])   # device sync
                    step_s = time.perf_counter() - t0
                    spans.enter_context(annotation(span_books))
                    row = {"step": t, "loss": loss,
                           "elapsed_s": round(time.time() - t_start, 1),
                           "step_s": step_s}
                    m_steps.inc(mode=mode)
                    m_step_s.observe(step_s, mode=mode)
                    m_loss.set(loss, mode=mode)
                    live_meta = (controller.meta if controller is not None
                                 else self.meta)
                    if (controller is not None
                            and len(controller.history) > n_plans):
                        n_plans = len(controller.history)
                        comm_bytes = _step_comm_bytes(live_meta,
                                                      state["params"])
                    m_comm.inc(comm_bytes, mode=mode)
                    waves = live_meta.get("waves")
                    if waves is not None and waves.predicted:
                        m_overlap.set(float(waves.predicted["overlap"]),
                                      mode=mode, source="predicted")
                    if (health_every > 0 and t % health_every == 0
                            and "health_delta" in metrics_out):
                        import numpy as _np
                        delta = _np.asarray(metrics_out["health_delta"])
                        dmax = float(metrics_out["health_delta_max"])
                        for leaf, v in zip(health_leaves, delta):
                            m_h_delta.set(
                                float(v), mode=mode,
                                leaf=ON.health_name("delta", leaf))
                        m_h_dmax.set(dmax, mode=mode)
                        for tier in ("flat", "inner", "outer"):
                            e = metrics_out.get(f"health_ef_energy_{tier}")
                            if e is None:
                                continue
                            for leaf, v in zip(health_leaves,
                                               _np.asarray(e)):
                                m_h_ef.set(
                                    float(v), mode=mode, tier=tier,
                                    leaf=ON.health_name(
                                        "ef_energy", f"{tier}/{leaf}"))
                        if "health_staleness" in metrics_out:
                            m_h_stale.set(
                                float(metrics_out["health_staleness"]),
                                mode=mode)
                        row["health"] = {"delta_max": dmax}
                        if health_monitor is not None:
                            alarm = health_monitor.observe(t, dmax)
                            if alarm is not None:
                                m_h_alarms.inc(mode=mode,
                                               reason=alarm["reason"])
                                evs.emit("health_alarm", step=t,
                                         name=ON.health_name("delta"),
                                         **{k: v for k, v in alarm.items()
                                            if k != "step"})
                                row["health"]["alarm"] = alarm
                                print_fn(f"step {t:4d}  HEALTH ALARM "
                                         f"[{alarm['reason']}] "
                                         f"delta_max={dmax:.3g}")
                    if publisher is not None:
                        pkt = publisher.maybe_publish(t, state["params"])
                        if pkt is not None:
                            row["publish"] = {"version": pkt.version,
                                              "kind": pkt.kind,
                                              "nbytes": pkt.nbytes}
                    if (controller is not None
                            and len(controller.history) > n_events):
                        ev = controller.last_event
                        n_events = len(controller.history)
                        row["replan"] = {
                            "swapped": ev.swapped,
                            "improvement": round(ev.improvement, 4),
                            "trigger": ev.trigger}
                        print_fn(f"step {t:4d}  replan[{ev.trigger}]: "
                                 f"swapped={ev.swapped} "
                                 f"pred_improvement={ev.improvement:.3f}")
                    history.append(row)
                    if log is not None:
                        log.write(json.dumps(row) + "\n")
                        log.flush()
                    if log_every and (t % log_every == 0
                                      or t == n_steps - 1):
                        print_fn(f"step {t:4d}  loss {row['loss']:.4f}  "
                                 f"({row['elapsed_s']}s)")
                    if ckpt_every and t and t % ckpt_every == 0:
                        save_ckpt(str(t))
        finally:
            if log is not None:
                log.close()
        save_ckpt("final")
        if out_dir:
            OM.save_snapshot(os.path.join(out_dir, "metrics_snapshot"),
                             reg, evs,
                             meta={"arch": self.cfg.name, "mode": mode,
                                   "n_steps": int(n_steps)})
        return state, history


def _step_spans(n_steps: int):
    """``(t, spans)`` for each step, the body running inside the
    ``lags/step`` profiler step annotation; ``spans`` (an ``ExitStack``)
    holds the host spans the body opens to the end of the step."""
    import contextlib

    import jax

    from repro.observe import names as ON
    for t in range(n_steps):
        with contextlib.ExitStack() as spans:
            spans.enter_context(
                jax.profiler.StepTraceAnnotation(ON.STEP, step_num=t))
            yield t, spans


def _step_comm_bytes(meta, params) -> int:
    """Predicted per-step exchange payload bytes under the live plan:
    ``sum(k_l) * payload_bytes_per_elem`` for a sparse exchange (the
    hierarchical modes count the cross-pod tier — the wire the plan
    budgets), raw fp32 gradient bytes for dense."""
    import jax

    from repro.core import bucketing
    ks = meta.get("ks")
    if ks is None:
        return int(sum(4 * x.size for x in jax.tree.leaves(params)))
    kept = sum(int(k) for k in jax.tree.leaves(ks))
    return int(kept) * bucketing.payload_bytes_per_elem()
