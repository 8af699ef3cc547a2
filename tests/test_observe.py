"""repro.observe: annotation-name grammar, fake-trace determinism,
trace->CommSample/backward-time attribution, step-time anomaly detection
edge cases, replan triggers, and the controller's trace-driven
measurement path (incl. detector state through checkpoint.io)."""
import dataclasses

import pytest

from repro.autotune import costfit, profiler
from repro.autotune import schedule as S
from repro.core import comm_model as cm
from repro.observe import anomaly as AN
from repro.observe import attribution as OA
from repro.observe import names
from repro.observe import trace as OT
from repro.observe import triggers as TG
from repro.runtime.telemetry import StepSample, Telemetry

FAST = cm.TPU_V5E_ICI
SLOW = cm.Hardware(name="degraded", alpha=50e-3, beta=1e-6, flops=FAST.flops)


def _leaves(ds=(1024, 8192, 65536, 262144), t_backward=1e-3):
    return [profiler.LeafSample(name=f"layers/{i}/w", d=d,
                                backward_flops=4.0 * d,
                                t_backward=t_backward)
            for i, d in enumerate(ds)]


def _fake(wires=None, tier_workers=None, leaves=None, **kw):
    return OT.FakeTraceBackend(
        leaves if leaves is not None else _leaves(),
        wires if wires is not None else {"flat": FAST},
        tier_workers if tier_workers is not None else {"flat": 8},
        t_forward=kw.pop("t_forward", 2e-3), **kw)


# ---------------------------------------------------------------------------
# names grammar
# ---------------------------------------------------------------------------

class TestNames:
    def test_comm_roundtrip_with_slashes_in_label(self):
        n = names.comm_name("inner", "allgather", "layers/0/attn/wq",
                            nbytes=4096.0, p=8)
        got = names.parse(n)
        assert got == {"type": "comm", "tier": "inner", "kind": "allgather",
                       "label": "layers/0/attn/wq", "nbytes": 4096.0,
                       "p": 8}

    def test_bwd_and_step(self):
        assert names.parse(names.bwd_name("layers/0/w")) == \
            {"type": "bwd", "leaf": "layers/0/w"}
        assert names.parse(names.STEP) == {"type": "step"}
        assert names.parse(names.FWD) == {"type": "fwd"}

    def test_foreign_names_ignored(self):
        assert names.parse("xla_fusion.1") is None
        assert names.parse("lags/comm/garbage") is None

    def test_malformed_metadata_degrades(self):
        got = names.parse("lags/comm/flat/allgather/l0?nbytes=oops&p=bad")
        assert got["nbytes"] == 0.0 and got["p"] == 1

    def test_serve_names_roundtrip(self):
        n = names.serve_name("apply", "delta", version=7)
        assert names.parse(n) == {"type": "serve", "kind": "apply",
                                  "label": "delta", "version": 7}
        assert names.parse(names.serve_name("prefill", "b2xl8")) == \
            {"type": "serve", "kind": "prefill", "label": "b2xl8",
             "version": None}
        assert names.parse("serve/oops") is None
        assert names.parse("serve/apply/x?version=bad")["version"] is None

    def test_phase_and_host_names_roundtrip(self):
        assert names.parse(names.phase_name("select", "l3")) == \
            {"type": "phase", "phase": "select", "label": "l3"}
        assert names.parse(names.phase_name("exchange")) == \
            {"type": "phase", "phase": "exchange", "label": ""}
        assert names.parse(names.phase_name("health")) == \
            {"type": "phase", "phase": "health", "label": ""}
        # the health quantities keep their own grammar
        assert names.parse(names.health_name("delta", "l0"))["type"] == \
            "health"
        assert names.parse(names.host_name("loss_sync")) == \
            {"type": "host", "span": "loss_sync"}
        assert names.parse("lags/host/nap") is None
        with pytest.raises(ValueError):
            names.phase_name("bwd")
        with pytest.raises(ValueError):
            names.host_name("nap")


#: op_name metadata of compiled ops (the spellings jax 0.9 gives) -> phase
PHASE_CASES = [
    ("jit(step)/lags/fwd/jvp()/while/body/closed_call/dot_general", "fwd"),
    ("jit(step)/lags/fwd/transpose(jvp())/while/body/closed_call/"
     "dot_general", "bwd"),
    # remat recompute sits under transpose( too
    ("jit(step)/lags/fwd/transpose(jvp())/while/body/checkpoint/"
     "rematted_computation/exp", "bwd"),
    ("jit(step)/jvp(lags/fwd)/mul", "fwd"),
    ("jit(step)/transpose(jvp(lags/fwd))/mul", "bwd"),
    # a wave tap's exchange runs inside the backward pass: not bwd
    ("jit(step)/lags/fwd/transpose(lags/fwd)/jvp(lags/exchange)/mul",
     "exchange"),
    ("jit(step)/lags/fwd/transpose(lags/fwd)/jvp(lags/exchange)/"
     "lags/select/l3/argmax", "select"),
    ("jit(step)/lags/exchange/lags/scatter_mean/l12/scatter-add",
     "scatter_mean"),
    ("jit(step)/lags/exchange/lags/comm/flat/allgather/blocks"
     "?nbytes=40&p=1/all_gather", "exchange"),
    ("jit(step)/lags/comm/flat/allreduce/l0?nbytes=4&p=2/psum", None),
    ("jit(step)/lags/apply/sub", "apply"),
    ("jit(step)/lags/health/reduce_sum", "health"),
    ("jit(step)/add", None),
    ("", None),
]


@pytest.mark.parametrize("op_name,phase", PHASE_CASES)
def test_phase_of(op_name, phase):
    assert names.phase_of(op_name) == phase
    assert names.step_phase(names.phase_of(op_name)) in \
        names.STEP_PHASES + (None,)


# ---------------------------------------------------------------------------
# fake backend + trace container
# ---------------------------------------------------------------------------

class TestFakeTrace:
    def test_deterministic(self):
        fake = _fake()
        assert fake.capture(0).events == fake.capture(7).events

    def test_json_roundtrip(self):
        tr = _fake().capture(0)
        assert OT.Trace.from_json(tr.to_json()) == tr

    def test_step_event_is_pipelined_total(self):
        fake = _fake()
        tr = fake.capture(0)
        comm = [e.dur for e in tr.named(names.COMM_PREFIX)]
        t_step = OA.step_time(tr)
        # pipelined: at least fwd+bwd, at most fully serialized
        assert t_step >= fake.t_forward + 4 * 1e-3 - 1e-12
        assert t_step <= fake.t_forward + 4 * 1e-3 + sum(comm) + 1e-12

    def test_wire_mutation_moves_step_time(self):
        wires = {"flat": FAST}
        fake = _fake(wires=wires)
        t_fast = OA.step_time(fake.capture(0))
        wires["flat"] = SLOW
        t_slow = OA.step_time(fake.capture(1))
        assert t_slow > 2 * t_fast

    def test_schedule_prices_sparse_allgather(self):
        sched = {"live": None}
        fake = _fake(schedule_fn=lambda: sched["live"])
        dense = fake.capture(0)
        assert all(names.parse(e.name)["kind"] == "allreduce"
                   for e in dense.named(names.COMM_PREFIX))
        from repro.autotune import planner
        sched["live"] = planner.plan_schedule(_leaves(), p=8, hw=SLOW,
                                              train_mode="lags_dp")
        sparse = fake.capture(1)
        kinds = {names.parse(e.name)["kind"]
                 for e in sparse.named(names.COMM_PREFIX)}
        assert "allgather" in kinds


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------

class TestAttribution:
    def test_costfit_recovers_wire_from_attributed_samples(self):
        tr = _fake(wires={"flat": SLOW}).capture(0)
        samples = OA.comm_samples(tr, tier="flat")
        assert samples and all(s.label.startswith("flat/")
                               for s in samples)
        alpha, beta = costfit.fit_alpha_beta(samples)
        assert abs(alpha - SLOW.alpha) / SLOW.alpha < 0.05
        assert abs(beta - SLOW.beta) / SLOW.beta < 0.05

    def test_tier_filtering(self):
        tr = _fake(wires={"inner": FAST, "outer": SLOW},
                   tier_workers={"inner": 4, "outer": 2}).capture(0)
        assert OA.comm_tiers(tr) == ("inner", "outer")
        inner = OA.comm_samples(tr, tier="inner")
        outer = OA.comm_samples(tr, tier="outer")
        assert inner and outer
        assert OA.comm_samples(tr, tier="flat") == []
        a_in, _ = costfit.fit_alpha_beta(inner)
        a_out, _ = costfit.fit_alpha_beta(outer)
        assert abs(a_in - FAST.alpha) / FAST.alpha < 0.05
        assert abs(a_out - SLOW.alpha) / SLOW.alpha < 0.05

    def test_single_worker_tier_dropped(self):
        tr = _fake(tier_workers={"flat": 1}).capture(0)
        assert OA.comm_samples(tr) == []

    def test_backward_times_average_multiple_events(self):
        ev = [OT.TraceEvent(names.bwd_name("w"), 0.0, 2e-3),
              OT.TraceEvent(names.bwd_name("w"), 1.0, 4e-3)]
        assert OA.backward_times(OT.Trace(tuple(ev))) == {"w": 3e-3}

    def test_attribute_leaves_full_coverage(self):
        leaves = _leaves(t_backward=0.0)
        tr = _fake(leaves=_leaves(t_backward=5e-4)).capture(0)
        got = OA.attribute_leaves(leaves, tr)
        assert all(abs(l.t_backward - 5e-4) < 1e-12 for l in got)

    def test_attribute_leaves_partial_splits_remainder(self):
        """Leaves the trace missed split the REMAINING budget by FLOPs
        share — never double-counting the measured mass."""
        leaves = _leaves(ds=(1000, 1000, 2000), t_backward=0.0)
        ev = (OT.TraceEvent(names.STEP, 0.0, 1.0),
              OT.TraceEvent(names.bwd_name("layers/0/w"), 0.0, 0.4))
        got = OA.attribute_leaves(leaves, OT.Trace(ev),
                                  t_backward_total=1.0)
        by = {l.name: l.t_backward for l in got}
        assert by["layers/0/w"] == 0.4          # measured wins
        # remainder 0.6 split 1000:2000 across the unmeasured leaves
        assert abs(by["layers/1/w"] - 0.2) < 1e-9
        assert abs(by["layers/2/w"] - 0.4) < 1e-9

    def test_attribute_leaves_no_events_falls_back(self):
        leaves = _leaves(t_backward=0.0)
        got = OA.attribute_leaves(leaves, OT.Trace(()),
                                  t_backward_total=0.9)
        apportioned = profiler.apportion_backward(leaves, 0.9)
        assert got == tuple(apportioned)


# ---------------------------------------------------------------------------
# anomaly detector edge cases
# ---------------------------------------------------------------------------

def _cfg(**kw):
    base = dict(warmup=1, recent=2, min_history=2, z=4.0, min_rel=0.2)
    base.update(kw)
    return AN.AnomalyConfig(**base)


def _steps(ts, start=0):
    return [StepSample(start + i, t, 1) for i, t in enumerate(ts)]


class TestAnomalyDetector:
    def test_empty_window_no_fire(self):
        assert AN.StepTimeAnomalyDetector(_cfg()).observe([]) is None

    def test_short_window_no_fire(self):
        det = AN.StepTimeAnomalyDetector(_cfg())
        # even a huge jump can't fire before min_history+recent samples
        assert det.observe(_steps([0.05, 0.05, 5.0])) is None

    def test_warmup_compile_spike_not_flagged(self):
        det = AN.StepTimeAnomalyDetector(_cfg(warmup=1))
        samples = _steps([5.0] + [0.05] * 6)   # step 0 = compile spike
        assert det.observe(samples) is None
        assert not det.fired

    def test_single_regression_flagged_exactly_once(self):
        det = AN.StepTimeAnomalyDetector(_cfg())
        samples = _steps([0.05] * 5)
        assert det.observe(samples) is None
        samples += _steps([0.2, 0.2], start=5)
        a = det.observe(samples)
        assert a is not None and a.t_recent == 0.2 and a.t_ref == 0.05
        assert a.step == 6
        # latched: more degraded samples do NOT re-fire
        samples += _steps([0.2] * 4, start=7)
        assert det.observe(samples) is None

    def test_reset_rearms_on_new_baseline(self):
        det = AN.StepTimeAnomalyDetector(_cfg())
        samples = _steps([0.05] * 5 + [0.2, 0.2])
        assert det.observe(samples) is not None
        det.reset()
        # post-reset: degraded times are the new normal -> quiet ...
        samples += _steps([0.2] * 6, start=7)
        assert det.observe(samples) is None
        # ... until a SECOND genuine regression
        samples += _steps([0.8, 0.8], start=13)
        a2 = det.observe(samples)
        assert a2 is not None and a2.t_ref == pytest.approx(0.2)

    def test_zero_noise_window_uses_mad_floor(self):
        """Deterministic fake traces produce identical step times (MAD=0)
        — the floor must keep the score finite and quiet."""
        det = AN.StepTimeAnomalyDetector(_cfg())
        assert det.observe(_steps([0.05] * 10)) is None
        assert not det.fired

    def test_state_dict_roundtrip(self):
        det = AN.StepTimeAnomalyDetector(_cfg())
        det.observe(_steps([0.05] * 5 + [0.2, 0.2]))
        det2 = AN.StepTimeAnomalyDetector(_cfg())
        det2.load_state_dict(det.state_dict())
        assert det2.state_dict() == det.state_dict()
        assert det2.fired == det.fired


# ---------------------------------------------------------------------------
# triggers
# ---------------------------------------------------------------------------

def _ctx(step, telemetry=None, schedule=None):
    # NB: an empty Telemetry is falsy (len 0) — compare against None
    tel = telemetry if telemetry is not None else Telemetry()
    return TG.TriggerContext(step=step, telemetry=tel,
                             schedule=schedule, mode="lags_dp")


class TestTriggers:
    def test_cadence_preserves_modulo_semantics(self):
        t = TG.CadenceTrigger(10)
        assert t.due(_ctx(10)) and t.due(_ctx(20))
        assert not t.due(_ctx(11))
        assert not TG.CadenceTrigger(0).due(_ctx(10))
        assert TG.default_triggers(5)[0].every == 5

    def test_anomaly_trigger_fires_and_rearms(self):
        tel = Telemetry(window=32)
        for i, t in enumerate([0.05] * 5):
            tel.record_step(i, t)
        trig = TG.AnomalyTrigger(cfg=_cfg())
        assert not trig.due(_ctx(5, tel))
        for i, t in enumerate([0.2, 0.2], start=5):
            tel.record_step(i, t)
        assert trig.due(_ctx(7, tel))
        assert trig.last is not None and trig.last.t_recent == 0.2
        trig.notify_replan(_ctx(7, tel), None)
        assert not trig.detector.fired
        assert not trig.due(_ctx(8, tel))   # consumed; new epoch quiet

    def test_fingerprint_trigger_detects_drift(self):
        from repro.autotune import planner
        sched = planner.plan_schedule(_leaves(), p=8, hw=FAST,
                                      train_mode="lags_dp")
        tel = Telemetry()
        tel.record_comm(OA.comm_samples(
            _fake(wires={"flat": SLOW}).capture(0)))
        trig = TG.FingerprintTrigger(drift=0.5)
        assert trig.due(_ctx(1, tel, schedule=sched))
        # same wire as the fingerprint: quiet
        tel2 = Telemetry()
        tel2.record_comm(OA.comm_samples(
            _fake(wires={"flat": FAST}).capture(0)))
        assert not trig.due(_ctx(1, tel2, schedule=sched))

    def test_fingerprint_hier_quiet_when_both_tiers_match(self):
        from repro.runtime import hier
        DCN = cm.TPU_DCN
        hs = hier.plan_hier_schedule(_leaves(), p_inner=4, p_outer=2,
                                     hw_inner=FAST, hw_outer=DCN,
                                     train_mode="lags_hier")
        tel = Telemetry()
        tel.record_comm(OA.comm_samples(
            _fake(wires={"inner": FAST, "outer": DCN},
                  tier_workers={"inner": 4, "outer": 2}).capture(0)))
        trig = TG.FingerprintTrigger(drift=0.5)
        assert not trig.due(_ctx(1, tel, schedule=hs))
        assert trig.last_tier is None

    def test_fingerprint_hier_ici_only_drift_fires(self):
        """An intra-pod (ICI) degradation must fire even while the DCN
        tier still matches its fingerprint — each tier is checked
        against its OWN recorded (alpha, beta)."""
        from repro.runtime import hier
        DCN = cm.TPU_DCN
        hs = hier.plan_hier_schedule(_leaves(), p_inner=4, p_outer=2,
                                     hw_inner=FAST, hw_outer=DCN,
                                     train_mode="lags_hier")
        tel = Telemetry()
        tel.record_comm(OA.comm_samples(
            _fake(wires={"inner": SLOW, "outer": DCN},
                  tier_workers={"inner": 4, "outer": 2}).capture(0)))
        trig = TG.FingerprintTrigger(drift=0.5)
        assert trig.due(_ctx(1, tel, schedule=hs))
        assert trig.last_tier == "inner"

    def test_fingerprint_hier_unlabelled_samples_check_outer(self):
        """Raw probe batches carry no tier prefix: they fall back to the
        outer (sparse-exchange) fingerprint, preserving the flat-schedule
        behaviour."""
        from repro.runtime import hier
        hs = hier.plan_hier_schedule(_leaves(), p_inner=4, p_outer=2,
                                     hw_inner=FAST, hw_outer=FAST,
                                     train_mode="lags_hier")
        tel = Telemetry()
        tel.record_comm(OA.comm_samples(        # labels: "flat/..."
            _fake(wires={"flat": SLOW}).capture(0)))
        trig = TG.FingerprintTrigger(drift=0.5)
        assert trig.due(_ctx(1, tel, schedule=hs))
        assert trig.last_tier == "outer"

    def test_fingerprint_silent_without_schedule_or_samples(self):
        trig = TG.FingerprintTrigger()
        assert not trig.due(_ctx(1, Telemetry(), schedule=None))
        from repro.autotune import planner
        sched = planner.plan_schedule(_leaves(), p=8, hw=FAST)
        assert not trig.due(_ctx(1, Telemetry(), schedule=sched))

    def test_rel_drift_static_fingerprint_is_zero(self):
        assert costfit.rel_drift({"name": "static"}, 1.0, 1.0) == 0.0
        assert costfit.rel_drift({"alpha": 1e-6, "beta": 1e-11},
                                 2e-6, 1e-11) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# controller integration: trace-driven measurement + checkpointed detector
# ---------------------------------------------------------------------------

def _model_cfg(mode="lags_dp"):
    from repro.configs import base
    return dataclasses.replace(
        base.get_smoke_config("tinyllama_1_1b"), n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab=64,
        dtype="float32", param_dtype="float32",
        train_mode=mode, compression_ratio=1.0)


def _trace_controller(wires, triggers=None):
    from repro.api import RunConfig
    from repro.launch import mesh as M
    from repro.runtime.controller import ReplanController, RuntimeConfig
    cfg = _model_cfg()
    ctl = ReplanController(
        cfg, M.make_host_mesh(data=1, model=1),
        rcfg=RuntimeConfig(replan_every=100, fence_every=1,
                           swap_threshold=0.05, min_step_samples=1),
        comm_probe=lambda mesh, axes: [],
        run=RunConfig(chunk=16, loss_chunk=16), triggers=triggers)
    ctl.meta["n_workers"] = 8   # single-device mesh: pretend 8 workers
    fake = OT.FakeTraceBackend(
        profiler.apportion_backward(ctl._leaf_template, 0.040),
        wires=wires, tier_workers={"flat": 8}, t_forward=0.020,
        schedule_fn=lambda: ctl.schedule)
    ctl.trace_source = fake.capture
    return ctl, fake


class TestControllerTraceDriven:
    def test_ingest_feeds_both_rings(self):
        wires = {"flat": FAST}
        ctl, fake = _trace_controller(wires)
        ctl.ingest_trace(1, fake.capture(1))
        assert len(ctl.telemetry) == 1
        assert ctl.telemetry.comm_samples()
        assert all(s.label.startswith("flat/")
                   for s in ctl.telemetry.comm_samples())

    def test_replan_consumes_trace_evidence(self):
        wires = {"flat": SLOW}
        ctl, fake = _trace_controller(wires)
        for i in range(1, 4):
            ctl.ingest_trace(i, fake.capture(i))
        ev = ctl.maybe_replan(3, trigger="test")
        assert ev.hw_name == "attr_wire_fit"       # costfit <- attribution
        assert ctl.measurement_source == "trace"   # budgets <- bwd events
        assert ev.swapped and ev.trigger == "test"
        # the candidate was solved against the slow wire: sparse plans
        assert any(lp.ratio > 1.0 for lp in ctl.schedule.leaves)
        # the fingerprint now matches the attributed fit within tolerance
        alpha, beta = costfit.fit_alpha_beta(
            OA.comm_samples(fake.capture(9), tier="flat"))
        assert ctl.schedule.hardware_drift(alpha, beta) < 0.1

    def test_anomaly_trigger_end_to_end_without_cadence(self):
        """Regression -> detector -> _fired_triggers -> replan+swap, all
        from trace evidence; cadence (100) never participates."""
        wires = {"flat": FAST}
        trig = TG.AnomalyTrigger(cfg=_cfg())
        ctl, fake = _trace_controller(wires, triggers=(
            TG.CadenceTrigger(100), trig))
        for i in range(1, 6):
            ctl.ingest_trace(i, fake.capture(i))
            ctl._step_count = i
            assert ctl._fired_triggers() == []
        wires["flat"] = SLOW                      # injected regression
        fired = []
        for i in range(6, 10):
            ctl.ingest_trace(i, fake.capture(i))
            ctl._step_count = i
            f = ctl._fired_triggers()
            if f:
                fired.append((i, f))
                ctl.maybe_replan(i, trigger=",".join(f))
        assert len(fired) == 1 and fired[0][1] == ["anomaly"]
        assert ctl.history[-1].swapped
        assert ctl.history[-1].trigger == "anomaly"
        assert fired[0][0] < 100                  # long before the cadence

    def test_eventless_trace_is_rejected_not_ingested(self):
        """The real backend's unparseable-XPlane capture is an EMPTY
        Trace: ingest must refuse it (returning False so step() falls
        back to the wall-clock fence) instead of starving every trigger
        of step samples forever."""
        ctl, _ = _trace_controller({"flat": FAST})
        assert ctl.ingest_trace(1, OT.Trace(())) is False
        assert len(ctl.telemetry) == 0
        assert ctl._fresh_trace() is None

    def test_stale_trace_ages_out_of_replanning(self):
        """A trace from an old wire epoch must not be branded as live
        measured evidence: past the telemetry window the controller
        falls back to the probe/window sources."""
        wires = {"flat": SLOW}
        ctl, fake = _trace_controller(wires)
        ctl.ingest_trace(1, fake.capture(1))
        ctl._step_count = 1 + ctl.rcfg.window + 1     # aged out
        for i in range(2, 5):                          # window still fed
            ctl.telemetry.record_step(ctl._step_count - i, 0.05)
        assert ctl._fresh_trace() is None
        ev = ctl.maybe_replan(ctl._step_count, trigger="test")
        assert ctl.measurement_source == "window"
        assert not ev.hw_name.startswith("attr_")

    def test_probe_samples_recorded_with_tier_labels(self):
        """Probe batches enter the comm ring tier-tagged so window fits
        (FingerprintTrigger) never mix two wires into one line."""
        from repro.api import RunConfig
        from repro.launch import mesh as M
        from repro.runtime.controller import (ReplanController,
                                              RuntimeConfig)
        def probe(mesh, axes):
            fake = OT.FakeTraceBackend(_leaves(), {"flat": FAST},
                                       {"flat": 8}, t_forward=1e-3)
            return OA.comm_samples(fake.capture(0))
        ctl = ReplanController(
            _model_cfg(), M.make_host_mesh(data=1, model=1),
            rcfg=RuntimeConfig(replan_every=10, min_step_samples=1),
            comm_probe=probe, run=RunConfig(chunk=16, loss_chunk=16))
        ctl.meta["n_workers"] = 8
        samples, prefix = ctl._tier_samples("flat", ("data",))
        assert prefix == ""                       # probe, not attributed
        assert all(s.label.startswith("flat/") for s in samples)
        assert all(s.label.startswith("flat/")
                   for s in ctl.telemetry.comm_samples())

    def test_detector_state_roundtrips_with_controller(self, tmp_path):
        wires = {"flat": FAST}
        trig = TG.AnomalyTrigger(cfg=_cfg())
        ctl, fake = _trace_controller(wires, triggers=(trig,))
        for i in range(1, 6):
            ctl.ingest_trace(i, fake.capture(i))
        ctl._step_count = 5
        path = ctl.save_state(str(tmp_path / "runtime"))

        trig2 = TG.AnomalyTrigger(cfg=_cfg())
        ctl2, _ = _trace_controller({"flat": FAST}, triggers=(trig2,))
        ctl2.restore_state(path)
        assert trig2.detector.state_dict() == trig.detector.state_dict()
        # the restored detector resumes mid-history: two more degraded
        # trace samples fire it, no warmup re-served
        wires2 = {"flat": SLOW}
        _, fake2 = _trace_controller(wires2)
        samples = ctl2.telemetry.step_samples()
        for i in range(6, 8):
            tr = fake2.capture(i)
            ctl2.ingest_trace(i, tr)
        assert trig2.due(TG.TriggerContext(
            step=7, telemetry=ctl2.telemetry, schedule=None,
            mode="lags_dp"))