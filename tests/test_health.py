"""repro.observe.health: the online convergence-health plane.

The load-bearing claim, pinned here: the in-graph estimator computes
EXACTLY the paper's Eq.-20 delta that
``core.assumption.delta_metric_tree(..., n_rand=0)`` measures offline by
materializing per-worker accumulators — for the flat exchange straight
from the EF identity ``acc_p = e_new_p + sel_p``, and for the two-level
hierarchy by reconstructing the outer-tier accumulators from the two
residual trees.  Also covered: the SimTrainer surface (tier-correct
metric keys, dispatch by registry ``ef_tiers`` rather than EF-state
shape), the HealthMonitor's threshold/drift alarm paths, the
HealthTrigger re-planning strictly earlier than the cadence, and the
``lags/health/...`` name grammar.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # property tests need hypothesis; the oracle sweeps below do not
    from hypothesis import given, settings, strategies as st
except ImportError:
    from conftest import given, settings, st

from repro.core import assumption, lags
from repro.observe import anomaly as AN
from repro.observe import health as H
from repro.observe import names as ON
from repro.observe import triggers as TG

SHAPES = {"b": (5,), "wk": (96,), "wq": (12, 8)}
KS = {"b": 2, "wk": 11, "wq": 13}


def _tree(seed, p, dtype=jnp.float32):
    key = jax.random.PRNGKey(seed)
    out = {}
    for i, (name, shape) in enumerate(sorted(SHAPES.items())):
        x = jax.random.normal(jax.random.fold_in(key, i), (p,) + shape)
        out[name] = (x * 3.0).astype(dtype)
    return out


def _stack(tree) -> np.ndarray:
    return np.stack([np.asarray(x, np.float64)
                     for x in jax.tree.leaves(tree)])


# ---------------------------------------------------------------------------
# names grammar
# ---------------------------------------------------------------------------

class TestHealthNames:
    def test_roundtrip_with_slashes_in_label(self):
        n = ON.health_name("delta", "blocks/0/attn/wq")
        assert ON.parse(n) == {"type": "health", "kind": "delta",
                               "label": "blocks/0/attn/wq"}

    def test_empty_label_and_kinds(self):
        assert ON.parse(ON.health_name("staleness")) == \
            {"type": "health", "kind": "staleness", "label": ""}
        for kind in ON.HEALTH_KINDS:
            assert ON.parse(ON.health_name(kind, "x"))["kind"] == kind

    def test_bare_prefix_rejected(self):
        assert ON.parse("lags/health/") is None

    def test_leaf_names_match_tree_flatten_order(self):
        tree = {"a": {"x": jnp.zeros(2), "y": jnp.zeros(3)},
                "b": jnp.zeros(4)}
        names = H.leaf_names(tree)
        assert names == ["a/x", "a/y", "b"]
        assert len(names) == len(jax.tree.leaves(tree))

    def test_lazy_exports(self):
        import repro.observe as O
        assert O.HealthMonitor is H.HealthMonitor
        assert O.HealthTrigger is TG.HealthTrigger
        assert O.health is H


# ---------------------------------------------------------------------------
# online delta == the offline oracle (flat exchange)
# ---------------------------------------------------------------------------

def _check_flat(seed, p, dtype, steps=3):
    """EF-warmed run: every step, the online estimator (worker-summed
    new residual + closed-form denominator) must equal
    ``delta_metric_tree`` on the materialized per-worker accumulators."""
    ex = lags.LAGSExchange(ks=KS, compressor_name="topk_exact")
    ef = ex.init(_tree(seed, p, dtype))
    for t in range(steps):
        updates = _tree(seed + 101 * t + 1, p, dtype)
        accs = jax.tree.map(lambda e, u: e + u, ef, updates)
        mean, new_ef = ex.exchange(updates, ef, None,
                                   key=jax.random.PRNGKey(t))
        e_sum = jax.tree.map(lambda e: e.sum(0), new_ef)
        online = H.delta_leaves_from_mean(e_sum, mean, ex.ks, p)
        oracle = assumption.delta_metric_tree(accs, ex.ks, None, n_rand=0)
        np.testing.assert_allclose(np.asarray(online, np.float64),
                                   _stack(oracle), rtol=1e-5, atol=1e-7)
        ef = new_ef


class TestOnlineDeltaFlat:
    @pytest.mark.parametrize("p", [1, 2, 4])
    def test_matches_oracle_f32(self, p):
        _check_flat(seed=3, p=p, dtype=jnp.float32)

    def test_matches_oracle_bf16_updates(self):
        # bf16 gradients, f32 residuals: both paths square-sum in f32
        _check_flat(seed=7, p=4, dtype=jnp.bfloat16)

    def test_ratio_one_delta_is_zero(self):
        ks = {k: int(np.prod(s)) for k, s in SHAPES.items()}
        ex = lags.LAGSExchange(ks=ks, compressor_name="topk_exact")
        u = _tree(11, 4)
        mean, new_ef = ex.exchange(u, ex.init(u), None)
        e_sum = jax.tree.map(lambda e: e.sum(0), new_ef)
        online = H.delta_leaves_from_mean(e_sum, mean, ks, 4)
        # k = d: zero residual over a zero closed-form denominator
        # must read 0 (the EPS floor), never inf/nan
        assert np.allclose(np.asarray(online), 0.0)

    @given(seed=st.integers(0, 2**31 - 1),
           p=st.sampled_from([1, 2, 4]),
           dtype=st.sampled_from(["float32", "bfloat16"]))
    @settings(max_examples=12, deadline=None)
    def test_property_random_trees(self, seed, p, dtype):
        _check_flat(seed=seed, p=p, dtype=jnp.dtype(dtype))


# ---------------------------------------------------------------------------
# online delta == the offline oracle (two-level hierarchy)
# ---------------------------------------------------------------------------

def _check_hier2(seed, n_inner, n_outer, dtype, steps=3):
    """The online estimator gates the slow OUTER wire.  The oracle
    reconstructs the outer-tier accumulators from both residual trees:
    per-worker inner selections via the inner EF identity, pod-averaged
    into the pod-replicated outer residual (one replica per pod)."""
    p = n_inner * n_outer
    ks_inner = {k: min(2 * v, int(np.prod(SHAPES[k])))
                for k, v in KS.items()}
    ex = lags.SparseHierLAGSExchange(ks=KS, ks_inner=ks_inner,
                                     n_inner=n_inner,
                                     compressor_name="topk_exact")
    ef = ex.init(_tree(seed, p, dtype))
    for t in range(steps):
        u = _tree(seed + 101 * t + 1, p, dtype)
        mean, new_ef = ex.exchange(u, ef, None, key=jax.random.PRNGKey(t))
        e_sum = jax.tree.map(lambda e: e.sum(0) / n_inner, new_ef["outer"])
        online = H.delta_leaves_from_mean(e_sum, mean, ex.ks, n_outer)

        sel_in = jax.tree.map(lambda eo, uu, en: eo + uu - en,
                              ef["inner"], u, new_ef["inner"])

        def pod_acc(eo_old, s):
            m_pod = s.reshape((n_outer, n_inner) + s.shape[1:]).mean(1)
            eo_pod = eo_old.reshape((n_outer, n_inner)
                                    + eo_old.shape[1:])[:, 0]
            return eo_pod + m_pod

        accs_out = jax.tree.map(pod_acc, ef["outer"], sel_in)
        oracle = assumption.delta_metric_tree(accs_out, ex.ks, None,
                                              n_rand=0)
        np.testing.assert_allclose(np.asarray(online, np.float64),
                                   _stack(oracle), rtol=1e-5, atol=1e-7)
        ef = new_ef


class TestOnlineDeltaHier2:
    @pytest.mark.parametrize("n_inner,n_outer", [(2, 2), (2, 1), (1, 3)])
    def test_matches_reconstructed_outer_oracle(self, n_inner, n_outer):
        _check_hier2(seed=5, n_inner=n_inner, n_outer=n_outer,
                     dtype=jnp.float32)

    @given(seed=st.integers(0, 2**31 - 1),
           n_inner=st.sampled_from([1, 2]),
           n_outer=st.sampled_from([1, 2]))
    @settings(max_examples=10, deadline=None)
    def test_property_random_trees(self, seed, n_inner, n_outer):
        _check_hier2(seed=seed, n_inner=n_inner, n_outer=n_outer,
                     dtype=jnp.float32)


# ---------------------------------------------------------------------------
# SimTrainer surface: tier-correct keys, registry-driven dispatch
# ---------------------------------------------------------------------------

def _sim(mode, n_workers, **run_kw):
    from repro import api
    from repro.training.train_loop import SimTrainer
    params = {"w": jnp.zeros((24,), jnp.float32),
              "v": jnp.zeros((6, 4), jnp.float32)}

    def loss_fn(p, b):
        pred = p["w"] * b["x"] + p["v"].reshape(-1)
        return jnp.mean((pred - b["y"]) ** 2), {}

    run_kw.setdefault("health_every", 1)
    run = api.RunConfig(mode=mode, ratio=4.0, lr=0.2, **run_kw)
    tr = SimTrainer(loss_fn, params, run, n_workers)

    def data_fn(t):
        k = jax.random.PRNGKey(100 + t)
        return {"x": jax.random.normal(k, (n_workers, 24)),
                "y": jax.random.normal(jax.random.fold_in(k, 1),
                                       (n_workers, 24))}

    return tr, data_fn


class TestSimTrainerHealth:
    def test_flat_keys_and_leaf_count(self):
        tr, data = _sim("lags_dp", 4)
        hist = tr.run(data, 2, log_every=1)
        row = hist[-1]
        assert len(row["health_delta"]) == len(tr.health_leaf_names) == 2
        assert np.isfinite(row["health_delta"]).all()
        assert row["health_delta_max"] == pytest.approx(
            max(row["health_delta"]))
        assert len(row["health_ef_energy_flat"]) == 2
        assert "health_ef_energy_inner" not in row

    def test_hier2_keys_dispatch_by_registry_not_ef_shape(self):
        # the EF state of a FLAT exchange over dict params is itself a
        # dict — only the registry's ef_tiers may pick the tiered branch
        tr, data = _sim("lags_hier2", 4, inner_workers=2)
        row = tr.run(data, 2, log_every=1)[-1]
        assert "health_ef_energy_inner" in row
        assert "health_ef_energy_outer" in row
        assert "health_ef_energy_flat" not in row
        assert np.isfinite(row["health_delta"]).all()

    def test_health_off_adds_no_keys(self):
        tr, data = _sim("lags_dp", 2, health_every=0)
        row = tr.run(data, 1, log_every=1)[-1]
        assert not any(k.startswith("health") for k in row)

    def test_sim_delta_matches_offline_oracle(self):
        """End-to-end on the training surface: the step's in-graph
        health_delta equals the oracle on accumulators rebuilt from the
        pre-step EF state and the step's actual updates (lr * grads)."""
        tr, data = _sim("lags_dp", 4)
        tr.run(data, 2, log_every=1)          # warm the residuals
        state = tr.state
        batch = data(2)

        def one(b):
            (l, _), g = jax.value_and_grad(tr.loss_fn, has_aux=True)(
                state["params"], b)
            return g

        grads = jax.vmap(one)(batch)
        lr = float(tr.run_config.lr_at(int(state["step"])))
        updates = jax.tree.map(lambda g: lr * g, grads)
        accs = jax.tree.map(lambda e, u: e + u, state["ef"], updates)
        oracle = assumption.delta_metric_tree(accs, tr.exchange.ks, None,
                                              n_rand=0)
        new_state, metrics = tr._step(state, batch)
        np.testing.assert_allclose(
            np.asarray(metrics["health_delta"], np.float64),
            _stack(oracle), rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# HealthMonitor: threshold + drift alarm paths
# ---------------------------------------------------------------------------

def _drift_cfg():
    return AN.AnomalyConfig(warmup=1, recent=2, min_history=2, z=4.0,
                            min_rel=0.2)


class TestHealthMonitor:
    def test_threshold_fires_immediately_and_latches(self):
        mon = H.HealthMonitor(threshold=1.0)
        assert mon.observe(0, 0.5) is None and not mon.alarming
        alarm = mon.observe(1, 1.5)
        assert alarm == {"reason": "threshold", "step": 1,
                         "delta_max": 1.5, "threshold": 1.0}
        assert mon.alarming
        # fire-once: further offenders stay quiet until reset
        assert mon.observe(2, 3.0) is None

    def test_consume_pops_pending(self):
        mon = H.HealthMonitor(threshold=1.0)
        mon.observe(0, 2.0)
        assert mon.consume()["reason"] == "threshold"
        assert not mon.alarming and mon.consume() is None
        assert mon.last_alarm["delta_max"] == 2.0   # diagnostics survive

    def test_reset_rearms_threshold(self):
        mon = H.HealthMonitor(threshold=1.0)
        assert mon.observe(0, 2.0) is not None
        mon.reset()
        assert not mon.alarming
        assert mon.observe(1, 2.0)["reason"] == "threshold"

    def test_drift_fires_without_threshold(self):
        mon = H.HealthMonitor(cfg=_drift_cfg())
        for t in range(5):
            assert mon.observe(t, 0.05) is None
        alarm = mon.observe(5, 0.3) or mon.observe(6, 0.3)
        assert alarm is not None and alarm["reason"] == "drift"
        assert alarm["delta_max"] > 0.05
        assert alarm["ref"] == pytest.approx(0.05)
        assert mon.alarming

    def test_threshold_wins_over_drift_same_sample(self):
        mon = H.HealthMonitor(threshold=0.1, cfg=None)
        assert mon.observe(0, 0.5)["reason"] == "threshold"

    def test_detector_and_cfg_mutually_exclusive(self):
        with pytest.raises(ValueError, match="not both"):
            H.HealthMonitor(detector=AN.StepTimeAnomalyDetector(),
                            cfg=_drift_cfg())

    def test_state_dict_roundtrip_json_clean(self):
        import json
        mon = H.HealthMonitor(threshold=1.0, cfg=None)
        mon.observe(0, 0.5)
        mon.observe(1, 2.0)
        state = json.loads(json.dumps(mon.state_dict()))
        mon2 = H.HealthMonitor(threshold=1.0)
        mon2.load_state_dict(state)
        assert mon2.alarming and mon2.consume() == mon.consume()
        # the restored latch holds: no re-fire on the next offender
        assert mon2.observe(2, 3.0) is None


# ---------------------------------------------------------------------------
# HealthTrigger: an injected over-aggressive delta re-plans strictly
# earlier than the cadence, through the real Session + controller
# ---------------------------------------------------------------------------

class TestHealthTriggerReplan:
    def test_trigger_polls_and_consumes_monitor(self):
        from repro.runtime.telemetry import Telemetry
        mon = H.HealthMonitor(threshold=1.0)
        trig = TG.HealthTrigger(mon)
        ctx = TG.TriggerContext(step=1, telemetry=Telemetry(),
                                schedule=None, mode="lags_dp")
        assert not trig.due(ctx)
        mon.observe(1, 2.0)
        assert trig.due(ctx)
        assert trig.last["reason"] == "threshold"
        assert not trig.due(ctx)            # consumed
        mon.observe(2, 9.0)                 # latched: monitor quiet
        assert not trig.due(ctx)
        trig.notify_replan(ctx, None)       # re-plan re-arms the monitor
        mon.observe(3, 2.0)
        assert trig.due(ctx)

    def test_alarm_replans_before_cadence(self, tmp_path):
        from repro import api
        from repro.configs import base
        from repro.data import synthetic
        from repro.launch import mesh as M
        from repro.observe import events as OE
        from repro.observe import metrics as OM
        from repro.runtime.controller import RuntimeConfig

        cfg = dataclasses.replace(
            base.get_smoke_config("tinyllama_1_1b"), n_layers=2,
            d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=64,
            dtype="float32", param_dtype="float32",
            train_mode="lags_dp", compression_ratio=8.0)
        mesh = M.make_host_mesh(data=1, model=1)
        reg, evs = OM.MetricsRegistry(), OE.EventLog()
        sess = api.Session(
            cfg, api.RunConfig(mode="lags_dp", ratio=8.0, lr=0.25,
                               chunk=16, loss_chunk=16, donate=False,
                               health_every=1),
            mesh=mesh)
        # threshold below any real delta: the first health fence alarms
        mon = H.HealthMonitor(threshold=1e-9)
        CADENCE = 100
        ctl = sess.controller(
            rcfg=RuntimeConfig(replan_every=CADENCE, fence_every=1,
                               swap_threshold=0.05, min_step_samples=1),
            comm_probe=lambda mesh, axes: [],
            triggers=(TG.CadenceTrigger(CADENCE), TG.HealthTrigger(mon)),
            metrics=reg, events=evs)
        ctl.meta["n_workers"] = 8
        data = synthetic.MarkovLM(vocab=cfg.vocab, seed=3)
        state, _ = sess.init_state()
        state, history = sess.run(
            lambda t: data.batch(t, 2, 16), 4, controller=ctl,
            state=state, health_monitor=mon, metrics=reg, events=evs,
            print_fn=lambda *a, **k: None)

        alarms = evs.events("health_alarm")
        assert alarms and alarms[0].data["reason"] == "threshold"
        assert alarms[0].name == ON.health_name("delta")
        fired = [e for e in evs.events("trigger") if e.name == "health"]
        assert fired, "HealthTrigger never fired"
        assert fired[0].step < CADENCE      # strictly earlier than cadence
        assert ctl.history and "health" in ctl.history[0].trigger
        assert reg.counter(
            "train_health_alarms_total",
            "Convergence-health alarms fired (threshold or drift).",
            ("mode", "reason")).value(mode="lags_dp",
                                      reason="threshold") >= 1
        # the session exported the per-leaf plane alongside the alarm
        rows = [r for r in reg.snapshot_rows()
                if r["name"] == "train_health_delta"]
        assert rows and all(
            ON.parse(r["labels"]["leaf"])["kind"] == "delta" for r in rows)
