"""Distributed-path tests.  Run in SUBPROCESSES with a multi-device host
platform (XLA_FLAGS) so the main pytest process keeps its single real CPU
device (see conftest.py).

Parity contract: one ``lags_dp`` train step on a (data=4, model=2) host mesh
must equal the single-device simulation path (leading-P worker axis) of the
SAME exchange, leaf by leaf.  Ditto dense.  This is the evidence that the
shard_map manual collectives implement Algorithm 1, not an approximation
of it.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, n_dev: int = 8, timeout: int = 540):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


COMMON = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.configs import base
from repro.core import lags
from repro.launch import mesh as M, train as TR, specs as SP
from repro.models import transformer as T

cfg = dataclasses.replace(
    base.get_smoke_config("tinyllama_1_1b"),
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=64,
    train_mode=MODE, compression_ratio=8.0,
    # fp32: the parity contract checks exchange/error-feedback semantics,
    # not bf16 rounding — at bf16 the 2e-4 atol sits below one ulp and
    # any partitioner-dependent matmul tiling flips it
    dtype="float32", param_dtype="float32")
mesh = M.make_host_mesh(data=4, model=2)
shape = base.InputShape("t", 16, 8, "train")
batch = SP.concrete_batch(cfg, shape)

from repro import api
step, state_specs, meta = api.build_train_step(
    cfg, mesh, api.RunConfig(lr=0.1, chunk=16, loss_chunk=16, donate=False))
state, _ = TR.init_state(cfg, mesh)
with compat.set_mesh(mesh):
    new_state, metrics = step(state, batch)
loss_dist = float(metrics["loss"])
params_dist = jax.tree.map(lambda x: np.asarray(jax.device_get(x), np.float32),
                           new_state["params"])

# ---- simulation reference: same exchange, leading-P layout --------------
P_W = meta["n_workers"]
params0, _ = T.init_model(jax.random.PRNGKey(0), cfg)  # init_state uses seed 0

def loss_fn(p, b):
    return T.loss_fn(p, cfg, b, chunk=16, loss_chunk=16)

vb = jax.tree.map(
    lambda x: x.reshape((P_W, x.shape[0] // P_W) + x.shape[1:]), batch)
(losses, _), grads = jax.vmap(
    lambda b: jax.value_and_grad(loss_fn, has_aux=True)(params0, b))(vb)
updates = jax.tree.map(lambda g: 0.1 * g.astype(jnp.float32), grads)
"""


@pytest.mark.slow
def test_lags_dp_matches_simulation():
    script = COMMON.replace("MODE", '"lags_dp"') + """
# reference exchange must use the SAME shard-aligned block layout as the
# distributed step (block partition determines which elements group)
row_axes = tuple(a for a in mesh.axis_names
                 if a not in meta["manual"] and a in ("data", "model"))
sdims = TR.shard_dims_tree(meta["pspecs"], row_axes)
exch = api.build_exchange(api.ExchangeSpec(
    mode="lags_dp", params_like=params0, ratio=cfg.compression_ratio,
    sim=False, shard_dims=sdims))
mean_upd, _ = exch.exchange(updates, exch.init(updates), None)
params_sim = jax.tree.map(
    lambda p, d: np.asarray((p.astype(jnp.float32) - d), np.float32),
    params0, mean_upd)
loss_sim = float(losses.mean())
assert abs(loss_dist - loss_sim) < 5e-3, (loss_dist, loss_sim)
flat_d = jax.tree.leaves(params_dist)
flat_s = jax.tree.leaves(params_sim)
for a, b in zip(flat_d, flat_s):
    np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)
print("OK lags_dp parity", loss_dist)
"""
    out = _run(script)
    assert "OK lags_dp parity" in out


@pytest.mark.slow
def test_dense_matches_simulation():
    script = COMMON.replace("MODE", '"dense"') + """
mean_upd = jax.tree.map(lambda u: u.mean(0), updates)
params_sim = jax.tree.map(
    lambda p, d: np.asarray((p.astype(jnp.float32) - d), np.float32),
    params0, mean_upd)
for a, b in zip(jax.tree.leaves(params_dist), jax.tree.leaves(params_sim)):
    np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)
print("OK dense parity", loss_dist)
"""
    out = _run(script)
    assert "OK dense parity" in out


@pytest.mark.slow
def test_hier_mode_runs_on_multipod_host_mesh():
    """lags_hier on a (pod=2, data=2, model=2) mesh: one step, finite loss,
    EF residuals have the pod-leading worker axis."""
    script = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.configs import base
from repro.launch import mesh as M, train as TR, specs as SP

cfg = dataclasses.replace(
    base.get_smoke_config("tinyllama_1_1b"),
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=64,
    train_mode="lags_hier", compression_ratio=8.0)
mesh = M.make_host_mesh(data=2, model=2, pod=2)
shape = base.InputShape("t", 16, 8, "train")
batch = SP.concrete_batch(cfg, shape)
from repro import api
step, state_specs, meta = api.build_train_step(
    cfg, mesh, api.RunConfig(lr=0.1, chunk=16, loss_chunk=16, donate=False))
assert meta["n_workers"] == 2, meta
state, _ = TR.init_state(cfg, mesh)
with compat.set_mesh(mesh):
    new_state, metrics = step(state, batch)
loss = float(metrics["loss"])
assert np.isfinite(loss), loss
ef_leaf = jax.tree.leaves(new_state["ef"])[0]
assert ef_leaf.shape[0] == 2
assert float(jnp.abs(ef_leaf).sum()) > 0.0  # residual actually accumulated
print("OK hier", loss)
"""
    out = _run(script)
    assert "OK hier" in out


@pytest.mark.slow
def test_hier_single_pod_matches_lags_dp_at_ratio_1():
    """ROADMAP degenerate path: lags_hier on a 1-pod mesh (no 'pod' axis)
    is FSDP + single-worker compression — the compressor and EF still run
    but there is no sparse comm.  At ratio 1 block-Top-k keeps every
    element, so one step must match lags_dp at ratio 1 on the SAME mesh:
    both reduce to the full-batch mean-gradient step."""
    script = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.configs import base
from repro.launch import mesh as M, train as TR, specs as SP

mesh = M.make_host_mesh(data=2, model=2)   # single pod: no 'pod' axis
shape = base.InputShape("t", 16, 8, "train")

def one_step(mode):
    cfg = dataclasses.replace(
        base.get_smoke_config("tinyllama_1_1b"),
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=64,
        train_mode=mode, compression_ratio=1.0,
        dtype="float32", param_dtype="float32")
    batch = SP.concrete_batch(cfg, shape)
    from repro import api
    step, _specs, meta = api.build_train_step(
        cfg, mesh, api.RunConfig(lr=0.1, chunk=16, loss_chunk=16,
                                 donate=False))
    state, _ = TR.init_state(cfg, mesh)
    with compat.set_mesh(mesh):
        new_state, metrics = step(state, batch)
    return new_state, float(metrics["loss"]), meta

hier_state, hier_loss, hier_meta = one_step("lags_hier")
dp_state, dp_loss, dp_meta = one_step("lags_dp")

# degenerate single-pod hier: exactly one LAGS worker, EF still carried
assert hier_meta["n_workers"] == 1, hier_meta["n_workers"]
ef_leaves = jax.tree.leaves(hier_state["ef"])
assert ef_leaves and ef_leaves[0].shape[0] == 1
# ratio 1 keeps everything -> residual exactly zero, but the EF machinery ran
assert all(float(jnp.abs(e).max()) == 0.0 for e in ef_leaves)

assert abs(hier_loss - dp_loss) < 5e-3, (hier_loss, dp_loss)
for a, b in zip(jax.tree.leaves(hier_state["params"]),
                jax.tree.leaves(dp_state["params"])):
    np.testing.assert_allclose(np.asarray(jax.device_get(a), np.float32),
                               np.asarray(jax.device_get(b), np.float32),
                               rtol=2e-3, atol=2e-4)
print("OK hier degenerate parity", hier_loss)
"""
    out = _run(script)
    assert "OK hier degenerate parity" in out


@pytest.mark.slow
def test_hier2_matches_simulation_on_multipod_mesh():
    """lags_hier2 (sparse intra-pod + sparse cross-pod) on a
    (pod=2, data=2, model=2) mesh: one distributed step must equal the
    SAME SparseHierLAGSExchange run on the leading-P simulation path —
    the evidence that the manual two-tier collectives implement the
    two-level selection, not an approximation of it."""
    script = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import api, compat
from repro.configs import base
from repro.launch import mesh as M, specs as SP
from repro.models import transformer as T

cfg = dataclasses.replace(
    base.get_smoke_config("tinyllama_1_1b"),
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=64,
    train_mode="lags_hier2", compression_ratio=8.0,
    dtype="float32", param_dtype="float32")
mesh = M.make_host_mesh(data=2, model=2, pod=2)
shape = base.InputShape("t", 16, 8, "train")
batch = SP.concrete_batch(cfg, shape)

run = api.RunConfig(lr=0.1, ratio_inner=4.0, chunk=16, loss_chunk=16,
                    donate=False)
sess = api.Session(cfg, run, mesh=mesh)
step, _specs, meta = sess.train_step()
assert meta["mode"] == "lags_hier2"
assert meta["manual"] == ("pod", "data"), meta["manual"]
assert meta["n_workers"] == 4
state, _ = sess.init_state()
with compat.set_mesh(mesh):
    new_state, metrics = step(state, batch)
loss_dist = float(metrics["loss"])
assert np.isfinite(loss_dist), loss_dist
# two-tier EF state, one residual tree per tier, worker-leading
assert set(new_state["ef"]) == {"inner", "outer"}
ef_in = jax.tree.leaves(new_state["ef"]["inner"])[0]
assert ef_in.shape[0] == 4
assert float(jnp.abs(ef_in).sum()) > 0.0

# ---- simulation reference: same exchange, leading-P layout --------------
params0, _ = T.init_model(jax.random.PRNGKey(0), cfg)

def loss_fn(p, b):
    return T.loss_fn(p, cfg, b, chunk=16, loss_chunk=16)

vb = jax.tree.map(lambda x: x.reshape((4, x.shape[0] // 4) + x.shape[1:]),
                  batch)
(losses, _), grads = jax.vmap(
    lambda b: jax.value_and_grad(loss_fn, has_aux=True)(params0, b))(vb)
updates = jax.tree.map(lambda g: 0.1 * g.astype(jnp.float32), grads)
exch = api.build_exchange(api.ExchangeSpec(
    mode="lags_hier2", params_like=params0, ratio=8.0, ratio_inner=4.0,
    sim=True, n_workers=4, n_inner=2))
mean_upd, _ef = exch.exchange(updates, exch.init(updates), None,
                              key=run.key_at(0))
params_sim = jax.tree.map(
    lambda p, d: np.asarray(p.astype(jnp.float32) - d, np.float32),
    params0, mean_upd)
params_dist = jax.tree.map(
    lambda x: np.asarray(jax.device_get(x), np.float32),
    new_state["params"])
assert abs(loss_dist - float(losses.mean())) < 5e-3
for a, b in zip(jax.tree.leaves(params_dist), jax.tree.leaves(params_sim)):
    np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)
print("OK hier2 parity", loss_dist)
"""
    out = _run(script)
    assert "OK hier2 parity" in out


# ---------------------------------------------------------------------------
# lags_hier2 degeneracy family (sim surface — no multi-device subprocess
# needed: the leading-P layout runs on the single CPU device).  Lemma 1
# licenses the two-level composition; these tests pin its degenerate
# corners against the strategies they must collapse to, for both a
# deterministic (topk) and a sampled (randk, fixed per-step keys)
# compressor.
# ---------------------------------------------------------------------------

def _quadratic_loss(p, b):
    # mean over the batch dim => grad(merged batch) == mean of sub-batch
    # grads, which is what makes pod-merged references exact
    import jax.numpy as jnp
    return (jnp.mean((p["w"][None, :] - b["w"]) ** 2)
            + jnp.mean((p["v"][None, :] - b["v"]) ** 2), {})


def _sim_batch(key, p_workers, b=4, d=48, e=20):
    import jax
    k1, k2 = jax.random.split(key)
    return {"w": jax.random.normal(k1, (p_workers, b, d)),
            "v": jax.random.normal(k2, (p_workers, b, e))}


def _sim_params():
    import jax
    import jax.numpy as jnp
    return {"w": jnp.linspace(-1.0, 1.0, 48),
            "v": 0.5 * jnp.ones((20,), jnp.float32)}


def _run_sim(run_kwargs, n_workers, batch_fn, n_steps=3):
    # drive SimTrainer directly with the same RunConfig the Session path
    # would pass through (Session needs a model cfg; this loss has none)
    from repro import api
    from repro.training import train_loop as TL
    trainer = TL.SimTrainer(_quadratic_loss, _sim_params(),
                            api.RunConfig(lr=0.2, **run_kwargs),
                            n_workers=n_workers)
    for t in range(n_steps):
        trainer.state, _ = trainer._step(trainer.state, batch_fn(t))
    return trainer.state


@pytest.mark.parametrize("compressor,backend", [
    ("topk_exact", "xla"), ("topk_exact", "kernel"), ("randk", "xla")])
def test_hier2_inner_ratio_one_matches_dense_inner_lags_hier(compressor,
                                                             backend):
    """2x2 sim mesh (2 pods x 2 intra-pod workers): lags_hier2 with a
    dense inner tier (ratio_inner=None -> 1.0) must match lags_hier —
    whose intra-pod reduction is the dense mean — run over the pod-merged
    batches, step for step."""
    import jax

    def batch4(t):
        return _sim_batch(jax.random.fold_in(jax.random.PRNGKey(5), t), 4)

    def batch_pods(t):
        # lags_hier reference: one worker per pod, batch = the pod's two
        # inner workers' batches concatenated (gradient of the mean loss
        # over the merged batch == mean of the sub-batch gradients)
        b4 = batch4(t)
        return jax.tree.map(
            lambda x: x.reshape((2, 2 * x.shape[1]) + x.shape[2:]), b4)

    s_hier2 = _run_sim(dict(mode="lags_hier2", ratio=4.0,
                            compressor=compressor, inner_workers=2,
                            selection_backend=backend),
                       n_workers=4, batch_fn=batch4)
    s_hier = _run_sim(dict(mode="lags_hier", ratio=4.0,
                           compressor=compressor,
                           selection_backend=backend),
                      n_workers=2, batch_fn=batch_pods)
    import numpy as np
    for a, b in zip(jax.tree.leaves(s_hier2["params"]),
                    jax.tree.leaves(s_hier["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    # dense inner tier: its residual is identically zero, and the outer
    # residual matches the reference's (pod-replicated copies agree)
    for r in jax.tree.leaves(s_hier2["ef"]["inner"]):
        assert float(jax.numpy.abs(r).max()) == 0.0
    ef2 = jax.tree.map(lambda r: np.asarray(r).reshape((2, 2) + r.shape[1:]),
                       s_hier2["ef"]["outer"])
    for r2, r1 in zip(jax.tree.leaves(ef2), jax.tree.leaves(s_hier["ef"])):
        np.testing.assert_allclose(r2[:, 0], np.asarray(r1),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(r2[:, 0], r2[:, 1], rtol=0, atol=0)


@pytest.mark.parametrize("compressor,backend", [
    ("topk_exact", "xla"), ("topk_exact", "kernel"), ("randk", "xla")])
def test_hier2_single_pod_degenerates_to_lags_dp(compressor, backend):
    """One pod (inner_workers == n_workers, no cross-pod axis) with a
    dense outer tier: lags_hier2 must reproduce lags_dp with
    ks == ks_inner exactly — same selections (same per-(step, leaf,
    worker) key stream), same EF residuals."""
    import jax
    import numpy as np

    def batch4(t):
        return _sim_batch(jax.random.fold_in(jax.random.PRNGKey(9), t), 4)

    s_hier2 = _run_sim(dict(mode="lags_hier2", ratio=1.0, ratio_inner=4.0,
                            compressor=compressor, inner_workers=4,
                            selection_backend=backend),
                       n_workers=4, batch_fn=batch4)
    s_dp = _run_sim(dict(mode="lags_dp", ratio=4.0, compressor=compressor,
                         selection_backend=backend),
                    n_workers=4, batch_fn=batch4)
    for a, b in zip(jax.tree.leaves(s_hier2["params"]),
                    jax.tree.leaves(s_dp["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(s_hier2["ef"]["inner"]),
                    jax.tree.leaves(s_dp["ef"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    # outer tier at ratio 1 keeps everything: residual identically zero
    for r in jax.tree.leaves(s_hier2["ef"]["outer"]):
        assert float(jax.numpy.abs(r).max()) == 0.0


@pytest.mark.slow
def test_serve_step_distributed():
    """Decode step on the host mesh for a decode-capable arch."""
    script = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.configs import base
from repro.launch import mesh as M, serve as SV
from repro.launch import train as TR
from repro.models import transformer as T
from repro.serving import engine

cfg = base.get_smoke_config("xlstm_1_3b")
mesh = M.make_host_mesh(data=4, model=2)
shape = base.InputShape("d", 64, 8, "decode")
with compat.set_mesh(mesh):
    fn, args = SV.make_serve_step(cfg, mesh, shape, chunk=16)
    lowered = fn.lower(*args)
    compiled = lowered.compile()
mem = compiled.memory_analysis()
print("OK serve lowered",
      getattr(mem, "peak_memory_in_bytes",
              getattr(mem, "temp_size_in_bytes", None)))
"""
    out = _run(script)
    assert "OK serve lowered" in out


@pytest.mark.slow
def test_lags_dp_kernel_backend_bitwise_under_shard_map():
    """selection_backend="kernel" vs "xla" on the real distributed surface:
    the same lags_dp exchange run under shard_map on a 4-device host mesh
    must produce bitwise-identical means and EF residuals.  The exchange
    operands here are materialized shards, so the jit-boundary fma caveat
    in ``core.lags.local_select_ef`` does not apply — this is the strict
    form of the parity contract, on real (forced-host) devices."""
    script = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro import api, compat

mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
leaves = {
    "w": jax.random.normal(jax.random.PRNGKey(0), (4, 257)),
    "b": jax.random.normal(jax.random.PRNGKey(1), (4, 96)),
}
ef0 = jax.tree.map(lambda u: 0.05 * u[:, ::-1], leaves)
outs = {}
for backend in ("xla", "kernel"):
    exch = api.build_exchange(api.ExchangeSpec(
        mode="lags_dp", params_like={k: v[0] for k, v in leaves.items()},
        ratio=4.0, compressor="topk_exact", selection_backend=backend,
        block_size=64, sim=False))
    f = compat.shard_map(
        lambda uu, ee: exch.exchange(uu, ee, ("data",)),
        mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P(), P("data")), check_vma=False)
    outs[backend] = jax.tree.map(np.asarray, f(leaves, ef0))
mean_x, ef_x = outs["xla"]
mean_k, ef_k = outs["kernel"]
for name in leaves:
    assert (mean_x[name] == mean_k[name]).all(), name
    assert (ef_x[name] == ef_k[name]).all(), name
    assert np.abs(ef_k[name]).sum() > 0.0, name  # residual is live
print("OK kernel shard_map bitwise")
"""
    out = _run(script, n_dev=4)
    assert "OK kernel shard_map bitwise" in out


@pytest.mark.slow
def test_lags_dp_tpu_select_bitwise_on_split_rows_and_pipelines():
    """The select a TPU runs by default (the fused pass, chosen by
    ``kops.on_tpu``) against the XLA branch, in whole train steps on a
    (data 2, model 2) host mesh, where the exchange splits each leaf's
    rows over ``model`` (the experts of an MoE leaf, the transposed
    output dim of a projection), for the monolithic exchange, the wave
    taps and the one-step-delayed exchange: parameters, optimizer and
    error-feedback state after two steps are bit for bit equal.  The
    kernel runs in interpret mode."""
    script = """
import dataclasses
import jax, numpy as np
from repro import api, compat
from repro.configs import base
from repro.kernels import ops
from repro.launch import mesh as M, train as TR, specs as SP

cfg = dataclasses.replace(base.get_smoke_config("olmoe_1b_7b"),
                          train_mode="lags_dp")
mesh = M.make_host_mesh(data=2, model=2)
batch = SP.concrete_batch(cfg, base.InputShape("t", 16, 8, "train"))
pack, calls = ops._ef.ef_select_pack_pallas, []

def spy(u, e, thr=None, *, interpret, **kw):
    calls.append(u.shape)
    return pack(u, e, thr, interpret=True, **kw)

ops._ef.ef_select_pack_pallas = spy
for pipeline in ("off", "wave", "async1"):
    got = {}
    for tpu in (False, True):
        ops.on_tpu = lambda: tpu
        calls.clear()
        run = api.RunConfig(mode="lags_dp", lr=1.0, ratio=8.0, block_size=64,
                            chunk=16, loss_chunk=16, donate=False,
                            pipeline=pipeline)
        step, _, _ = api.build_train_step(cfg, mesh, run)
        state, _ = TR.init_state(cfg, mesh, pipeline=pipeline)
        with compat.set_mesh(mesh):
            for _ in range(2):
                state, _ = step(state, batch)
        got[tpu] = [np.atleast_1d(np.asarray(x))
                    for x in jax.tree.leaves(state)]
        assert bool(calls) == tpu, (pipeline, tpu, len(calls))
    assert len(got[False]) == len(got[True])
    for a, b in zip(got[False], got[True]):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert (a.view(np.uint8) == b.view(np.uint8)).all(), pipeline
    print("OK", pipeline, len(calls))
"""
    out = _run(script, n_dev=4)
    assert out.count("OK ") == 3
