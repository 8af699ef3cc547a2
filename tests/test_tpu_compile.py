"""Compile rehearsal for a TPU v5e: the TPU compiler, run for a chip that
is described and not attached.

The Pallas kernels compile through Mosaic at TinyLlama-1.1B and
Mistral-7B leaf widths, a 2-layer full-width TinyLlama train step
compiles with the kernel selection backend, and full-width Mistral-7B
and OLMoE-1B-7B steps with the default ``RunConfig`` select in the fused
kernel, on one chip and on four.  Nothing runs here: these tests catch what Mosaic or XLA:TPU
would refuse (tilings, kernels that cannot be partitioned, programs that
do not fit) before a chip run does.  Every other kernel test runs the
same kernels in interpret mode on the CPU.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import api, compat
from repro.configs import base, llava_next_mistral_7b, olmoe_1b_7b
from repro.kernels import block_topk as BT
from repro.kernels import ef_sparsify as EF
from repro.kernels import ops

HBM_BYTES = 16 * 2**30            # one v5e chip
ROWS, BS = 2816, 4096             # one 2048 x 5632 FFN matrix as block rows


@pytest.fixture(scope="module")
def topo():
    """v5e 2x2 topology, described inside the test so that only the worker
    running this file loads the TPU library; the persistent compilation
    cache is off meanwhile (it cannot read back TPU programs here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
            jax.config.update("jax_enable_compilation_cache", was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("rows,cols,k", [
    (1, BS, 5), (1024, BS, 5), (4096, BS, 5), (14336, BS, 5),
    (32000, BS, 5), (4096 * 32, 128, 5), (2 * 4096, 14336, 5),
    (4096, BS, 41), (4096, BS, 256)])
def test_ef_select_pack_compiles(one_chip, rows, cols, k):
    """Mistral-7B's leaves in the views the exchange hands the kernel:
    a norm, block views of 1024 to 32000 rows (``wk``/``wv``, ``wo``,
    ``w_down``, the embedding, ``lm_head``), and two layers of ``wq``
    and of ``w_gate`` read in place (rows of 128 lanes, rows of 3.5
    blocks); k=5 is the per-block budget at c=1000, k=41 at c=100,
    k=256 the largest the exchange gives the kernel (c=16).
    Each with the grid that ``select_geometry`` chooses for it."""
    assert EF.select_geometry(rows, cols, BS) is not None
    u = _sds((rows, cols), jnp.float32, one_chip)
    c = _compile(lambda u, e: EF.ef_select_pack_pallas(
        u, e, k=k, block_size=BS, interpret=False), u, u)
    assert "tpu_custom_call" in c.as_text()


def test_block_topk_compiles(one_chip):
    x = _sds((ROWS, BS), jnp.bfloat16, one_chip)
    c = _compile(lambda x: BT.block_topk_pallas(x, 5, interpret=False), x)
    assert "tpu_custom_call" in c.as_text()


def test_ef_block_candidates_compiles(one_chip):
    g = _sds((ROWS, BS), jnp.bfloat16, one_chip)
    e = _sds((ROWS, BS), jnp.float32, one_chip)
    c = _compile(lambda g, e: EF.ef_block_candidates_pallas(
        g, e, r=4, interpret=False), g, e)
    assert "tpu_custom_call" in c.as_text()


def test_ef_accum_sparsify_compiles(one_chip):
    d = 2048 * 5632
    g = _sds((d,), jnp.bfloat16, one_chip)
    e = _sds((d,), jnp.float32, one_chip)
    c = _compile(lambda g, e: EF.ef_accum_sparsify_pallas(
        g, e, 1.0, 0.5, interpret=False), g, e)
    assert "tpu_custom_call" in c.as_text()


def test_train_step_compiles_with_kernels(topo, monkeypatch):
    """2-layer TinyLlama at full width, lags_dp with the kernel backend on
    a one-chip mesh: the kernels sit inside the step as Mosaic calls and
    the program fits one chip's HBM."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = dataclasses.replace(base.get_config("tinyllama_1_1b"), n_layers=2)
    mesh = compat.make_mesh((1, 1), ("data", "model"),
                            devices=topo.devices[:1])
    run = api.RunConfig(mode="lags_dp", ratio=1000.0,
                        selection_backend="kernel")
    step, specs, _ = api.Session(cfg, run, mesh=mesh).train_step()
    tok = _sds((4, 2048), jnp.int32, NamedSharding(mesh, P("data", None)))
    with compat.set_mesh(mesh):
        c = step.lower(specs, {"tokens": tok, "labels": tok}).compile()
    assert "tpu_custom_call" in c.as_text()
    m = c.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert live < HBM_BYTES


def _mistral_7b(n_layers):
    return dataclasses.replace(llava_next_mistral_7b.CONFIG, family="dense",
                               frontend=None, n_frontend_tokens=0,
                               n_layers=n_layers)


@pytest.mark.parametrize("model,layers,mesh_shape,pipeline", [
    ("mistral_7b", 2, (1, 1), "off"),
    ("mistral_7b", 1, (4, 1), "wave"),
    ("olmoe_1b_7b", 1, (2, 2), "off"),
    ("olmoe_1b_7b", 1, (2, 2), "async1"),
])
def test_default_lags_step_selects_in_a_kernel(topo, monkeypatch, model,
                                               layers, mesh_shape,
                                               pipeline):
    """Full-width ``lags_dp`` steps with the default ``RunConfig`` but for
    the pipeline, compiled as for a TPU: every leaf's select is a Mosaic
    call under ``lags/select/``, and the program fits one chip's HBM.
    Mistral-7B on one chip, and its wave taps over four; OLMoE-1B-7B's
    expert leaves over a (data 2, model 2) mesh, where the exchange
    splits each leaf's rows over ``model`` (its experts), with the
    monolithic exchange and the one-step-delayed one."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = (_mistral_7b(layers) if model == "mistral_7b" else
           dataclasses.replace(olmoe_1b_7b.CONFIG, n_layers=layers))
    n = mesh_shape[0] * mesh_shape[1]
    mesh = compat.make_mesh(mesh_shape, ("data", "model"),
                            devices=topo.devices[:n])
    run = api.RunConfig(mode="lags_dp", ratio=1000.0, pipeline=pipeline)
    assert run.selection_backend == "xla"
    step, specs, _ = api.Session(cfg, run, mesh=mesh).train_step()
    tok = _sds((mesh_shape[0], 4096), jnp.int32,
               NamedSharding(mesh, P("data", None)))
    with compat.set_mesh(mesh):
        c = step.lower(specs, {"tokens": tok, "labels": tok}).compile()
    kernels = [line for line in c.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    n_leaves = len(jax.tree.leaves(specs["params"]))
    assert len(kernels) == n_leaves
    assert all("/lags/select/l" in line for line in kernels)
    m = c.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert live < HBM_BYTES
