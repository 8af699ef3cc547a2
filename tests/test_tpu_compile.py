"""Compile rehearsal for a TPU v5e: the TPU compiler, run for a chip that
is described and not attached.

The Pallas kernels compile through Mosaic at TinyLlama-1.1B leaf widths,
and a 2-layer full-width TinyLlama train step compiles with the kernel
selection backend.  Nothing runs here: these tests catch what Mosaic or
XLA:TPU would refuse (tilings, kernels that cannot be partitioned,
programs that do not fit) before a chip run does.  Every other kernel
test runs the same kernels in interpret mode on the CPU.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import api, compat
from repro.configs import base
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


@pytest.mark.parametrize("k", [5, 41])
def test_ef_select_pack_compiles(one_chip, k):
    """k=5 is the per-block budget at c=1000, k=41 at c=100."""
    g = _sds((ROWS, BS), jnp.bfloat16, one_chip)
    e = _sds((ROWS, BS), jnp.float32, one_chip)
    c = _compile(lambda g, e: EF.ef_select_pack_pallas(
        g, e, 1.0, -jnp.inf, k=k, interpret=False), g, e)
    assert "tpu_custom_call" in c.as_text()


def test_block_topk_compiles(one_chip):
    x = _sds((ROWS, BS), jnp.bfloat16, one_chip)
    c = _compile(lambda x: BT.block_topk_pallas(x, 5, interpret=False), x)
    assert "tpu_custom_call" in c.as_text()


def test_ef_block_candidates_compiles(one_chip):
    g = _sds((ROWS, BS), jnp.bfloat16, one_chip)
    e = _sds((ROWS, BS), jnp.float32, one_chip)
    c = _compile(lambda g, e: EF.ef_block_candidates_pallas(
        g, e, 1.0, r=4, interpret=False), g, e)
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
    monkeypatch.setattr(ops, "_interpret", lambda: False)
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
