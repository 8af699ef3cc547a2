"""The comparison that decides ``correct``, driven through the whole run
on the CPU at a small size: the harness's look for a chip is skipped,
everything else runs as on the chip.  The unbroken program (in float32,
so only rounding separates it from the reference) comes out correct;
with the timed path broken underneath it does not; nor does the control,
the reference computed in float8 put in the program's place.  Each is
held to the limits of the cell it stands for."""
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import pytest

from lagsbench import check, harness, tiny
from lagsbench.spec import BENCH_DIR

SEED = 4294967311


def _run(cell):
    return harness.run(cell, SEED, 0.2, False, t_start=time.perf_counter(),
                       require_chip=False)


def _broken(monkeypatch, wrap):
    """Build every step as ``wrap(step)`` (jitted)."""
    from repro.api import session
    orig = session.build_train_step

    def build(cfg, mesh, run=None):
        step, specs, meta = orig(cfg, mesh, run)
        return jax.jit(wrap(step)), specs, meta

    monkeypatch.setattr(session, "build_train_step", build)


def _state_unchanged(step):
    return lambda state, batch: (state, step(state, batch)[1])


def _half_batch(step):
    def run(state, batch):
        labels = batch["labels"]
        labels = labels.at[:, labels.shape[1] // 2:].set(-1)
        return step(state, {**batch, "labels": labels})
    return run


@pytest.mark.parametrize("limits,config,traffic", [
    ("mistral7b-lags-1chip", "mistral-7b", "lags-c1000-b1x4096"),
    ("mistral7b-lags-1chip", "olmoe-1b-7b", "lags-c1000-b1x4096"),
    ("mistral7b-dense-1chip", "mistral-7b", "dense-b1x4096"),
])
def test_unbroken_program_is_correct(limits, config, traffic):
    cell = tiny.cell(config, traffic, limits=limits)
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res["checks"]) == list(cell["limits"])
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("limits,traffic", [
    ("mistral7b-lags-1chip", "lags-c1000-b1x4096"),
    ("mistral7b-dense-1chip", "dense-b1x4096"),
])
def test_broken_program_is_not_correct(monkeypatch, fault, limits, traffic):
    _broken(monkeypatch, fault)
    res = _run(tiny.cell("mistral-7b", traffic, limits=limits))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("limits,config,traffic", [
    ("mistral7b-lags-1chip", "mistral-7b", "lags-c1000-b1x4096"),
    ("mistral7b-lags-1chip", "olmoe-1b-7b", "lags-c1000-b1x4096"),
    ("mistral7b-dense-1chip", "mistral-7b", "dense-b1x4096"),
])
def test_control_in_float8_is_not_correct(limits, config, traffic):
    cell = tiny.cell(config, traffic, dtype="bfloat16", limits=limits)
    prog = harness.Program(cell, harness.devices_for(1, False))
    batches = prog.batches(SEED)
    ref = prog.reference(SEED, batches[:3])
    ctl = prog.reference(SEED, batches[:3], prec="fp8")
    ok, report = check.judge(check.numbers(ctl, ref, cell["config"]),
                             cell["limits"])
    assert not ok, report


FOUR_WORKERS = textwrap.dedent("""
    import json, sys, time
    import jax, jax.numpy as jnp
    sys.path.insert(0, {bench!r})
    from lagsbench import harness, tiny

    def run():
        cell = tiny.cell("mistral-7b", "lags-wave-c1000-b1x4096", chips=4,
                         limits="mistral7b-lags-1chip")
        return harness.run(cell, {seed}, 0.2, False,
                           t_start=time.perf_counter(), require_chip=False)

    out = {{"unbroken": run()["correct"]}}

    def own_payload_only(x, axis_name, **kw):
        n = 1
        for a in (axis_name if isinstance(axis_name, tuple) else (axis_name,)):
            n *= jax.lax.axis_size(a)
        return jnp.broadcast_to(x[None], (n,) + x.shape)

    jax.lax.all_gather = own_payload_only
    out["no_exchange"] = run()["correct"]
    print(json.dumps(out))
""")


def test_exchange_left_out_is_not_correct_on_four_workers():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_WORKERS.format(bench=str(BENCH_DIR), seed=SEED)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out == {"unbroken": True, "no_exchange": False}
