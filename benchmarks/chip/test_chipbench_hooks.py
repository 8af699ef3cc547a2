"""A configuration that brings its own architecture as files: the test
fixture ``testdata/hooked-lm.json`` and its reference module
``testdata/hooked_lm.py`` use every hook (``program.model``, ``tiny``,
``flops_per_token``, ``numbers`` and a ``block`` with readings of its
own), driven through the whole run on the CPU as the oracle tests drive
the benchmark's cells, with no harness file edited for it."""
import json
import time

import pytest

from lagsbench import check, flops, harness, reference, tiny
from lagsbench.spec import BENCH_DIR

TESTDATA = BENCH_DIR / "testdata"
SEED = 4294967311
#: The limits of the cell the fixture stands in for, and one for the
#: fixture's own number.
LIMITS = {"loss_gap": 0.00011, "grad_norm_gap": 0.0025,
          "change_norm_gap": 0.11, "history_loss_gap": 0.00011}


@pytest.fixture(autouse=True)
def fixture_modules(monkeypatch):
    monkeypatch.setattr(
        reference, "model_module",
        lambda config: reference.load_module(
            TESTDATA / f"{config['reference']}.py"))


def _cell(limits=LIMITS, **kw):
    cell = tiny.cell("hooked-lm", configs_dir=TESTDATA, **kw)
    cell["limits"] = dict(limits)
    return cell


def _run(cell):
    return harness.run(cell, SEED, 0.2, False, t_start=time.perf_counter(),
                       require_chip=False)


def test_tiny_block_and_program_model_reach_the_program():
    cell = _cell()
    assert "tiny" not in cell["config"]
    assert cell["config"]["attention_head_size"] == 32
    prog = harness.Program(cell, harness.devices_for(1, False))
    assert (prog.cfg.hd, prog.cfg.d_model, prog.cfg.n_heads) == (32, 64, 4)


def test_flops_per_token_comes_from_the_reference_module():
    with open(TESTDATA / "hooked-lm.json") as f:
        cfg = json.load(f)
    d, h, kv, hd, f, v = 1024, 8, 4, 256, 2816, 32000
    per_layer = (2 * d * hd * (h + 2 * kv) + 2 * h * hd * d
                 + 2 * 4096 * h * hd + 6 * d * f)
    assert flops.model_flops_per_token(cfg, 4096) == \
        3 * (2 * per_layer + 2 * d * v)


def test_fourth_number_is_judged():
    res = _run(_cell())
    assert res["correct"], res["checks"]
    assert list(res["checks"]) == list(LIMITS)
    assert 0 <= res["checks"]["history_loss_gap"]["value"] <= 0.00011


def test_fourth_number_alone_fails_a_broken_program(monkeypatch):
    from repro.api import session
    orig = session.build_train_step

    def half_batch(cfg, mesh, run=None):
        import jax
        step, specs, meta = orig(cfg, mesh, run)

        def broken(state, batch):
            labels = batch["labels"]
            labels = labels.at[:, labels.shape[1] // 2:].set(-1)
            return step(state, {**batch, "labels": labels})
        return jax.jit(broken), specs, meta

    monkeypatch.setattr(session, "build_train_step", half_batch)
    res = _run(_cell({"history_loss_gap": LIMITS["history_loss_gap"]}))
    assert not res["correct"], res["checks"]


def test_a_limit_on_a_number_nothing_produces_fails():
    res = _run(_cell({**LIMITS, "routing_gap": 1.0}))
    assert not res["correct"]
    assert res["checks"]["routing_gap"] == {"value": None, "limit": 1.0}
    assert all(r["value"] <= r["limit"] for n, r in res["checks"].items()
               if n != "routing_gap")


def test_control_reads_the_configurations_numbers():
    import control
    from repro.observe import metrics as OM
    cell = _cell()
    prog = harness.Program(cell, harness.devices_for(1, False))
    rows = control.readings_for_seed(prog, SEED, OM.MetricsRegistry(), True)
    assert [r["who"] for r in rows] == ["program", "control", "half_batch"]
    for row in rows:
        assert row["history_loss_gap"] >= 0
        json.loads(json.dumps(row, default=control._plain))
    blocks = rows[0]["ref"]["history"][0]["blocks"]
    assert len(blocks) == 2 and len(blocks[0]) == 1
    assert set(blocks[0][0]) == {"out_rms"}
    ctl = {r["who"]: check.judge(r, LIMITS)[0] for r in rows}
    assert ctl == {"program": True, "control": False, "half_batch": False}
