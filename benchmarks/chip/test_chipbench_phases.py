"""Phase attribution of a trace (``lagsbench.phases``): on hand-built
traces, on the recorded trace of a step without phase scopes, and on one
recorded with them (``testdata/make_phases_fixture.py``)."""
import json

import pytest

from lagsbench import phases, xplane
from lagsbench.spec import BENCH_DIR
from repro.observe import names

TESTDATA = BENCH_DIR / "testdata"

FWD = "jit(step)/lags/fwd/jvp()/dot_general"
BWD = "jit(step)/lags/fwd/transpose(jvp())/dot_general"
SELECT = "jit(step)/lags/exchange/lags/select/l0/argmax"
SCATTER = "jit(step)/lags/exchange/lags/scatter_mean/l0/scatter-add"
APPLY = "jit(step)/lags/apply/sub"


def _trace(ops, op_names, window=(0.0, 10.0), steps=1, host=()):
    return xplane.Reduced(ops, list(host), window, steps, op_names)


def _busy_ms(trace):
    return 1e3 * trace.busy_s / trace.steps


def test_innermost_phase_wins_and_parts_sit_in_exchange():
    ops = {0: [(0, 1, "%a.1 = f()"), (1, 2, "%b.2 = f()"),
               (2, 3, "%c.3 = f()")]}
    t = _trace(ops, {"a.1": SELECT, "b.2": SCATTER,
                     "c.3": "jit(step)/lags/exchange/mul"})
    ms = phases.per_step(t)
    assert ms["exchange"] == pytest.approx(3000)
    assert ms["select"] == pytest.approx(1000)
    assert ms["scatter_mean"] == pytest.approx(1000)
    assert ms["fwd"] == ms["bwd"] == ms["unattributed"] == 0


def test_transpose_means_bwd_and_wave_exchange_is_exchange():
    wave = ("jit(step)/lags/fwd/transpose(lags/fwd)/jvp(lags/exchange)/"
            "lags/select/l3/argmax")
    ops = {0: [(0, 1, "%a.1 = f()"), (1, 3, "%b.2 = f()"),
               (3, 4, "%c.3 = f()")]}
    ms = phases.per_step(_trace(ops, {"a.1": FWD, "b.2": BWD, "c.3": wave}))
    assert (ms["fwd"], ms["bwd"], ms["exchange"], ms["select"]) == \
        pytest.approx((1000, 2000, 1000, 1000))


def test_ops_are_clipped_to_the_window():
    ops = {0: [(-2, 1, "%a.1 = f()"), (1, 3, "%b.2 = f()"),
               (3, 9, "%c.3 = f()")]}
    t = _trace(ops, {"a.1": FWD, "b.2": BWD, "c.3": APPLY}, window=(0, 5))
    ms = phases.per_step(t)
    assert (ms["fwd"], ms["bwd"], ms["apply"]) == \
        pytest.approx((1000, 2000, 2000))


def test_mean_over_chips_and_per_step():
    ops = {0: [(0, 2, "%a.1 = f()")], 1: [(0, 4, "%a.1 = f()")]}
    ms = phases.per_step(_trace(ops, {"a.1": APPLY}, steps=2))
    assert ms["apply"] == pytest.approx(1e3 * (2 + 4) / 2 / 2)


def test_phases_and_remainder_add_up_to_busy_time():
    """A loop's own time counts for its op_name, its body's ops for
    theirs; an op with no op_name takes its operand's phase, else its
    consumer's; one with an op_name outside every scope, or with
    neither, is the remainder."""
    ops = {0: [(0, 6, "%while.1 = while(f32[] %p.0)"),
               (1, 2, "%a.2 = f()"), (2, 4, "%b.3 = f()"),
               (6, 7, "%copy.4 = f32[8] copy(f32[8] %b.3)"),
               (7, 8, "%iota.5 = iota()"),
               (9, 9.5, "%fusion.6 = fusion(f32[] %param.7)"),
               (10, 11, "%copy-start.8 = copy-start(f32[] %param.9)"),
               (11, 12, "%copy-done.10 = copy-done(f32[] %copy-start.8)"),
               (12, 13, "%c.11 = f(f32[] %copy-done.10)")]}
    t = _trace(ops, {"while.1": FWD, "a.2": FWD, "b.3": BWD,
                     "iota.5": "jit(step)/iota", "c.11": APPLY},
               window=(0, 13))
    ms = phases.per_step(t)
    total = sum(ms[k] for k in names.STEP_PHASES + (phases.UNATTRIBUTED,))
    assert total == pytest.approx(_busy_ms(t))
    assert ms["fwd"] == pytest.approx(1e3 * (3 + 1))
    assert ms["bwd"] == pytest.approx(1e3 * (2 + 1))      # the copy too
    assert ms["apply"] == pytest.approx(1e3 * 3)   # its async copy too
    assert ms["unattributed"] == pytest.approx(1e3 * 1.5)
    top = phases.unattributed_ops(t)
    assert [op for op, _, _ in top] == ["iota", "fusion"]
    assert top[0][1] == "jit(step)/iota" and top[1][1] == ""


def test_no_phase_scopes_read_as_none():
    ops = {0: [(0, 1, "%a.1 = f()")]}
    t = _trace(ops, {"a.1": "jit(step)/jvp()/dot_general"},
               host=[(0, 1, "lags/host/loss_sync")])
    assert phases.per_step(t) is None
    assert phases.unattributed_ops(t) is None
    assert phases.per_step(_trace({}, {})) is None


def test_sync_idle_is_the_gap_each_loss_read_returns_into():
    # two steps: a bubble inside step 0, the gap after each step; on
    # chip 0 the first read returns (host clock) after the next step's
    # first op, with another bubble in between: the longest gap counts
    ops = {0: [(0, 1, "%a.1 = f()"), (1.1, 3, "%a.1 = f()"),
               (4, 4.2, "%a.1 = f()"), (4.25, 7, "%a.1 = f()")],
           1: [(0, 3, "%a.1 = f()"), (3.5, 7, "%a.1 = f()")]}
    host = [(0.2, 4.3, "lags/host/loss_sync"),
            (4.4, 7.1, "lags/host/loss_sync"), (0, 8, "lags/step")]
    t = _trace(ops, {"a.1": APPLY}, window=(0, 8), steps=2, host=host)
    # chip 0: gaps (3, 4) and (7, 8); chip 1: (3, 3.5) and (7, 8)
    assert phases.sync_idle_per_step(t) == pytest.approx(
        1e3 * ((1 + 1) + (0.5 + 1)) / 2 / 2)
    idle_ms = 1e3 * (t.window_s - t.busy_s) / t.steps
    assert phases.sync_idle_per_step(t) <= idle_ms
    no_sync = _trace(ops, {"a.1": APPLY}, window=(0, 8), steps=2)
    assert phases.sync_idle_per_step(no_sync) is None


def _recorded(stem, steps):
    with open(TESTDATA / f"{stem}_op_names.json") as f:
        names = json.load(f)
    return xplane.read(str(TESTDATA / f"{stem}.xplane.pb"), [0],
                       "bench/session_run", steps=steps, op_names=names)


def test_recorded_trace_without_scopes_reads_as_none():
    t = _recorded("tiny", 3)
    assert t.busy_s > 0
    assert phases.per_step(t) is None


def test_recorded_scoped_step_attributes_its_phases():
    t = _recorded("tiny_phases", 3)
    ms = phases.per_step(t)
    for k in ("fwd", "bwd", "apply", "exchange", "select"):
        assert ms[k] > 0, k
    assert ms["select"] + ms["scatter_mean"] <= ms["exchange"]
    total = sum(ms[k] for k in names.STEP_PHASES + (phases.UNATTRIBUTED,))
    assert total == pytest.approx(_busy_ms(t), rel=1e-6)
    idle_ms = 1e3 * (t.window_s - t.busy_s) / t.steps
    assert 0 < phases.sync_idle_per_step(t) <= idle_ms
