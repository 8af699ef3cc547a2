"""The trace reduction: interval arithmetic, and the whole reduction on
a small trace recorded on a TPU v5e (``testdata/make_fixture.py``)."""
import json

import pytest

from lagsbench import xplane
from lagsbench.spec import BENCH_DIR

FIXTURE = BENCH_DIR / "testdata" / "tiny.xplane.pb"


def test_merge_and_measure():
    assert xplane.merge([(3, 4), (0, 2), (1, 2.5)]) == [[0, 2.5], [3, 4]]
    assert xplane.measure([(0, 2), (1, 3), (5, 6)]) == 4


def test_subtract_covers_partial_overlaps():
    a = [(0, 10)]
    b = [(1, 2), (4, 6), (9, 12)]
    assert xplane.subtract(a, b) == pytest.approx(10 - 1 - 2 - 1)
    assert xplane.subtract([(0, 1), (2, 3)], []) == 2
    assert xplane.subtract([(0, 1)], [(0, 1)]) == 0


def test_leaves_and_self_times_take_out_nested_ops():
    events = [(0, 10, "%while.1 = while(...)"), (1, 4, "%fusion.2 = f()"),
              (5, 9, "%all-gather-done.3 = g()"), (11, 12, "%copy.4 = c()")]
    names = [n for _, _, n in xplane.leaves(events)]
    assert names == ["%fusion.2 = f()", "%all-gather-done.3 = g()",
                     "%copy.4 = c()"]
    selft = {n: st for _, _, n, st in xplane.self_times(events)}
    assert selft["%while.1 = while(...)"] == 10 - 3 - 4
    assert xplane.instruction("%all-gather-done.3 = g()") == \
        "all-gather-done.3"
    assert xplane.opcode("all-gather-done.3") == "all-gather-done"


def test_reduced_busy_idle_and_exposed_collectives():
    ops = {0: [(0.0, 1.0, "%fusion.1 = x"), (1.5, 2.0, "%all-reduce.2 = y"),
               (2.0, 3.0, "%fusion.3 = z")],
           1: [(0.0, 2.0, "%fusion.1 = x"), (2.0, 2.5, "%all-reduce.2 = y")]}
    host = [(0.0, 4.0, "bench/session_run"), (1.0, 1.5, "np.asarray")]
    r = xplane.Reduced(ops, host, (0.0, 4.0), steps=2)
    assert r.window_s == 4.0
    assert r.busy_s == pytest.approx(2.5)
    assert r.collective_exposed_s() == pytest.approx((0.5 + 0.5) / 2)
    gaps = r.idle_gaps(0)
    assert [(s, e) for s, e, _ in gaps] == [(1.0, 1.5), (3.0, 4.0)]
    assert gaps[0][2] == "np.asarray"
    assert gaps[1][2] == "bench/session_run"
    bd = r.breakdown()
    assert bd["idle_gaps"][0] == ["bench/session_run", 1.0]
    assert len(bd["device_ops"]) <= 10


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_trace_reduces_to_steps_and_idle_share():
    with open(BENCH_DIR / "testdata" / "tiny_op_names.json") as f:
        names = json.load(f)
    r = xplane.read(str(FIXTURE), [0], "bench/session_run", steps=3,
                    op_names=names)
    assert list(r.device_ops) == [0]
    assert len(r.device_ops[0]) > 100
    assert 0.0 < r.busy_s < r.window_s
    assert r.collective_exposed_s() == 0.0       # one chip: no collectives
    bd = r.breakdown()
    assert 1 <= len(bd["device_ops"]) <= 10
    assert 1 <= len(bd["idle_gaps"]) <= 10
    known = sum(v for k, v in bd["device_ops"] if k.startswith("jit(step)"))
    assert known > 0.5 * sum(v for _, v in bd["device_ops"])
