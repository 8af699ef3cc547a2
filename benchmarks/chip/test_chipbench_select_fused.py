"""The ``select_fused_share`` reader: on hand-built traces, and on the
recorded trace of a step whose select ran as XLA's own passes
(``testdata/make_phases_fixture.py``)."""
import json
import types

import pytest

from lagsbench import xplane
from lagsbench.reference import load_module
from lagsbench.spec import BENCH_DIR

READER = load_module(BENCH_DIR / "metrics" / "select_fused_share.py")
TESTDATA = BENCH_DIR / "testdata"

SELECT = "jit(step)/lags/exchange/lags/select/l0/{}"
FWD = "jit(step)/lags/fwd/jvp()/dot_general"
KERNEL = ('%ef_select_pack_pallas.{0} = (f32[8,5]{{1,0}}, s32[8,5]{{1,0}}, '
          'f32[8,4096]{{1,0}}) custom-call(f32[8,4096]{{1,0}} %p.1, '
          'f32[8,4096]{{1,0}} %p.2), custom_call_target="tpu_custom_call"')


def _read(ops, op_names, steps=1):
    trace = xplane.Reduced(ops, [], (0.0, 10.0), steps, op_names)
    return READER.read(types.SimpleNamespace(trace=trace))


def test_select_of_plain_fusions_reads_zero():
    ops = {0: [(0, 1, "%fusion.1 = f()"), (1, 3, "%fusion.2 = f()"),
               (3, 4, "%dot.3 = f()")]}
    share = _read(ops, {"fusion.1": SELECT.format("argmax"),
                        "fusion.2": SELECT.format("select_n"),
                        "dot.3": FWD})
    assert share == 0.0


def test_kernel_share_of_the_select_phase_over_chips():
    # chip 0: kernel 3 of select 4 (the fusion's operand-less copy takes
    # the kernel's phase as its consumer); chip 1: kernel 1 of select 4
    ops = {0: [(0, 1, "%copy.1 = f32[8] copy()"),
               (1, 4, KERNEL.format(7) + " %copy.1"),
               (4, 6, "%dot.3 = f()")],
           1: [(0, 3, "%fusion.2 = f()"), (3, 4, KERNEL.format(7))]}
    names = {"ef_select_pack_pallas.7": SELECT.format("ef_select_pack"),
             "fusion.2": SELECT.format("argmax"), "dot.3": FWD}
    assert _read(ops, names, steps=2) == pytest.approx(100.0 * 4 / 8)


def test_no_select_phase_reads_none():
    ops = {0: [(0, 1, "%dot.3 = f()"), (1, 2, KERNEL.format(1))]}
    assert _read(ops, {"dot.3": FWD,
                       "ef_select_pack_pallas.1": FWD}) is None
    assert _read({0: [(0, 1, "%dot.3 = f()")]},
                 {"dot.3": "jit(step)/jvp()/dot_general"}) is None


def test_recorded_step_with_xla_select_reads_zero():
    with open(TESTDATA / "tiny_phases_op_names.json") as f:
        names = json.load(f)
    trace = xplane.read(str(TESTDATA / "tiny_phases.xplane.pb"), [0],
                        "bench/session_run", steps=3, op_names=names)
    assert READER.read(types.SimpleNamespace(trace=trace)) == 0.0
