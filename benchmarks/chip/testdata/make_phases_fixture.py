"""Record the small trace of a step with phase scopes (run on a TPU).

    python3 benchmarks/chip/testdata/make_phases_fixture.py

The cell of ``make_fixture.py`` (a two-layer, 64-wide LAGS model) for
three steps through the harness's ``Program``, with the profiler on, on
a program whose step runs under the ``lags/<phase>`` scopes and whose
``Session.run`` writes the ``lags/host/...`` spans.  Writes the trace
(``tiny_phases.xplane.pb``) and the compiled step's op names
(``tiny_phases_op_names.json``) beside this file.
"""
import glob
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(BENCH)), "src"))

from make_fixture import tiny_cell  # noqa: E402


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from lagsbench import harness, xplane
    from repro.observe import metrics as OM
    devices = harness.devices_for(1, True)
    harness.use_compile_cache()
    prog = harness.Program(tiny_cell(), devices)
    reg = OM.MetricsRegistry()
    batches = prog.batches(7)
    state, _ = prog.first_steps(7, batches, reg)
    cap = xplane.Capture()
    cap.start()
    for t in range(3):
        state, _ = prog.step(state, batches[1 + t], reg)
    cap.stop()
    path = glob.glob(os.path.join(cap.dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    shutil.copy(path, os.path.join(HERE, "tiny_phases.xplane.pb"))
    names = xplane.op_names_from_hlo(prog.compiled_text(batches[0]))
    with open(os.path.join(HERE, "tiny_phases_op_names.json"), "w") as f:
        json.dump(names, f, indent=0, sort_keys=True)
    shutil.rmtree(cap.dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
