"""Record the small trace the reduction tests read (run on a TPU).

    python3 benchmarks/chip/testdata/make_fixture.py

Trains a two-layer, 64-wide LAGS model for three steps through the
harness's ``Program`` with the profiler on, and writes the trace
(``tiny.xplane.pb``) and the compiled step's op names
(``tiny_op_names.json``) beside this file.
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


def tiny_cell() -> dict:
    with open(os.path.join(BENCH, "configs", "mistral-7b.json")) as f:
        config = json.load(f)
    config.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
                  vocab_size=512)
    traffic = {"name": "tiny", "run": {"mode": "lags_dp", "ratio": 100.0,
                                       "lr": 1.0, "block_size": 64},
               "batch_per_chip": 1, "seq_len": 256, "tokens": "uniform",
               "pool_steps": 6, "check_steps": 1}
    return {"name": "tiny", "chips": 1, "config": config, "traffic": traffic,
            "limits": {}, "end_to_end": [], "per_layer": []}


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
    shutil.copy(path, os.path.join(HERE, "tiny.xplane.pb"))
    names = xplane.op_names_from_hlo(prog.compiled_text(batches[0]))
    with open(os.path.join(HERE, "tiny_op_names.json"), "w") as f:
        json.dump(names, f, indent=0, sort_keys=True)
    shutil.rmtree(cap.dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
