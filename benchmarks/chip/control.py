"""Readings that set a cell's limits: the program's numbers on many
seeds, and the control's and the planted faults' numbers on a few.

    python3 benchmarks/chip/control.py --workload <cell> \\
        --seeds 11,12,... --control-seeds 11,12,13 [--out file.jsonl]

One process builds the cell's program once; for every seed it runs the
program's first steps (the timed path, as a run does) and the float32
reference, and prints the compared numbers.  For each control
seed it also puts the reference in the program's place, and reads the
same numbers, the configuration's own among them (``check.numbers``):

- ``control``: the reference computed in float8 (e4m3, one scale per
  tensor), the precision below the configuration's bfloat16;
- ``half_batch``: the reference with half of each worker's tokens left
  out of the loss;
- ``no_exchange`` (cells on several chips): the reference with each
  worker applying only its own payload.

A step that returns its state unchanged reads 1 on both gradient and
change numbers by their definition and needs no run.  Each row keeps
the per-leaf readings (``grad_norms``, ``change_norms``; the program's
row the reference's too, under ``ref``) and each step's ``history``
entry, so that other numbers can be worked out from them afterwards.
The benchmark's own runs never run this script.  Exits 2 without a TPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))


def variants(chips: int) -> list:
    out = [("control", "fp8", None), ("half_batch", "f32", "half_batch")]
    if chips > 1:
        out.append(("no_exchange", "f32", "no_exchange"))
    return out


def readings_for_seed(prog, seed: int, registry, with_control: bool) -> list:
    from lagsbench import check
    config = prog.cell["config"]
    batches = prog.batches(seed)
    k = int(prog.traffic["check_steps"])
    t = time.perf_counter()
    state, got = prog.first_steps(seed, batches, registry)
    del state
    gc.collect()
    t_prog = time.perf_counter() - t
    t = time.perf_counter()
    ref = prog.reference(seed, batches[:k])
    t_ref = time.perf_counter() - t
    rows = [{"seed": seed, "who": "program", "prog_s": t_prog,
             "ref_s": t_ref, **check.numbers(got, ref, config), **got,
             "ref": ref}]
    if with_control:
        for who, prec, fault in variants(prog.chips):
            t = time.perf_counter()
            alt = prog.reference(seed, batches[:k], prec=prec, fault=fault)
            rows.append({"seed": seed, "who": who,
                         "ref_s": time.perf_counter() - t,
                         **check.numbers(alt, ref, config), **alt})
    return rows


def _plain(x):
    """JSON for the arrays a reference's ``blocks`` readings hold."""
    if hasattr(x, "tolist"):
        return x.tolist()
    raise TypeError(f"{type(x).__name__} is not JSON serializable")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from lagsbench import harness, spec
    from repro.observe import metrics as OM
    cell = spec.load_cell(args.workload)
    try:
        devices = harness.devices_for(cell["chips"], True)
    except harness.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    prog = harness.Program(cell, devices)
    registry = OM.MetricsRegistry()
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    out = open(args.out, "a") if args.out else None
    try:
        for s in (int(x) for x in args.seeds.split(",")):
            for row in readings_for_seed(prog, s, registry, s in ctl):
                row["workload"] = args.workload
                line = json.dumps(row, default=_plain)
                print(line, flush=True)
                if out is not None:
                    out.write(line + "\n")
                    out.flush()
    finally:
        if out is not None:
            out.close()
    print(f"control.py: {time.perf_counter() - T_START:.1f} s",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
