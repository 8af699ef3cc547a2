"""End-to-end distributed training driver.

Trains a ~100M-parameter llama-family model with LAGS-SGD on a multi-device
host mesh (data x model), using the SAME production path as the dry-run:
``repro.api.Session`` over the partial-auto shard_map step (block-LAGS
sparse exchange with error feedback), synthetic Markov-LM data, periodic
checkpointing and a JSONL metrics log — the whole loop is one
``Session.run`` call.

  PYTHONPATH=src python examples/train_e2e.py --steps 300          # ~100M
  PYTHONPATH=src python examples/train_e2e.py --preset small --steps 50
  # online schedule re-planning (repro.runtime) every 50 steps:
  PYTHONPATH=src python examples/train_e2e.py --steps 300 --replan-every 50
  # wave-pipelined exchange (repro.pipeline): per-bucket collectives
  # launched inside backprop, bitwise-identical losses to --pipeline off:
  PYTHONPATH=src python examples/train_e2e.py --steps 300 --pipeline wave
  # evidence-driven re-planning: a step-time anomaly (repro.observe)
  # re-plans immediately instead of waiting for the cadence boundary:
  PYTHONPATH=src python examples/train_e2e.py --steps 300 \
      --replan-every 100 --replan-on-anomaly
  # hierarchical mode on a 2-pod mesh consuming a planned two-tier schedule:
  PYTHONPATH=src python examples/train_e2e.py --method lags_hier \
      --pod 2 --data-par 2 --hier-schedule artifacts/runtime/..._t2_....json
  # two-level SPARSE hierarchy (sparse intra-pod + cross-pod exchange);
  # the schedule's inner tier budgets the ICI exchange, or use
  # --ratio-inner for a scalar inner budget without a schedule:
  PYTHONPATH=src python examples/train_e2e.py --method lags_hier2 \
      --pod 2 --data-par 2 --hier-schedule artifacts/runtime/hier2_schedule.json

NOTE: sets XLA_FLAGS before importing jax to get an 8-device host platform.
"""
import os

if "--help" not in __import__("sys").argv:
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import argparse
import dataclasses

import jax

from repro import api
from repro.configs import base
from repro.data import synthetic
from repro.launch import compile_cache
from repro.launch import mesh as M


PRESETS = {
    # ~103M params: 12 x (GQA 768 + SwiGLU 2048) + 16k vocab tied embed
    "100m": dict(n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
                 d_ff=2048, vocab=16384, head_dim=64),
    # ~4M params: CI-speed
    "small": dict(n_layers=4, d_model=128, n_heads=4, n_kv_heads=2,
                  d_ff=512, vocab=2048, head_dim=32),
    # unit-test scale, leaf-for-leaf the config benchmarks.bench_runtime
    # drives — its saved hier2_schedule.json ingests directly here
    "tiny": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                 d_ff=128, vocab=64),
}


def main():
    compile_cache.place()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="100m", choices=sorted(PRESETS))
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=0.25)
    ap.add_argument("--ratio", type=float, default=100.0)
    ap.add_argument("--method", default="lags_dp",
                    choices=["lags_dp", "lags_hier", "lags_hier2", "dense"])
    ap.add_argument("--pipeline", default="off",
                    choices=["off", "wave", "async1"],
                    help="wave-pipelined exchange (repro.pipeline): "
                         "'wave' launches each bucket's exchange inside "
                         "backprop (bitwise-identical to 'off'); 'async1' "
                         "double-buffers with one-step staleness")
    ap.add_argument("--ratio-inner", type=float, default=None,
                    help="intra-pod tier compression for --method "
                         "lags_hier2 (default: dense inner tier; a "
                         "--hier-schedule's inner tier wins over this)")
    ap.add_argument("--data-par", type=int, default=4)
    ap.add_argument("--model-par", type=int, default=2)
    ap.add_argument("--pod", type=int, default=1,
                    help="pod axis size (>1 gives lags_hier a real "
                         "cross-pod exchange; pod*data*model must not "
                         "exceed the 8 host devices)")
    ap.add_argument("--out", default="artifacts/train_e2e")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--replan-every", type=int, default=0,
                    help="re-plan the LAGS schedule online every N steps "
                         "(0 = static; see repro.runtime)")
    ap.add_argument("--replan-on-anomaly", action="store_true",
                    help="also re-plan when the repro.observe step-time "
                         "anomaly detector fires (needs --replan-every>0 "
                         "for the cadence fallback it composes with)")
    ap.add_argument("--swap-threshold", type=float, default=0.05,
                    help="min predicted relative improvement before an "
                         "online re-plan swaps the schedule")
    ap.add_argument("--fence-every", type=int, default=8,
                    help="telemetry fence cadence (block_until_ready "
                         "every N steps); short CI runs need 1 so the "
                         "trigger window fills before the run ends")
    ap.add_argument("--hier-schedule", default=None,
                    help="two-tier HierSchedule JSON for --method "
                         "lags_hier (from bench_runtime or the planner)")
    ap.add_argument("--health-every", type=int, default=0,
                    help="convergence-health cadence (repro.observe."
                         "health): compute + emit the online per-leaf "
                         "Assumption-1 delta / EF energy / staleness "
                         "every N steps (0 = off)")
    ap.add_argument("--health-threshold", type=float, default=2.0,
                    help="absolute delta_max above which the health "
                         "monitor raises a health_alarm (and, with "
                         "--replan-every, a HealthTrigger re-plan)")
    args = ap.parse_args()

    cfg = dataclasses.replace(
        base.get_smoke_config("tinyllama_1_1b"), **PRESETS[args.preset],
        dtype="float32", param_dtype="float32",
        train_mode=args.method, compression_ratio=args.ratio)
    mesh = M.make_host_mesh(data=args.data_par, model=args.model_par,
                            pod=args.pod)
    data = synthetic.MarkovLM(vocab=cfg.vocab, seed=11)

    schedule = None
    if args.hier_schedule:
        from repro.autotune import schedule as SCH
        schedule = SCH.load_any(args.hier_schedule)

    sess = api.Session(
        cfg,
        api.RunConfig(mode=args.method, ratio=args.ratio,
                      ratio_inner=args.ratio_inner, lr=args.lr,
                      schedule=schedule, pipeline=args.pipeline,
                      chunk=min(1024, args.seq),
                      loss_chunk=min(512, args.seq), donate=False,
                      health_every=args.health_every),
        mesh=mesh)
    monitor = None
    if args.health_every > 0:
        from repro.observe import health as OH
        monitor = OH.HealthMonitor(threshold=args.health_threshold)
    controller = None
    if args.replan_every > 0:
        from repro.observe import triggers as TG
        from repro.runtime import RuntimeConfig
        trig = [TG.CadenceTrigger(args.replan_every)]
        if args.replan_on_anomaly:
            trig.append(TG.AnomalyTrigger())
        if monitor is not None:
            trig.append(TG.HealthTrigger(monitor))
        controller = sess.controller(
            rcfg=RuntimeConfig(replan_every=args.replan_every,
                               swap_threshold=args.swap_threshold,
                               fence_every=args.fence_every),
            triggers=tuple(trig))

    state, _ = sess.init_state()
    # the controller owns its own (already-built) step; don't make the
    # session compile a second one just to read the meta
    meta = controller.meta if controller is not None else sess.meta
    n_params = sum(int(x.size) for x in jax.tree.leaves(state["params"]))
    print(f"arch={cfg.name} preset={args.preset}: {n_params / 1e6:.1f}M "
          f"params | mesh {mesh.devices.shape} {mesh.axis_names} | "
          f"mode={meta['mode']} workers={meta['n_workers']} "
          f"c={args.ratio} pipeline={args.pipeline}"
          + (f" waves={meta['waves'].n_waves}"
             if meta.get("waves") is not None else ""), flush=True)

    log_path = os.path.join(args.out, "metrics.jsonl")
    os.makedirs(args.out, exist_ok=True)
    _, history = sess.run(
        lambda t: data.batch(t, args.global_batch, args.seq),
        args.steps, controller=controller, state=state,
        log_path=log_path, log_every=10,
        ckpt_every=args.ckpt_every, out_dir=args.out,
        health_monitor=monitor)
    if controller is not None:
        swaps = sum(1 for e in controller.history if e.swapped)
        print(f"runtime: {len(controller.history)} re-plans, "
              f"{swaps} swaps (state saved for resume)")
    if args.health_every > 0:
        from repro.observe import metrics as OM
        snap = OM.save_snapshot(
            os.path.join(args.out, "metrics_snapshot"),
            meta={"example": "train_e2e", "n_steps": int(args.steps),
                  "health_every": int(args.health_every)})
        print(f"metrics: snapshot -> {snap} (gate with `python -m "
              f"repro.observe.check {snap} --require-health`)")
    print(f"done: {args.steps} steps, log at {log_path}")


if __name__ == "__main__":
    main()
