"""Eq. 18 in practice: pick per-layer compression ratios for llama3-8b from
the communication-to-computation ratio, on two networks (the paper's 1 Gbps
Ethernet and TPU v5e ICI), then bucket the resulting sparse messages (§5).

With ``--schedule PATH`` the ratios come from a measured-profile autotune
``Schedule`` (produced by ``python -m benchmarks.bench_autotune`` or saved
here with ``--save-schedule``) instead of the static α–β constants; when
the file is missing the example falls back to the static selection below.

  PYTHONPATH=src python examples/adaptive_ratios.py
  PYTHONPATH=src python examples/adaptive_ratios.py --save-schedule s.json
  PYTHONPATH=src python examples/adaptive_ratios.py --schedule s.json
"""
import argparse
import os

from repro.autotune import planner, profiler
from repro.autotune.schedule import Schedule
from repro.configs import base
from repro.core import adaptive, bucketing, comm_model as cm
from repro.launch import compile_cache


def profile_layers(arch: str, seq_tokens: int = 4096 * 8):
    """Backprop-ordered per-leaf samples for an arch (``LeafSample`` has
    the name/d/backward_flops fields both ``adaptive.choose_ratios`` and
    ``planner.plan_schedule`` read)."""
    cfg = base.get_config(arch)
    return cfg, profiler.backprop_leaves(cfg, seq_tokens)


def report(cfg, layers, ratios: dict, tag: str):
    ks = [max(1, int(l.d / ratios[l.name])) for l in layers]
    buckets = bucketing.assign_buckets(ks, target_bytes=1 << 20)
    stats = bucketing.bucket_stats(buckets)
    dense_bytes = 4 * sum(l.d for l in layers)
    # sparse leaves ship (value, index) pairs; dense-planned leaves (c<=1)
    # go over the 4-byte/elem all-reduce, not the sparse exchange
    sparse_bytes = sum(8 * k if ratios[l.name] > 1.0 else 4 * l.d
                       for l, k in zip(layers, ks))
    print(f"\n--- {tag} ---")
    shown = 0
    for l in layers:
        if shown < 6 and l.d > 1e6:
            print(f"  {l.name[:60]:60s} d={l.d / 1e6:7.1f}M "
                  f"c={ratios[l.name]:6.0f}")
            shown += 1
    print(f"  traffic: dense {dense_bytes / 1e9:.2f} GB -> sparse "
          f"{sparse_bytes / 1e6:.1f} MB "
          f"({dense_bytes / sparse_bytes:.0f}x reduction)")
    print(f"  buckets: {stats['n_buckets']} "
          f"(mean {stats['mean_bytes'] / 1e6:.2f} MB)")


def main(argv=None):
    compile_cache.place()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_8b")
    ap.add_argument("--schedule", default=None,
                    help="autotuned Schedule JSON; falls back to the "
                         "static Eq. 18 selection if absent")
    ap.add_argument("--save-schedule", default=None,
                    help="plan with the analytic profile and save here")
    args = ap.parse_args(argv)

    cfg, layers = profile_layers(args.arch)
    print(f"{cfg.name}: {len(layers)} learnable tensors, "
          f"{sum(l.d for l in layers) / 1e9:.2f}B params")

    if args.save_schedule:
        sched = planner.plan_schedule(layers, p=256, hw=cm.TPU_V5E_ICI,
                                      arch=cfg.name, shape="train_4k")
        sched.save(args.save_schedule)
        print(f"wrote analytic schedule to {args.save_schedule}")

    if args.schedule and os.path.exists(args.schedule):
        sched = Schedule.load(args.schedule)
        sched.validate_sizes({l.name: l.d for l in layers})
        ratios = {lp.name: lp.ratio for lp in sched.leaves}
        report(cfg, layers, ratios,
               f"autotuned: {sched.hardware['name']} (P={sched.n_workers})")
        return
    if args.schedule:
        print(f"(schedule {args.schedule!r} not found — "
              f"falling back to static Eq. 18 ratios)")

    for hw, p in ((cm.ETH_1GBPS, 16), (cm.TPU_V5E_ICI, 256)):
        ratios = adaptive.choose_ratios(layers, p=p, hw=hw)
        report(cfg, layers, ratios, f"{hw.name} (P={p})")


if __name__ == "__main__":
    main()
