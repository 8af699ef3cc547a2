"""Train-and-serve: a serving fleet following a live run at delta bandwidth.

One process plays both ends of the ``repro.stream`` pipeline:

  1. **train** — ``api.Session.run`` with a ``StreamPublisher`` attached:
     every ``--every`` steps the publisher cuts a versioned sparse-delta
     packet (LAGS top-k + error feedback on ``params_now -
     params_published``, per-leaf budget split) into ``--out``, at
     ``--budget-frac`` of full-checkpoint bytes per publish.
  2. **serve** — a cold ``ServeSession`` bootstraps from the full
     baseline packet and follows every delta through the production
     prefill/decode path, each candidate update scored by a
     ``RolloutGuard`` (held-out NLL change-point detector) BEFORE it is
     committed.
  3. **verify** — after the publisher's final flush the subscriber must
     be bitwise-identical to the trained params; then it generates a few
     tokens from the streamed weights.

Because train, publish, guard and serve all report into the process-wide
metrics plane, the final ``--out``/metrics_snapshot artifact covers all
four subsystems in one export — CI validates it with
``python -m repro.observe.check``.

  PYTHONPATH=src python examples/train_and_serve.py --steps 20
  PYTHONPATH=src python examples/train_and_serve.py --steps 2   # CI smoke
"""
import argparse
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.configs import base
from repro.data import synthetic
from repro.launch import compile_cache
from repro.launch import mesh as M
from repro.stream import (DeltaCodec, RolloutGuard, ServeSession,
                          StreamPublisher, quality_probe)

TINY = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
            d_ff=128, vocab=64)


def main():
    compile_cache.place()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=0.25)
    ap.add_argument("--every", type=int, default=2,
                    help="publish cadence in train steps")
    ap.add_argument("--budget-frac", type=float, default=0.1,
                    help="per-publish byte budget as a fraction of one "
                         "full checkpoint")
    ap.add_argument("--gen", type=int, default=8,
                    help="tokens to generate from the streamed weights")
    ap.add_argument("--out", default="artifacts/stream")
    args = ap.parse_args()

    cfg = dataclasses.replace(
        base.get_smoke_config("tinyllama_1_1b"), **TINY,
        dtype="float32", param_dtype="float32",
        train_mode="lags_dp", compression_ratio=8.0)
    mesh = M.make_host_mesh(data=1, model=1)
    data = synthetic.MarkovLM(vocab=cfg.vocab, seed=11)
    chunk = min(16, args.seq)

    # -- train side: Session.run with the publisher attached ----------------
    # health_every=1: the snapshot carries the convergence-health plane
    # (online per-leaf delta + EF energy), alongside the stream codec's
    # residual gauges the publisher emits — CI gates both with
    # ``observe.check --require-health``
    sess = api.Session(
        cfg, api.RunConfig(mode="lags_dp", ratio=8.0, lr=args.lr,
                           chunk=chunk, loss_chunk=chunk, donate=False,
                           health_every=1),
        mesh=mesh)
    state, _ = sess.init_state()
    full_bytes = DeltaCodec(state["params"]).full_bytes
    pkt_dir = os.path.join(args.out, "packets")
    os.makedirs(pkt_dir, exist_ok=True)
    pub = StreamPublisher(
        state["params"], every=args.every,
        budget_bytes=max(64, int(full_bytes * args.budget_frac)),
        out_dir=pkt_dir)
    print(f"train: {args.steps} steps, publishing every {args.every} at "
          f"{pub.budget_bytes}B/packet (full checkpoint {full_bytes}B)",
          flush=True)
    state, _ = sess.run(
        lambda t: data.batch(t, args.global_batch, args.seq),
        args.steps, state=state, publisher=pub,
        log_every=max(1, args.steps // 4))
    pub.flush(args.steps, state["params"])    # drain the EF residual

    # -- serve side: cold subscriber follows the packet files ---------------
    holdout = data.batch(10_000, 2, args.seq)
    guard = RolloutGuard(quality_probe(cfg, holdout, chunk=chunk,
                                       loss_chunk=chunk))
    zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype),
                         state["params"])
    sub = ServeSession(cfg, base.InputShape("serve", args.seq, 2, "decode"),
                       zeros, mesh=mesh, chunk=chunk, guard=guard)
    for path in pub.packet_paths:
        status = sub.apply_packet_file(path)
        row = sub.log[-1]
        print(f"serve: v{row['version']:<3d} {row['kind']:<5s} "
              f"{row['nbytes']:>8d}B  {status}  "
              f"nll={guard.last_nll:.4f}", flush=True)
        if status != "applied":
            raise SystemExit(f"stream broke at {path}: {status}")

    same = all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(sub.params),
                               jax.tree.leaves(state["params"])))
    ratio = pub.bytes_streamed / max(pub.bytes_full_equiv, 1)
    print(f"stream: {pub.n_publishes} packets, {pub.bytes_streamed}B vs "
          f"{pub.bytes_full_equiv}B full-checkpoint equivalent "
          f"({100 * ratio:.1f}%) | post-flush bitwise match: {same}")
    if not same:
        raise SystemExit("subscriber diverged from trained params")

    prompts = data.batch(7, 2, 8)["tokens"]
    toks = sub.generate(prompts, args.gen)
    print(f"generate: {toks.shape[1]} tokens from streamed v{sub.version} "
          f"weights -> {np.asarray(toks).tolist()}")
    rec = sub.requests[-1]
    print(f"request: prefill {rec.prefill_s * 1e3:.1f}ms "
          f"({rec.prefill_jit})  decode {rec.decode_tok_s:.1f} tok/s "
          f"({rec.decode_jit})  v{rec.version} cache={rec.cache}")

    # one snapshot over the whole round trip: train + stream + serve
    from repro.observe import metrics as OM
    snap = OM.save_snapshot(
        os.path.join(args.out, "metrics_snapshot"),
        meta={"example": "train_and_serve", "n_steps": int(args.steps)})
    print(f"metrics: snapshot -> {snap}")


if __name__ == "__main__":
    main()
