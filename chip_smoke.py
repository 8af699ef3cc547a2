"""Smoke run of LAGS-SGD training on TPU at full TinyLlama-1.1B size.

    python chip_smoke.py              # one chip: phases a-d
    python chip_smoke.py --chips 4    # four chips: sparse all-gather vs psum

A smoke run, not a benchmark: it drives the training path a user calls
(``api.Session(...).run(...)`` -> ``launch.train.build_train_step`` ->
the registered exchange) at the published width and depth of
TinyLlama-1.1B (``configs/tinyllama_1_1b.py``, bf16 params, random
weights from a seed), and checks the results by the repo's own means.
Every check is fatal.  Every step of a phase trains on the same global
batch of ``synthetic.MarkovLM`` tokens, so the loss measures fitting and
a working optimizer lowers it within a few steps (fresh batches of a
32000-state chain barely move it that soon).

One chip (mesh data=1 x model=1, seq 2048, global batch 4):
  a. ``lags_dp`` c=1000, XLA selection (the default path)
  b. the same with the Pallas kernels; the step's HLO must hold
     ``tpu_custom_call`` (kernels compiled by Mosaic, not interpreted)
  c. ``dense`` S-SGD; its loss must fall
  d. a 2-layer full-width model: ``lags_dp`` c=1 (k = d) against dense
Losses of a and b agree (equal at step 0), and d agrees with dense.

Four chips (mesh data=4 x model=1, batch 4 per chip): ``lags_dp`` c=1000
with ``pipeline="off"`` and ``"wave"`` (equal losses), ``dense``, and the
c=1 check; params must span all four devices, the EF residual's worker
axis must be sharded over ``data``, and the compiled steps must hold an
all-gather (lags) or all-reduce (dense) over four replicas.

Exits non-zero, printing no result, where JAX finds no TPU.  The last
line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "tinyllama_1_1b"
SEQ = 2048
BATCH_PER_CHIP = 4
STEPS = 5
EQUIV_LAYERS = 2
EQUIV_STEPS = 3
RATIO = 1000.0
LR = 0.1
RTOL = 1e-3


def say(msg: str):
    print(f"[smoke] {msg}", flush=True)


def check(cond: bool, msg: str):
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


def rel_gap(x: float, y: float) -> float:
    return abs(x - y) / max(abs(y), 1e-30)


def make_batch(vocab: int, batch: int, seq: int, seed: int, mesh):
    """One global batch of MarkovLM tokens, sharded over ``data``.

    The chain's (vocab, vocab) transition matrix is generated on the host
    CPU backend, so the device's peak memory counts only training."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data import synthetic
    data = synthetic.MarkovLM(vocab=vocab, seed=seed)
    sample = jax.jit(data.sample, static_argnums=(1, 2))
    with jax.default_device(jax.devices("cpu")[0]):
        toks = np.asarray(sample(jax.random.PRNGKey(seed), batch, seq + 1))
    sh = NamedSharding(mesh, P("data", None))
    return {"tokens": jax.device_put(toks[:, :-1], sh),
            "labels": jax.device_put(toks[:, 1:], sh)}


def peak_bytes(devices) -> list:
    """Per-device ``peak_bytes_in_use`` (process peak so far), or None."""
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


@dataclasses.dataclass
class Phase:
    name: str
    losses: list
    step_s: list
    compile_cold_s: float
    compile_warm_s: float
    hlo: str


def run_phase(name: str, cfg, run, mesh, batch, steps: int, *,
              seed: int = 0, inspect=None) -> Phase:
    """Build, compile and train ``steps`` steps on ``batch`` via
    Session.run.

    Compile seconds (lower + compile of the step program): cold is the
    first in this process, warm a second after ``jax.clear_caches()``,
    so it is served by the persistent compilation cache.  A cold number
    close to the warm one means an earlier run had filled that cache.
    ``inspect(state)`` runs on the trained state before it is dropped."""
    import jax

    from repro import api, compat
    sess = api.Session(cfg, run, mesh=mesh)
    step, _, _ = sess.train_step()
    state, _ = sess.init_state(seed=seed)
    with compat.set_mesh(mesh):
        t0 = time.perf_counter()
        compiled = step.lower(state, batch).compile()
        cold = time.perf_counter() - t0
        jax.clear_caches()
        t0 = time.perf_counter()
        step.lower(state, batch).compile()
        warm = time.perf_counter() - t0
    state, hist = sess.run(lambda t: batch, steps, state=state,
                           log_every=0)
    if inspect is not None:
        inspect(state)
    del state
    ph = Phase(name, [h["loss"] for h in hist], [h["step_s"] for h in hist],
               cold, warm, compiled.as_text())
    peaks = peak_bytes(mesh.devices.flat)
    say(f"{name}: compile cold {ph.compile_cold_s:.2f} s warm "
        f"{ph.compile_warm_s:.2f} s | losses "
        f"{[round(x, 6) for x in ph.losses]} | step s "
        f"{[round(x, 4) for x in ph.step_s]} | peak_bytes_in_use "
        f"(process so far) {peaks}")
    check(all(math.isfinite(x) for x in ph.losses),
          f"{name}: non-finite loss {ph.losses}")
    return ph


def collective_group_sizes(hlo: str, op: str) -> set:
    """Replica-group sizes of every ``op`` (e.g. 'all-gather') in HLO."""
    sizes = set()
    for line in hlo.splitlines():
        if not re.search(rf"\b{op}(-start)?\(", line):
            continue
        m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
        if m:
            sizes.add(len(m.group(1).split(",")))
            continue
        m = re.search(r"replica_groups=\[\d+,(\d+)\]<=", line)
        if m:
            sizes.add(int(m.group(1)))
    return sizes


def check_agree(name: str, got: list, ref: list, *, exact_first: bool):
    check(len(got) == len(ref), f"{name}: {len(got)} vs {len(ref)} steps")
    if exact_first:
        check(got[0] == ref[0], f"{name}: step-0 loss {got[0]} != {ref[0]}")
    gaps = [rel_gap(x, y) for x, y in zip(got, ref)]
    say(f"{name}: max relative loss gap {max(gaps):.3e} (bound {RTOL})")
    check(max(gaps) <= RTOL, f"{name}: losses {got} vs {ref}")


def equivalence(cfg, mesh, batch, lr: float) -> None:
    """lags_dp at c=1 keeps every element (k = d): its losses are dense's."""
    from repro import api
    cfg2 = dataclasses.replace(cfg, n_layers=EQUIV_LAYERS)
    d = run_phase(f"d {EQUIV_LAYERS}-layer lags_dp c=1", cfg2,
                  api.RunConfig(mode="lags_dp", ratio=1.0, lr=lr), mesh,
                  batch, EQUIV_STEPS)
    ref = run_phase(f"d {EQUIV_LAYERS}-layer dense", cfg2,
                    api.RunConfig(mode="dense", lr=lr), mesh, batch,
                    EQUIV_STEPS)
    # not bitwise at step 0: the two step programs differ beyond the
    # exchange, and XLA:TPU may fuse their forward passes differently
    check_agree("d c=1 vs dense", d.losses, ref.losses, exact_first=False)


def one_chip(cfg, mesh, batch, lr: float) -> None:
    from repro import api
    a = run_phase("a lags_dp c=1000 xla", cfg,
                  api.RunConfig(mode="lags_dp", ratio=RATIO, lr=lr), mesh,
                  batch, STEPS)
    b = run_phase("b lags_dp c=1000 kernel", cfg,
                  api.RunConfig(mode="lags_dp", ratio=RATIO, lr=lr,
                                selection_backend="kernel"), mesh, batch,
                  STEPS)
    check("tpu_custom_call" in b.hlo,
          "b: no tpu_custom_call in the kernel step's HLO")
    c = run_phase("c dense", cfg, api.RunConfig(mode="dense", lr=lr), mesh,
                  batch, STEPS)
    check(c.losses[-1] < c.losses[0], f"c: dense loss did not fall {c.losses}")
    check_agree("a vs b", b.losses, a.losses, exact_first=True)
    equivalence(cfg, mesh, batch, lr)


def four_chips(cfg, mesh, batch, lr: float) -> None:
    import jax

    from repro import api
    n = mesh.devices.size

    def placed(state):
        for x in jax.tree.leaves(state["params"]):
            check(len(x.sharding.device_set) == n,
                  f"param leaf on {len(x.sharding.device_set)} devices")
        for e in jax.tree.leaves(state["ef"]):
            check(e.sharding.spec[0] == "data",
                  f"EF worker axis spec {e.sharding.spec}")
            devs = {s.device for s in e.addressable_shards}
            check(len(devs) == n and all(s.data.shape[0] == 1
                                         for s in e.addressable_shards),
                  "EF residual not one worker row per device")
        say(f"placement: every param leaf spans {n} devices; EF worker "
            f"axis sharded over data, one row per device")

    off = run_phase("lags_dp c=1000 pipeline=off", cfg,
                    api.RunConfig(mode="lags_dp", ratio=RATIO, lr=lr), mesh,
                    batch, STEPS, inspect=placed)
    check(collective_group_sizes(off.hlo, "all-gather") == {n},
          f"lags step all-gather groups "
          f"{collective_group_sizes(off.hlo, 'all-gather')}")
    wave = run_phase("lags_dp c=1000 pipeline=wave", cfg,
                     api.RunConfig(mode="lags_dp", ratio=RATIO, lr=lr,
                                   pipeline="wave"), mesh, batch, STEPS)
    check(wave.losses == off.losses,
          f"wave losses {wave.losses} != off losses {off.losses}")
    dense = run_phase("dense", cfg, api.RunConfig(mode="dense", lr=lr),
                      mesh, batch, STEPS)
    check(n in collective_group_sizes(dense.hlo, "all-reduce"),
          f"dense step all-reduce groups "
          f"{collective_group_sizes(dense.hlo, 'all-reduce')}")
    check(dense.losses[-1] < dense.losses[0],
          f"dense loss did not fall {dense.losses}")
    equivalence(cfg, mesh, batch, lr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}; no CPU fallback", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.launch import compile_cache
    cache_dir = compile_cache.place()
    from repro import compat
    from repro.configs import base

    kind = devices[0].device_kind
    say(f"smoke run, not a benchmark | jax {jax.__version__} | "
        f"device_kind {kind} | chips {args.chips} | compile cache "
        f"{cache_dir}")
    cfg = base.get_config(ARCH)
    mesh = compat.make_mesh((args.chips, 1), ("data", "model"),
                            devices=devices[:args.chips])
    batch = BATCH_PER_CHIP * args.chips
    say(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.param_dtype} params, seq {SEQ}, global batch {batch}, "
        f"{STEPS} steps per phase, lr {LR}")
    t_start = t0 = time.perf_counter()
    tokens = make_batch(cfg.vocab, batch, SEQ, args.seed, mesh)
    say(f"data: MarkovLM batch made in {time.perf_counter() - t0:.2f} s")
    if args.chips == 1:
        one_chip(cfg, mesh, tokens, LR)
    else:
        four_chips(cfg, mesh, tokens, LR)
    say(f"all checks passed in {time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
