"""One run of one cell: set-up, the first steps with their readings, the
timed window, the traced window, the reference, the result line.

The window drives the user's loop, ``api.Session(cfg, run, mesh).run``:
``launch.train.build_train_step``, the registered exchange and the SGD
apply, with the per-step loss sync.  Set-up builds one object, the
session with its compiled step and its state, drives it through the
first ``check_steps`` steps (which compile and warm it), and hands the
same state to the window.
"""
from __future__ import annotations

import gc
import math
import sys
import time

from lagsbench import check, feed, readings, reference, spec
from lagsbench import weights as W

#: Host spans the benchmark writes into the profiler's trace.
SPAN_STEP = "bench/session_run"
SPAN_DATA = "bench/data_fn"


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def use_compile_cache() -> str:
    """JAX's persistent compilation cache where the program puts it
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), for
    every program however short its compile."""
    import jax

    from repro.launch import compile_cache
    where = compile_cache.place()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def devices_for(chips: int, require_chip: bool) -> list:
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform!r} devices, no TPU; "
                     f"this benchmark never falls back to the CPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs[:chips]


class Program:
    """The system under test for one cell: the session, its compiled
    step and the layout of its parameters."""

    def __init__(self, cell: dict, devices: list):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro import api, compat
        self.cell = cell
        self.chips = cell["chips"]
        self.traffic = cell["traffic"]
        self.cfg = spec.model_config(cell["config"], cell.get("config_file"))
        self.run_cfg = api.RunConfig(**self.traffic["run"])
        self.mesh = compat.make_mesh((self.chips, 1), ("data", "model"),
                                     devices=devices)
        self.session = api.Session(self.cfg, self.run_cfg, mesh=self.mesh)
        _, self.specs, self.meta = self.session.train_step()
        self.lay = W.layout(self.specs["params"])
        self.replicated = NamedSharding(self.mesh, P())
        self.rows = NamedSharding(self.mesh, P("data", None))
        self.devices = devices

    def weights0(self, seed: int) -> dict:
        return W.make_named(seed, self.lay, self.replicated)

    def start(self, seed: int):
        """The train state at step 0: the seed's weights, zeros elsewhere."""
        import jax
        import jax.numpy as jnp
        lay, specs = self.lay, self.specs
        shardings = jax.tree.map(lambda s: s.sharding, specs)

        def build(named):
            state = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)
            state["params"] = W.to_program(named, lay, specs["params"])
            return state

        named = self.weights0(seed)
        state = jax.jit(build, out_shardings=shardings)(named)
        del named
        return state

    def batches(self, seed: int) -> list:
        return feed.make(self.traffic, self.cfg.vocab, self.chips, seed,
                         self.rows)

    def step(self, state, batch, registry):
        """One step through ``Session.run``; (state, its history entry)."""
        import jax
        with jax.profiler.TraceAnnotation(SPAN_STEP):
            state, hist = self.session.run(
                lambda t: _data(batch), 1, state=state, log_every=0,
                metrics=registry)
        return state, hist[0]

    def first_steps(self, seed: int, batches: list, registry):
        """Weights from the seed, then ``check_steps`` steps with the
        readings the reference is held to, each step's whole history
        entry among them.  Returns (state, readings)."""
        k = int(self.traffic["check_steps"])
        lr = float(self.traffic["run"]["lr"])
        state = self.start(seed)
        history, grads = [], None
        for t in range(k):
            state, row = self.step(state, batches[t], registry)
            history.append(row)
            if t == 0:
                grads = readings.grad_norms(state, self.weights0(seed),
                                            self.lay, lr)
        changes = readings.change_norms(state, self.weights0(seed), self.lay)
        return state, {"losses": [row["loss"] for row in history],
                       "grad_norms": grads, "change_norms": changes,
                       "history": history}

    def reference(self, seed: int, batches: list, prec: str = "f32",
                  fault: str | None = None) -> dict:
        k = int(self.traffic["check_steps"])
        rows = [[feed.worker_rows(batches[t], self.chips, w)
                 for w in range(self.chips)] for t in range(k)]
        return reference.follow(self.cell["config"], self.traffic, self.lay,
                                seed, self.devices, self.chips, rows, prec,
                                fault)

    def compiled_text(self, batch) -> str:
        """HLO text of the compiled step (a persistent-cache hit)."""
        from repro import compat
        with compat.set_mesh(self.mesh):
            return self.session.step_fn.lower(self.specs, batch).compile() \
                .as_text()

    def memory_peak(self) -> int | None:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None


class GcPauses:
    """Python's garbage collections while it is installed: how many, and
    the seconds they took."""

    def __init__(self):
        self.n, self.seconds, self._t = 0, 0.0, None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.n += 1
            self.seconds += time.perf_counter() - self._t

    def stop(self) -> None:
        gc.callbacks.remove(self._cb)

    def __str__(self) -> str:
        return f"{self.n} gc pauses, {self.seconds * 1e3:.1f} ms"


def _data(batch):
    import jax
    with jax.profiler.TraceAnnotation(SPAN_DATA):
        return batch


def run(cell: dict, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True) -> dict:
    """One run; returns the result line's object."""
    import jax

    from repro.observe import metrics as OM

    devices = devices_for(cell["chips"], require_chip)
    kind = devices[0].device_kind
    peaks = None
    if require_chip:
        from lagsbench import peaks as PK
        peaks = PK.lookup(kind)
    say(f"cell {cell['name']} | seed {seed} | {len(devices)} x {kind} | "
        f"jax {jax.__version__} | compile cache "
        f"{jax.config.jax_compilation_cache_dir}")

    prog = Program(cell, devices)
    registry = OM.MetricsRegistry()
    batches = prog.batches(seed)
    state, prog_read = prog.first_steps(seed, batches, registry)
    k = len(prog_read["losses"])
    tokens_per_step = feed.global_batch(cell["traffic"], cell["chips"]) \
        * int(cell["traffic"]["seq_len"])

    tracer = None
    if trace:
        from lagsbench import xplane
        tracer = xplane.Capture()
        tracer.start()
    # A full collection walks the whole heap (about 0.1 s with JAX and
    # the program loaded) and used to land inside the window at random;
    # what set-up left alive is frozen, so a collection in the window
    # walks only what the window made.
    gc.collect()
    gc.freeze()
    pauses = GcPauses()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    n, losses, step_s = 0, [], []
    while True:
        t = time.perf_counter()
        state, row = prog.step(state, batches[(k + n) % len(batches)],
                               registry)
        step_s.append(time.perf_counter() - t)
        losses.append(row["loss"])
        n += 1
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    pauses.stop()
    if tracer is not None:
        tracer.stop()
    memory_peak = prog.memory_peak()
    del state
    gc.collect()
    slow = max(range(n), key=step_s.__getitem__)
    say(f"set-up {setup_s:.3f} s | window {window_s:.3f} s, {n} steps, "
        f"step median {sorted(step_s)[n // 2]:.4f} s, slowest "
        f"{step_s[slow]:.4f} s (step {slow}) | {pauses} | losses "
        f"{min(losses):.4f}-{max(losses):.4f} | peak {memory_peak} B")

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": False, "attempted": n,
              "failed": sum(1 for x in losses if not math.isfinite(x))}
    if trace:
        from lagsbench import per_layer
        t_read = time.perf_counter()
        reduced = tracer.reduce(prog, batches[0], SPAN_STEP, n)
        say(f"trace read in {time.perf_counter() - t_read:.1f} s")
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        ctx = per_layer.Context(cell=cell, trace=reduced, peaks=peaks,
                                tokens_per_step=tokens_per_step)
        result["metrics"] = per_layer.read_all(cell, ctx)
        result["device"] = device
        result["breakdown"] = reduced.breakdown()
    else:
        measured = {"tokens_per_s": n * tokens_per_step / window_s,
                    "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": measured[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell["end_to_end"]}
        result["device"] = device

    t_ref = time.perf_counter()
    ref_read = prog.reference(seed, batches[:k])
    nums = check.numbers(prog_read, ref_read, cell["config"])
    correct, report = check.judge(nums, cell["limits"])
    say(f"reference {time.perf_counter() - t_ref:.1f} s | losses program "
        f"{prog_read['losses']} reference {ref_read['losses']} | worst "
        f"leaves {nums['_worst_leaf']}")
    for name, v in nums.items():
        if name not in report and not name.startswith("_"):
            say(f"reading {name} = {v!r} (not compared: see limits file)")
    for name, r in report.items():
        say(f"check {name} = {r['value']!r} (limit {r['limit']!r})")
    result["correct"] = correct
    result["checks"] = report
    return result
