"""The plain reference of a training cell: the configuration's model
(``configs/<reference>.py``) and the traffic's exchange
(``modes/<mode>.py``), followed through the first steps of the run.

Every worker trains on its own rows, on its own chip where there are
enough.  Layer by layer: the forward pass keeps each layer's input; the
backward pass recomputes one layer at a time, and each layer's leaves go
through the exchange and the SGD apply as soon as their gradients exist
(a layer's backward pass never reads a weight of another layer, so the
order changes nothing).  Weights are stored in the configuration's
parameter type, as the program stores them, and every product runs in
float32 at ``Precision.HIGHEST`` (``prec="fp8"``: the control).

After the first step it reads, per named leaf, the norm of the gradient
as the optimizer got it, worked out from the state: ``(mean over workers
of the residual + (w0 - w1)) / lr``; after the last step, the norm of
each leaf's change ``w - w0``.  :mod:`lagsbench.readings` reads the
program's state by the same formulas.  Each step's ``history`` entry
holds its loss and, where the configuration's ``block`` returns a third
value (readings of its own, such as routing counts), those values per
layer and worker under ``blocks``, for the module's ``numbers`` hook
(:mod:`lagsbench.check`).
"""
from __future__ import annotations

import importlib.util
import json
import math

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from lagsbench import weights as W
from lagsbench.spec import BENCH_DIR, as_run

F32 = jnp.float32


def load_module(path):
    spec = importlib.util.spec_from_file_location(path.stem.replace("-", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_module(config: dict):
    return load_module(BENCH_DIR / "configs" / f"{config['reference']}.py")


def mode_module(run: dict):
    mod = load_module(BENCH_DIR / "modes" / f"{run['mode']}.py")
    pipeline = run.get("pipeline", "off")
    if pipeline not in mod.PIPELINES:
        raise ValueError(f"modes/{run['mode']}.py has no reference for "
                         f"pipeline {pipeline!r}")
    return mod


def _layers(lay: tuple) -> tuple[list, dict, list]:
    """(layer count, names per layer, names of the head)."""
    per: dict = {}
    head = []
    for name, *_ in lay:
        if name.startswith("layer"):
            l, rest = name[5:].split(".", 1)
            per.setdefault(int(l), []).append(name)
        elif name != "embed.embedding":
            head.append(name)
    return per, head


class Reference:
    """The reference run of one cell.

    ``prec``: "f32", or "fp8" for the control.  ``fault`` plants one of
    the faults the comparison has to catch: "half_batch" (the second half
    of each worker's tokens left out of the loss, the mean taken over the
    rest) or "no_exchange" (each worker applies only its own payload)."""

    def __init__(self, config: dict, traffic: dict, lay: tuple, seed: int,
                 devices: list, n_workers: int, prec: str = "f32",
                 fault: str | None = None):
        if fault not in (None, "half_batch", "no_exchange"):
            raise ValueError(f"unknown fault {fault!r}")
        self.fault = fault
        config = as_run(config)
        run = traffic["run"]
        self._f = _functions(config, run, prec)
        mode = self._f.mode
        self.lay = lay
        self.seed = seed
        self.lr = float(run["lr"])
        block_size = int(run.get("block_size", 4096))
        self.n_workers = n_workers
        self.devs = [devices[w % len(devices)] for w in range(n_workers)]
        self.uniq = list(dict.fromkeys(self.devs))
        self.shapes = {n: s for n, s, *_ in lay}
        self.params = {d: W.make_named(seed, lay, SingleDeviceSharding(d))
                       for d in self.uniq}
        self.ef = None
        if mode.NEEDS_EF:
            self.ef = [{n: jax.device_put(jnp.zeros(s, F32), d)
                        for n, s, *_ in lay} for d in self.devs]
        self.per_layer, self.head = _layers(lay)
        # a stacked leaf whose layer is not a whole number of blocks is
        # blocked across its layers, in layer order, as one vector
        groups: dict = {}
        if getattr(mode, "BLOCKED", False):
            for n, s, _, parts, slot in lay:
                if slot is not None and math.prod(s) % block_size:
                    groups.setdefault(parts, []).append((slot, n))
        self.groups = [[n for _, n in sorted(g)] for g in groups.values()]
        self.deferred = {n for g in self.groups for n in g}
        self._held: dict = {}
        self.aux_w = self._f.model.aux_weight(config)

    # -- one step -------------------------------------------------------------
    def _w(self, dev, names, strip: bool):
        ps = self.params[dev]
        if strip:
            return {n.split(".", 1)[1]: ps[n] for n in names}
        return {n: ps[n] for n in names}

    def _exchange(self, grads: list, names: list, strip: bool):
        """grads[w]: {leaf key: f32 gradient} of worker w, for ``names``.
        Leaves blocked across layers wait for :meth:`_flush`."""
        for n in names:
            key = n.split(".", 1)[1] if strip else n
            g = [grads[w][key] for w in range(self.n_workers)]
            if n in self.deferred:
                self._held[n] = g
            else:
                self._exchange_unit([n], [g])

    def _flush(self):
        for group in self.groups:
            self._exchange_unit(group, [self._held.pop(n) for n in group])

    def _exchange_unit(self, names: list, gs: list):
        """One exchange over ``names`` taken as one vector in layer order;
        gs[i][w]: gradient of names[i] on worker w."""
        one = len(names) == 1
        sizes = [math.prod(self.shapes[n]) for n in names]
        payloads = []
        for w in range(self.n_workers):
            g = gs[0][w] if one else _concat([x[w] for x in gs])
            e = None
            if self.ef is not None:
                e = self.ef[w][names[0]] if one else _concat(
                    [self.ef[w][n] for n in names])
            payload, new_e = self._f.select(g, e)
            if self.ef is not None:
                if one:
                    self.ef[w][names[0]] = new_e
                else:
                    for n, part in zip(names, _split(new_e, sizes)):
                        self.ef[w][n] = part.reshape(self.shapes[n])
            payloads.append(payload)
        for d in self.uniq:
            mine = payloads
            if self.fault == "no_exchange":
                mine = [p for p, dw in zip(payloads, self.devs) if dw == d]
            here = jax.device_put(mine, d)
            new = self._f.apply(tuple(self.params[d][n] for n in names), here,
                              tuple(self.shapes[n] for n in names))
            for n, p in zip(names, new):
                self.params[d][n] = p

    def step(self, rows: list) -> dict:
        """One training step; rows[w] = {"tokens", "labels"} (numpy).
        Returns its history entry."""
        nw, n_layers = self.n_workers, len(self.per_layer)
        toks = [jax.device_put(r["tokens"], d) for r, d in zip(rows, self.devs)]
        labs = [jax.device_put(_labels(r["labels"], self.fault), d)
                for r, d in zip(rows, self.devs)]
        emb = ["embed.embedding"]
        xs = [[self._f.embed_fwd(self._w(d, emb, False), t)]
              for d, t in zip(self.devs, toks)]
        aux = [jnp.zeros((), F32)] * nw
        blocks = []
        for l in range(n_layers):
            got = []
            for w, d in enumerate(self.devs):
                y, a, *more = self._f.fwd(self._w(d, self.per_layer[l], True),
                                          xs[w][-1])
                xs[w].append(y)
                aux[w] = aux[w] + a
                got.extend(jax.device_get(more))
            if got:
                blocks.append(got)
        losses, dxs, grads = [], [], []
        for w, d in enumerate(self.devs):
            wh = self._w(d, self.head, False)
            ce, cnt = self._f.head_fwd(wh, xs[w][-1], labs[w])
            losses.append(float(ce) / float(cnt) + self.aux_w * float(aux[w]))
            g, dx = self._f.head_bwd(wh, xs[w][-1], labs[w],
                                   jnp.asarray(1.0 / float(cnt), F32))
            grads.append(g)
            dxs.append(dx)
        self._exchange(grads, self.head, strip=False)
        daux = jnp.asarray(self.aux_w, F32)
        for l in reversed(range(n_layers)):
            grads = []
            for w, d in enumerate(self.devs):
                g, dxs[w] = self._f.bwd(self._w(d, self.per_layer[l], True),
                                      xs[w][l], dxs[w], daux)
                grads.append(g)
            self._exchange(grads, self.per_layer[l], strip=True)
        grads = [{"embed.embedding": self._f.embed_bwd(
            toks[w], dxs[w], self.shapes["embed.embedding"])}
            for w in range(nw)]
        self._exchange(grads, emb, strip=False)
        self._flush()
        entry = {"loss": sum(losses) / nw}
        if blocks:
            entry["blocks"] = blocks
        return entry

    # -- readings ---------------------------------------------------------------
    def grad_norms(self) -> dict:
        d0 = self.uniq[0]
        w0 = W.make_named(self.seed, self.lay, SingleDeviceSharding(d0))
        out = {}
        for n in self.shapes:
            efs = ([jax.device_put(self.ef[w][n], d0)
                    for w in range(self.n_workers)]
                   if self.ef is not None else None)
            out[n] = float(_grad_norm(w0[n], self.params[d0][n], efs,
                                      self.lr))
        return out

    def change_norms(self) -> dict:
        d0 = self.uniq[0]
        w0 = W.make_named(self.seed, self.lay, SingleDeviceSharding(d0))
        return {n: float(_change_norm(w0[n], self.params[d0][n]))
                for n in self.shapes}


_FUNCTIONS: dict = {}


def _functions(config: dict, run: dict, prec: str):
    """The reference's jitted pieces for one (configuration, run,
    precision), built once per process."""
    import types
    key = (json.dumps(config, sort_keys=True), json.dumps(run, sort_keys=True),
           prec)
    if key in _FUNCTIONS:
        return _FUNCTIONS[key]
    model, mode = model_module(config), mode_module(run)
    cfg = config
    lr, ratio = float(run["lr"]), run.get("ratio")
    bsz = int(run.get("block_size", 4096))

    def cast(ws):
        return {k: v.astype(F32) for k, v in ws.items()}

    def fwd(ws, x):
        return model.block(cast(ws), x, cfg, prec)

    def bwd(ws, x, dy, daux):
        _, vjp = jax.vjp(lambda w, xx: model.block(w, xx, cfg, prec)[:2],
                         cast(ws), x)
        return vjp((dy, daux))

    def head_fwd(ws, x, labels):
        return model.head_loss(cast(ws), x, labels, cfg, prec)

    def head_bwd(ws, x, labels, scale):
        _, vjp = jax.vjp(
            lambda w, xx: model.head_loss(w, xx, labels, cfg, prec)[0],
            cast(ws), x)
        return vjp(scale)

    def embed_fwd(ws, tokens):
        return model.embed(cast(ws), tokens)

    def embed_bwd(tokens, dx, shape):
        return jnp.zeros(shape, F32).at[tokens].add(dx)

    def select(g, e):
        return mode.select(lr * g, e, ratio, bsz)

    def apply(ws, payloads, shapes):
        sizes = [math.prod(s) for s in shapes]
        upd = mode.combine(payloads, (sum(sizes),), ratio, bsz)
        return tuple((w.astype(F32) - u.reshape(s)).astype(w.dtype)
                     for w, u, s in zip(ws, _split(upd, sizes), shapes))

    fns = types.SimpleNamespace(
        model=model, mode=mode, fwd=jax.jit(fwd), bwd=jax.jit(bwd), head_fwd=jax.jit(head_fwd),
        head_bwd=jax.jit(head_bwd), embed_fwd=jax.jit(embed_fwd),
        embed_bwd=jax.jit(embed_bwd, static_argnums=2),
        select=jax.jit(select), apply=jax.jit(apply, static_argnums=2))
    _FUNCTIONS[key] = fns
    return fns


def _concat(xs):
    return jnp.concatenate([x.reshape(-1) for x in xs])


def _split(x, sizes):
    out, off = [], 0
    for n in sizes:
        out.append(x[off:off + n])
        off += n
    return out


def _labels(labels, fault):
    if fault != "half_batch":
        return labels
    labels = labels.copy()
    labels[:, labels.shape[1] // 2:] = -1
    return labels


@jax.jit
def _grad_norm(w0, w1, efs, lr):
    g = w0.astype(F32) - w1.astype(F32)
    if efs is not None:
        g = g + sum(efs) / len(efs)
    return jnp.sqrt(jnp.sum(jnp.square(g / lr)))


@jax.jit
def _change_norm(w0, w):
    return jnp.sqrt(jnp.sum(jnp.square(w.astype(F32) - w0.astype(F32))))


def follow(config: dict, traffic: dict, lay: tuple, seed: int, devices: list,
           n_workers: int, rows_per_step: list, prec: str = "f32",
           fault: str | None = None) -> dict:
    """Run the reference through ``len(rows_per_step)`` steps; returns
    ``{"losses", "grad_norms", "change_norms", "history"}``."""
    ref = Reference(config, traffic, lay, seed, devices, n_workers, prec,
                    fault)
    history, grads = [], None
    for t, rows in enumerate(rows_per_step):
        history.append(ref.step(rows))
        if t == 0:
            grads = ref.grad_norms()
    out = {"losses": [h["loss"] for h in history], "grad_norms": grads,
           "change_norms": ref.change_norms(), "history": history}
    del ref
    return out

