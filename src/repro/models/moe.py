"""Mixture-of-Experts layer: top-k router + capacity-bounded scatter dispatch.

Dispatch strategy (TPU-classic, GShard/Switch style adapted to gather/scatter
instead of giant one-hot einsums):

  1. router logits (T, E) -> top-k experts per token, softmax over selected.
  2. per-(token, slot) flat assignment; position within expert via a cumsum
     over the flattened assignment order; tokens beyond ``capacity`` drop
     (their combine weight is zeroed — residual connection carries them).
  3. scatter tokens into an (E, C, D) buffer, run the expert FFNs as one
     batched einsum over the expert axis, gather back and weight-combine.

Expert sharding: the (E, D, F) stacks carry logical axes
("experts", "embed", "expert_ffn"); rules.py maps "experts" -> 'model' when
E divides the tp size, else shards "expert_ffn".
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro import compat
from repro.models import layers as L


def init_moe(key, d_model: int, d_ff: int, n_experts: int, dtype,
             gated: bool = True):
    k0, k1, k2, k3 = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff)
    params = {
        "router": jax.random.normal(k0, (d_model, n_experts), dtype) * s_in,
        "w_up": jax.random.normal(k2, (n_experts, d_model, d_ff), dtype) * s_in,
        "w_down": jax.random.normal(k3, (n_experts, d_ff, d_model), dtype) * s_out,
    }
    axes = {
        "router": ("embed", None),
        "w_up": ("experts", "embed", "expert_ffn"),
        "w_down": ("experts", "expert_ffn", "embed"),
    }
    if gated:
        params["w_gate"] = jax.random.normal(k1, (n_experts, d_model, d_ff),
                                             dtype) * s_in
        axes["w_gate"] = ("experts", "embed", "expert_ffn")
    return params, axes


def _route(p, xt, top_k: int):
    """Router: (T, D) -> (gate_vals (T,K), expert_idx (T,K), aux_loss)."""
    t = xt.shape[0]
    e = p["w_up"].shape[0]
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)         # (T, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    # load-balance auxiliary loss (Switch): E * sum_e f_e * p_e
    me = probs.mean(0)                                           # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[expert_idx.reshape(-1)].add(
        1.0 / (t * top_k))
    aux_loss = e * jnp.sum(me * ce)
    return gate_vals, expert_idx, aux_loss


def _positions(flat_expert, e: int, capacity: int):
    """Slot position of each (token, k) within its expert segment."""
    n = flat_expert.shape[0]
    order = jnp.argsort(flat_expert, stable=True)
    sorted_experts = flat_expert[order]
    seg_start = jnp.searchsorted(sorted_experts, jnp.arange(e), side="left")
    pos_sorted = jnp.arange(n) - seg_start[sorted_experts]
    position = jnp.zeros((n,), jnp.int32).at[order].set(
        pos_sorted.astype(jnp.int32))
    keep = position < capacity
    return position, keep


def _expert_ffn(p, buf, act, dtype):
    pet = dtype
    up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(dtype),
                    preferred_element_type=pet)
    if "w_gate" in p:
        gate = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(dtype),
                          preferred_element_type=pet)
        h = act(gate) * up
    else:
        h = act(up)
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dtype),
                      preferred_element_type=pet)


def _dense_core(p, xt, *, top_k: int, act, capacity: int):
    """Scatter-dispatch MoE over flat tokens xt: (T, D) -> ((T, D), aux)."""
    t, d = xt.shape
    e = p["w_up"].shape[0]
    gate_vals, expert_idx, aux_loss = _route(p, xt, top_k)
    flat_expert = expert_idx.reshape(-1)                         # (T*K,)
    position, keep = _positions(flat_expert, e, capacity)
    gates_flat = gate_vals.reshape(-1) * keep

    # scatter tokens into (E, C, D)
    buf = jnp.zeros((e, capacity, d), xt.dtype)
    tok_ids = jnp.repeat(jnp.arange(t), top_k)
    write_pos = jnp.where(keep, position, capacity - 1)
    contrib = jnp.where(keep[:, None], xt[tok_ids], 0).astype(xt.dtype)
    buf = buf.at[flat_expert, write_pos].add(contrib)

    out_buf = _expert_ffn(p, buf, act, xt.dtype)

    # gather back + combine
    gathered = out_buf[flat_expert, write_pos]                   # (T*K, D)
    weighted = gathered.astype(jnp.float32) * gates_flat[:, None]
    out = jnp.zeros((t, d), jnp.float32).at[tok_ids].add(weighted)
    return out.astype(xt.dtype), aux_loss


def moe_forward(p, x, *, top_k: int, activation: str = "silu",
                capacity_factor: float = 1.25):
    """x: (B, S, D) -> (B, S, D), plus aux load-balance loss.

    Plain scatter dispatch over all tokens at once.  Use
    ``moe_forward_auto`` in distributed code: it groups tokens by the
    data-sharded batch dim so all dispatch scatters stay device-local."""
    b, s, d = x.shape
    t = b * s
    e = p["w_up"].shape[0]
    act = L.ACTIVATIONS[activation]
    capacity = max(1, int(capacity_factor * t * top_k / e))
    out, aux = _dense_core(p, x.reshape(t, d), top_k=top_k, act=act,
                           capacity=capacity)
    return out.reshape(b, s, d), aux


def moe_forward_grouped(p, x, *, top_k: int, activation: str = "silu",
                        capacity_factor: float = 1.25, groups: int = 1,
                        data_axes: tuple = (), tp_axis: str = "model"):
    """Grouped dispatch: tokens split into ``groups`` along the (data-
    sharded) batch dim; every dispatch op is written batched over the
    group dim with EXPLICIT sharding constraints (group dim -> data axes,
    expert d_ff dim -> TP axis), so the partitioner keeps the big
    (G, E, C, ·) buffers fully sharded even in the remat-recomputed
    backward (without the pins, GSPMD's backward propagation replicated
    them — 140 GiB/dev per MoE layer on jamba).  Per-group capacity,
    standard GShard/Switch semantics."""
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    if groups <= 1 or b % groups:
        return moe_forward(p, x, top_k=top_k, activation=activation,
                           capacity_factor=capacity_factor)
    act = L.ACTIVATIONS[activation]
    e = p["w_up"].shape[0]
    g = groups
    tg = (b // g) * s
    capacity = max(1, int(capacity_factor * tg * top_k / e))
    dg = (tuple(data_axes) if len(data_axes) > 1
          else (data_axes[0] if data_axes else None))
    have_mesh = bool(getattr(compat.get_abstract_mesh(), "shape", {}))

    def pin(v, *rest):
        if not have_mesh:
            return v
        return jax.lax.with_sharding_constraint(v, P(dg, *rest))

    xt = pin(x.reshape(g, tg, d), None, None)                    # (G,Tg,D)

    # --- routing (batched over G) ------------------------------------------
    logits = jnp.einsum("gtd,de->gte", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)          # (G,Tg,K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                        1e-9)
    me = probs.mean(1)                                           # (G,E)
    flat_expert = expert_idx.reshape(g, tg * top_k)              # (G,TK)
    ce = jnp.zeros((g, e), jnp.float32).at[
        jnp.arange(g)[:, None], flat_expert].add(1.0 / (tg * top_k))
    aux_loss = e * jnp.sum(me * ce, axis=-1).mean()

    # --- per-group positions (argsort along the token axis is local) -------
    order = jnp.argsort(flat_expert, axis=1, stable=True)
    sorted_experts = jnp.take_along_axis(flat_expert, order, axis=1)
    seg_start = jax.vmap(lambda se: jnp.searchsorted(
        se, jnp.arange(e), side="left"))(sorted_experts)         # (G,E)
    pos_sorted = jnp.arange(tg * top_k)[None, :] \
        - jnp.take_along_axis(seg_start, sorted_experts, axis=1)
    position = jnp.zeros((g, tg * top_k), jnp.int32).at[
        jnp.arange(g)[:, None], order].set(pos_sorted.astype(jnp.int32))
    keep = position < capacity
    gates_flat = gate_vals.reshape(g, tg * top_k) * keep

    # --- scatter into (G, E, C, D), batched --------------------------------
    g_ids = jnp.arange(g)[:, None]
    tok_ids = jnp.repeat(jnp.arange(tg), top_k)[None, :]         # (1,TK)
    write_pos = jnp.where(keep, position, capacity - 1)
    contrib = jnp.where(
        keep[..., None],
        jnp.take_along_axis(xt, jnp.broadcast_to(
            tok_ids[..., None], (g, tg * top_k, d)), axis=1), 0
    ).astype(x.dtype)
    contrib = pin(contrib, None, None)
    buf = pin(jnp.zeros((g, e, capacity, d), x.dtype), None, None, None) \
        .at[g_ids, flat_expert, write_pos].add(contrib)
    buf = pin(buf, None, None, None)

    # --- expert FFN (partition over G x F) ----------------------------------
    pet = x.dtype
    up = pin(jnp.einsum("gecd,edf->gecf", buf, p["w_up"].astype(x.dtype),
                        preferred_element_type=pet),
             None, None, tp_axis)
    if "w_gate" in p:
        gate = pin(jnp.einsum("gecd,edf->gecf", buf,
                              p["w_gate"].astype(x.dtype),
                              preferred_element_type=pet),
                   None, None, tp_axis)
        h = act(gate) * up
    else:
        h = act(up)
    h = pin(h, None, None, tp_axis)
    out_buf = pin(jnp.einsum("gecf,efd->gecd", h,
                             p["w_down"].astype(x.dtype),
                             preferred_element_type=pet),
                  None, None, None)

    # --- gather back + combine ----------------------------------------------
    gathered = out_buf[g_ids, flat_expert, write_pos]            # (G,TK,D)
    weighted = gathered.astype(jnp.float32) * gates_flat[..., None]
    out = jnp.zeros((g, tg, d), jnp.float32).at[
        g_ids, jnp.broadcast_to(tok_ids, (g, tg * top_k))].add(weighted)
    out = pin(out, None, None)
    return out.reshape(b, s, d).astype(x.dtype), aux_loss


# ---------------------------------------------------------------------------
# Expert-parallel dispatch (TPU-native): shard_map over the TP axis.
# ---------------------------------------------------------------------------

def moe_forward_ep(p, x, *, top_k: int, activation: str = "silu",
                   capacity_factor: float = 1.25, axis: str = "model"):
    """Expert-parallel MoE: experts sharded over ``axis``, activations
    replicated over it (as they already are between TP blocks).

    Each rank runs the (deterministic, replicated) router, keeps only the
    slots owned by its local experts, scatters into a LOCAL (E/n, C, D)
    buffer, runs the local expert FFNs, and contributes a partial (T, D)
    output; one ``psum`` over ``axis`` combines — the same collective a
    dense TP FFN already pays.  No GSPMD scatter over a sharded expert dim
    -> none of the (E, C, D) replication all-gathers of the dense path.
    """
    from jax.sharding import PartitionSpec as P

    mesh = compat.get_abstract_mesh()
    n = mesh.shape[axis]
    e = p["w_up"].shape[0]
    e_local = e // n
    act = L.ACTIVATIONS[activation]
    b, s, d = x.shape
    t = b * s
    capacity = max(1, int(capacity_factor * t * top_k / e))

    w_specs = {k: (P() if k == "router" else P(axis)) for k in p}

    def body(pp, xx):
        r = jax.lax.axis_index(axis)
        xt = xx.reshape(t, d)
        gate_vals, expert_idx, aux_loss = _route_global(
            pp["router"], xt, top_k, e)
        flat_expert = expert_idx.reshape(-1)
        position, keep = _positions(flat_expert, e, capacity)
        lo = r * e_local
        mine = (flat_expert >= lo) & (flat_expert < lo + e_local)
        sel = keep & mine
        gates_flat = gate_vals.reshape(-1) * sel

        buf = jnp.zeros((e_local, capacity, d), xx.dtype)
        tok_ids = jnp.repeat(jnp.arange(t), top_k)
        local_e = jnp.clip(flat_expert - lo, 0, e_local - 1)
        write_pos = jnp.where(sel, position, capacity - 1)
        contrib = jnp.where(sel[:, None], xt[tok_ids], 0).astype(xx.dtype)
        buf = buf.at[local_e, write_pos].add(contrib)

        out_buf = _expert_ffn(pp, buf, act, xx.dtype)

        gathered = out_buf[local_e, write_pos]
        weighted = gathered.astype(jnp.float32) * gates_flat[:, None]
        out = jnp.zeros((t, d), jnp.float32).at[tok_ids].add(weighted)
        out = jax.lax.psum(out, axis)
        return out.reshape(b, s, d).astype(xx.dtype), aux_loss

    sm = compat.shard_map(body, mesh=mesh, in_specs=(w_specs, P()),
                          out_specs=(P(), P()), axis_names={axis},
                          check_vma=False)
    return sm(p, x)


def _route_global(router, xt, top_k: int, e: int):
    """Router on replicated activations (identical on every EP rank)."""
    t = xt.shape[0]
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                        1e-9)
    me = probs.mean(0)
    ce = jnp.zeros((e,), jnp.float32).at[expert_idx.reshape(-1)].add(
        1.0 / (t * top_k))
    aux_loss = e * jnp.sum(me * ce)
    return gate_vals, expert_idx, aux_loss


def moe_forward_auto(p, x, *, top_k: int, activation: str = "silu",
                     capacity_factor: float = 1.25, axis: str = "model"):
    """Dispatch selection for the ambient mesh.

    Tokens are grouped by the product of AUTO (GSPMD) data-like axes so
    the per-group scatters partition; axes already bound manual by an
    enclosing shard_map (the lags_dp train step) see local tokens and need
    no grouping.  Expert weights shard on d_ff (rules.TP_PRIORITY), which
    keeps the buffers unsharded — the partitioner never has to replicate
    them.  (An explicit expert-parallel shard_map variant exists as
    ``moe_forward_ep`` but is not auto-selected: nested manual regions are
    rejected by Shardy inside lags_dp, and the pure-auto hier step
    triggers an XLA SPMD crash — 'Invalid binary instruction opcode
    copy' — when it is scanned+rematted; see EXPERIMENTS §Perf.)"""
    mesh = compat.get_abstract_mesh()
    groups = 1
    data_axes = []
    auto_names = set(compat.auto_axis_names(mesh))
    sizes = getattr(mesh, "shape", {})
    for nm in getattr(mesh, "axis_names", ()):
        if nm in ("pod", "data") and nm in auto_names:
            groups *= sizes[nm]
            data_axes.append(nm)
    return moe_forward_grouped(p, x, top_k=top_k, activation=activation,
                               capacity_factor=capacity_factor,
                               groups=groups, data_axes=tuple(data_axes),
                               tp_axis=axis)
