"""Basic building blocks: norms, embeddings, rotary, activations.

All modules are pure functions over explicit parameter pytrees.  ``init_*``
functions return (params, axes) where ``axes`` is a matching pytree of
*logical axis name* tuples (e.g. ("embed", "heads", "head_dim")); the
mapping to physical mesh axes — with divisibility fallbacks and optional
FSDP folding — happens in ``repro.sharding.rules``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import compat


def pin_act(x: jax.Array, tp_dim: int | None = None) -> jax.Array:
    """Sharding constraint for a big activation: batch dim -> the AUTO
    'data' axis, ``tp_dim`` -> 'model' (when divisible).

    Why: GSPMD's sharding propagation through the remat-recomputed
    backward loses the forward's activation shardings and falls back to
    full all-gathers (measured 288 GiB/dev per FFN layer on nemotron-340b
    in FSDP mode).  Explicit constraints are part of the rematted jaxpr,
    so they survive into the recompute.  No-op without an ambient mesh,
    on manual (shard_map-bound) axes, or on non-divisible dims."""
    mesh = compat.get_abstract_mesh()
    sizes = dict(getattr(mesh, "shape", {}))
    if not sizes:
        return x
    from jax.sharding import PartitionSpec as P
    auto = set(compat.auto_axis_names(mesh))
    spec = [None] * x.ndim
    if "data" in auto and x.shape[0] % sizes["data"] == 0:
        spec[0] = "data"
    if (tp_dim is not None and "model" in auto
            and x.shape[tp_dim] % sizes["model"] == 0):
        spec[tp_dim] = "model"
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def apply_norm(kind: str, x, p):
    if kind == "rmsnorm":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p["bias"])


def init_norm(kind: str, d: int, dtype):
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((d,), dtype)}, {"scale": ("embed",)}
    return ({"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)},
            {"scale": ("embed",), "bias": ("embed",)})


# -- activations -------------------------------------------------------------

def squared_relu(x):
    r = jax.nn.relu(x)
    return r * r


ACTIVATIONS = {
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "relu": jax.nn.relu,
    "squared_relu": squared_relu,
}


# -- rotary ------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0):
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                        # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -- embeddings --------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, dtype):
    scale = 1.0 / jnp.sqrt(d)
    w = jax.random.normal(key, (vocab, d), dtype) * scale
    return {"embedding": w}, {"embedding": ("vocab", "embed")}


def embed(p, tokens: jax.Array, dtype) -> jax.Array:
    return p["embedding"].astype(dtype)[tokens]


def unembed(p, x: jax.Array) -> jax.Array:
    """Logits in f32 (softmax stability)."""
    return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                      p["embedding"].astype(jnp.float32))


def init_linear(key, d_in: int, d_out: int, dtype,
                axes=("embed", "ffn")):
    scale = 1.0 / jnp.sqrt(d_in)
    w = jax.random.normal(key, (d_in, d_out), dtype) * scale
    return {"w": w}, {"w": axes}


def linear(p, x):
    return jnp.einsum("...i,io->...o", x, p["w"].astype(x.dtype))
