"""Grouped-query attention with chunked online-softmax ("flash" in pure JAX),
sliding-window support, and a KV-cache decode path.

Memory discipline: scores are never materialized beyond
(B, KV, G, Sq_chunk_or_S, Ck) per KV chunk, so 32k prefill lowers with
bounded live memory.  The KV-chunk loop is a ``lax.scan`` carrying the
online-softmax state (m, l, acc) in f32.

Cache layouts
  full cache : k/v (B, S_cap, KV, hd); entries at index <= pos are valid.
  ring cache : k/v (B, W,     KV, hd); write at pos % W; all entries valid
               in steady state (dry-run decodes at pos = S >= W).
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers as L

NEG_INF = -1e30


def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(n_heads * head_dim)
    params = {
        "wq": jax.random.normal(k1, (d_model, n_heads, head_dim), dtype) * s_in,
        "wk": jax.random.normal(k2, (d_model, n_kv_heads, head_dim), dtype) * s_in,
        "wv": jax.random.normal(k3, (d_model, n_kv_heads, head_dim), dtype) * s_in,
        "wo": jax.random.normal(k4, (n_heads, head_dim, d_model), dtype) * s_out,
    }
    axes = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    return params, axes


def _qkv(p, x, n_kv_heads):
    """Project and reshape to grouped layout.  q: (B,S,KV,G,hd).

    preferred_element_type pinned to the activation dtype so TP partial-sum
    collectives run in bf16 (see ffn.ffn_forward)."""
    pet = x.dtype
    q = L.pin_act(jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype),
                             preferred_element_type=pet), 2)
    k = L.pin_act(jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype),
                             preferred_element_type=pet), 2)
    v = L.pin_act(jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype),
                             preferred_element_type=pet), 2)
    b, s, h, hd = q.shape
    g = h // n_kv_heads
    q = q.reshape(b, s, n_kv_heads, g, hd)
    return q, k, v


def _out_proj(p, o, dtype):
    """o: (B, S, KV, G, hd) -> (B, S, D)."""
    b, s, kv, g, hd = o.shape
    o = o.reshape(b, s, kv * g, hd)
    return L.pin_act(
        jnp.einsum("bshk,hkd->bsd", o.astype(dtype), p["wo"].astype(dtype),
                   preferred_element_type=jnp.dtype(dtype)))


def chunked_attention(q, k, v, *, q_positions, k_positions, causal=True,
                      window: int | None = None, chunk: int = 1024,
                      k_valid_len=None):
    """Online-softmax attention over KV chunks.

    q: (B, Sq, KV, G, hd);  k, v: (B, Sk, KV, hd)
    q_positions: (Sq,) absolute positions of queries
    k_positions: (Sk,) absolute positions of keys
    k_valid_len: optional scalar; keys with index >= k_valid_len are masked.
    Returns (B, Sq, KV, G, hd).
    """
    b, sq, kvh, g, hd = q.shape
    sk = k.shape[1]
    chunk = min(chunk, sk)
    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, (0, pad), constant_values=2**30)
        if k_valid_len is None:
            k_valid_len = sk
    scale = 1.0 / math.sqrt(hd)
    qf = (q.astype(jnp.float32) * scale).transpose(0, 2, 3, 1, 4)  # B,KV,G,Sq,hd

    kc = k.reshape(b, n_chunks, chunk, kvh, hd).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, n_chunks, chunk, kvh, hd).transpose(1, 0, 3, 2, 4)
    kpos_c = k_positions.reshape(n_chunks, chunk)
    kidx_c = jnp.arange(n_chunks * chunk).reshape(n_chunks, chunk)

    m0 = jnp.full((b, kvh, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, kvh, g, sq, hd), jnp.float32)

    def body(carry, xs):
        m, l, acc = carry
        kj, vj, kpos, kidx = xs
        # scores: (B, KV, G, Sq, Ck)
        s = jnp.einsum("bhgqd,bhcd->bhgqc", qf, kj.astype(jnp.float32))
        mask = jnp.ones((sq, chunk), bool)
        if causal:
            mask &= kpos[None, :] <= q_positions[:, None]
        if window is not None:
            mask &= kpos[None, :] > q_positions[:, None] - window
        if k_valid_len is not None:
            mask &= (kidx[None, :] < k_valid_len)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        corr = jnp.exp(m - m_new)
        p_ = jnp.exp(s - m_new[..., None])
        l = l * corr + p_.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqc,bhcd->bhgqd", p_, vj.astype(jnp.float32))
        return (m_new, l, acc), None

    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), (m0, l0, acc0),
                                  (kc, vc, kpos_c, kidx_c))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)  # B,Sq,KV,G,hd


def attention_forward(p, x, *, n_kv_heads: int, rope_theta: float = 10000.0,
                      window: int | None = None, chunk: int = 1024,
                      positions=None, use_rope: bool = True):
    """Training / encoding path (self-attention, causal unless window=-1)."""
    b, s, d = x.shape
    q, k, v = _qkv(p, x, n_kv_heads)
    if positions is None:
        positions = jnp.arange(s)
    if use_rope:
        bq, sq_, kvh, g, hd = q.shape
        q = L.apply_rope(q.reshape(b, s, kvh * g, hd), positions,
                         rope_theta).reshape(b, s, kvh, g, hd)
        k = L.apply_rope(k, positions, rope_theta)
    causal = window != -1
    win = None if (window in (None, -1)) else window
    o = chunked_attention(q, k, v, q_positions=positions,
                          k_positions=positions, causal=causal,
                          window=win, chunk=chunk)
    return _out_proj(p, o, x.dtype)


def attention_encoder(p, x, *, n_kv_heads: int, chunk: int = 1024):
    """Bidirectional (encoder) self-attention, no rope by default callers."""
    return attention_forward(p, x, n_kv_heads=n_kv_heads, window=-1,
                             chunk=chunk, use_rope=False)


def cross_attention_forward(p, x, memory, *, n_kv_heads: int,
                            chunk: int = 1024):
    """Decoder cross-attention over encoder output ``memory`` (B, Sm, D)."""
    b, s, d = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", memory, p["wk"].astype(memory.dtype))
    v = jnp.einsum("bsd,dhk->bshk", memory, p["wv"].astype(memory.dtype))
    h, hd = q.shape[2], q.shape[3]
    g = h // n_kv_heads
    q = q.reshape(b, s, n_kv_heads, g, hd)
    o = chunked_attention(q, k, v, q_positions=jnp.arange(s),
                          k_positions=jnp.arange(memory.shape[1]),
                          causal=False, chunk=chunk)
    return _out_proj(p, o, x.dtype)


# ---------------------------------------------------------------------------
# KV cache (decode)
# ---------------------------------------------------------------------------

def init_cache(batch: int, capacity: int, n_kv_heads: int, head_dim: int,
               dtype) -> dict:
    return {
        "k": jnp.zeros((batch, capacity, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, capacity, n_kv_heads, head_dim), dtype),
    }


def cache_axes() -> dict:
    # decode cache is sharded batch over data axes, SEQUENCE over 'model'
    # (flash-decoding style) — uniform regardless of kv-head divisibility.
    return {"k": ("cache_batch", "cache_seq", None, None),
            "v": ("cache_batch", "cache_seq", None, None)}


def prefill_attention(p, x, *, n_kv_heads: int, rope_theta: float = 10000.0,
                      window: int | None = None, chunk: int = 1024):
    """Forward + return the populated cache (ring-truncated if windowed)."""
    b, s, d = x.shape
    q, k, v = _qkv(p, x, n_kv_heads)
    positions = jnp.arange(s)
    kvh, g, hd = q.shape[2], q.shape[3], q.shape[4]
    q = L.apply_rope(q.reshape(b, s, kvh * g, hd), positions,
                     rope_theta).reshape(b, s, kvh, g, hd)
    k = L.apply_rope(k, positions, rope_theta)
    win = None if (window in (None, -1)) else window
    o = chunked_attention(q, k, v, q_positions=positions,
                          k_positions=positions, causal=True, window=win,
                          chunk=chunk)
    out = _out_proj(p, o, x.dtype)
    if win is not None and win < s:
        cache = {"k": k[:, -win:], "v": v[:, -win:]}
    else:
        cache = {"k": k, "v": v}
    return out, cache


def decode_attention(p, x, cache, pos, *, n_kv_heads: int,
                     rope_theta: float = 10000.0, window: int | None = None,
                     chunk: int = 2048):
    """One-token decode.  x: (B, 1, D); pos: scalar int32 (current absolute
    position).  Returns (out (B,1,D), updated cache).

    Full cache: write at index pos (capacity must exceed pos at trace time
    is NOT required — pos is clamped; masking uses absolute positions).
    Ring cache (window): write at pos % W; all entries valid in steady state.
    """
    b = x.shape[0]
    q, k_new, v_new = _qkv(p, x, n_kv_heads)
    kvh, g, hd = q.shape[2], q.shape[3], q.shape[4]
    posv = jnp.full((1,), pos, jnp.int32)
    q = L.apply_rope(q.reshape(b, 1, kvh * g, hd), posv,
                     rope_theta).reshape(b, 1, kvh, g, hd)
    k_new = L.apply_rope(k_new, posv, rope_theta)

    cap = cache["k"].shape[1]
    win = None if (window in (None, -1)) else window
    if win is not None and cap <= win:
        slot = jnp.mod(pos, cap)
    else:
        slot = jnp.minimum(pos, cap - 1)
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype),
                                     (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype),
                                     (0, slot, 0, 0))

    if win is not None and cap <= win:
        # ring: every entry is within the window; positions are implicit and
        # rope was applied at write time — attend to all written slots.
        # Slots fill in order (token i -> i % cap), so until the ring wraps
        # only the first pos+1 slots hold real keys; masking the rest makes
        # cold-start / short-prompt decode exact instead of steady-state-only.
        k_positions = jnp.zeros((cap,), jnp.int32)  # pass-through (no causal)
        o = chunked_attention(q, k, v, q_positions=posv,
                              k_positions=k_positions, causal=False,
                              chunk=chunk,
                              k_valid_len=jnp.minimum(pos + 1, cap))
    else:
        k_positions = jnp.arange(cap)
        o = chunked_attention(q, k, v, q_positions=posv,
                              k_positions=k_positions, causal=True,
                              window=win, chunk=chunk,
                              k_valid_len=jnp.minimum(pos + 1, cap))
    out = _out_proj(p, o, x.dtype)
    return out, {"k": k, "v": v}
