"""Plain float32 reference of a decoder-only language model: Mistral-7B
and OLMoE-1B-7B, as their configuration files state them.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: RMSNorm, rotary
embedding (rotate-half), grouped-query causal attention with a full
softmax per key/value head, a SiLU-gated FFN or a top-k routed mixture of
experts, an untied head and mean next-token cross-entropy.  It imports
nothing of the program.  Each function takes the weights of one layer as
a dict keyed by the leaf names of ``lagsbench.weights`` (``attn.wq``,
``ffn.w_up``, ``moe.router``, ``ln_attn.scale``), cast to float32.

What the program computes differently from the published models is
stated in each configuration file under ``departures``; the keys that
state it (``rms_norm_eps``, ``norm_topk_prob``, ``capacity_factor``) are
read from the file's ``as_run`` view (``lagsbench.spec.as_run``), so this
reference computes the configuration as run.

``prec="fp8"`` is the control: every matrix product takes its operands,
and its backward pass its cotangent, rounded to float8 e4m3 with one
scale per tensor.

``flops_per_token`` counts the model's work for ``step_mfu``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
E4M3_MAX = 448.0
#: Loss weight of the mixture-of-experts load-balance term (the
#: program's ``aux_weight``; OLMoE's ``router_aux_loss_coef``).
AUX_KEY = "router_aux_loss_coef"


def _q8(x):
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, E4M3_MAX / amax, 1.0)
    return (x * s).astype(jnp.float8_e4m3fn).astype(F32) / s


@jax.custom_vjp
def fp8(x):
    return _q8(x)


def _fp8_fwd(x):
    return _q8(x), None


def _fp8_bwd(_, g):
    return (_q8(g),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


def mm(eq: str, a, b, prec: str):
    if prec == "fp8":
        a, b = fp8(a), fp8(b)
    return jnp.einsum(eq, a, b, precision=lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def rms_norm(x, offset, eps: float):
    """RMSNorm; the weight is stored as its offset from 1."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * (1.0 + offset)


def rope(x, theta: float):
    """Rotary embedding, rotate-half form.  x: (B, S, heads, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(w, x, cfg: dict, prec: str):
    b, s, _ = x.shape
    kv = int(cfg["num_key_value_heads"])
    h = int(cfg["num_attention_heads"])
    hd = int(cfg["head_dim"])
    theta = float(cfg["rope_theta"])
    q = rope(mm("bsd,dhk->bshk", x, w["attn.wq"], prec), theta)
    k = rope(mm("bsd,dhk->bshk", x, w["attn.wk"], prec), theta)
    v = mm("bsd,dhk->bshk", x, w["attn.wv"], prec)
    g = h // kv
    qg = q.reshape(b, s, kv, g, hd).transpose(2, 0, 1, 3, 4)
    kg, vg = k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_kv_head(args):
        qj, kj, vj = args                   # (B,S,G,hd), (B,S,hd) x2
        sc = mm("bqgd,bkd->bgqk", qj, kj, prec) / math.sqrt(hd)
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return mm("bgqk,bkd->bqgd", p, vj, prec)

    o = lax.map(jax.checkpoint(one_kv_head), (qg, kg, vg))  # (KV,B,S,G,hd)
    o = o.transpose(1, 2, 0, 3, 4).reshape(b, s, h, hd)
    return mm("bshk,hkd->bsd", o, w["attn.wo"], prec)


def ffn(w, x, prec: str):
    gate = mm("bsd,df->bsf", x, w["ffn.w_gate"], prec)
    up = mm("bsd,df->bsf", x, w["ffn.w_up"], prec)
    return mm("bsf,fd->bsd", jax.nn.silu(gate) * up, w["ffn.w_down"], prec)


def moe(w, x, cfg: dict, prec: str):
    """Top-k routed experts with the configuration's capacity rule.

    A token's k-th choice takes the next free slot of its expert, in
    token order then choice order; choices past ``capacity_factor * T *
    k / E`` slots are dropped.  Returns (output, load-balance term)."""
    b, s, d = x.shape
    t = b * s
    e = int(cfg["num_experts"])
    top = int(cfg["num_experts_per_tok"])
    xt = x.reshape(t, d)
    probs = jax.nn.softmax(mm("td,de->te", xt, w["moe.router"], prec), -1)
    vals, idx = lax.top_k(probs, top)
    if cfg["norm_topk_prob"]:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    cap = max(1, int(float(cfg["capacity_factor"]) * t * top / e))
    flat_e = idx.reshape(-1)
    onehot = (flat_e[:, None] == jnp.arange(e)[None, :]).astype(jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                              flat_e[:, None], axis=1)[:, 0]
    keep = pos < cap
    slot = jnp.where(keep, pos, cap)        # cap = out of range: dropped
    tok = jnp.repeat(jnp.arange(t), top)
    tok_table = jnp.full((e, cap), t).at[flat_e, slot].set(tok, mode="drop")
    w_table = jnp.zeros((e, cap), F32).at[flat_e, slot].set(
        vals.reshape(-1), mode="drop")
    xin = jnp.concatenate([xt, jnp.zeros((1, d), F32)])[tok_table]
    gate = mm("ecd,edf->ecf", xin, w["moe.w_gate"], prec)
    up = mm("ecd,edf->ecf", xin, w["moe.w_up"], prec)
    y = mm("ecf,efd->ecd", jax.nn.silu(gate) * up, w["moe.w_down"], prec)
    out = jnp.zeros((t + 1, d), F32).at[tok_table].add(
        y * w_table[..., None])[:t]
    frac = lax.stop_gradient(onehot.sum(0) / (t * top))
    aux = e * jnp.sum(probs.mean(0) * frac)
    return out.reshape(b, s, d), aux


def block(w, x, cfg: dict, prec: str = "f32"):
    """One decoder layer: (x_next, load-balance term)."""
    eps = float(cfg["rms_norm_eps"])
    x = x + attention(w, rms_norm(x, w["ln_attn.scale"], eps), cfg, prec)
    h = rms_norm(x, w["ln_ffn.scale"], eps)
    if int(cfg.get("num_experts", 0)):
        out, aux = moe(w, h, cfg, prec)
    else:
        out, aux = ffn(w, h, prec), jnp.zeros((), F32)
    return x + out, aux


def embed(w, tokens):
    return w["embed.embedding"][tokens]


def head_loss(w, x, labels, cfg: dict, prec: str = "f32", chunk: int = 512):
    """Summed next-token cross-entropy over positions with a label >= 0,
    and their count."""
    h = rms_norm(x, w["final_norm.scale"], float(cfg["rms_norm_eps"]))
    b, s, d = h.shape
    chunk = min(chunk, s)
    n = s // chunk
    hc = h.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    yc = labels.reshape(b, n, chunk).transpose(1, 0, 2)

    def one(args):
        hh, yy = args
        logits = mm("bsd,dv->bsv", hh, w["lm_head.w"], prec)
        gold = jnp.take_along_axis(logits, jnp.maximum(yy, 0)[..., None],
                                   axis=-1)[..., 0]
        m = (yy >= 0).astype(F32)
        return (jnp.sum((jax.nn.logsumexp(logits, -1) - gold) * m),
                jnp.sum(m))

    ce, cnt = lax.map(jax.checkpoint(one), (hc, yc))
    return jnp.sum(ce), jnp.sum(cnt)


def aux_weight(cfg: dict) -> float:
    return float(cfg.get(AUX_KEY, 0.0)) if int(cfg.get("num_experts", 0)) \
        else 0.0


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Matrix-multiply FLOP of the forward and backward passes (3x the
    forward) per trained token.  Forward per layer: the q/k/v/o
    projections, causal attention (QK^T and PV over the ``S/2`` earlier
    positions on average: ``2 * S * H * hd``), and the FFN (three
    matrices) or, for a mixture of experts, the router and the ``top_k``
    experts a token is sent to; then the output head.  The embedding
    lookup, norms, softmax and recomputation are not counted."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    kv = int(cfg["num_key_value_heads"])
    hd = int(cfg["head_dim"])
    f = int(cfg["intermediate_size"])
    v = int(cfg["vocab_size"])
    n_layers = int(cfg["num_hidden_layers"])
    proj = 2 * d * hd * (h + 2 * kv) + 2 * h * hd * d
    attn = 2 * seq_len * h * hd
    e = int(cfg.get("num_experts", 0))
    if e:
        ffn = 2 * d * e + int(cfg["num_experts_per_tok"]) * 3 * 2 * d * f
    else:
        ffn = 3 * 2 * d * f
    head = 2 * d * v
    return 3.0 * (n_layers * (proj + attn + ffn) + head)
