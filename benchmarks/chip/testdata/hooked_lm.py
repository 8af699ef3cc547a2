"""Reference of the test fixture ``hooked-lm.json``: a decoder LM whose
source names its head size ``attention_head_size``, a key the harness
does not know, so its file states the program's ``head_dim`` under
``program.model`` and this module reads the source's key.  It carries
every hook a configuration's reference module may: ``flops_per_token``,
a ``block`` that returns readings of its own beside the layer's output,
and ``numbers``."""
import jax.numpy as jnp

from lagsbench.reference import load_module
from lagsbench.spec import BENCH_DIR

_LM = load_module(BENCH_DIR / "configs" / "decoder_lm.py")
embed = _LM.embed
head_loss = _LM.head_loss


def _as_lm(cfg: dict) -> dict:
    return {**cfg, "head_dim": int(cfg["attention_head_size"])}


def block(w, x, cfg: dict, prec: str = "f32"):
    y, aux = _LM.block(w, x, _as_lm(cfg), prec)
    return y, aux, {"out_rms": jnp.sqrt(jnp.mean(y * y))}


def aux_weight(cfg: dict) -> float:
    return 0.0


def flops_per_token(cfg: dict, seq_len: int) -> float:
    return _LM.flops_per_token(_as_lm(cfg), seq_len)


def numbers(prog: dict, ref: dict) -> dict:
    """``history_loss_gap``: the largest relative gap between the loss a
    step's history entry holds and the reference's."""
    return {"history_loss_gap": max(
        abs(p["loss"] - r["loss"]) / abs(r["loss"])
        for p, r in zip(prog["history"], ref["history"], strict=True))}
