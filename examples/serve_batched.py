"""Batched serving demo: prefill a batch of prompts, then decode tokens
with the production serving engine (KV caches / SSM states per layer).

Uses a reduced xLSTM (O(1) decode state) and a reduced llama-family model
(full KV cache) to show both cache regimes.

The prompt is processed exactly once: ``engine.prefill`` builds the
caches and ``engine.pad_states_for_decode`` fits them onto the
capacity-(prompt+gen) decode layout (zero-padding short prompts, rolling
full sliding-window rings so slot = pos % cap), so decode starts straight
at the first generated position.

  PYTHONPATH=src python examples/serve_batched.py
"""
import time

import jax
import jax.numpy as jnp

from repro.configs import base
from repro.launch import compile_cache
from repro.models import transformer as T
from repro.serving import engine


def demo(arch: str, batch: int = 4, prompt_len: int = 24,
         gen_tokens: int = 8):
    cfg = base.get_smoke_config(arch)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(42)
    prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab)

    t0 = time.time()
    logits, states = jax.jit(
        lambda p, x: engine.prefill(p, cfg, x, chunk=16))(params, prompts)
    t_prefill = time.time() - t0

    # hand the prefill caches straight to decode, padded to a
    # capacity-(prompt+gen) layout — no token-by-token prompt replay
    capacity = prompt_len + gen_tokens
    states = jax.jit(lambda st: engine.pad_states_for_decode(
        cfg, st, prompt_len, capacity))(states)
    step = jax.jit(lambda p, tok, st, pos: engine.serve_step(
        p, cfg, tok, st, pos, chunk=16))
    t0 = time.time()
    generated = []
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for i in range(gen_tokens):
        generated.append(tok)
        logits, states = step(params, tok, states,
                              jnp.int32(prompt_len + i))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    t_decode = time.time() - t0
    gen = jnp.concatenate(generated, axis=1)

    print(f"[{arch}] batch={batch} prompt={prompt_len} gen={gen_tokens}")
    print(f"  prefill: {t_prefill * 1e3:.0f} ms   "
          f"decode: {t_decode / gen_tokens * 1e3:.0f} ms/tok")
    for b in range(min(batch, 2)):
        print(f"  seq[{b}]: ...{prompts[b, -4:].tolist()} -> "
              f"{gen[b].tolist()}")


def main():
    compile_cache.place()
    demo("tinyllama_1_1b")     # full KV cache
    demo("xlstm_1_3b")         # O(1) recurrent state
    demo("jamba_v0_1_52b")     # hybrid: ring/full caches + SSM states


if __name__ == "__main__":
    main()
