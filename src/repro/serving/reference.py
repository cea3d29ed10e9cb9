"""The seed's token-by-token decode loop, kept as the golden parity oracle.

This is the pre-engine serving path: prefill runs the prompt one token at
a time through ``decode_step`` (P dispatches for a P-token prompt), then
greedy decode continues a token at a time.  The engine's batched-prefill
path must produce token-for-token identical output to this loop
(tests/test_serving_parity.py); it stays here, not in launch/serve.py,
precisely so the fast path can never drift unnoticed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import decode_step, init_caches


def token_by_token_greedy(params, cfg: ModelConfig, prompts: jax.Array,
                          max_new: int, max_len: int,
                          return_logits: bool = False):
    """prompts: (B, P) int32.  Returns (B, max_new) generated tokens, and
    with ``return_logits`` also the (B, max_new, vocab_size) float32
    logits each token was chosen from."""
    b, p = prompts.shape
    caches = init_caches(cfg, b, max_len)
    step = jax.jit(lambda pr, tok, c, pos: decode_step(pr, cfg, tok, c, pos))

    for t in range(p):
        logits, caches = step(params, prompts[:, t:t + 1], caches,
                              jnp.full((b,), t, jnp.int32))
    out, seen = [], []
    for i in range(max_new):
        lg = logits[..., : cfg.vocab_size].astype(jnp.float32)
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        out.append(tok)
        seen.append(lg)
        if i == max_new - 1:
            break  # the seed loop discarded this step's logits anyway
        logits, caches = step(params, tok, caches,
                              jnp.full((b,), p + i, jnp.int32))
    tokens = jnp.concatenate(out, axis=1)
    if return_logits:
        return tokens, jnp.concatenate(seen, axis=1)
    return tokens
