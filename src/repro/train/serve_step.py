"""Serving steps: batched prefill and single-token decode (greedy/temperature).

``decode_32k`` / ``long_500k`` cells lower ``decode_step`` — one new token
against a KV/state cache of the shape's seq_len — per the assignment.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.common import ModelConfig
from repro.models import layers as L
from repro.obs import runtime

runtime.record_compiles()


def make_prefill_step(entry, cfg: ModelConfig, *, max_len: int,
                      policy: L.Policy = L.Policy(),
                      cache_dtype=jnp.bfloat16, logits_mode: str = "all"):
    module = entry.module

    def prefill_step(params, tokens, frontend=None):
        kw = {} if frontend is None else {"frontend": frontend}
        out = module.prefill(params, cfg, tokens, max_len=max_len,
                             policy=policy, cache_dtype=cache_dtype,
                             logits_mode=logits_mode, **kw)
        next_logits = out["logits"][:, -1]
        return {"next_token_logits": next_logits, "cache": out["cache"]}

    return prefill_step


def make_decode_step(entry, cfg: ModelConfig, *,
                     policy: L.Policy = L.Policy(), greedy: bool = True,
                     temperature: float = 1.0):
    module = entry.module

    def decode_step(params, cache, tokens, rng=None):
        logits, new_cache = module.decode_step(params, cfg, tokens, cache,
                                               policy=policy)
        last = logits[:, -1]
        if greedy:
            nxt = jnp.argmax(last, axis=-1)
        else:
            nxt = jax.random.categorical(rng, last / temperature, axis=-1)
        return nxt[:, None].astype(jnp.int32), new_cache

    return decode_step
