"""Serving drivers: batched generation loop over prefill + decode_step.

The per-token loop runs one fused jitted dispatch per token
(:func:`_fused_decode_step`): the decode step, the RNG fold and the token
sampling all live in a single module-scope compiled program (one trace per
(config, shapes, temperature, dense_moe) for the process lifetime) with
the carried cache donated. With ``kv_compress=`` the prefilled
global-attention caches are converted to decode-native compressed caches
(:mod:`repro.serve.kv_cache`) before the loop, so the same single program
folds generated tokens into the streaming factorization as it decodes.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import decode_step, init_cache, prefill
from repro.models.config import ModelConfig

from .kv_compress import KVCompressionConfig
from .kv_cache import compress_prefill_cache


def sample_token(key, logits: jax.Array, temperature: float = 0.0) -> jax.Array:
    """logits (B, 1, V) → (B, 1) int32.

    A row with any non-finite logit samples ``-1``, an id outside the
    vocabulary, so corrupt logits surface in the output instead of as a
    plausible token (``argmax`` over NaNs would return 0).
    """
    if temperature <= 0.0:
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        tok = jax.random.categorical(key, logits[:, 0] / temperature)[:, None].astype(jnp.int32)
    return jnp.where(jnp.all(jnp.isfinite(logits), axis=-1), tok, -1)


@partial(jax.jit, static_argnums=(1, 6, 7), donate_argnums=(2,))
def _fused_decode_step(params, cfg, cache, tok, key, step_i, temperature, dense_moe):
    # single dispatch per token: decode + RNG fold + sampling in one
    # program. The key chain reproduces the legacy host loop exactly:
    # key_{i+1} = fold_in(key_i, i), sampled with key_{i+1}.
    key_i = jax.random.fold_in(key, step_i)
    logits, cache = decode_step(params, cfg, cache, tok, dense_moe=dense_moe)
    return sample_token(key_i, logits, temperature), cache, key_i


def generate(
    params,
    cfg: ModelConfig,
    prompt: jax.Array,
    n_tokens: int,
    *,
    key=None,
    temperature: float = 0.0,
    vision: Optional[jax.Array] = None,
    dense_moe: bool = False,
    kv_compress: Optional[KVCompressionConfig] = None,
    registry=None,
):
    """Greedy/temperature generation. prompt: (B, S). Returns (B, n_tokens).

    ``kv_compress`` switches every global-attention layer onto the
    decode-native compressed cache after prefill (see
    :func:`repro.serve.kv_cache.compress_prefill_cache`; the conversion key
    is ``fold_in(key, n_tokens)``, disjoint from the sampling chain).
    ``registry`` forwards a :class:`repro.obs.metrics.MetricsRegistry` to
    the conversion for cache-size metrics.
    """
    B, S = prompt.shape
    key = key if key is not None else jax.random.key(0)
    cache_len = S + n_tokens
    logits, cache = prefill(params, cfg, prompt, cache_len, vision=vision, dense_moe=dense_moe)
    if kv_compress is not None:
        ckey = jax.random.fold_in(key, n_tokens)
        cache = compress_prefill_cache(ckey, cfg, cache, kv_compress, registry=registry)

    toks = [sample_token(key, logits, temperature)]
    for i in range(n_tokens - 1):
        tok, cache, key = _fused_decode_step(
            params, cfg, cache, toks[-1], key, jnp.asarray(i, jnp.int32),
            temperature, dense_moe,
        )
        toks.append(tok)
    return jnp.concatenate(toks, axis=1)
