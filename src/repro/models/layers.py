"""Shared neural layers: RMSNorm, RoPE, FFN (SwiGLU/GELU), embeddings.

Pure functional: ``init_*`` returns a param pytree; ``apply`` functions take
(params, inputs). Norms and softmaxes compute in fp32 regardless of the
bf16 parameter/compute policy.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig


def truncated_normal_init(key, shape, dtype, scale: float):
    return (scale * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


def match_vma(x, ref):
    """Make ``x``'s varying-manual-axes match ``ref``'s (shard_map VMA).

    Scan carries initialized from constants are device-invariant; when model
    code runs inside a partially-manual ``shard_map`` (e.g. the compressed
    gradient step) the carry must be marked varying over the manual axes its
    inputs vary over. No-op outside shard_map.
    """
    extra = jax.typeof(ref).vma - jax.typeof(x).vma
    return jax.lax.pcast(x, tuple(extra), to="varying") if extra else x


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * params["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate pairs (non-interleaved / llama layout).

    x: (..., S, H, D); positions: broadcastable to (..., S).
    """
    d = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(d, theta))  # (d/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs[None, :]  # (..., S, d/2)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., S, 1, d/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# FFN (SwiGLU / GELU-MLP)
# ---------------------------------------------------------------------------


def init_ffn(key, d_model: int, d_ff: int, dtype, activation: str = "silu") -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = 1.0 / np.sqrt(d_model)
    scale_out = 1.0 / np.sqrt(d_ff)
    p = {
        "w_up": truncated_normal_init(k2, (d_model, d_ff), dtype, scale_in),
        "w_down": truncated_normal_init(k3, (d_ff, d_model), dtype, scale_out),
    }
    if activation == "silu":  # SwiGLU needs the gate branch
        p["w_gate"] = truncated_normal_init(k1, (d_model, d_ff), dtype, scale_in)
    return p


def ffn(params: dict, x: jax.Array, activation: str = "silu") -> jax.Array:
    from repro.distributed.sharding import shard_act

    up = shard_act(x @ params["w_up"], "btf")
    if activation == "silu":
        h = jax.nn.silu(shard_act(x @ params["w_gate"], "btf")) * up
    elif activation == "gelu":
        h = jax.nn.gelu(up)
    else:
        raise ValueError(activation)
    return h @ params["w_down"]


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------


def init_embedding(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    p = {"tok": truncated_normal_init(k1, (cfg.vocab_size, cfg.d_model), cfg.param_dtype, 0.02)}
    if not cfg.tie_embeddings:
        p["lm_head"] = truncated_normal_init(
            k2, (cfg.d_model, cfg.vocab_size), cfg.param_dtype, 1.0 / np.sqrt(cfg.d_model)
        )
    return p


def embed_tokens(params: dict, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    return jnp.take(params["tok"], tokens, axis=0)


def lm_logits(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    from repro.distributed.sharding import shard_act

    w = params["tok"].T if cfg.tie_embeddings else params["lm_head"]
    logits = shard_act((x @ w).astype(jnp.float32), "btv")
    if cfg.logit_softcap:
        cap = cfg.logit_softcap
        logits = cap * jnp.tanh(logits / cap)
    return logits
