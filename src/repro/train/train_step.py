"""Train-step builders: plain pjit path and GMR-compressed-gradient path.

* :func:`make_train_step` — standard SPMD step: value_and_grad under jit,
  DP reduction inserted by the partitioner, AdamW update. Knobs: remat
  policy, microbatch accumulation.
* :func:`make_compressed_train_step` — the paper's Algorithm 1 replacing
  the dense DP all-reduce (train/grad_compress.py). Built with
  ``jax.shard_map`` *manual* over the DP axes and *auto* over `model`, so
  tensor parallelism stays partitioner-managed while DP communication is
  explicit and sketched.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import (
    ParallelismRules,
    batch_pspec,
    param_pspecs,
)
from repro.models import train_logits
from repro.models.config import ModelConfig

from .grad_compress import CompressionConfig, compressed_mean_grads, init_error_state, is_compressible
from .optimizer import OptimizerConfig, adamw_update, init_opt_state


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token NLL. logits (B,S,V) fp32, labels (B,S) int32.

    The gold logit is gathered by masked reduction, not take_along_axis:
    with a vocab-sharded V axis the mask+sum stays local per shard and the
    partitioner finishes with a psum, whereas a gather on the sharded axis
    forces an all-gather of the full logits.
    """
    logz = jax.nn.logsumexp(logits, axis=-1)
    V = logits.shape[-1]
    onehot = labels[..., None] == jax.lax.broadcasted_iota(jnp.int32, (1, 1, V), 2)
    gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    return jnp.mean(logz - gold)


def make_loss_fn(cfg: ModelConfig, *, remat=None, dense_moe=False):
    def loss_fn(params, batch):
        logits, aux = train_logits(
            params, cfg, batch["tokens"], batch.get("vision"), dense_moe=dense_moe, remat=remat
        )
        ce = cross_entropy(logits[:, :-1], batch["labels"][:, 1:] if "labels" in batch else batch["tokens"][:, 1:])
        loss = ce + cfg.router_aux_weight * aux
        return loss, {"ce": ce, "aux": aux}

    return loss_fn


def _grads_microbatched(loss_fn, params, batch, n_micro: int):
    """lax.scan gradient accumulation over leading-batch splits."""
    if n_micro <= 1:
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        return loss, metrics, grads

    def resplit(x):
        b = x.shape[0]
        return x.reshape(n_micro, b // n_micro, *x.shape[1:])

    micro = {k: resplit(v) for k, v in batch.items()}

    def body(carry, mb):
        acc, loss_acc = carry
        (loss, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
        acc = jax.tree.map(jnp.add, acc, g)
        return (acc, loss_acc + loss), metrics

    from repro.models.layers import match_vma

    ref = batch["tokens"]
    zeros = jax.tree.map(lambda p: match_vma(jnp.zeros(p.shape, jnp.float32), ref), params)
    (gsum, loss_sum), metrics = jax.lax.scan(body, (zeros, match_vma(jnp.asarray(0.0), ref)), micro)
    grads = jax.tree.map(lambda g: g / n_micro, gsum)
    metrics = jax.tree.map(lambda m: m[-1], metrics)
    return loss_sum / n_micro, metrics, grads


def init_train_state(key, cfg: ModelConfig, oc: OptimizerConfig):
    from repro.models import init_params

    params = init_params(key, cfg)
    return {"params": params, "opt": init_opt_state(params, oc)}


def make_train_step(
    cfg: ModelConfig,
    oc: OptimizerConfig,
    *,
    remat: Optional[str] = "dots",
    microbatch: int = 1,
    dense_moe: bool = False,
):
    """Plain SPMD train step: (state, batch) → (state, metrics)."""
    loss_fn = make_loss_fn(cfg, remat=remat, dense_moe=dense_moe)

    def train_step(state, batch):
        loss, metrics, grads = _grads_microbatched(loss_fn, state["params"], batch, microbatch)
        params, opt, opt_metrics = adamw_update(grads, state["opt"], state["params"], oc)
        metrics = {"loss": loss, **metrics, **opt_metrics}
        return {"params": params, "opt": opt}, metrics

    return train_step


def make_compressed_train_step(
    cfg: ModelConfig,
    oc: OptimizerConfig,
    ccfg: CompressionConfig,
    mesh: Mesh,
    rules: ParallelismRules,
    *,
    remat: Optional[str] = "dots",
    dense_moe: bool = False,
):
    """GMR-compressed DP step. State gains an `err` EF pytree with a
    leading worker dim (sharded over the DP axes); `key` drives the shared
    per-step sketches.

    Returns (train_step, make_state_specs) where train_step(state, batch, key).
    """
    if rules.fsdp:
        raise ValueError(
            "gradient compression replaces the DP all-reduce; with FSDP the DP "
            "reduction is a reduce-scatter of sharded weights — unsupported combination"
        )
    loss_fn = make_loss_fn(cfg, remat=remat, dense_moe=dense_moe)
    dp = rules.dp_axes

    def inner(params, opt, err, batch, key):
        # local grads (batch is per-DP-shard here; no automatic DP psum since
        # the dp axes are manual)
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        err_local = jax.tree.map(lambda e: e[0], err)  # drop worker dim
        # resolve EF placeholders for non-compressible leaves to zeros_like(grad)
        err_local = jax.tree.map(
            lambda e, g: e if is_compressible(g, ccfg) else jnp.zeros(g.shape, jnp.float32),
            err_local,
            grads,
        )
        gbar, new_err, cstats = compressed_mean_grads(
            grads, err_local, key, ccfg, dp, with_stats=True
        )
        new_err = jax.tree.map(
            lambda e, g: (e if is_compressible(g, ccfg) else jnp.zeros((1,), jnp.float32))[None],
            new_err,
            grads,
        )
        params, opt, opt_metrics = adamw_update(gbar, opt, params, oc)
        nw = 1
        for a in dp:
            nw *= jax.lax.axis_size(a)
        # psum local metrics so every output except `err` is dp-invariant
        # (check_vma=True verifies this; partial-manual + check_vma=False is
        # broken in jax 0.8.2 — see DESIGN.md §Environment). The per-step
        # compression-quality stats ride along: worker-varying ones (EF norm,
        # reconstruction error) become DP means, config-static ones stay put.
        metrics = {k: jax.lax.psum(v, dp) / nw for k, v in {**metrics, **cstats}.items()}
        metrics = {"loss": jax.lax.psum(loss, dp) / nw, **metrics, **opt_metrics}
        return params, opt, new_err, metrics

    def err_spec(e):
        return P(dp, *([None] * (e.ndim - 1)))

    def train_step(state, batch, key):
        params, opt, err = state["params"], state["opt"], state["err"]
        pspec = jax.tree.map(lambda _: P(), params)
        ospec = jax.tree.map(lambda _: P(), opt)
        espec = jax.tree.map(err_spec, err)
        bspec = {k: P(dp, *([None] * (v.ndim - 1))) for k, v in batch.items()}
        mspec = P()

        metric_keys = (
            "loss", "ce", "aux", "grad_norm", "lr",
            "comp/wire_floats", "comp/dense_floats", "comp/ratio",
            "comp/ef_norm", "comp/rel_err",
        )
        fn = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(pspec, ospec, espec, bspec, P()),
            out_specs=(pspec, ospec, espec, {k: mspec for k in metric_keys}),
            axis_names=set(dp),
            check_vma=True,
        )
        params, opt, err, metrics = jax.jit(fn)(params, opt, err, batch, key)
        return {"params": params, "opt": opt, "err": err}, metrics

    def init_err(params):
        nw = int(np.prod([mesh.shape[a] for a in dp]))
        return init_error_state(params, ccfg, nw)

    return train_step, init_err
