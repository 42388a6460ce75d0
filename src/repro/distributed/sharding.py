"""Logical-axis sharding rules (MaxText-style) → PartitionSpecs per tensor.

The mesh is (pod?, data, model). Policy knobs per arch live in
``ParallelismRules``, not in model code.

Conventions:
* TP ("model" axis): attention q/o width, FFN hidden, MoE expert dim,
  vocab dim of the embedding/lm_head, Mamba-2 inner width / heads.
* DP ("pod","data"): the batch dim of activations.
* FSDP (optional): weights additionally sharded over the data axes on
  their non-TP dim (kimi-k2-1t, llama-vision-90b — TP-only shards exceed
  a v5e's 16 GB HBM).
* A dim is only sharded if divisible by the axis size — otherwise the rule
  silently degrades to replication (recorded by ``explain()``).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParallelismRules:
    tp_axis: str = "model"
    dp_axes: Tuple[str, ...] = ("data",)  # ("pod","data") on the multi-pod mesh
    fsdp: bool = False
    fsdp_axes: Tuple[str, ...] = ("data",)
    shard_vocab: bool = True
    # sequence parallelism (§Perf C1): shard the S axis of activations over
    # tp_axis and replicate weights (tp_enabled=False). Wins for SSM prefill,
    # where cross-shard traffic is only conv halos + chunk states.
    tp_enabled: bool = True
    seq_parallel: bool = False

    def with_mesh(self, mesh: Mesh) -> "ParallelismRules":
        names = tuple(mesh.axis_names)
        dp = tuple(a for a in ("pod", "data") if a in names)
        return dataclasses.replace(self, dp_axes=dp, fsdp_axes=("data",))


# leaf-name → semantic layout of the LAST dims. Semantics:
#   tp   — shard over tp_axis;   fsdp — shard over fsdp_axes when rules.fsdp
#   ep   — expert dim over tp_axis;   vocab — over tp_axis when shard_vocab
#   -    — never sharded
_LEAF_LAYOUTS = {
    # attention / generic projections: (in, out)
    "w_q": ("fsdp", "tp"),
    "w_k": ("fsdp", "tp"),
    "w_v": ("fsdp", "tp"),
    "w_o": ("tp", "fsdp"),
    # FFN
    "w_gate": ("fsdp", "tp"),
    "w_up": ("fsdp", "tp"),
    "w_down": ("tp", "fsdp"),
    # embedding / head
    "tok": ("vocab", "fsdp"),
    "lm_head": ("fsdp", "vocab"),
    # MLA
    "w_dkv": ("fsdp", "-"),
    "w_uk": ("-", "tp"),
    "w_uv": ("-", "tp"),
    # Mamba-2
    "w_z": ("fsdp", "tp"),
    "w_x": ("fsdp", "tp"),
    "w_bc": ("fsdp", "-"),
    "w_dt": ("fsdp", "-"),
    "conv_x_w": ("-", "tp"),
    "conv_x_b": ("tp",),
    "conv_bc_w": ("-", "-"),
    "conv_bc_b": ("-",),
    "dt_bias": ("-",),
    "a_log": ("-",),
    "d_skip": ("-",),
    "norm_scale": ("tp",),
    # MoE
    "router": ("fsdp", "-"),
    # misc
    "vision_proj": ("-", "fsdp"),
    "gate": (),
    "scale": ("-",),
}

# MoE expert tensors are 3-D (E, in, out) and shadow FFN names — resolved by rank.
_MOE_LAYOUTS = {
    "w_gate": ("ep", "fsdp", "-"),
    "w_up": ("ep", "fsdp", "-"),
    "w_down": ("ep", "-", "fsdp"),
}


def _axis_for(sem: str, rules: ParallelismRules):
    if sem == "dp":
        return rules.dp_axes
    if sem == "tp" or sem == "ep":
        return rules.tp_axis if rules.tp_enabled else None
    if sem == "vocab":
        return rules.tp_axis if (rules.shard_vocab and rules.tp_enabled) else None
    if sem == "fsdp":
        return rules.fsdp_axes if rules.fsdp else None
    if sem == "seq":
        return rules.tp_axis if rules.seq_parallel else None
    return None


def _divisible(dim: int, axis, mesh: Mesh) -> bool:
    if axis is None:
        return True
    sizes = [mesh.shape[a] for a in (axis if isinstance(axis, tuple) else (axis,))]
    return dim % int(np.prod(sizes)) == 0


def leaf_pspec(path, leaf, rules: ParallelismRules, mesh: Mesh) -> P:
    """PartitionSpec for one parameter leaf based on its path tail + rank."""
    name = None
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            name = entry.key
            break
    in_moe = any(
        isinstance(e, jax.tree_util.DictKey) and e.key == "ffn" for e in path
    ) and leaf.ndim >= 3 and name in _MOE_LAYOUTS
    layout = _MOE_LAYOUTS[name] if in_moe else _LEAF_LAYOUTS.get(name)
    if layout is None:
        return P()
    # leaves inside stacked scan segments carry a leading repeat dim
    extra = leaf.ndim - len(layout)
    spec = [None] * extra
    for sem, dim in zip(layout, leaf.shape[extra:]):
        axis = _axis_for(sem, rules)
        spec.append(axis if _divisible(dim, axis, mesh) else None)
    return P(*spec)


def param_shardings(params, rules: ParallelismRules, mesh: Mesh):
    """NamedSharding pytree matching ``params``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, leaf_pspec(path, leaf, rules, mesh)), params
    )


def param_pspecs(params, rules: ParallelismRules, mesh: Mesh):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf_pspec(path, leaf, rules, mesh), params
    )


# ---------------------------------------------------------------------------
# Activation / input specs
# ---------------------------------------------------------------------------


def batch_pspec(rules: ParallelismRules) -> P:
    """(B, S) token batches: batch over DP axes (+ seq over tp_axis in SP mode)."""
    return P(rules.dp_axes, rules.tp_axis if rules.seq_parallel else None)


def cache_pspec(path, leaf, rules: ParallelismRules, mesh: Mesh, *, seq_shard: bool) -> P:
    """KV-cache leaves.

    Default: batch over DP, KV-heads over TP when divisible.
    ``seq_shard`` (long_500k, batch=1): sequence dim over the DP axes
    instead — distributed decode attention (LSE combine via SPMD).
    """
    name = None
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            name = entry.key
            break
    extra_dims = leaf.ndim
    if name in ("k", "v"):  # (B, S|window|patches, KV, hd) (+repeat prefix)
        extra = leaf.ndim - 4
        b, s, kv, hd = leaf.shape[extra:]
        spec = [None] * extra
        if seq_shard:
            spec += [None, rules.dp_axes if _divisible(s, rules.dp_axes, mesh) else None]
        else:
            spec += [rules.dp_axes if _divisible(b, rules.dp_axes, mesh) else None, None]
        spec += [rules.tp_axis if _divisible(kv, rules.tp_axis, mesh) else None, None]
        return P(*spec)
    if name == "latent":  # (B, S, r+rope)
        extra = leaf.ndim - 3
        b, s, r = leaf.shape[extra:]
        spec = [None] * extra
        if seq_shard:
            spec += [None, rules.dp_axes if _divisible(s, rules.dp_axes, mesh) else None, None]
        else:
            spec += [rules.dp_axes if _divisible(b, rules.dp_axes, mesh) else None, None, None]
        return P(*spec)
    if name == "ssm":  # (B, H, N, P)
        extra = leaf.ndim - 4
        b, h, n, p_ = leaf.shape[extra:]
        spec = [None] * extra
        spec += [rules.dp_axes if _divisible(b, rules.dp_axes, mesh) else None]
        spec += [rules.tp_axis if _divisible(h, rules.tp_axis, mesh) else None, None, None]
        return P(*spec)
    if name in ("conv_x", "conv_bc"):  # (B, K-1, C)
        extra = leaf.ndim - 3
        b, k, cdim = leaf.shape[extra:]
        spec = [None] * extra + [rules.dp_axes if _divisible(b, rules.dp_axes, mesh) else None, None]
        spec += [rules.tp_axis if (name == "conv_x" and _divisible(cdim, rules.tp_axis, mesh)) else None]
        return P(*spec)
    if name == "length":
        return P()
    return P()


def cache_shardings(cache, rules: ParallelismRules, mesh: Mesh, *, seq_shard: bool = False):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, cache_pspec(path, leaf, rules, mesh, seq_shard=seq_shard)
        ),
        cache,
    )


# ---------------------------------------------------------------------------
# Activation sharding constraints (context-scoped, set at trace time)
# ---------------------------------------------------------------------------

_ACT_CTX: contextvars.ContextVar = contextvars.ContextVar("repro_act_sharding", default=None)

# semantic layouts for the LAST dims of an activation; leading dims → None.
#   dp — batch over the DP axes; tp — over the model axis; "-" — unsharded
_ACT_KINDS = {
    "btd": ("dp", "seq", "-"),  # (B, S, D) residual stream
    "btf": ("dp", "seq", "tp"),  # (B, S, F) FFN hidden
    "bthd": ("dp", "seq", "tp", "-"),  # (B, S, H, hd) per-head
    "btv": ("dp", "seq", "tp"),  # (B, S, V) logits
    "pecd": ("dp", "tp", "-", "-"),  # (P, E, cap, D) MoE dispatch: token
    #                            groups over data, experts over model (without
    #                            the dp dim every data rank recomputes all
    #                            experts — measured 16x on kimi, §Perf B5)
    "te": ("dp", "-"),  # (T, E) router logits
}


@contextlib.contextmanager
def activation_sharding(mesh: Mesh, rules: ParallelismRules):
    """Enable ``shard_act`` constraints while tracing model code."""
    tok = _ACT_CTX.set((mesh, rules))
    try:
        yield
    finally:
        _ACT_CTX.reset(tok)


def shard_act(x, kind: str):
    """with_sharding_constraint by semantic kind; no-op outside the context
    and for dims not divisible by their assigned axes. Axes the value is
    already *manual* over (inside shard_map, e.g. the compressed-gradient
    step's dp axes) are dropped from the constraint — they are per-shard
    there, not partitioner-managed."""
    ctx = _ACT_CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    layout = _ACT_KINDS[kind]
    if x.ndim < len(layout):
        return x
    if jax.typeof(x).vma:
        # inside a shard_map manual region constraints over the (auto-typed)
        # mesh are rejected for vma-carrying values; the partial-auto
        # partitioner propagates TP shardings from the parameters instead
        return x
    extra = x.ndim - len(layout)
    spec = [None] * extra
    for sem, dim in zip(layout, x.shape[extra:]):
        axis = _axis_for(sem, rules)
        spec.append(axis if (axis and _divisible(dim, axis, mesh)) else None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def explain(params, rules: ParallelismRules, mesh: Mesh) -> str:
    """Human-readable table of leaf → spec (+ replication fallbacks)."""
    lines = []

    def visit(path, leaf):
        spec = leaf_pspec(path, leaf, rules, mesh)
        key = jax.tree_util.keystr(path)
        lines.append(f"{key:60s} {str(leaf.shape):24s} {spec}")
        return leaf

    jax.tree_util.tree_map_with_path(visit, params)
    return "\n".join(lines)
