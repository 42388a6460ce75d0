"""GMR gradient compression — the paper's Algorithm 1 as a distributed-
training communication primitive.

Data-parallel all-reduce of a weight gradient ``G (m×n)`` moves m·n floats
per step per worker. Instead each worker:

  1. draws the *same* sketches from a step-shared seed:
     Ω (n×c), Ψ (r×m) Gaussian outer sketches and S_C (s_c×m), S_R (s_r×n)
     inner sketches (paper §6.1 protocol: c=r, s=a·c);
  2. forms  C = GΩ,  R = ΨG,  M = S_C G S_Rᵀ  — all *linear* in G;
  3. psums (C, R, M)  — (m+n)·c + s² floats instead of m·n;
  4. reconstructs  Ĝ = C · (S_C C)† M (R S_Rᵀ)† · R  (Algorithm 1 verbatim,
     with A = ΣᵢGᵢ, never materialized);
  5. keeps a local error-feedback residual e ← (G+e) − Ĝ folded into the
     next step (Ĝ is biased; EF restores convergence — standard for
     PowerSGD-family compressors; validated in examples/train_lm.py).

Linearity of step 2 is what makes the compressed psum exact:
``Σᵢ(Gᵢ Ω) = (Σᵢ Gᵢ) Ω`` — the sketch of the sum is the sum of sketches.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gmr import fast_gmr_core
from repro.core.sketching import draw_sketch


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    rank: int = 64  # c = r — outer sketch size
    sketch_factor: int = 4  # a: inner sketch size s = a·rank (paper §6.1)
    min_dim: int = 512  # compress only 2-D leaves with both dims ≥ this
    inner_sketch: str = "gaussian"
    error_feedback: bool = True

    @property
    def s(self) -> int:
        return self.sketch_factor * self.rank


def is_compressible(leaf, ccfg: CompressionConfig) -> bool:
    """2-D weights, or scan-stacked (L, m, n) weights (compressed per layer
    with shared sketches — linearity holds independently per slice)."""
    if leaf.ndim == 2:
        return min(leaf.shape) >= ccfg.min_dim
    if leaf.ndim == 3:
        return min(leaf.shape[1:]) >= ccfg.min_dim
    return False


def compression_ratio(params, ccfg: CompressionConfig) -> float:
    """Dense vs compressed DP-all-reduce volume over the whole tree."""
    dense = comp = 0
    for leaf in jax.tree.leaves(params):
        n = int(np.prod(leaf.shape))
        dense += n
        if is_compressible(leaf, ccfg):
            L = leaf.shape[0] if leaf.ndim == 3 else 1
            m, nn = leaf.shape[-2:]
            comp += L * ((m + nn) * ccfg.rank + ccfg.s * ccfg.s)
        else:
            comp += n
    return dense / comp


def _sketches_for(key, shape, ccfg: CompressionConfig):
    m, n = shape
    c = ccfg.rank
    ks = jax.random.split(key, 4)
    omega = draw_sketch(ks[0], "gaussian", c, n)  # right outer: C = G Ωᵀ' (n×c)
    psi = draw_sketch(ks[1], "gaussian", c, m)  # left outer: R = Ψ G
    s_c = draw_sketch(ks[2], ccfg.inner_sketch, ccfg.s, m)
    s_r = draw_sketch(ks[3], ccfg.inner_sketch, ccfg.s, n)
    return omega, psi, s_c, s_r


def compress(key, G: jax.Array, ccfg: CompressionConfig):
    """Local sketching (step 2). Returns the (C, R, M) triple — linear in G.

    Stacked (L, m, n) gradients are sketched per slice with shared sketches
    (vmapped); the triple gains a leading L dim.
    """
    if G.ndim == 3:
        omega, psi, s_c, s_r = _sketches_for(key, G.shape[1:], ccfg)

        def one(g):
            gf = g.astype(jnp.float32)
            return omega.apply(gf.T).T, psi.apply(gf), s_r.apply_t(s_c.apply(gf))

        return jax.vmap(one)(G)
    omega, psi, s_c, s_r = _sketches_for(key, G.shape, ccfg)
    Gf = G.astype(jnp.float32)
    C = omega.apply(Gf.T).T  # G Ωᵀ: (m, c)
    R = psi.apply(Gf)  # Ψ G: (c, n)
    M = s_r.apply_t(s_c.apply(Gf))  # S_C G S_Rᵀ: (s, s)
    return C, R, M


def decompress(key, triple, shape, ccfg: CompressionConfig) -> jax.Array:
    """Algorithm 1 reconstruction from the (psum-reduced) triple."""
    C, R, M = triple
    if len(shape) == 3:
        omega, psi, s_c, s_r = _sketches_for(key, shape[1:], ccfg)

        def one(C, R, M):
            X = fast_gmr_core(s_c.apply(C), M, s_r.apply(R.T).T)
            return C @ (X @ R)

        return jax.vmap(one)(C, R, M)
    omega, psi, s_c, s_r = _sketches_for(key, shape, ccfg)
    ScC = s_c.apply(C)  # (s, c)
    RSr = s_r.apply(R.T).T  # (c, s)
    X = fast_gmr_core(ScC, M, RSr)
    return C @ (X @ R)


def compressed_mean_grads(
    grads,
    err,
    key,
    ccfg: CompressionConfig,
    axes: Tuple[str, ...],
    *,
    with_stats: bool = False,
):
    """Inside shard_map(manual over ``axes``): replace the dense DP psum.

    grads: local gradient pytree. err: local EF residual pytree (zeros tree
    when EF disabled). Returns (global mean-ish grads, new err).
    Small leaves take the dense psum path unchanged.

    ``with_stats=True`` additionally returns a dict of *traced* per-step
    compression-quality scalars (this runs inside shard_map — no host
    metrics registry here; the train step psums them into its metrics, and
    the host loop can then forward them to :mod:`repro.obs.metrics`):

    * ``comp/wire_floats`` / ``comp/dense_floats`` — floats actually
      all-reduced vs the dense-gradient volume (static per config);
    * ``comp/ratio`` — their quotient, the realized compression ratio;
    * ``comp/ef_norm`` — this worker's error-feedback residual norm
      ``√Σ‖e‖²`` over compressible leaves (EF health: should stay O(‖g‖),
      not grow step over step);
    * ``comp/rel_err`` — this worker's relative reconstruction error
      ``‖(g+e) − ĝ‖ / ‖g+e‖`` over compressible leaves.
    """
    nworkers = 1
    for a in axes:
        nworkers *= jax.lax.axis_size(a)

    flat, tdef = jax.tree.flatten(grads)
    flat_err = tdef.flatten_up_to(err)
    out, out_err = [], []
    wire = dense = 0  # static float counts (python ints — config-determined)
    ef_sq = local_sq = resid_sq = jnp.zeros((), jnp.float32)
    for i, (g, e) in enumerate(zip(flat, flat_err)):
        dense += int(np.prod(g.shape))
        if is_compressible(g, ccfg):
            k = jax.random.fold_in(key, i)
            local = g.astype(jnp.float32) + (e if ccfg.error_feedback else 0.0)
            triple = compress(k, local, ccfg)
            triple = tuple(jax.lax.psum(t, axes) / nworkers for t in triple)
            ghat = decompress(k, triple, g.shape, ccfg)
            new_e = (local - ghat) if ccfg.error_feedback else jnp.zeros_like(local)
            out.append(ghat.astype(g.dtype))
            out_err.append(new_e)
            if with_stats:
                wire += sum(int(np.prod(t.shape)) for t in triple)
                ef_sq = ef_sq + jnp.sum(new_e * new_e)
                local_sq = local_sq + jnp.sum(local * local)
                resid_sq = resid_sq + jnp.sum((local - ghat) ** 2)
        else:
            out.append(jax.lax.psum(g, axes) / nworkers)
            out_err.append(jnp.zeros_like(e))
            wire += int(np.prod(g.shape))
    result = jax.tree.unflatten(tdef, out), jax.tree.unflatten(tdef, out_err)
    if not with_stats:
        return result
    stats = {
        "comp/wire_floats": jnp.asarray(wire, jnp.float32),
        "comp/dense_floats": jnp.asarray(dense, jnp.float32),
        "comp/ratio": jnp.asarray(dense / max(wire, 1), jnp.float32),
        "comp/ef_norm": jnp.sqrt(ef_sq),
        "comp/rel_err": jnp.sqrt(resid_sq)
        / jnp.maximum(jnp.sqrt(local_sq), jnp.finfo(jnp.float32).tiny),
    }
    return (*result, stats)


def init_error_state(params, ccfg: CompressionConfig, nworkers: int):
    """EF residuals: one per DP worker, stored with a leading worker dim."""

    def leaf(p):
        if is_compressible(p, ccfg):
            return jnp.zeros((nworkers, *p.shape), jnp.float32)
        return jnp.zeros((nworkers, 1), jnp.float32)  # placeholder, unused

    return jax.tree.map(leaf, params)
