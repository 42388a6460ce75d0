"""Deterministic synthetic data: test matrices + shardable token pipeline.

Matrix generators (:func:`powerlaw_matrix`, :func:`sparse_matrix`,
:func:`lowrank_plus_noise`) are the offline substitutions for the paper's
LIBSVM datasets (matched spectral / sparsity profiles, DESIGN.md §8) and
the ground truth for the CUR / GMR / SVD test-and-benchmark suites;
:func:`clustered_points` and :func:`tune_rbf_sigma` give the RBF-kernel
inputs of §6.2.

Stateless-by-construction: ``batch_at(step)`` derives every batch from
``fold_in(seed, step)``, so restart-from-checkpoint only needs the step
counter — no iterator state files, no skew between hosts (each host can
slice its DP shard of the same deterministic batch).

Token stream: a Zipf-ish unigram mixture with short Markov motifs so the
loss has real structure to learn (pure uniform tokens give a flat loss and
hide optimizer bugs).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Test matrices (paper §6 dataset substitutions)
# ---------------------------------------------------------------------------


def powerlaw_matrix(key, m: int, n: int, decay: float = 1.0, dtype=jnp.float32):
    """Dense matrix with σ_i ∝ i^-decay (the spectral profile of the paper's
    dense LIBSVM datasets)."""
    k1, k2 = jax.random.split(key)
    r = min(m, n)
    U, _ = jnp.linalg.qr(jax.random.normal(k1, (m, r), dtype))
    V, _ = jnp.linalg.qr(jax.random.normal(k2, (n, r), dtype))
    sv = jnp.arange(1, r + 1, dtype=dtype) ** (-decay)
    return (U * sv[None, :]) @ V.T


def sparse_matrix(key, m: int, n: int, density: float = 0.002, dtype=jnp.float32):
    """Sparse-profile matrix (rcv1/news20 substitution): Bernoulli mask × normal."""
    k1, k2 = jax.random.split(key)
    mask = jax.random.bernoulli(k1, density, (m, n))
    vals = jax.random.normal(k2, (m, n), dtype)
    return jnp.where(mask, vals, 0.0)


def lowrank_plus_noise(key, m: int, n: int, rank: int = 10, snr: float = 10.0, dtype=jnp.float32):
    """Exactly-rank-k signal plus white noise at the given signal-to-noise
    ratio — the regime where CUR / randomized SVD guarantees are sharpest."""
    k1, k2, k3 = jax.random.split(key, 3)
    L = jax.random.normal(k1, (m, rank), dtype)
    Rf = jax.random.normal(k2, (rank, n), dtype)
    signal = (L @ Rf) / np.sqrt(rank)
    noise = jax.random.normal(k3, (m, n), dtype)
    return signal + (jnp.linalg.norm(signal) / (snr * jnp.linalg.norm(noise))) * noise


def spiked_decay_matrix(
    key, m: int, n: int, n_spikes: int = 8, spike: float = 6.0, noise: float = 0.05,
    dtype=jnp.float32,
):
    """Fast-decaying background plus a few heavy columns at random positions
    — the regime where adaptive (residual-driven) column selection separates
    from uniform pre-pass selection. Returns ``(A, spike_positions)``."""
    k1, k2, k3 = jax.random.split(key, 3)
    B = noise * powerlaw_matrix(k1, m, n, 1.5, dtype=dtype)
    pos = jax.random.choice(k2, n, (n_spikes,), replace=False)
    return B.at[:, pos].add(spike * jax.random.normal(k3, (m, n_spikes), dtype)), pos


def late_spike_matrix(
    key, m: int, n: int, n_early: int = 8, n_late: int = 6,
    early: float = 3.0, late: float = 9.0, noise: float = 0.05,
    early_frac: float = 0.3, late_frac: float = 0.7, dtype=jnp.float32,
):
    """The adversarial stream for admission-*only* policies: enough
    moderately-heavy columns early in the stream to fill any column budget
    ``c ≤ n_early``, then strictly heavier columns after ``late_frac·n`` —
    by which point an admission-only policy has no free slots left and loses
    them, while an eviction policy swaps its weakest admits out. Returns
    ``(A, early_positions, late_positions)``."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    B = noise * powerlaw_matrix(k1, m, n, 1.5, dtype=dtype)
    n_head = max(int(early_frac * n), n_early)
    late_lo = min(int(late_frac * n), n - n_late)
    if n_head > late_lo:
        raise ValueError(
            f"early window [0, {n_head}) overlaps late window [{late_lo}, {n}); "
            f"need a larger n (or fewer/narrower spike windows) for m×n={m}×{n}"
        )
    early_pos = jax.random.choice(k2, n_head, (n_early,), replace=False)
    late_pos = late_lo + jax.random.choice(k3, n - late_lo, (n_late,), replace=False)
    B = B.at[:, early_pos].add(early * jax.random.normal(k4, (m, n_early), dtype))
    B = B.at[:, late_pos].add(late * jax.random.normal(k5, (m, n_late), dtype))
    return B, early_pos, late_pos


def spiked_rows_matrix(
    key, m: int, n: int, n_spikes: int = 6, spike: float = 6.0, noise: float = 0.05,
    dtype=jnp.float32,
):
    """Transposed analogue of :func:`spiked_decay_matrix`: a few heavy *rows*
    at random positions over a decaying background — the regime where
    adaptive in-stream row admission separates from fixed pre-pass (uniform)
    row selection. Returns ``(A, spiked_row_positions)``."""
    k1, k2, k3 = jax.random.split(key, 3)
    B = noise * powerlaw_matrix(k1, m, n, 1.5, dtype=dtype)
    pos = jax.random.choice(k2, m, (n_spikes,), replace=False)
    return B.at[pos, :].add(spike * jax.random.normal(k3, (n_spikes, n), dtype)), pos


def drifting_spectrum_matrix(
    key, m: int, n: int, n_blocks: int = 4, rank: int = 4, ramp: float = 2.5,
    noise: float = 0.05, dtype=jnp.float32,
):
    """Column stream whose dominant subspace *drifts*: each successive
    column block carries a fresh random rank-``rank`` subspace whose energy
    grows by ``ramp×`` per block. Early blocks clear any data-relative
    admission threshold and fill the budget; the strictly stronger late
    blocks then require eviction to be represented. Returns ``(A,
    block_bounds)`` with ``block_bounds`` the (n_blocks+1,) column offsets."""
    keys = jax.random.split(key, n_blocks + 1)
    B = noise * jax.random.normal(keys[0], (m, n), dtype)
    bounds = np.linspace(0, n, n_blocks + 1).astype(int)
    for b in range(n_blocks):
        lo, hi = int(bounds[b]), int(bounds[b + 1])
        kL, kR = jax.random.split(keys[b + 1])
        L = jax.random.normal(kL, (m, rank), dtype)
        Rf = jax.random.normal(kR, (rank, hi - lo), dtype)
        B = B.at[:, lo:hi].add((ramp ** b) * (L @ Rf) / np.sqrt(rank))
    return B, jnp.asarray(bounds, jnp.int32)


def clustered_points(key, n: int, d: int, n_clusters: int = 10, spread: float = 1.0):
    """Clustered Gaussian points for RBF kernels (the substitution for the
    §6.2 datasets)."""
    k1, k2, k3 = jax.random.split(key, 3)
    centers = jax.random.normal(k1, (n_clusters, d)) * 3.0
    assign = jax.random.randint(k2, (n,), 0, n_clusters)
    return centers[assign] + spread * jax.random.normal(k3, (n, d))


def tune_rbf_sigma(X, k: int = 15, target_eta: float = 0.7, iters: int = 20) -> float:
    """Bisect σ so that η = ‖K_k‖²_F / ‖K‖²_F ≈ ``target_eta`` for the RBF
    kernel of ``X`` (the paper's Table 6 protocol)."""
    from ..spsd import rbf_kernel_oracle  # lazy: the token pipeline needs no SPSD stack

    lo, hi = 1e-6, 1e2
    for _ in range(iters):
        mid = float(np.sqrt(lo * hi))
        K = rbf_kernel_oracle(X, mid)(None, None)
        ev2 = jnp.sort(jnp.linalg.eigvalsh(K) ** 2)[::-1]
        eta = float(jnp.sum(ev2[:k]) / jnp.sum(ev2))
        if eta > target_eta:
            lo = mid  # K = exp(−σ d²): η falls as σ grows
        else:
            hi = mid
        if abs(eta - target_eta) < 0.05:
            return mid
    return float(np.sqrt(lo * hi))


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab_size: int
    batch: int
    seq_len: int
    seed: int = 0
    zipf_a: float = 1.2
    motif_len: int = 8


class SyntheticLM:
    def __init__(self, dc: DataConfig):
        self.dc = dc
        probs = 1.0 / np.arange(1, dc.vocab_size + 1) ** dc.zipf_a
        self._logits = jnp.asarray(np.log(probs / probs.sum()), jnp.float32)
        self._base = jax.random.key(dc.seed)
        self._batch_at = jax.jit(self._make_batch, static_argnums=())

    def _make_batch(self, step):
        dc = self.dc
        key = jax.random.fold_in(self._base, step)
        k1, k2, k3 = jax.random.split(key, 3)
        toks = jax.random.categorical(
            k1, jnp.broadcast_to(self._logits, (dc.batch, dc.seq_len, dc.vocab_size))
        )
        # overlay deterministic motifs: every motif_len-run repeats its first token
        # with p=0.5 — gives learnable bigram structure
        rep = jnp.repeat(
            toks[:, :: dc.motif_len], dc.motif_len, axis=1
        )[:, : dc.seq_len]
        gate = jax.random.bernoulli(k2, 0.5, toks.shape)
        toks = jnp.where(gate, rep, toks)
        return {"tokens": toks.astype(jnp.int32)}

    def batch_at(self, step: int):
        return self._batch_at(jnp.asarray(step, jnp.int32))

    def state(self, step: int) -> dict:
        """Checkpointable iterator state (trivially the step)."""
        return {"step": int(step), "seed": self.dc.seed}
