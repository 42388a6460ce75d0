"""Sketching library: §2.3 families, Lemma 1 properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.sketching import draw_sketch, fwht

KINDS = ["gaussian", "srht", "countsketch", "osnap", "uniform", "osnap+gaussian"]


@pytest.mark.parametrize("kind", KINDS)
def test_apply_matches_materialized(kind):
    key = jax.random.key(0)
    m, n, s = 150, 37, 64
    A = jax.random.normal(jax.random.key(1), (m, n))
    S = draw_sketch(key, kind, s, m)
    Smat = S.materialize()
    np.testing.assert_allclose(S.apply(A), Smat @ A, rtol=0, atol=2e-5)
    np.testing.assert_allclose(S.apply_t(A.T), A.T @ Smat.T, rtol=0, atol=2e-5)


@pytest.mark.parametrize("kind", ["gaussian", "countsketch", "osnap", "osnap+gaussian"])
def test_cols_slicing(kind):
    """Streaming sub-sketch == column slice of the materialized sketch."""
    key = jax.random.key(2)
    S = draw_sketch(key, kind, 32, 200)
    sub = S.cols(40, 100)
    np.testing.assert_allclose(
        sub.materialize(), S.materialize()[:, 40:140], rtol=0, atol=1e-6
    )


# (family, p): CountSketch, and OSNAP at p = 1, 2 and 4
HASHED = [("countsketch", 1), ("osnap", 1), ("osnap", 2), ("osnap", 4)]


@pytest.mark.parametrize("kind,p", HASHED, ids=lambda v: str(v))
def test_transpose_apply_matches_materialized(kind, p):
    """``transpose_apply(Y)`` is ``materialize().T @ Y``: on the whole
    sketch, whose ``pad_cols`` columns give zero rows; on a ``cols(off, L)``
    window with a traced ``off`` under jit, here straddling the padding; and
    under vmap over a batch of sketches."""
    s, m, total, L = 48, 300, 360, 64
    ks = jax.random.split(jax.random.key(3 + p), 3)
    S = draw_sketch(ks[0], kind, s, m, p=p).pad_cols(total)
    Y = jax.random.normal(ks[1], (s, 7))
    want = jnp.matmul(S.materialize().T, Y, precision="highest")
    got = S.transpose_apply(Y)
    assert got.shape == (total, 7) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert not bool(jnp.any(got[m:]))

    window = jax.jit(lambda S, off, Y: S.cols(off, L).transpose_apply(Y))
    for off in (0, 130, m - 20):
        np.testing.assert_allclose(window(S, off, Y), want[off:off + L], rtol=1e-6, atol=1e-6)

    batch = [draw_sketch(k, kind, s, m, p=p) for k in jax.random.split(ks[2], 3)]
    Ys = jax.random.normal(ks[2], (3, s, 7))
    stacked = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *batch)
    mapped = jax.vmap(lambda S, Y: S.transpose_apply(Y))(stacked, Ys)
    for i, one in enumerate(batch):
        np.testing.assert_allclose(mapped[i], one.transpose_apply(Ys[i]), rtol=0, atol=0)


@pytest.mark.slow
@settings(deadline=None, max_examples=15)
@given(
    kind=st.sampled_from(["gaussian", "countsketch", "osnap", "srht"]),
    m=st.integers(40, 300),
    seed=st.integers(0, 2**30),
)
def test_subspace_embedding_property(kind, m, seed):
    """Lemma 1 property 1: singular values of S·U within [1−η, 1+η] for an
    orthonormal U, at generous sketch size (η ≤ 0.7 w.h.p.)."""
    k = 8
    key = jax.random.key(seed)
    U, _ = jnp.linalg.qr(jax.random.normal(jax.random.fold_in(key, 1), (m, k)))
    s = min(m, 40 * k)
    S = draw_sketch(jax.random.fold_in(key, 2), kind, s, m)
    sv = jnp.linalg.svd(S.apply(U), compute_uv=False)
    assert float(sv.max()) < 1.8 and float(sv.min()) > 0.3, (kind, sv)


@settings(deadline=None, max_examples=10)
@given(seed=st.integers(0, 2**30))
def test_matrix_product_preservation(seed):
    """Lemma 1 property 2: ||Bᵀ Sᵀ S A − Bᵀ A||_F ≤ ε ||A||_F ||B||_F."""
    key = jax.random.key(seed)
    m = 200
    A = jax.random.normal(jax.random.fold_in(key, 1), (m, 12))
    B = jax.random.normal(jax.random.fold_in(key, 2), (m, 9))
    errs = []
    for t in range(5):
        S = draw_sketch(jax.random.fold_in(key, 10 + t), "countsketch", 400, m)
        err = jnp.linalg.norm(B.T @ S.materialize().T @ S.apply(A) - B.T @ A)
        errs.append(float(err / (jnp.linalg.norm(A) * jnp.linalg.norm(B))))
    assert np.mean(errs) < 0.3, errs


def test_fwht_orthogonality():
    m = 64
    H = fwht(jnp.eye(m))
    np.testing.assert_allclose(H @ H.T / m, jnp.eye(m), atol=1e-5)


def test_unbiasedness_sts():
    """E[SᵀS] ≈ I over many draws (Gaussian & CountSketch)."""
    m, s, reps = 24, 48, 200
    for kind in ("gaussian", "countsketch"):
        acc = jnp.zeros((m, m))
        for t in range(reps):
            S = draw_sketch(jax.random.key(t), kind, s, m).materialize()
            acc = acc + S.T @ S
        acc = acc / reps
        assert float(jnp.max(jnp.abs(acc - jnp.eye(m)))) < 0.25


def test_seed_determinism():
    """Identical keys ⇒ identical sketches (gradient compression relies on it)."""
    for kind in KINDS:
        a = draw_sketch(jax.random.key(7), kind, 16, 100).materialize()
        b = draw_sketch(jax.random.key(7), kind, 16, 100).materialize()
        np.testing.assert_array_equal(a, b)
