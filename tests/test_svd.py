"""§5 single-pass SVD: Algorithm 3 streaming semantics + Theorem 4 claims."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    fast_sp_svd,
    practical_sp_svd,
    sp_svd_finalize,
    sp_svd_init,
    sp_svd_update,
    svd_error_ratio,
)
from repro.data import powerlaw_matrix


@pytest.fixture(scope="module")
def A():
    return powerlaw_matrix(jax.random.key(0), 500, 400, 1.0)


SIZES = dict(c=40, r=40, c0=120, r0=120, s_c=120, s_r=120)


def test_streaming_matches_oneshot(A):
    """Panel-streamed accumulators == single-panel pass (algebraic identity)."""
    m, n = A.shape
    s1 = sp_svd_init(jax.random.key(1), m, n, sizes=SIZES)
    for off in range(0, n, 100):
        s1 = sp_svd_update(s1, A[:, off : off + 100])
    s2 = sp_svd_init(jax.random.key(1), m, n, sizes=SIZES)
    s2 = sp_svd_update(s2, A)
    np.testing.assert_allclose(s1.C, s2.C, atol=2e-3)
    np.testing.assert_allclose(s1.R, s2.R, atol=2e-3)
    np.testing.assert_allclose(s1.M, s2.M, atol=2e-3)


def test_panel_size_invariance(A):
    """Different L panels give identical finalized factors (same sketches)."""
    outs = []
    for panel in (64, 200):
        U, S, V = fast_sp_svd(jax.random.key(2), A, sizes=SIZES, panel=panel)
        outs.append((U * S[None]) @ V.T)
    np.testing.assert_allclose(outs[0], outs[1], atol=5e-3)


def test_relative_error_bound(A):
    """Theorem 4: (1+ε) error vs ||A − A_k||_F at moderate sketch sizes."""
    k = 10
    errs = [
        float(svd_error_ratio(A, *fast_sp_svd(jax.random.key(10 + t), A, sizes=SIZES), k))
        for t in range(3)
    ]
    assert np.mean(errs) < 0.5, errs


def test_fast_beats_practical(A):
    """§6.3 headline: Fast SP-SVD ≪ Practical SP-SVD at equal budget."""
    k = 10
    e_fast = np.mean([
        float(svd_error_ratio(A, *fast_sp_svd(jax.random.key(20 + t), A, sizes=SIZES), k))
        for t in range(3)
    ])
    e_prac = np.mean([
        float(svd_error_ratio(A, *practical_sp_svd(jax.random.key(30 + t), A, c=40, r=40), k))
        for t in range(3)
    ])
    assert e_fast < e_prac, (e_fast, e_prac)


@pytest.mark.slow
def test_error_decreases_with_budget(A):
    k = 10
    errs = []
    for f in (2, 6):
        sizes = dict(c=f * k, r=f * k, c0=3 * f * k, r0=3 * f * k, s_c=3 * f * k, s_r=3 * f * k)
        e = np.mean([
            float(svd_error_ratio(A, *fast_sp_svd(jax.random.key(40 + t), A, sizes=sizes), k))
            for t in range(3)
        ])
        errs.append(e)
    assert errs[1] < errs[0], errs


def test_fixed_rank_truncation(A):
    U, S, V = fast_sp_svd(jax.random.key(3), A, sizes=SIZES, fixed_rank=10)
    assert U.shape[1] == 10 and S.shape == (10,) and V.shape[1] == 10


def test_orthonormal_outputs(A):
    U, S, V = fast_sp_svd(jax.random.key(4), A, sizes=SIZES)
    np.testing.assert_allclose(U.T @ U, np.eye(U.shape[1]), atol=1e-4)
    np.testing.assert_allclose(V.T @ V, np.eye(V.shape[1]), atol=1e-4)
    assert bool(jnp.all(S[:-1] >= S[1:]))  # sorted singular values


# ------------------------------------------- engine against a plain reference

# a ragged stream (250 columns in panels of 64 or 32) with sketch sizes that embed
# the bases well (s = 4c), so the floored core solve and a plain pinv agree
ENGINE_SIZES = dict(c=20, r=20, c0=60, r0=60, s_c=80, s_r=80)


def _plain_spsvd(A, sk):
    """Algorithm 3 in plain float32 at ``highest`` from the dense forms of
    the engine's sketches: C, R, M, then steps 10-13 with pinv solves."""
    with jax.default_matmul_precision("highest"):
        d = {name: np.asarray(getattr(sk, name).materialize()) for name in
             ("psi", "g_r", "omega", "g_c", "s_c", "s_r")}
        m, n = A.shape
        A = jnp.asarray(A)
        C = (A @ d["omega"][:, :n].T) @ d["g_c"].T
        R = d["g_r"] @ (d["psi"] @ A)
        M = d["s_c"] @ A @ d["s_r"][:, :n].T
        Q_C, _ = jnp.linalg.qr(C)
        Q_R, _ = jnp.linalg.qr(R.T)
        N = jnp.linalg.pinv(d["s_c"] @ Q_C) @ M @ jnp.linalg.pinv(d["s_r"][:, :n] @ Q_R).T
        return C, R, M, Q_C @ N @ Q_R.T


# (route, panel): panels on each side of c0 = 60; both form the C update as
# A_L (G_C Ω_L)ᵀ
ENGINE_CASES = [
    pytest.param("segment_sum", 64, id="segment_sum"),
    pytest.param("kernel", 64, id="kernel"),
    pytest.param("segment_sum", 32, id="segment_sum-panel32"),
    pytest.param("kernel", 32, id="kernel-panel32"),
]


@pytest.mark.parametrize("route,panel", ENGINE_CASES)
def test_engine_matches_plain_reference(route, panel, monkeypatch):
    """``spsvd_engine_init`` on injected sketches, ``stream_panels`` (the
    legacy scan body, ragged tail) and ``spsvd_engine_finalize`` give the
    C, R, M and reconstruction of a plain float32 Algorithm 3 on the same
    sketches; with OSNAP on the ``countsketch`` kernel (interpret mode) too,
    and with panels narrower and wider than c0."""
    from repro.core.sketching import GaussianSketch, OSNAPSketch
    from repro.core.svd import SPSVDSketches, spsvd_engine_finalize, spsvd_engine_init
    from repro.kernels import ops as kops
    from repro.stream import stream_panels

    if route == "kernel":
        monkeypatch.setattr(kops, "kernel_route_enabled", lambda: True)
    jax.clear_caches()
    m, n = 300, 250
    A = powerlaw_matrix(jax.random.key(11), m, n, 1.0)
    z = ENGINE_SIZES
    ks = jax.random.split(jax.random.key(12), 6)
    sk = SPSVDSketches(
        psi=OSNAPSketch.draw(ks[0], z["r0"], m), g_r=GaussianSketch.draw(ks[1], z["r"], z["r0"]),
        omega=OSNAPSketch.draw(ks[2], z["c0"], n), g_c=GaussianSketch.draw(ks[3], z["c"], z["c0"]),
        s_c=OSNAPSketch.draw(ks[4], z["s_c"], m), s_r=OSNAPSketch.draw(ks[5], z["s_r"], n),
    )
    C, R, M, recon = _plain_spsvd(A, sk)
    with jax.default_matmul_precision("highest"):
        st = spsvd_engine_init(jax.random.key(0), m, n, sizes=z, panel=panel, sketches=sk)
        st = stream_panels(st, A, panel)
        U, S, V = spsvd_engine_finalize(st)
        got = (U * S[None, :]) @ V.T
    jax.clear_caches()

    def rel(a, b):
        return float(jnp.linalg.norm(jnp.asarray(a) - b) / jnp.linalg.norm(b))

    assert rel(st.C, C) < 1e-5 and rel(st.R[:, :n], R) < 1e-5 and rel(st.M, M) < 1e-5
    assert not bool(jnp.any(st.R[:, n:]))  # the padded tail stays zero
    assert rel(got, recon) < 1e-4


def test_injected_sketches_equal_drawn():
    """``spsvd_engine_init(sketches=...)`` with the operators the key draws
    gives the drawn path's state, padding included; a wrong shape raises."""
    from repro.core.svd import spsvd_engine_init

    m, n, panel = 120, 100, 32
    drawn = spsvd_engine_init(jax.random.key(5), m, n, sizes=ENGINE_SIZES, panel=panel)
    bare = spsvd_engine_init(jax.random.key(5), m, n, sizes=ENGINE_SIZES)
    given = spsvd_engine_init(jax.random.key(6), m, n, sizes=ENGINE_SIZES, panel=panel,
                              sketches=bare.ctx)
    assert jax.tree_util.tree_structure(given) == jax.tree_util.tree_structure(drawn)
    for a, b in zip(jax.tree_util.tree_leaves(given), jax.tree_util.tree_leaves(drawn)):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="omega"):
        spsvd_engine_init(jax.random.key(6), m, n + 1, sizes=ENGINE_SIZES, sketches=bare.ctx)
