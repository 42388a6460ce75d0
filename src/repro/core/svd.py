"""Single-pass SVD (paper §5).

* **Algorithm 3 (Fast SP-SVD, ours/paper)** — streaming API
  (:func:`sp_svd_init` / :func:`sp_svd_update` / :func:`sp_svd_finalize`)
  mirroring the paper's while-loop over L-column panels, plus a one-shot
  convenience :func:`fast_sp_svd`.
* **Algorithm 4 (Practical SP-SVD, Tropp et al. 2017)** — the baseline,
  :func:`practical_sp_svd`.

Sketch construction follows Algorithm 3 step 3: OSNAP (p = O(1) nonzeros
per column) composed with Gaussian projections for Ψ̃/Ω̃, and plain OSNAP
for the inner S_C/S_R. Space: C (m×c) + R (r×n) + M (s_c×s_r) — the
O((m+n)k/ε) footprint of Theorem 4; the input panels are never retained.

The per-panel accumulator mechanics live in the shared
:mod:`repro.stream.engine` (``PanelState`` + ``SP_SVD_OPS``); this module
keeps the Algorithm-3 surface as thin wrappers. The engine-level
constructor/finalizer pair (:func:`spsvd_engine_init` /
:func:`spsvd_engine_finalize`, explicit sketch sizes, jit/vmap-safe) is the
layer downstream plug-ins — e.g. the serving KV-cache compressor — build
on; the classic loop names delegate to it. ``fast_sp_svd`` streams
through the engine's scan-compiled whole-stream path — one ``lax.scan``
program per (shape, panel) with the carried state's buffers donated, the
ragged tail zero-padded to the panel width (exact: ``pad_cols`` sketch
windows past ``n`` are zero-scaled), and the per-panel jitted step
available behind ``jit="per-panel"`` for parity checks. DP-sharded
ingestion comes for free via :mod:`repro.stream.distributed`.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.spans import spanned
from ..stream.engine import (
    SCOPE_COLSKETCH,
    SCOPE_SOLVE,
    PanelOps,
    PanelState,
    fresh_pytree,
    padded_n,
    panel_update,
    stream_panels,
    truncated_R,
)
from .gmr import _solve_least_squares, fast_gmr_core
from .sketching import CountSketch, GaussianSketch, OSNAPSketch, draw_sketch

__all__ = [
    "SPSVDSketches",
    "SPSVDState",
    "SP_SVD_OPS",
    "sp_svd_sizes",
    "spsvd_engine_init",
    "spsvd_engine_finalize",
    "sp_svd_init",
    "sp_svd_update",
    "sp_svd_finalize",
    "fast_sp_svd",
    "practical_sp_svd",
    "svd_error_ratio",
]


def sp_svd_sizes(k: int, eps: float, gamma: float = 0.25) -> dict:
    """Algorithm 3 step 2 sketch sizes (constants chosen per §6.3's recipe)."""
    ke = k / eps
    c = r = int(np.ceil(3 * ke))
    c0 = r0 = int(np.ceil(3 * ke ** (1.0 + gamma)))
    s = int(np.ceil(3 * k / eps**1.5))
    return dict(c=c, r=r, c0=c0, r0=r0, s_c=s, s_r=s)


@dataclasses.dataclass(frozen=True)
class SPSVDSketches:
    """The six sketching operators of Algorithm 3 step 3."""

    psi: OSNAPSketch  # (r0, m)
    g_r: GaussianSketch  # (r, r0)
    omega: OSNAPSketch  # (c0, n)
    g_c: GaussianSketch  # (c, c0)
    s_c: OSNAPSketch  # (s_c, m)
    s_r: OSNAPSketch  # (s_r, n)


jax.tree_util.register_dataclass(
    SPSVDSketches, data_fields=["psi", "g_r", "omega", "g_c", "s_c", "s_r"], meta_fields=[]
)


# ---------------------------------------------------------------------------
# PanelStream plug-in (Algorithm 3 steps 6–8): ctx is the SPSVDSketches.
# ---------------------------------------------------------------------------


def _svd_core_sketches(sk: SPSVDSketches):
    return sk.s_c, sk.s_r


def _svd_update_c(sk: SPSVDSketches, C, A_L, sc_a, off):
    # C += A_L · Ω̃[cols]  with  Ω̃[cols] = Ω[:, cols]ᵀ · G_Cᵀ (L, c), a gather
    # that needs no pass over A; the panel is read once, by one matmul at
    # HIGH, so that on a TPU A_L is not rounded to one bfloat16 pass
    W = sk.omega.cols(off, A_L.shape[1]).transpose_apply(sk.g_c.mat.T)
    return sk, C + jnp.matmul(A_L, W, precision=jax.lax.Precision.HIGH).astype(C.dtype)


def _svd_r_block(sk: SPSVDSketches, A_L, off):
    # R[:, cols] = G_R · (Ψ A_L)
    return sk.g_r.apply(sk.psi.apply(A_L))  # (r, L)


SP_SVD_OPS = PanelOps(
    name="sp_svd",
    core_sketches=_svd_core_sketches,
    update_c=_svd_update_c,
    r_block=_svd_r_block,
    c_scope=SCOPE_COLSKETCH,
)

# Streaming state: the generic engine state with ctx = SPSVDSketches
# (``state.sketches`` resolves to ctx for back-compat).
SPSVDState = PanelState


def _check_sketch_shapes(sk: SPSVDSketches, m: int, n: int, sizes: dict) -> None:
    """Raise unless each operator of ``sk`` is ``(rows, cols)`` as ``sizes``
    and ``(m, n)`` ask."""
    want = {"psi": (sizes["r0"], m), "g_r": (sizes["r"], sizes["r0"]),
            "omega": (sizes["c0"], n), "g_c": (sizes["c"], sizes["c0"]),
            "s_c": (sizes["s_c"], m), "s_r": (sizes["s_r"], n)}
    for name, shape in want.items():
        op = getattr(sk, name)
        if (op.s, op.m) != shape:
            raise ValueError(f"sketches.{name} is {(op.s, op.m)}, the sizes ask for {shape}")


@spanned("stream/sp_svd/init")
def spsvd_engine_init(
    key,
    m: int,
    n: int,
    *,
    sizes: dict,
    dtype=jnp.float32,
    osnap_p: int = 2,
    panel: Optional[int] = None,
    sketches: Optional[SPSVDSketches] = None,
) -> SPSVDState:
    """Engine-level Algorithm 3 state constructor (explicit ``sizes``).

    Draws the six sketching operators and allocates zero accumulators
    (Algorithm 3 steps 2–4), returning a :class:`repro.stream.PanelState`
    ready for ``panel_update``/``scan_panels``/``stream_panels``. This is
    the constructor serving-side plug-ins build on; :func:`sp_svd_init`
    layers the paper's k/eps sizing recipe on top.

    ``panel`` declares a fixed streaming width: the n-dim sketches and the
    ``R`` accumulator are zero-pad-extended to a whole number of panels so a
    ragged final panel can be zero-padded instead of retraced (the sketches
    themselves are drawn over ``n`` — padding never consumes randomness, so
    results are identical across panel choices). vmap-compatible: all draw
    paths use traced-key-safe jax.random primitives.

    ``sketches`` replaces the six drawn operators with the caller's (shapes
    checked against ``sizes``; ``key`` and ``osnap_p`` are then unused), so
    a caller can hand the engine a draw that it rebuilds elsewhere. They are
    copied, as the scan path donates the state it consumes.
    """
    c, r, c0, r0, s_c, s_r = (sizes[x] for x in ("c", "r", "c0", "r0", "s_c", "s_r"))
    n_pad = padded_n(n, panel) if panel else n
    if sketches is None:
        keys = jax.random.split(key, 6)
        sk = SPSVDSketches(
            psi=OSNAPSketch.draw(keys[0], r0, m, p=osnap_p, dtype=dtype),
            g_r=GaussianSketch.draw(keys[1], r, r0, dtype=dtype),
            omega=OSNAPSketch.draw(keys[2], c0, n, p=osnap_p, dtype=dtype),
            g_c=GaussianSketch.draw(keys[3], c, c0, dtype=dtype),
            s_c=OSNAPSketch.draw(keys[4], s_c, m, p=osnap_p, dtype=dtype),
            s_r=OSNAPSketch.draw(keys[5], s_r, n, p=osnap_p, dtype=dtype),
        )
    else:
        _check_sketch_shapes(sketches, m, n, sizes)
        sk = fresh_pytree(sketches)
    sk = dataclasses.replace(sk, omega=sk.omega.pad_cols(n_pad), s_r=sk.s_r.pad_cols(n_pad))
    return SPSVDState(
        C=jnp.zeros((m, c), dtype),
        R=jnp.zeros((r, n_pad), dtype),
        M=jnp.zeros((s_c, s_r), dtype),
        offset=jnp.zeros((), jnp.int32),
        ctx=sk,
        ops=SP_SVD_OPS,
        n=n,
    )


def sp_svd_init(
    key,
    m: int,
    n: int,
    *,
    k: Optional[int] = None,
    eps: float = 0.5,
    sizes: Optional[dict] = None,
    dtype=jnp.float32,
    osnap_p: int = 2,
    panel: Optional[int] = None,
) -> SPSVDState:
    """Draw sketches and allocate zero accumulators (Algorithm 3 steps 2–4).

    Thin wrapper over :func:`spsvd_engine_init` that resolves the paper's
    k/eps sizing recipe (:func:`sp_svd_sizes`) when explicit ``sizes`` are
    not given.
    """
    if sizes is None:
        if k is None:
            raise ValueError("pass either `k` (+eps) or explicit `sizes`")
        sizes = sp_svd_sizes(k, eps)
    return spsvd_engine_init(key, m, n, sizes=sizes, dtype=dtype, osnap_p=osnap_p, panel=panel)


def sp_svd_update(state: SPSVDState, A_L: jax.Array) -> SPSVDState:
    """Consume one L-column panel (Algorithm 3 steps 6–8). jit-compatible."""
    return panel_update(state, A_L)


def spsvd_engine_finalize(
    state: SPSVDState, k: Optional[int] = None
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Algorithm 3 steps 10–13: QR bases, sketched core solve, small SVD.

    Returns (U, Σ, V) with ``A ≈ U diag(Σ) Vᵀ``; ranks are c/r (not k) unless
    ``k`` is given, matching §6.3's "without fixed rank" protocol. Pure jax —
    safe under jit/vmap (the serving head-batch path maps it over heads).
    """
    sk = state.ctx
    with jax.named_scope(SCOPE_SOLVE):
        R = truncated_R(state)
        dt = jnp.promote_types(state.C.dtype, jnp.float32)
        U_C, _ = jnp.linalg.qr(state.C.astype(dt))  # (m, c)
        V_R, _ = jnp.linalg.qr(R.T.astype(dt))  # (n, r)

        ScU = sk.s_c.apply(U_C.astype(state.C.dtype)).astype(dt)  # (s_c, c)
        SrV = sk.s_r.apply(V_R.astype(state.C.dtype)).astype(dt)  # (s_r, r)
        # N = (S_C U_C)† M (V_Rᵀ S_Rᵀ)†  — Fast GMR core (Eqn. 5.3)
        N = fast_gmr_core(ScU, state.M.astype(dt), SrV.T)

        U_N, S, V_Nt = jnp.linalg.svd(N, full_matrices=False)
        U = U_C @ U_N
        V = V_R @ V_Nt.T
    if k is not None:
        U, S, V = U[:, :k], S[:k], V[:, :k]
    return U, S, V


# Compiled at module scope (one trace per shape and ``k``) and dispatched
# inside a host span where the host calls it; under a caller's jit or vmap
# (serving) it traces into the caller's program. The state is not donated.
spsvd_engine_finalize = spanned("stream/sp_svd/finalize")(
    jax.jit(spsvd_engine_finalize, static_argnames="k")
)


def sp_svd_finalize(
    state: SPSVDState, k: Optional[int] = None
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Legacy Algorithm-3 finalize name — thin shim over :func:`spsvd_engine_finalize`."""
    return spsvd_engine_finalize(state, k=k)


def fast_sp_svd(
    key,
    A: jax.Array,
    *,
    k: Optional[int] = None,
    eps: float = 0.5,
    sizes: Optional[dict] = None,
    panel: int = 512,
    fixed_rank: Optional[int] = None,
    jit="scan",
):
    """One-shot Algorithm 3: stream ``A`` through the panel loop internally.

    The stream runs on the engine's scan-compiled path by default — the
    whole panel loop is one compiled program per (m, n, panel) shape for the
    process lifetime, with every panel (including a ragged tail, zero-padded
    to ``panel``) consumed in place. ``jit="per-panel"`` falls back to one
    jitted dispatch per panel (the parity oracle; see
    :func:`repro.stream.stream_panels`).
    """
    m, n = A.shape
    state = sp_svd_init(key, m, n, k=k, eps=eps, sizes=sizes, dtype=A.dtype, panel=panel)
    state = stream_panels(state, A, panel, jit=jit)
    return sp_svd_finalize(state, k=fixed_rank)


def practical_sp_svd(
    key,
    A: jax.Array,
    *,
    c: int,
    r: int,
    sketch: str = "gaussian",
    fixed_rank: Optional[int] = None,
):
    """Algorithm 4 (Tropp et al. 2017) — the baseline Practical SP-SVD.

    C = A Ω̃, R = Ψ̃ A, N' = (Ψ̃ U_C)† (R V_R); same single-pass structure but
    the core is *not* a GMR solution (§5.3's comparison point).
    """
    m, n = A.shape
    k_psi, k_om = jax.random.split(key)
    psi = draw_sketch(k_psi, sketch, r, m, dtype=A.dtype)  # Ψ̃ (r, m)
    omega = draw_sketch(k_om, sketch, c, n, dtype=A.dtype)  # Ω̃ᵀ (c, n)

    C = omega.apply_t(A)  # A Ω̃ (m, c)
    R = psi.apply(A)  # Ψ̃ A (r, n)

    dt = jnp.promote_types(A.dtype, jnp.float32)
    U_C, _ = jnp.linalg.qr(C.astype(dt))
    V_R, _ = jnp.linalg.qr(R.T.astype(dt))

    PsiU = psi.apply(U_C.astype(A.dtype)).astype(dt)  # (r, c)
    N = _solve_least_squares(PsiU, (R.astype(dt) @ V_R))  # (c, r)

    U_N, S, V_Nt = jnp.linalg.svd(N, full_matrices=False)
    U = U_C @ U_N
    V = V_R @ V_Nt.T
    if fixed_rank is not None:
        U, S, V = U[:, :fixed_rank], S[:fixed_rank], V[:, :fixed_rank]
    return U, S, V


def svd_error_ratio(A: jax.Array, U, S, V, k: int) -> jax.Array:
    """§6.3 metric: ||A − UΣVᵀ||_F / ||A − A_k||_F − 1 (can be negative)."""
    dt = jnp.promote_types(A.dtype, jnp.float32)
    approx = (U * S[None, :]) @ V.T
    num = jnp.linalg.norm(A.astype(dt) - approx.astype(dt))
    sv = jnp.linalg.svd(A.astype(dt), compute_uv=False)
    den = jnp.sqrt(jnp.sum(sv[k:] ** 2))
    return num / jnp.maximum(den, jnp.finfo(dt).tiny) - 1.0
