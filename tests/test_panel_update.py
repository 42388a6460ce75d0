"""Fused panel-update megakernel: interpret parity + stream-route parity.

Two layers of evidence:

* kernel vs :func:`repro.kernels.panel_update_ref` (the unfused XLA oracle)
  across ragged tails, tied symmetric operands, empty admission masks and
  bf16 inputs with fp32 accumulation — interpret mode executes the real
  kernel body, so the admission arithmetic (threshold, rank-based slot
  assignment, one-hot C scatter) is checked bit-for-bit against the
  ``top_k``/cumsum path it replaces;
* the engine routes — the fused scan body (taken whenever the state
  allows it) and the forced kernel route (``_FORCE_KERNEL_ROUTE``) — vs the
  per-panel oracle driver on whole streams, so the megakernel's wiring into
  :mod:`repro.stream.engine` reproduces the committed factors.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic import spiked_decay_matrix
from repro.kernels import panel_update, panel_update_ref
from repro.stream.adaptive import adaptive_cur_init
from repro.stream.engine import _fused_route_ok, stream_panels

from test_stream import _assert_states_close


def _inputs(key, s_c, m, L, c, s_r, filled=None, dtype=jnp.float32):
    """Half-filled basis + partially filled C/M, matching mid-stream state."""
    ks = jax.random.split(key, 6)
    filled = max(1, c // 2) if filled is None else filled
    sc = jax.random.normal(ks[0], (s_c, m), jnp.float32).astype(dtype)
    a_l = jax.random.normal(ks[1], (m, L), jnp.float32).astype(dtype)
    srt = jax.random.normal(ks[2], (L, s_r), jnp.float32).astype(dtype)
    Q, _ = jnp.linalg.qr(jax.random.normal(ks[3], (s_c, c), jnp.float32))
    q = Q * (jnp.arange(c) < filled)
    C = jax.random.normal(ks[4], (m, c), jnp.float32) * (jnp.arange(c) < filled)
    M = jax.random.normal(ks[5], (s_c, s_r), jnp.float32)
    kw = dict(min_gain=0.5, run_mean=0.0, true_cols=float(L),
              n_filled=filled, free=c - filled, panel_cap=3)
    return sc, a_l, srt, q, C, M, kw


def _check(out, ref, atol_scale=1e-5):
    for got, want, name in zip(out[:5], ref[:5], ("C", "M", "sc_a", "resid2", "energy")):
        scale = float(jnp.max(jnp.abs(want))) + 1e-30
        np.testing.assert_allclose(got, want, rtol=0, atol=atol_scale * scale,
                                   err_msg=name)
    np.testing.assert_array_equal(out[5], ref[5], err_msg="slots")


PU_SHAPES = [
    (72, 300, 96, 16, 72),  # every dim unaligned → padding path
    (240, 1024, 128, 16, 240),  # the adaptive-CUR acceptance shape
    (64, 256, 40, 8, 48),  # ragged panel, L < LANE
    (128, 512, 256, 32, 128),  # aligned
]


@pytest.mark.parametrize("shape", PU_SHAPES)
def test_panel_update_allclose(shape):
    s_c, m, L, c, s_r = shape
    args = _inputs(jax.random.key(sum(shape)), *shape)
    sc, a_l, srt, q, C, M, kw = args
    out = panel_update(sc, a_l, srt, q, C, M, interpret=True, **kw)
    ref = panel_update_ref(sc, a_l, srt, q, C, M, **kw)
    _check(out, ref)
    # admitted count within both budgets
    admitted = int(jnp.sum(out[5] < c))
    assert admitted <= min(kw["panel_cap"], kw["free"])


def test_panel_update_empty_admission_mask():
    """Nothing eligible (huge min_gain): C must pass through untouched,
    every slot the sentinel — but M still folds the panel's sketch."""
    s_c, m, L, c, s_r = 64, 256, 40, 8, 64
    sc, a_l, srt, q, C, M, kw = _inputs(jax.random.key(5), s_c, m, L, c, s_r)
    kw["min_gain"] = 1e9
    out = panel_update(sc, a_l, srt, q, C, M, interpret=True, **kw)
    ref = panel_update_ref(sc, a_l, srt, q, C, M, **kw)
    _check(out, ref)
    np.testing.assert_array_equal(out[0], C)
    np.testing.assert_array_equal(out[5], jnp.full((L,), c, jnp.int32))
    assert float(jnp.max(jnp.abs(out[1] - M))) > 0.0  # M fold still happened


def test_panel_update_budget_exhausted():
    """``free == 0``: eligible columns exist but none may be admitted."""
    s_c, m, L, c, s_r = 64, 256, 64, 8, 64
    sc, a_l, srt, q, C, M, kw = _inputs(jax.random.key(6), s_c, m, L, c, s_r,
                                        filled=c)
    assert kw["free"] == 0
    out = panel_update(sc, a_l, srt, q, C, M, interpret=True, **kw)
    ref = panel_update_ref(sc, a_l, srt, q, C, M, **kw)
    _check(out, ref)
    np.testing.assert_array_equal(out[5], jnp.full((L,), c, jnp.int32))


def test_panel_update_symmetric_tied_operands():
    """SPSD-symmetric mode: one sketch on both sides (``S_C == S_R``), the
    ``srt`` window a transposed slice of the same ``sc`` buffer."""
    s_c, m, L, c = 64, 256, 64, 8
    off = 96
    sc, a_l, _, q, C, M, kw = _inputs(jax.random.key(7), s_c, m, L, c, s_c)
    srt = jax.lax.dynamic_slice_in_dim(sc, off, L, axis=1).T  # tied operand
    out = panel_update(sc, a_l, srt, q, C, M, interpret=True, **kw)
    ref = panel_update_ref(sc, a_l, srt, q, C, M, **kw)
    _check(out, ref)


def test_panel_update_bf16_inputs_fp32_accum():
    """bf16 panel/sketch inputs: the kernel must accumulate in fp32 —
    outputs land in fp32 and match the fp32-accumulating oracle to bf16
    input precision (not bf16 accumulation precision, which would drift
    far beyond 3e-2 at m=1024)."""
    s_c, m, L, c, s_r = 72, 1024, 96, 16, 72
    sc, a_l, srt, q, C, M, kw = _inputs(jax.random.key(8), s_c, m, L, c, s_r,
                                        dtype=jnp.bfloat16)
    out = panel_update(sc, a_l, srt, q, C, M, interpret=True, **kw)
    ref = panel_update_ref(sc, a_l, srt, q, C, M, **kw)
    assert out[2].dtype == jnp.float32  # sc_a
    assert out[3].dtype == jnp.float32  # resid2
    _check(out, ref, atol_scale=3e-2)


# ---------------------------------------------------------------------------
# engine routes: fused scan body + forced kernel route vs the per-panel oracle
# ---------------------------------------------------------------------------


def test_fused_scan_flag_parity():
    """The default scan (an admission-only CountSketch state takes the
    chunk-hoisted fused body) and the per-panel oracle driver must produce
    identical factors and identical admission decisions on an adaptive
    stream."""
    m, n, panel = 200, 250, 40
    B, _ = spiked_decay_matrix(jax.random.key(30), m, n)

    def init():
        return adaptive_cur_init(
            jax.random.key(31), m, n, 10, jnp.arange(12, dtype=jnp.int32),
            sketch="countsketch", panel=panel, panel_cap=2,
        )

    assert _fused_route_ok(init())
    oracle = stream_panels(init(), B, panel, jit="per-panel")
    fused = stream_panels(init(), B, panel)
    _assert_states_close(fused, oracle)
    np.testing.assert_array_equal(fused.ctx.col_idx, oracle.ctx.col_idx)
    np.testing.assert_allclose(fused.ctx.ScC, oracle.ctx.ScC, atol=2e-5)


def test_evict_stream_stays_on_oracle_body():
    """Eviction-enabled adaptive CUR (no adaptive rows): the scan route must
    match the per-panel driver decision-for-decision."""
    m, n, panel = 200, 200, 40
    B, _ = spiked_decay_matrix(jax.random.key(40), m, n)

    def init():
        return adaptive_cur_init(
            jax.random.key(41), m, n, 8, jnp.arange(8, dtype=jnp.int32),
            sketch="countsketch", panel=panel, panel_cap=2, swap_gain=2.0,
        )

    ref = stream_panels(init(), B, panel, jit="per-panel")
    got = stream_panels(init(), B, panel, jit="scan")
    _assert_states_close(got, ref)
    np.testing.assert_array_equal(got.ctx.col_idx, ref.ctx.col_idx)
    assert int(got.ctx.n_evicted) == int(ref.ctx.n_evicted)


@pytest.mark.parametrize("jit", ["per-panel", "scan"])
def test_forced_kernel_route_end_to_end(jit):
    """Route B: with ``_FORCE_KERNEL_ROUTE`` the engine sends every panel of
    a gaussian-sketch admission-only stream through the Pallas megakernel
    (interpret mode on CPU). Factors and admissions must match the normal
    XLA path on the whole stream."""
    from repro.kernels import ops as kops

    m, n, panel = 256, 160, 32
    B, _ = spiked_decay_matrix(jax.random.key(50), m, n)

    def init():
        return adaptive_cur_init(
            jax.random.key(51), m, n, 8, jnp.arange(8, dtype=jnp.int32),
            s_c=64, s_r=64, sketch="gaussian", panel=panel, panel_cap=2,
        )

    ref = stream_panels(init(), B, panel, jit=jit)
    assert not kops.kernel_route_enabled()  # CPU: kernel off by default
    kops._FORCE_KERNEL_ROUTE = True
    try:
        got = stream_panels(init(), B, panel, jit=jit)
    finally:
        kops._FORCE_KERNEL_ROUTE = False
    _assert_states_close(got, ref, atol=2e-5)
    np.testing.assert_array_equal(got.ctx.col_idx, ref.ctx.col_idx)
    np.testing.assert_allclose(got.ctx.ScC, ref.ctx.ScC, atol=2e-4)
    np.testing.assert_allclose(got.ctx.slot_score, ref.ctx.slot_score, atol=2e-4)
    assert int(got.ctx.n_filled) == int(ref.ctx.n_filled)
