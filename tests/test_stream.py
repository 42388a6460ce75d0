"""Unified panel-streaming engine (repro/stream/): shared contract,
DP-sharded ingestion parity, adaptive column admission/eviction, adaptive
row admission with sketched backfill, edge cases."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    fast_sp_svd,
    sp_svd_finalize,
    sp_svd_init,
    sp_svd_update,
)
from repro.cur import (
    cur_reconstruct,
    cur_relative_error,
    fast_cur,
    select_rows,
    streaming_cur_finalize,
    streaming_cur_init,
    streaming_cur_update,
)
from repro.data.synthetic import (
    drifting_spectrum_matrix,
    late_spike_matrix,
    powerlaw_matrix,
    spiked_decay_matrix,
    spiked_rows_matrix,
)
from repro.stream import (
    adaptive_cur_finalize,
    adaptive_cur_init,
    jitted_panel_update,
    merge_states,
    padded_n,
    shard_panel_ranges,
    simulate_sharded_stream,
    stream_panels,
)

SIZES = dict(c=24, r=24, c0=72, r0=72, s_c=72, s_r=72)
M, N = 220, 180


@pytest.fixture(scope="module")
def A():
    return powerlaw_matrix(jax.random.key(0), M, N, 1.0)


# ---------------------------------------------------------------------------
# shared engine: panel-width / ordering edge cases
# ---------------------------------------------------------------------------


def test_irregular_panel_widths_match_oneshot(A):
    """Permuted irregular panel partitions hit identical accumulators."""
    ref = sp_svd_update(sp_svd_init(jax.random.key(1), M, N, sizes=SIZES), A)
    for widths in ([37, 80, 13, 50], [80, 50, 37, 13], [1, 99, 2, 78]):
        assert sum(widths) == N
        st = sp_svd_init(jax.random.key(1), M, N, sizes=SIZES)
        off = 0
        for w in widths:
            st = sp_svd_update(st, A[:, off : off + w])
            off += w
        np.testing.assert_allclose(st.C, ref.C, atol=2e-3)
        np.testing.assert_allclose(st.R, ref.R, atol=2e-3)
        np.testing.assert_allclose(st.M, ref.M, atol=2e-3)


def test_ragged_tail_zero_padding_is_exact(A):
    """fast_sp_svd with a non-dividing panel == one whole-matrix panel."""
    outs = []
    for panel in (N, 96):  # 180 = 96 + 84 → zero-padded tail
        U, S, V = fast_sp_svd(jax.random.key(2), A, sizes=SIZES, panel=panel)
        outs.append((U * S[None]) @ V.T)
    np.testing.assert_allclose(outs[1], outs[0], atol=5e-3)


def test_jitted_step_is_cached_across_calls(A):
    """The engine step is jitted once at module scope — repeat fast_sp_svd
    calls (same shapes) must not add traces (the old per-call jax.jit
    rebuild retraced every invocation)."""
    fast_sp_svd(jax.random.key(3), A, sizes=SIZES, panel=96)
    before = jitted_panel_update._cache_size()
    fast_sp_svd(jax.random.key(4), A, sizes=SIZES, panel=96)
    fast_sp_svd(jax.random.key(5), A, sizes=SIZES, panel=96)
    assert jitted_panel_update._cache_size() == before


def test_streaming_cur_duplicate_col_idx(A):
    """Duplicate entries in col_idx fill every duplicated slot, and the
    streamed accumulators equal the one-shot sketched pieces. (U itself is
    not compared: with duplicated columns the core solve is rank-deficient,
    so U is non-unique — only the accumulators and the fit are determined.)"""
    # 8 slots / 8 rows / panel 32: shares the jitted-step cache entry with
    # the sharded-parity tests below
    ci = jnp.asarray([5, 5, 40, 171, 40, 3, 99, 120], jnp.int32)
    ri = select_rows(jax.random.key(6), A, 8, "uniform").idx
    st = streaming_cur_init(jax.random.key(7), M, N, ci, ri, sketch="countsketch", panel=32)
    st = stream_panels(st, A, 32)
    res = streaming_cur_finalize(st)
    np.testing.assert_array_equal(res.C, jnp.take(A, ci, axis=1))
    np.testing.assert_array_equal(res.R, jnp.take(A, ri, axis=0))
    np.testing.assert_allclose(st.M, st.S_R.apply_t(st.S_C.apply(A)), atol=2e-3)
    assert bool(jnp.all(jnp.isfinite(res.U)))


# ---------------------------------------------------------------------------
# DP-sharded ingestion: simulated-worker parity (acceptance criterion)
# ---------------------------------------------------------------------------


def test_shard_panel_ranges_cover_and_align():
    for n, panel, w in [(180, 64, 4), (180, 64, 2), (500, 100, 3), (64, 64, 4)]:
        ranges = shard_panel_ranges(n, panel, w)
        assert ranges[0][0] == 0 and ranges[-1][1] == n
        for (lo, hi), (lo2, _) in zip(ranges, ranges[1:]):
            assert hi == lo2 and lo % panel == 0
        assert all(lo <= hi for lo, hi in ranges)


@pytest.mark.parametrize("workers", [2, 4])
def test_sp_svd_sharded_parity(A, workers):
    """DP-sharded SP-SVD == single-host within fp32 summation tolerance."""
    single = stream_panels(sp_svd_init(jax.random.key(8), M, N, sizes=SIZES, panel=32), A, 32)
    shard = simulate_sharded_stream(
        sp_svd_init(jax.random.key(8), M, N, sizes=SIZES, panel=32), A, 32, workers
    )
    np.testing.assert_allclose(shard.C, single.C, atol=2e-3)
    np.testing.assert_allclose(shard.R, single.R, atol=2e-3)
    np.testing.assert_allclose(shard.M, single.M, atol=2e-3)
    U1, S1, V1 = sp_svd_finalize(single)
    U2, S2, V2 = sp_svd_finalize(shard)
    np.testing.assert_allclose(
        (U1 * S1[None]) @ V1.T, (U2 * S2[None]) @ V2.T, atol=5e-3
    )


@pytest.mark.parametrize("workers", [2, 4])
def test_streaming_cur_sharded_parity(A, workers):
    """DP-sharded streaming CUR == single-host factors."""
    ci = jnp.asarray([3, 50, 99, 120, 164, 7, 31, 88], jnp.int32)
    ri = select_rows(jax.random.key(9), A, 8, "uniform").idx

    def init():
        return streaming_cur_init(
            jax.random.key(10), M, N, ci, ri, sketch="countsketch", panel=32
        )

    single = streaming_cur_finalize(stream_panels(init(), A, 32))
    shard = streaming_cur_finalize(simulate_sharded_stream(init(), A, 32, workers))
    np.testing.assert_array_equal(shard.C, single.C)
    np.testing.assert_array_equal(shard.R, single.R)
    np.testing.assert_allclose(shard.U, single.U, atol=2e-3)


def test_merge_states_is_accumulator_sum(A):
    """merge_states is literally Σ_w of the worker accumulators."""
    states = []
    for w, (lo, hi) in enumerate(shard_panel_ranges(N, 32, 3)):
        st = sp_svd_init(jax.random.key(8), M, N, sizes=SIZES, panel=32)
        import dataclasses

        st = dataclasses.replace(st, offset=jnp.asarray(lo, jnp.int32))
        st = stream_panels(st, A, 32, stop=hi)
        states.append(st)
    merged = merge_states(states)
    np.testing.assert_allclose(merged.M, sum(s.M for s in states), atol=1e-6)


# ---------------------------------------------------------------------------
# adaptive column admission (acceptance criterion: beats fixed-uniform)
# ---------------------------------------------------------------------------


def test_adaptive_admits_spiked_columns():
    B, pos = spiked_decay_matrix(jax.random.key(20), 250, 200)
    ri = select_rows(jax.random.key(21), B, 20, "uniform").idx
    st = adaptive_cur_init(
        jax.random.key(22), 250, 200, 10, ri, sketch="countsketch", panel=40, panel_cap=3
    )
    st = stream_panels(st, B, 40)
    res = adaptive_cur_finalize(st)
    admitted = set(np.asarray(res.col_idx).tolist())
    missed = set(np.asarray(pos).tolist()) - admitted
    assert len(missed) <= 1, (sorted(admitted), sorted(np.asarray(pos).tolist()))


def test_adaptive_beats_fixed_uniform_at_equal_budget():
    """The §ROADMAP claim: residual admission < uniform pre-pass selection
    on a spiked-decay matrix at the same column budget c."""
    errs_a, errs_u = [], []
    for t in range(2):
        B, _ = spiked_decay_matrix(jax.random.key(30 + t), 250, 200)
        ri = select_rows(jax.random.key(40 + t), B, 20, "uniform").idx
        st = adaptive_cur_init(
            jax.random.key(50 + t), 250, 200, 10, ri, sketch="countsketch", panel=40, panel_cap=3
        )
        res_a = adaptive_cur_finalize(stream_panels(st, B, 40))
        errs_a.append(float(cur_relative_error(B, res_a)))
        ci = jax.random.choice(jax.random.key(60 + t), 200, (10,), replace=False)
        stu = streaming_cur_init(
            jax.random.key(70 + t), 250, 200, ci, ri, sketch="countsketch", panel=40
        )
        res_u = streaming_cur_finalize(stream_panels(stu, B, 40))
        errs_u.append(float(cur_relative_error(B, res_u)))
    assert np.mean(errs_a) < np.mean(errs_u), (errs_a, errs_u)


def test_adaptive_unfilled_slots_are_inert():
    """A stream with fewer interesting columns than budget leaves slots
    unfilled (col_idx −1, zero C columns, zero U rows) — finite everywhere."""
    B = 0.01 * jax.random.normal(jax.random.key(80), (250, 200))
    B = B.at[:, 13].add(9.0)
    ri = select_rows(jax.random.key(82), B, 20, "uniform").idx
    # same (m, n, c, r, panel) as the sharded test → shared compile cache
    st = adaptive_cur_init(
        jax.random.key(81), 250, 200, 8, ri, sketch="countsketch", panel=25,
        panel_cap=1, min_gain=5.0,
    )
    res = adaptive_cur_finalize(stream_panels(st, B, 25))
    idx = np.asarray(res.col_idx)
    assert (idx == -1).any() and 13 in idx.tolist()
    unfilled = idx == -1
    assert bool(jnp.all(jnp.isfinite(res.U)))
    np.testing.assert_allclose(np.asarray(res.U)[unfilled], 0.0)
    np.testing.assert_allclose(np.asarray(res.C)[:, unfilled], 0.0)


@pytest.mark.parametrize("workers", [2, 4])
def test_adaptive_sharded_still_finds_spikes(workers):
    """Distributed adaptive admission (per-worker slot ranges) still
    captures the heavy columns and stays a valid CUR factorization."""
    B, pos = spiked_decay_matrix(jax.random.key(90), 250, 200, n_spikes=4)
    ri = select_rows(jax.random.key(91), B, 20, "uniform").idx
    # panel_cap=1: with only c/W = 2–4 slots per worker, a larger cap would
    # let a worker exhaust its budget on its first panel before spikes arrive
    st = adaptive_cur_init(
        jax.random.key(92), 250, 200, 8, ri, sketch="countsketch", panel=25, panel_cap=1
    )
    res = adaptive_cur_finalize(simulate_sharded_stream(st, B, 25, workers))
    admitted = set(np.asarray(res.col_idx).tolist())
    missed = set(np.asarray(pos).tolist()) - admitted
    assert len(missed) <= 1, (sorted(admitted), sorted(np.asarray(pos).tolist()))
    assert float(cur_relative_error(B, res)) < 0.5


# ---------------------------------------------------------------------------
# v2: column eviction (acceptance: beats admission-only on late-spike streams)
# ---------------------------------------------------------------------------


def _late_spike_run(key_data, swap_gain, c=8, m=300, n=240, panel=40):
    A, early, late = late_spike_matrix(key_data, m, n)
    ri = select_rows(jax.random.key(101), A, 16, "uniform").idx
    # panel_cap=c//2: the early/weaker spikes genuinely fill the budget
    # before the heavy late ones arrive — the regime eviction exists for
    st = adaptive_cur_init(
        jax.random.key(102), m, n, c, ri, sketch="countsketch", panel=panel,
        panel_cap=c // 2, swap_gain=swap_gain,
    )
    st = stream_panels(st, A, panel)
    return A, late, st, adaptive_cur_finalize(st)


def test_eviction_recovers_late_spikes():
    """Acceptance criterion: at equal (c, r) budget on a late-spike stream,
    eviction-enabled adaptive CUR strictly beats PR 2's admission-only
    policy, because it swaps the late heavy columns over its weakest
    admits."""
    errs = {}
    for sg in (None, 2.0):
        A, late, st, res = _late_spike_run(jax.random.key(100), sg)
        errs[sg] = float(cur_relative_error(A, res))
        captured = len(set(np.asarray(late).tolist()) & set(np.asarray(res.col_idx).tolist()))
        if sg is None:
            assert int(st.ctx.n_evicted) == 0
            n_admit_only_late = captured
        else:
            assert int(st.ctx.n_evicted) > 0
            assert captured > n_admit_only_late, (captured, n_admit_only_late)
    assert errs[2.0] < errs[None], errs


def test_eviction_on_drifting_spectrum():
    """Admission-only locks onto the weak early blocks of a drifting
    spectrum; eviction follows the drift and lands much lower error."""
    A, _bounds = drifting_spectrum_matrix(jax.random.key(110), 300, 240)
    ri = select_rows(jax.random.key(111), A, 16, "uniform").idx
    errs = {}
    for sg in (None, 2.0):
        st = adaptive_cur_init(
            jax.random.key(112), 300, 240, 8, ri, sketch="countsketch", panel=40,
            panel_cap=4, swap_gain=sg,
        )
        res = adaptive_cur_finalize(stream_panels(st, A, 40))
        errs[sg] = float(cur_relative_error(A, res))
    assert errs[2.0] < errs[None], errs


def test_eviction_keeps_slot_invariants():
    """Evictions overwrite in place: col_idx entries stay unique and
    in-range, C columns match the claimed source columns exactly, and the
    filled count never exceeds the budget."""
    A, _late, st, res = _late_spike_run(jax.random.key(120), 2.0)
    idx = np.asarray(res.col_idx)
    filled = idx[idx >= 0]
    assert len(np.unique(filled)) == len(filled)  # no duplicate admissions
    assert np.all(filled < 240)
    np.testing.assert_array_equal(
        np.asarray(res.C)[:, idx >= 0], np.asarray(jnp.take(A, jnp.asarray(filled), axis=1))
    )
    assert int(st.ctx.n_filled) <= 8


# ---------------------------------------------------------------------------
# v2: adaptive row admission + sketched backfill
# ---------------------------------------------------------------------------


def test_adaptive_rows_beat_fixed_prepass():
    """Acceptance criterion: in-stream row admission beats fixed pre-pass
    uniform rows at equal r budget on a spiked-rows matrix (same adaptive
    column policy on both sides)."""
    errs = {}
    for t in range(2):
        A, rpos = spiked_rows_matrix(jax.random.key(130 + t), 300, 240)
        for method in ("fixed", "adaptive"):
            kw = (
                dict(row_idx=select_rows(jax.random.key(140 + t), A, 8, "uniform").idx)
                if method == "fixed"
                else dict(row_idx=None, r=8, panel_cap_rows=2)
            )
            st = adaptive_cur_init(
                jax.random.key(150 + t), 300, 240, 12, sketch="countsketch",
                panel=40, panel_cap=2, **kw,
            )
            res = adaptive_cur_finalize(stream_panels(st, A, 40))
            errs.setdefault(method, []).append(float(cur_relative_error(A, res)))
            if method == "adaptive":
                admitted = set(np.asarray(res.row_idx).tolist())
                missed = set(np.asarray(rpos).tolist()) - admitted
                assert len(missed) <= 1, (sorted(admitted), sorted(np.asarray(rpos).tolist()))
    assert np.mean(errs["adaptive"]) < np.mean(errs["fixed"]), errs


def test_row_backfill_beats_zero_prefix():
    """A row whose energy only appears mid-stream is admitted late; its
    missed column prefix is backfilled from the sketched min-norm
    reconstruction, which must be strictly closer to the true prefix than
    the zeros it replaces (it recovers the prefix's projection onto the
    s_r-dimensional row space of S_R)."""
    m, n, panel = 200, 240, 40
    A = 0.02 * jax.random.normal(jax.random.key(160), (m, n))
    # row 77: sub-threshold structure early, heavy only from column 120 on
    A = A.at[77, :120].set(0.04 * jnp.sin(jnp.arange(120) / 7.0))
    A = A.at[77, 120:].add(8.0 * jax.random.normal(jax.random.key(161), (n - 120,)))
    st = adaptive_cur_init(
        jax.random.key(162), m, n, 6, None, r=4, sketch="countsketch",
        panel=panel, panel_cap=1, panel_cap_rows=1, s_r=96, min_gain_rows=4.0,
    )
    st = stream_panels(st, A, panel)
    res = adaptive_cur_finalize(st)
    idx = np.asarray(res.row_idx)
    assert 77 in idx.tolist()
    slot = int(np.where(idx == 77)[0][0])
    admit_off = int(np.asarray(st.ctx.rows.admit_off)[slot])
    assert admit_off >= 120  # admitted only once the heavy block streamed by
    true_prefix = np.asarray(A)[77, :admit_off]
    got_prefix = np.asarray(res.R)[slot, :admit_off]
    err = np.linalg.norm(got_prefix - true_prefix)
    assert err < 0.95 * np.linalg.norm(true_prefix), (err, np.linalg.norm(true_prefix))
    # and the seen suffix is copied exactly, not reconstructed
    np.testing.assert_array_equal(
        np.asarray(res.R)[slot, admit_off + panel:], np.asarray(A)[77, admit_off + panel:]
    )


def test_unfilled_row_slots_are_inert():
    """A stream with fewer interesting rows than budget leaves row slots
    unfilled (row_idx −1, zero R rows, zero U columns) — finite everywhere."""
    B = 0.01 * jax.random.normal(jax.random.key(170), (200, 240))
    B = B.at[42, :].add(7.0)
    st = adaptive_cur_init(
        jax.random.key(171), 200, 240, 6, None, r=6, sketch="countsketch",
        panel=40, panel_cap=1, panel_cap_rows=1, min_gain_rows=5.0,
    )
    res = adaptive_cur_finalize(stream_panels(st, B, 40))
    idx = np.asarray(res.row_idx)
    assert (idx == -1).any() and 42 in idx.tolist()
    unfilled = idx == -1
    assert bool(jnp.all(jnp.isfinite(res.U)))
    np.testing.assert_allclose(np.asarray(res.U)[:, unfilled], 0.0)
    np.testing.assert_allclose(np.asarray(res.R)[unfilled, :], 0.0)


# ---------------------------------------------------------------------------
# v2: DP-sharded ingestion with eviction + row admission (acceptance)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workers", [2, 4])
def test_v2_sharded_parity_eviction_and_rows(workers):
    """simulate_sharded_stream with eviction + adaptive rows enabled:
    disjoint per-worker slot ranges merge into a valid, finite
    factorization that still captures the planted structure (the adaptive
    paths' parity contract — admission decisions are worker-local, so the
    merge is a valid outcome rather than bitwise single-host equality)."""
    A, rpos = spiked_rows_matrix(jax.random.key(180), 300, 240)
    st = adaptive_cur_init(
        jax.random.key(181), 300, 240, 8, None, r=8, sketch="countsketch",
        panel=20, panel_cap=1, panel_cap_rows=1, swap_gain=2.0,
    )
    res = adaptive_cur_finalize(simulate_sharded_stream(st, A, 20, workers))
    err = float(cur_relative_error(A, res))
    assert np.isfinite(err) and err < 1.0, err
    admitted = set(np.asarray(res.row_idx).tolist())
    missed = set(np.asarray(rpos).tolist()) - admitted
    assert len(missed) <= 2, (sorted(admitted), sorted(np.asarray(rpos).tolist()))
    # slot-range discipline survived the merge: unique filled indices
    for idx in (np.asarray(res.col_idx), ):
        filled = idx[idx >= 0]
        assert len(np.unique(filled)) == len(filled)


def test_cross_worker_row_dedup():
    """ROADMAP open item: rows are global, so two workers admit the same
    heavy row into different slots. The merge_state hook must consolidate
    the duplicates into the lowest slot (summing their disjoint-support R
    pieces — here recovering the *full* row exactly) and free the rest,
    on the scan and per-panel sharded drivers alike."""
    m, n, panel = 200, 240, 40
    A = 0.02 * jax.random.normal(jax.random.key(400), (m, n))
    # rows 77/131 are heavy across the whole stream → every worker admits them
    A = A.at[77, :].add(8.0 * jax.random.normal(jax.random.key(401), (n,)))
    A = A.at[131, :].add(5.0 * jax.random.normal(jax.random.key(402), (n,)))
    for jit in ("scan", "per-panel"):
        st = adaptive_cur_init(
            jax.random.key(403), m, n, 6, None, r=4, sketch="countsketch",
            panel=panel, panel_cap=1, panel_cap_rows=1,
        )
        st_out = simulate_sharded_stream(st, A, panel, 2, jit=jit)
        res = adaptive_cur_finalize(st_out)
        idx = np.asarray(res.row_idx)
        filled = idx[idx >= 0]
        assert len(np.unique(filled)) == len(filled), (jit, idx)
        assert {77, 131} <= set(filled.tolist()), (jit, idx)
        # consolidation: the kept slot holds the union of both workers'
        # column ranges — the complete true row, not a half-zeroed one
        slot = int(np.where(idx == 77)[0][0])
        np.testing.assert_allclose(
            np.asarray(res.R)[slot], np.asarray(A)[77], atol=1e-5
        )
        # freed slots are fully inert: zero R rows, zero U columns, and the
        # filled-count accounting reflects the dedup
        unfilled = idx == -1
        np.testing.assert_allclose(np.asarray(res.R)[unfilled], 0.0)
        np.testing.assert_allclose(np.asarray(res.U)[:, unfilled], 0.0)
        assert int(st_out.ctx.rows.n_filled) == len(filled), jit
        assert bool(jnp.all(jnp.isfinite(res.U)))


def test_v2_shard_budget_must_divide():
    """prep_shard refuses budgets that don't split across workers."""
    st = adaptive_cur_init(
        jax.random.key(190), 100, 120, 10, None, r=6, sketch="countsketch", panel=20
    )
    with pytest.raises(ValueError, match="row budget"):
        simulate_sharded_stream(st, jnp.zeros((100, 120)), 20, 5)


# ---------------------------------------------------------------------------
# multi-device shard_map path (subprocess, forced host devices)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_multidev_stream_parity():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    script = os.path.join(os.path.dirname(__file__), "multidev_scenario.py")
    proc = subprocess.run(
        [sys.executable, script, "stream"], capture_output=True, text=True, env=env, timeout=900
    )
    assert proc.returncode == 0, f"\nSTDOUT:{proc.stdout[-2000:]}\nSTDERR:{proc.stderr[-3000:]}"
    assert "OK scenario" in proc.stdout


# ---------------------------------------------------------------------------
# scan-path parity: the compiled lax.scan driver (default) must reproduce the
# per-panel jitted_panel_update loop for every configuration
# ---------------------------------------------------------------------------


def _assert_states_close(a, b, atol=2e-5):
    np.testing.assert_allclose(a.C, b.C, atol=atol)
    np.testing.assert_allclose(a.R, b.R, atol=atol)
    np.testing.assert_allclose(a.M, b.M, atol=atol)
    assert int(a.offset) == int(b.offset)


def test_scan_parity_spsvd_fixed(A):
    """SP-SVD: scan path vs per-panel path, including a ragged tail
    (N=180, panel=48 → 3 full panels + 36-column zero-padded tail)."""
    for panel in (45, 48):  # dividing and ragged
        ref = stream_panels(
            sp_svd_init(jax.random.key(201), M, N, sizes=SIZES, panel=panel),
            A, panel, jit="per-panel",
        )
        got = stream_panels(
            sp_svd_init(jax.random.key(201), M, N, sizes=SIZES, panel=panel),
            A, panel, jit="scan",
        )
        _assert_states_close(got, ref)


def test_scan_parity_streaming_cur_fixed(A):
    ci = jnp.asarray([3, 50, 99, 120, 164, 7, 31, 88], jnp.int32)
    ri = select_rows(jax.random.key(202), A, 8, "uniform").idx
    for panel in (32, 50):  # 180 % 50 != 0 → ragged tail
        def init():
            return streaming_cur_init(
                jax.random.key(203), M, N, ci, ri, sketch="countsketch", panel=panel
            )
        ref = stream_panels(init(), A, panel, jit="per-panel")
        got = stream_panels(init(), A, panel, jit="scan")
        _assert_states_close(got, ref)
        np.testing.assert_array_equal(got.C, ref.C)
        np.testing.assert_array_equal(got.R, ref.R)


@pytest.mark.parametrize("jit", [True, False, "eager"])
def test_stream_panels_takes_two_jit_modes(A, jit):
    """``jit`` names a driver, ``"scan"`` or ``"per-panel"``; anything else
    is refused before a panel is read, in both stream drivers."""
    ci = jnp.asarray([3, 50, 99, 120], jnp.int32)
    st = streaming_cur_init(jax.random.key(204), M, N, ci, ci, panel=45)
    with pytest.raises(ValueError, match="jit must be one of"):
        stream_panels(st, A, 45, jit=jit)
    with pytest.raises(ValueError, match="jit must be one of"):
        simulate_sharded_stream(st, A, 45, 2, jit=jit)
    assert int(st.offset) == 0


def test_scan_parity_adaptive_cols_evict_rows():
    """Adaptive CUR with eviction + adaptive rows: the scan carry includes
    the whole AdaptiveCURCtx/AdaptiveRowState — admission decisions, slot
    tables, backfills must match the per-panel driver decision-for-decision."""
    m, n, panel = 300, 240, 40
    B, _ = spiked_rows_matrix(jax.random.key(210), m, n)

    def init():
        return adaptive_cur_init(
            jax.random.key(211), m, n, 8, None, r=8, sketch="countsketch",
            panel=panel, panel_cap=2, panel_cap_rows=1, swap_gain=2.0,
        )

    ref = stream_panels(init(), B, panel, jit="per-panel")
    got = stream_panels(init(), B, panel, jit="scan")
    _assert_states_close(got, ref)
    np.testing.assert_array_equal(got.ctx.col_idx, ref.ctx.col_idx)
    np.testing.assert_array_equal(got.ctx.row_idx, ref.ctx.row_idx)
    np.testing.assert_array_equal(got.ctx.rows.admit_off, ref.ctx.rows.admit_off)
    assert int(got.ctx.n_evicted) == int(ref.ctx.n_evicted)
    np.testing.assert_allclose(got.ctx.ScC, ref.ctx.ScC, atol=2e-5)
    np.testing.assert_allclose(
        got.ctx.rows.row_sketch, ref.ctx.rows.row_sketch, atol=2e-4
    )


def test_scan_parity_adaptive_ragged_tail():
    """Adaptive CUR on a stream where n is not a panel multiple (250 = 6×40
    + 10): the zero-padded tail must admit/score identically on both paths."""
    m, n, panel = 200, 250, 40
    B, _ = spiked_decay_matrix(jax.random.key(212), m, n)
    ri = select_rows(jax.random.key(213), B, 12, "uniform").idx

    def init():
        return adaptive_cur_init(
            jax.random.key(214), m, n, 10, ri, sketch="countsketch",
            panel=panel, panel_cap=2,
        )

    ref = stream_panels(init(), B, panel, jit="per-panel")
    got = stream_panels(init(), B, panel, jit="scan")
    _assert_states_close(got, ref)
    np.testing.assert_array_equal(got.ctx.col_idx, ref.ctx.col_idx)
    res_ref = adaptive_cur_finalize(ref)
    res_got = adaptive_cur_finalize(got)
    np.testing.assert_allclose(res_got.U, res_ref.U, atol=2e-4)


@pytest.mark.parametrize("workers", [2, 4])
def test_scan_parity_sharded_fixed(A, workers):
    """simulate_sharded_stream: fused single-program driver vs the per-panel
    per-worker loop (fixed-index ops — chained accumulators are provably the
    merged accumulators)."""
    ci = jnp.asarray([3, 50, 99, 120, 164, 7, 31, 88], jnp.int32)
    ri = select_rows(jax.random.key(220), A, 8, "uniform").idx

    def init():
        return streaming_cur_init(
            jax.random.key(221), M, N, ci, ri, sketch="countsketch", panel=32
        )

    ref = simulate_sharded_stream(init(), A, 32, workers, jit="per-panel")
    got = simulate_sharded_stream(init(), A, 32, workers, jit="scan")
    _assert_states_close(got, ref)
    np.testing.assert_array_equal(got.C, ref.C)
    np.testing.assert_array_equal(got.R, ref.R)


@pytest.mark.parametrize("workers", [2, 4])
def test_scan_parity_sharded_adaptive(workers):
    """Sharded adaptive (divergent per-worker ctx → true per-worker
    accumulators + in-program merge): same admissions as the per-panel
    sharded driver, worker for worker."""
    m, n, panel = 300, 240, 20
    B, _ = spiked_rows_matrix(jax.random.key(230), m, n)

    def init():
        return adaptive_cur_init(
            jax.random.key(231), m, n, 8, None, r=8, sketch="countsketch",
            panel=panel, panel_cap=1, panel_cap_rows=1, swap_gain=2.0,
        )

    ref = simulate_sharded_stream(init(), B, panel, workers, jit="per-panel")
    got = simulate_sharded_stream(init(), B, panel, workers, jit="scan")
    _assert_states_close(got, ref, atol=2e-4)
    np.testing.assert_array_equal(got.ctx.col_idx, ref.ctx.col_idx)
    np.testing.assert_array_equal(got.ctx.row_idx, ref.ctx.row_idx)
    assert int(got.ctx.n_filled) == int(ref.ctx.n_filled)


def test_scan_stream_is_compile_cached(A):
    """Repeated scan-path streams of the same shape must reuse the
    module-scope compiled entry (no per-call retrace)."""
    from repro.stream.engine import _scan_stream_panels

    def run():
        st = sp_svd_init(jax.random.key(240), M, N, sizes=SIZES, panel=45)
        return stream_panels(st, A, 45)

    run()
    before = _scan_stream_panels._cache_size()
    run()
    run()
    assert _scan_stream_panels._cache_size() == before


def test_donation_consumes_input_state(A):
    """The scan path donates the input state's buffers — using the input
    after streaming must raise, and caller-provided index arrays must stay
    alive (init copies them)."""
    ci = jnp.asarray([3, 50, 99, 120, 164, 7, 31, 88], jnp.int32)
    ri = select_rows(jax.random.key(250), A, 8, "uniform").idx
    st0 = streaming_cur_init(jax.random.key(251), M, N, ci, ri, sketch="countsketch", panel=32)
    st1 = stream_panels(st0, A, 32)
    assert int(st1.offset) == padded_n(N, 32)  # tail panel zero-padded
    # caller-held arrays survive (defensive copies at init)
    np.testing.assert_array_equal(np.asarray(ci)[:3], [3, 50, 99])
    _ = np.asarray(ri)
    if st0.C.is_deleted():  # donation active on this backend
        with pytest.raises(RuntimeError):
            _ = np.asarray(st0.C)


def test_adaptive_scorer_survives_duplicate_admissions():
    """Near-duplicate heavy columns make the admitted Gram numerically
    rank-deficient; the whitened-basis scorer must stay NaN-free (the
    no-NaN contract of the floored-QR path it replaced) and keep admitting
    later structure instead of silently going dead."""
    m, n, panel = 200, 240, 40
    B = 0.01 * jax.random.normal(jax.random.key(300), (m, n))
    spike = jax.random.normal(jax.random.key(301), (m,)) * 9.0
    # two (near-)identical heavy columns in the first panel...
    B = B.at[:, 3].add(spike).at[:, 17].add(spike)
    # ...and a genuinely new heavy column long after
    B = B.at[:, 200].add(9.0 * jax.random.normal(jax.random.key(302), (m,)))
    ri = select_rows(jax.random.key(303), B, 8, "uniform").idx
    st = adaptive_cur_init(
        jax.random.key(304), m, n, 6, ri, sketch="countsketch", panel=panel, panel_cap=2
    )
    st = stream_panels(st, B, panel)
    res = adaptive_cur_finalize(st)
    assert bool(jnp.all(jnp.isfinite(res.U)))
    assert bool(jnp.all(jnp.isfinite(st.ctx.slot_score)))
    admitted = set(np.asarray(res.col_idx).tolist())
    assert {3, 17} & admitted  # the duplicates were scoreable
    assert 200 in admitted, sorted(admitted)  # scorer still alive afterwards
