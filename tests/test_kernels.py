"""Per-kernel allclose sweeps: Pallas (interpret) vs pure-jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    countsketch_apply,
    countsketch_ref,
    panel_score,
    panel_score_ref,
    twoside_sketch,
    twoside_sketch_ref,
)

TWOSIDE_SHAPES = [
    (64, 300, 200, 64),  # unaligned m/n → padding path
    (128, 512, 512, 96),
    (32, 130, 260, 48),
    (256, 1024, 384, 128),
    (128, 256, 256, 128),  # exactly aligned
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", TWOSIDE_SHAPES)
def test_twoside_sketch_allclose(shape, dtype):
    s_c, m, n, s_r = shape
    ks = jax.random.split(jax.random.key(sum(shape)), 3)
    Sc = jax.random.normal(ks[0], (s_c, m), jnp.float32).astype(dtype)
    A = jax.random.normal(ks[1], (m, n), jnp.float32).astype(dtype)
    SrT = jax.random.normal(ks[2], (n, s_r), jnp.float32).astype(dtype)
    out = twoside_sketch(Sc, A, SrT, interpret=True)
    ref = twoside_sketch_ref(Sc, A, SrT)
    tol = 1e-5 if dtype == jnp.float32 else 2.5e-2
    rel = float(jnp.max(jnp.abs(out - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert rel < tol, (shape, dtype, rel)


CS_SHAPES = [(64, 300, 200), (100, 512, 384), (200, 1000, 130), (128, 256, 256)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", CS_SHAPES)
def test_countsketch_allclose(shape, dtype):
    s, m, n = shape
    ks = jax.random.split(jax.random.key(sum(shape)), 3)
    h = jax.random.randint(ks[0], (m,), 0, s)
    sg = jax.random.rademacher(ks[1], (m,), jnp.float32)
    A = jax.random.normal(ks[2], (m, n), jnp.float32).astype(dtype)
    out = countsketch_apply(h, sg, A, s, interpret=True)
    ref = countsketch_ref(h, sg, A, s)
    tol = 1e-5 if dtype == jnp.float32 else 2.5e-2
    rel = float(jnp.max(jnp.abs(out - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert rel < tol, (shape, dtype, rel)


def test_countsketch_padding_no_bucket_pollution():
    """Padded rows must not contribute to any bucket (zero signs)."""
    s, m, n = 64, 100, 50  # m=100 pads to 256
    ks = jax.random.split(jax.random.key(0), 3)
    h = jax.random.randint(ks[0], (m,), 0, s)
    sg = jax.random.rademacher(ks[1], (m,), jnp.float32)
    A = jnp.ones((m, n))
    out = countsketch_apply(h, sg, A, s, interpret=True)
    ref = countsketch_ref(h, sg, A, s)
    np.testing.assert_allclose(out, ref, atol=1e-5)


def _segment_sum_apply(S, A):
    """``CountSketch.apply`` as it runs off the TPU: the signed segment sum."""
    from repro.kernels import ops as kops

    assert not kops.kernel_route_enabled()
    return S.apply(A)


def _assert_same_sums(out, ref):
    """Bitwise where the kernel adds in segment_sum's order (float32 sums of
    exactly converted rows); otherwise within 1e-6 of the largest entry."""
    if out.dtype == ref.dtype == jnp.float32:
        np.testing.assert_array_equal(out, ref)
    else:
        scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32)))) + 1e-30
        diff = jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))
        assert float(jnp.max(diff)) <= 1e-6 * scale


# (s, m, n, block_m, block_n): several row and column blocks, ragged edges
CS_KERNEL_CASES = [
    (64, 300, 200, 128, 128),  # s < 128, m and n ragged against the blocks
    (130, 1000, 300, 256, 128),  # s not a multiple of 128 (nor of 8)
    (256, 512, 384, 128, 256),  # aligned: no edge block
    (7, 100, 50, 2048, 1024),  # one block larger than the operand
]


@pytest.mark.parametrize("case", CS_KERNEL_CASES, ids=lambda c: "s{}_m{}_n{}_bm{}_bn{}".format(*c))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_countsketch_kernel_matches_segment_sum(case, dtype):
    from repro.core.sketching import CountSketch

    s, m, n, bm, bn = case
    ks = jax.random.split(jax.random.key(sum(case)), 2)
    S = CountSketch.draw(ks[0], s, m)
    A = jax.random.normal(ks[1], (m, n), jnp.float32).astype(dtype)
    out = countsketch_apply(S.hashes, S.signs, A, s, block_m=bm, block_n=bn, interpret=True)
    assert out.shape == (s, n) and out.dtype == jnp.float32
    _assert_same_sums(out, _segment_sum_apply(S, A))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_countsketch_kernel_all_rows_one_bucket(dtype):
    """Every row in one bucket: the most collisions, one long running sum."""
    from repro.core.sketching import CountSketch

    s, m, n = 40, 700, 260
    ks = jax.random.split(jax.random.key(3), 2)
    S = CountSketch.draw(ks[0], s, m)
    S = CountSketch(hashes=jnp.full((m,), 17, jnp.int32), signs=S.signs, s=s)
    A = jax.random.normal(ks[1], (m, n), jnp.float32).astype(dtype)
    out = countsketch_apply(S.hashes, S.signs, A, s, block_m=256, block_n=128, interpret=True)
    ref = _segment_sum_apply(S, A)
    _assert_same_sums(out, ref)
    assert not bool(jnp.any(out[:17])) and not bool(jnp.any(out[18:]))


def test_countsketch_kernel_pad_cols_rows_are_inert():
    """``pad_cols`` rows (hash 0, sign 0) add nothing — also where the
    operand's padded rows hold data, and to bucket 0 in particular."""
    from repro.core.sketching import CountSketch

    s, m, total, n = 48, 300, 520, 200
    ks = jax.random.split(jax.random.key(4), 2)
    S = CountSketch.draw(ks[0], s, m).pad_cols(total)
    A = jax.random.normal(ks[1], (total, n), jnp.float32) + 3.0
    out = countsketch_apply(S.hashes, S.signs, A, s, block_m=128, block_n=128, interpret=True)
    _assert_same_sums(out, _segment_sum_apply(S, A))
    unpadded = countsketch_apply(S.hashes[:m], S.signs[:m], A[:m], s, interpret=True)
    np.testing.assert_array_equal(out, unpadded)


@pytest.fixture
def countsketch_kernel_route(monkeypatch):
    """``CountSketch.apply`` takes the Pallas kernel (interpret mode) as on a
    TPU; every program traces anew so the route reaches the engine's jits."""
    from repro.kernels import ops as kops
    from repro.obs.metrics import MetricsRegistry, set_registry

    monkeypatch.setattr(kops, "kernel_route_enabled", lambda: True)
    jax.clear_caches()
    reg = MetricsRegistry()
    prev = set_registry(reg)
    yield reg
    set_registry(prev)
    jax.clear_caches()


def test_countsketch_apply_routes_to_kernel(countsketch_kernel_route):
    from repro.core.sketching import CountSketch

    reg = countsketch_kernel_route
    S = CountSketch.draw(jax.random.key(5), 96, 400)
    A = jax.random.normal(jax.random.key(6), (400, 150))
    out = S.apply(A)
    assert reg.counters == {"sketch.countsketch.route.kernel": 1}
    np.testing.assert_array_equal(out, jax.ops.segment_sum(A * S.signs[:, None], S.hashes, num_segments=96))
    # rank 3 stays on segment_sum; apply_t goes through apply
    S.apply(A.reshape(400, 10, 15))
    S.apply_t(A.T)
    assert reg.counters == {"sketch.countsketch.route.kernel": 2,
                            "sketch.countsketch.route.segment_sum": 1}


def test_countsketch_apply_off_tpu_is_segment_sum():
    from repro.core.sketching import CountSketch
    from repro.obs.metrics import MetricsRegistry, set_registry

    prev = set_registry(MetricsRegistry())
    try:
        S = CountSketch.draw(jax.random.key(7), 32, 64)
        S.apply(jnp.ones((64, 8)))
        from repro.obs.metrics import default_registry

        assert default_registry().counters == {"sketch.countsketch.route.segment_sum": 1}
    finally:
        set_registry(prev)


_MESH_ROUTE_SCRIPT = r"""
import json
import jax, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.sketching import CountSketch
from repro.kernels import ops as kops
from repro.obs.metrics import MetricsRegistry, set_registry

kops.kernel_route_enabled = lambda: True
reg = MetricsRegistry()
set_registry(reg)
mesh = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
S = CountSketch.draw(jax.random.key(0), 96, 700)
A = jax.random.normal(jax.random.key(1), (700, 512))
ref = np.asarray(jax.ops.segment_sum(A * S.signs[:, None], S.hashes, num_segments=96))
out = {}
for name, spec in (("replicated", P()), ("columns", P(None, "data"))):
    got = jax.jit(lambda S, a: S.apply(a))(jax.device_put(S, NamedSharding(mesh, P())),
                                           jax.device_put(A, NamedSharding(mesh, spec)))
    out[name] = bool((np.asarray(got) == ref).all())
out["on_mesh"] = dict(reg.counters)
reg.counters.clear()
sm = jax.jit(jax.shard_map(S.apply, mesh=mesh, in_specs=P(None, "data"), out_specs=P(None, "data")))
out["shard_map"] = bool((np.asarray(sm(jax.device_put(A, NamedSharding(mesh, P(None, "data"))))) == ref).all())
out["in_shard_map"] = dict(reg.counters)
print(json.dumps(out))
"""


def test_countsketch_route_on_a_mesh():
    """On 4 devices (virtual CPUs, kernel route forced): an operand laid out
    over the mesh by a jit takes ``segment_sum`` (XLA cannot partition a
    kernel); inside ``shard_map`` each device runs the kernel on its columns
    (interpret mode) — both equal to the unsharded segment sum."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, "-c", _MESH_ROUTE_SCRIPT], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["replicated"] and out["columns"] and out["shard_map"], out
    assert out["on_mesh"] == {"sketch.countsketch.route.segment_sum": 2}
    assert out["in_shard_map"] == {"sketch.countsketch.route.kernel": 1}


def test_engine_on_countsketch_kernel_matches_per_panel_oracle(countsketch_kernel_route):
    """Fused scan (one chunk sketch, per-panel M fold) and the per-panel
    oracle, both on the kernel route, give the C, R and M of the
    segment_sum route: adaptive CUR (admission in-stream) and SPSD."""
    from repro.data.synthetic import spiked_decay_matrix
    from repro.kernels import ops as kops
    from repro.spsd import streaming_spsd_init
    from repro.stream.adaptive import adaptive_cur_init
    from repro.stream.engine import stream_panels

    reg = countsketch_kernel_route
    m, n, panel = 200, 250, 40
    B, _ = spiked_decay_matrix(jax.random.key(30), m, n)
    K = B[:, :m] @ B[:, :m].T

    def cur():
        return adaptive_cur_init(
            jax.random.key(31), m, n, 10, jnp.arange(12, dtype=jnp.int32),
            sketch="countsketch", panel=panel, panel_cap=2,
        )

    def spsd():
        return streaming_spsd_init(
            jax.random.key(32), m, jnp.arange(0, m, 20, dtype=jnp.int32), s=64, panel=panel
        )

    results = {}
    for name, init, mat in (("cur", cur, B), ("spsd", spsd, K)):
        for jit in ("per-panel", "scan"):
            results[name, jit] = stream_panels(init(), mat, panel, jit=jit)
    assert reg.counters["sketch.countsketch.route.kernel"] > 0
    assert "sketch.countsketch.route.segment_sum" not in reg.counters
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kops, "kernel_route_enabled", lambda: False)
        jax.clear_caches()
        for name, init, mat in (("cur", cur, B), ("spsd", spsd, K)):
            results[name, "segment_sum"] = stream_panels(init(), mat, panel, jit="per-panel")
    for name in ("cur", "spsd"):
        ref = results[name, "per-panel"]
        for got in (results[name, "scan"], results[name, "segment_sum"]):
            np.testing.assert_array_equal(got.C, ref.C)
            np.testing.assert_array_equal(got.R, ref.R)
            np.testing.assert_array_equal(got.M, ref.M)
            assert int(got.offset) == int(ref.offset)
    np.testing.assert_array_equal(results["cur", "scan"].ctx.col_idx, results["cur", "per-panel"].ctx.col_idx)


# (p, s, m, n): ragged m and n; s not a multiple of 8; fewer rows than s
# buckets (the shape of SP-SVD's Omega side, a panel's transpose)
OSNAP_CASES = [(2, 130, 1000, 300), (4, 130, 1000, 300), (2, 7, 100, 50), (4, 37, 300, 200),
               (2, 1292 // 4, 64, 700), (4, 1292 // 4, 64, 700)]


@pytest.mark.parametrize("case", OSNAP_CASES, ids=lambda c: "p{}_s{}_m{}_n{}".format(*c))
def test_osnap_kernel_route_matches_segment_sum(case, countsketch_kernel_route):
    """``OSNAPSketch.apply`` on the kernel route (interpret mode) equals its
    ``segment_sum`` route: each hash row's term is the kernel's float32
    segment sum, and the ``p`` terms are added in hash-row order. Bit for
    bit where the signs ``+-1/sqrt(p)`` are exact (p = 4); at p = 2 each
    signed row rounds, except where the compiler fuses the kernel's
    multiply and add (XLA on the CPU does), so the sums agree to float32
    rounding of the products."""
    from repro.core.sketching import OSNAPSketch
    from repro.kernels import ops as kops

    reg = countsketch_kernel_route
    p, s, m, n = case
    ks = jax.random.split(jax.random.key(sum(case)), 2)
    S = OSNAPSketch.draw(ks[0], s, m, p=p)
    A = jax.random.normal(ks[1], (m, n), jnp.float32)
    out = jax.jit(S.apply)(A)
    assert reg.counters == {"sketch.osnap.route.kernel": 1}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kops, "kernel_route_enabled", lambda: False)
        ref = jax.jit(S.apply)(A)
    assert reg.counters == {"sketch.osnap.route.kernel": 1, "sketch.osnap.route.segment_sum": 1}
    assert out.shape == (s, n) and out.dtype == jnp.float32
    terms = [jax.ops.segment_sum(A * S.signs[j][:, None], S.hashes[j], num_segments=s)
             for j in range(p)]
    in_order = terms[0]
    for t in terms[1:]:
        in_order = in_order + t
    if p == 4:
        np.testing.assert_array_equal(out, in_order)
        np.testing.assert_array_equal(out, ref)
    else:
        scale = float(jnp.max(jnp.abs(ref))) + 1e-30
        assert float(jnp.max(jnp.abs(out - ref))) <= 1e-6 * scale
        assert float(jnp.max(jnp.abs(out - in_order))) <= 1e-6 * scale


def test_osnap_kernel_route_pad_cols_and_apply_t(countsketch_kernel_route):
    """``pad_cols`` columns of an OSNAP sketch add nothing on the kernel
    route, and ``apply_t`` (the Omega side, ``A_L Omega_L^T``) takes it too."""
    from repro.core.sketching import OSNAPSketch

    reg = countsketch_kernel_route
    s, m, total, n = 48, 300, 520, 200
    ks = jax.random.split(jax.random.key(8), 2)
    S = OSNAPSketch.draw(ks[0], s, m, p=2)
    A = jax.random.normal(ks[1], (total, n), jnp.float32) + 3.0
    padded = S.pad_cols(total).apply(A)
    np.testing.assert_array_equal(padded, S.apply(A[:m]))
    window = S.pad_cols(total).cols(m - 40, 80)  # straddles the padding
    B = jax.random.normal(ks[1], (n, 80), jnp.float32)
    np.testing.assert_allclose(window.apply_t(B), B @ window.materialize().T, rtol=1e-5, atol=1e-5)
    assert reg.counters == {"sketch.osnap.route.kernel": 3}


def test_osnap_apply_off_tpu_is_segment_sum():
    from repro.core.sketching import OSNAPSketch
    from repro.obs.metrics import MetricsRegistry, set_registry

    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        S = OSNAPSketch.draw(jax.random.key(9), 32, 64, p=2)
        S.apply(jnp.ones((64, 8)))
        S.apply(jnp.ones((64, 2, 4)))  # rank 3
        assert reg.counters == {"sketch.osnap.route.segment_sum": 2}
    finally:
        set_registry(prev)


def test_twoside_block_shape_sweep():
    """Same result across BlockSpec tilings (grid decomposition invariance)."""
    s_c, m, n, s_r = 128, 512, 512, 128
    ks = jax.random.split(jax.random.key(1), 3)
    Sc = jax.random.normal(ks[0], (s_c, m))
    A = jax.random.normal(ks[1], (m, n))
    SrT = jax.random.normal(ks[2], (n, s_r))
    ref = twoside_sketch_ref(Sc, A, SrT)
    scale = float(jnp.max(jnp.abs(ref)))
    for bm, bn in [(128, 128), (256, 256), (512, 128)]:
        out = twoside_sketch(Sc, A, SrT, block_m=bm, block_n=bn, interpret=True)
        # different tilings reorder the fp32 reduction; tolerance scales with |M|
        np.testing.assert_allclose(out, ref, rtol=0, atol=2e-4 * scale)


# ---------------------------------------------------------------------------
# panel_score: fused streaming panel scoring (sc_a + resid2 + energy)
# ---------------------------------------------------------------------------

PS_SHAPES = [
    (72, 300, 96, 16),  # every dim unaligned → padding path
    (240, 1024, 128, 16),  # the adaptive-CUR bench shape
    (128, 512, 256, 32),  # aligned
    (64, 130, 40, 8),  # tiny ragged panel
]


@pytest.mark.parametrize("shape", PS_SHAPES)
def test_panel_score_allclose(shape):
    s_c, m, L, c = shape
    ks = jax.random.split(jax.random.key(sum(shape)), 3)
    Sc = jax.random.normal(ks[0], (s_c, m))
    A_L = jax.random.normal(ks[1], (m, L))
    Q, _ = jnp.linalg.qr(jax.random.normal(ks[2], (s_c, c)))
    Qm = Q * (jnp.arange(c) < max(1, c // 2))
    sc_a, r2, en = panel_score(Sc, A_L, Qm, interpret=True)
    sc_ref, r2_ref, en_ref = panel_score_ref(Sc, A_L, Qm)
    scale = float(jnp.max(en_ref)) + 1e-9
    np.testing.assert_allclose(sc_a, sc_ref, atol=1e-4 * float(jnp.max(jnp.abs(sc_ref))))
    np.testing.assert_allclose(r2, r2_ref, atol=2e-4 * scale)
    np.testing.assert_allclose(en, en_ref, atol=2e-4 * scale)


def test_panel_score_empty_and_full_basis():
    """Unfilled basis ⇒ resid2 == energy; full orthonormal basis that spans
    the sketch space ⇒ resid2 == 0."""
    s_c, m, L = 32, 200, 64
    ks = jax.random.split(jax.random.key(7), 2)
    Sc = jax.random.normal(ks[0], (s_c, m))
    A_L = jax.random.normal(ks[1], (m, L))
    zero_q = jnp.zeros((s_c, 8))
    _, r2, en = panel_score(Sc, A_L, zero_q, interpret=True)
    np.testing.assert_allclose(r2, en, rtol=1e-6)
    full_q = jnp.eye(s_c)  # spans everything
    _, r2f, enf = panel_score(Sc, A_L, full_q, interpret=True)
    np.testing.assert_allclose(r2f, jnp.zeros_like(r2f), atol=2e-3 * float(jnp.max(enf)))


def test_panel_score_block_shape_sweep():
    """Grid-decomposition invariance across (block_m, block_l) tilings."""
    s_c, m, L, c = 128, 512, 256, 16
    ks = jax.random.split(jax.random.key(11), 3)
    Sc = jax.random.normal(ks[0], (s_c, m))
    A_L = jax.random.normal(ks[1], (m, L))
    Q, _ = jnp.linalg.qr(jax.random.normal(ks[2], (s_c, c)))
    _, r2_ref, en_ref = panel_score_ref(Sc, A_L, Q)
    scale = float(jnp.max(en_ref))
    for bm, bl in [(128, 128), (256, 128), (512, 256)]:
        _, r2, en = panel_score(Sc, A_L, Q, block_m=bm, block_l=bl, interpret=True)
        np.testing.assert_allclose(r2, r2_ref, rtol=0, atol=2e-4 * scale)
        np.testing.assert_allclose(en, en_ref, rtol=0, atol=2e-4 * scale)
