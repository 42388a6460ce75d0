"""Observability subsystem (repro.obs): telemetry parity, the a-posteriori
error estimator, and the host-side metrics/spans registry.

The load-bearing guarantees, in test order:

* telemetry **off** compiles the byte-identical scan program (tel=None has
  no pytree leaves — jit keys, donation layout and HLO are untouched);
* telemetry **on** leaves every factor bit-identical (the hook runs after
  the C/R/M updates and only writes the diagnostics frame);
* the in-stream test sketch ``Ψ = A Ω_test`` is exact (single-host and
  simulated-sharded), and the estimator lands inside a 2× band of the true
  relative error on the three synthetic stream families;
* worker telemetry frames merge by summation to the single-stream frame;
* the engine's device scopes name every stage in the compiled program's
  ``op_name`` metadata and change nothing else, and its host spans reach a
  live profiler whether or not the registry is enabled.
"""

import contextlib
import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.svd import spsvd_engine_finalize, spsvd_engine_init
from repro.cur import cur_relative_error, streaming_cur_finalize, streaming_cur_init
from repro.data.synthetic import (
    drifting_spectrum_matrix,
    late_spike_matrix,
    powerlaw_matrix,
    spiked_decay_matrix,
)
from repro.obs import (
    EVENT_BUDGET_FULL,
    MetricsRegistry,
    default_registry,
    estimate_rel_error,
    set_registry,
    span,
    telemetry_summary,
)
from repro.spsd import (
    adaptive_spsd_finalize,
    adaptive_spsd_init,
    streaming_spsd_finalize,
    streaming_spsd_init,
)
from repro.stream import (
    adaptive_cur_finalize,
    adaptive_cur_init,
    simulate_sharded_stream,
    stream_panels,
)
from repro.stream import engine
from repro.stream.engine import SCOPES, scan_chunk

M, N, PANEL = 160, 128, 32
CI = jnp.asarray([3, 17, 40, 63, 77, 90, 101, 120], jnp.int32)
RI = jnp.asarray([5, 12, 30, 44, 61, 80, 99, 140], jnp.int32)


def _A():
    A, _pos = spiked_decay_matrix(jax.random.key(0), M, N)
    return A


def _fixed_state(telemetry: bool):
    return streaming_cur_init(
        jax.random.key(2), M, N, CI, RI, sketch="countsketch", panel=PANEL,
        telemetry=telemetry,
    )


def _adaptive_state(telemetry: bool):
    # eviction + adaptive rows on: the richest telemetry surface
    return adaptive_cur_init(
        jax.random.key(3), M, N, 8, None, r=8, sketch="countsketch",
        panel=PANEL, panel_cap=1, panel_cap_rows=1, swap_gain=2.0,
        telemetry=telemetry,
    )


# ---------------------------------------------------------------- HLO parity


def _chunk_hlo(state) -> str:
    # fresh (non-donating) jit wrapper so the census text is cache-independent
    chunk = jax.ShapeDtypeStruct((M, N), jnp.float32)
    fn = jax.jit(scan_chunk, static_argnames="panel")
    return fn.lower(state, chunk, panel=PANEL).compile().as_text()


def test_telemetry_off_is_hlo_identical():
    """tel=None contributes no leaves: the scan program of a telemetry=False
    state is byte-identical to one built before the telemetry field existed
    (same init, default kwarg)."""
    st_default = streaming_cur_init(
        jax.random.key(2), M, N, CI, RI, sketch="countsketch", panel=PANEL
    )
    assert _chunk_hlo(_fixed_state(False)) == _chunk_hlo(st_default)
    assert _chunk_hlo(_fixed_state(True)) != _chunk_hlo(st_default)


# ----------------------------------------------------- bit-identical factors


@pytest.mark.parametrize(
    "make,finalize",
    [
        (_fixed_state, streaming_cur_finalize),
        (_adaptive_state, adaptive_cur_finalize),
    ],
    ids=["fixed_cur", "adaptive_cur"],
)
def test_factors_bitwise_identical_on_off(make, finalize):
    A = _A()
    off = stream_panels(make(False), A, PANEL)
    on = stream_panels(make(True), A, PANEL)
    np.testing.assert_array_equal(np.asarray(off.C), np.asarray(on.C))
    np.testing.assert_array_equal(np.asarray(off.R), np.asarray(on.R))
    np.testing.assert_array_equal(np.asarray(off.M), np.asarray(on.M))
    r_off, r_on = finalize(off), finalize(on)
    np.testing.assert_array_equal(np.asarray(r_off.U), np.asarray(r_on.U))


def test_spsd_factors_bitwise_identical_on_off():
    n = 128
    G = powerlaw_matrix(jax.random.key(8), n, 32, 1.0)
    K = G @ G.T + 0.01 * jnp.eye(n)
    ki = jnp.asarray([3, 17, 40, 63, 77, 90, 101, 120], jnp.int32)

    def fixed(telemetry):
        return streaming_spsd_init(
            jax.random.key(9), n, ki, s=48, panel=PANEL, telemetry=telemetry
        )

    def adaptive(telemetry):
        return adaptive_spsd_init(
            jax.random.key(10), n, 8, s=48, panel=PANEL, panel_cap=2,
            swap_gain=2.0, telemetry=telemetry,
        )

    for make, finalize in ((fixed, streaming_spsd_finalize), (adaptive, adaptive_spsd_finalize)):
        off = stream_panels(make(False), K, PANEL)
        on = stream_panels(make(True), K, PANEL)
        np.testing.assert_array_equal(np.asarray(off.C), np.asarray(on.C))
        np.testing.assert_array_equal(np.asarray(off.M), np.asarray(on.M))
        np.testing.assert_array_equal(
            np.asarray(finalize(off).X), np.asarray(finalize(on).X)
        )


# ----------------------------------------------------------- telemetry frame


def test_psi_is_exact_and_counts_consistent():
    """Ψ accumulated panel-by-panel equals A·Ω_test in one shot, and the
    fixed-index frame's counters match the static selection table."""
    A = _A()
    st = stream_panels(_fixed_state(True), A, PANEL)
    tel = st.tel
    np.testing.assert_allclose(
        np.asarray(tel.psi), np.asarray(A @ tel.omega[:N]), rtol=1e-5, atol=1e-4
    )
    s = telemetry_summary(st)
    assert s["total_admitted"] == CI.shape[0]
    assert s["occupancy"][-1] == CI.shape[0]
    assert s["panels_seen"] == N // PANEL
    assert np.asarray(tel.events)[-1] & EVENT_BUDGET_FULL
    assert s["energy_mass"] > 0


def test_adaptive_counters_match_ctx():
    A = _A()
    st = stream_panels(_adaptive_state(True), A, PANEL)
    s = telemetry_summary(st)
    assert s["total_admitted"] == int(st.ctx.n_filled)
    assert s["total_evicted"] == int(st.ctx.n_evicted)
    assert s["total_rows_admitted"] == int(st.ctx.rows.n_filled)
    # panel-local deltas, never cumulative — each slot ≤ the panel admission cap
    assert s["admitted"].max() <= PANEL


def test_sharded_telemetry_merges_to_single_stream():
    """Worker frames merge by summation: Ψ stays exact and the fixed-index
    frame is bitwise identical at any worker count (global formulas +
    disjoint panel writes)."""
    A = _A()
    single = stream_panels(_fixed_state(True), A, PANEL)
    for w in (2, 4):
        shard = simulate_sharded_stream(_fixed_state(True), A, PANEL, w)
        np.testing.assert_allclose(
            np.asarray(shard.tel.psi), np.asarray(A @ shard.tel.omega[:N]),
            rtol=1e-5, atol=1e-4,
        )
        for leaf in ("admitted", "occupancy", "events", "panels_seen"):
            np.testing.assert_array_equal(
                np.asarray(getattr(shard.tel, leaf)),
                np.asarray(getattr(single.tel, leaf)),
            )
    # adaptive: per-worker slot ranges — merged totals must equal ctx counters
    for w in (2, 4):
        st = simulate_sharded_stream(_adaptive_state(True), A, PANEL, w)
        s = telemetry_summary(st)
        assert s["total_admitted"] == int(st.ctx.n_filled), w


def test_telemetry_requires_panel():
    with pytest.raises(ValueError, match="panel"):
        streaming_cur_init(jax.random.key(0), M, N, CI, RI, telemetry=True)
    with pytest.raises(ValueError, match="panel"):
        adaptive_cur_init(jax.random.key(0), M, N, 8, RI, telemetry=True)


# ------------------------------------------------------------ error estimate


@pytest.mark.parametrize("family", ["spiked", "late-spike", "drift"])
def test_estimator_within_2x_band(family):
    """est = ‖Ψ − ÂΩ‖/‖Ψ‖ lands within 2× of the true relative Frobenius
    error (both directions) on each synthetic stream family, single-pass."""
    m, n, panel = 200, 160, 32
    if family == "spiked":
        A, _ = spiked_decay_matrix(jax.random.key(21), m, n)
    elif family == "late-spike":
        A, _e, _l = late_spike_matrix(jax.random.key(22), m, n)
    else:
        A, _b = drifting_spectrum_matrix(jax.random.key(23), m, n)
    st = adaptive_cur_init(
        jax.random.key(24), m, n, 12, None, r=12, sketch="countsketch",
        panel=panel, panel_cap=2, panel_cap_rows=2, swap_gain=2.0,
        telemetry=True,
    )
    st = stream_panels(st, A, panel)
    est = float(estimate_rel_error(st))
    true = float(cur_relative_error(A, adaptive_cur_finalize(st)))
    assert 0.5 * true <= est <= 2.0 * true, (family, est, true)


def test_estimator_spsd_band():
    n, panel = 192, 32
    G = powerlaw_matrix(jax.random.key(30), n, 24, 1.0)
    K = G @ G.T + 0.01 * jnp.eye(n)
    ki = jnp.asarray(np.arange(0, n, n // 12)[:12], jnp.int32)
    st = stream_panels(
        streaming_spsd_init(jax.random.key(31), n, ki, s=64, panel=panel, telemetry=True),
        K, panel,
    )
    res = streaming_spsd_finalize(st)
    recon = np.asarray(res.C) @ np.asarray(res.X) @ np.asarray(res.C).T
    true = float(np.linalg.norm(np.asarray(K) - recon) / np.linalg.norm(np.asarray(K)))
    est = float(estimate_rel_error(st))
    assert 0.5 * true <= est <= 2.0 * true, (est, true)


def test_estimator_mid_stream_cur():
    """CUR mid-stream semantics: the estimate covers the columns seen so far
    (R and Ψ are both zero on unseen columns)."""
    A = _A()
    stop = (N // PANEL) // 2 * PANEL
    st = stream_panels(_fixed_state(True), A, PANEL, stop=stop)
    est = float(estimate_rel_error(st))
    res = streaming_cur_finalize(st)
    ahat = np.asarray(res.C) @ np.asarray(res.U) @ np.asarray(res.R)
    seen = np.asarray(A)[:, :stop]
    true = float(np.linalg.norm(seen - ahat[:, :stop]) / np.linalg.norm(seen))
    assert 0.5 * true <= est <= 2.0 * true, (est, true)


def test_estimator_requires_telemetry():
    A = _A()
    st = stream_panels(_fixed_state(False), A, PANEL)
    with pytest.raises(ValueError, match="telemetry"):
        estimate_rel_error(st)


# ------------------------------------------------------------ device scopes

# debug information of a compiled program: op-name metadata and the
# source-location tables it points into
_METADATA = re.compile(r',? metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
_FRAMES = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*", re.M)


def _scopes_in(hlo: str) -> set:
    """The scope names (``stream.*``, ``finalize.*``) in a program's op names."""
    paths = " ".join(re.findall(r'op_name="([^"]*)"', hlo))
    return set(re.findall(r"(?:stream|finalize)\.[a-z_]+", paths))


def _stripped(hlo: str) -> str:
    return _FRAMES.sub("", _METADATA.sub("", hlo))


def _gauss_state():
    return adaptive_cur_init(
        jax.random.key(51), M, N, 8, jnp.arange(8, dtype=jnp.int32),
        s_c=64, s_r=64, sketch="gaussian", panel=PANEL, panel_cap=2,
    )


def _fixed_rows_state():
    return adaptive_cur_init(
        jax.random.key(4), M, N, 8, RI, sketch="countsketch", panel=PANEL, panel_cap=2
    )


SVD_SIZES = dict(c=8, r=8, c0=24, r0=24, s_c=32, s_r=32)


def _svd_state(**sizes):
    return spsvd_engine_init(jax.random.key(52), M, N, sizes={**SVD_SIZES, **sizes}, panel=PANEL)


@contextlib.contextmanager
def _kernel_route(on: bool):
    from repro.kernels import ops as kops

    kops._FORCE_KERNEL_ROUTE = on
    try:
        yield
    finally:
        kops._FORCE_KERNEL_ROUTE = False


# route -> (state, kernel route forced, scopes its stream program holds)
ROUTES = {
    "fused": (_fixed_rows_state, False,
              {"stream.sketch", "stream.chunk_fold", "stream.mfold", "stream.admit"}),
    # an armed quarantine guard declines the fused body: the per-panel body
    "per_panel": (lambda: engine.with_quarantine(_fixed_state(False)), False,
                  {"stream.sketch", "stream.mfold", "stream.admit", "stream.rows"}),
    "kernel": (_gauss_state, True, {"stream.sketch", "stream.panel_kernel", "stream.rows"}),
    # SP-SVD declares no fused hooks: the per-panel body, OSNAP on segment_sum
    # and (forced) on the countsketch kernel; other shapes than the first, as
    # the stream's jit caches its trace by shape and not by route
    "sp_svd": (_svd_state, False,
               {"stream.sketch", "stream.mfold", "stream.colsketch", "stream.rows"}),
    "sp_svd_kernel": (lambda: _svd_state(c0=40, r0=40), True,
                      {"stream.sketch", "stream.mfold", "stream.colsketch", "stream.rows"}),
}


def _stream_hlo(route: str, scan=None) -> str:
    make, kernel, _ = ROUTES[route]
    A = jax.ShapeDtypeStruct((M, N), jnp.float32)
    with _kernel_route(kernel):
        fn = scan or engine._scan_stream_panels
        return fn.lower(make(), A, num_panels=N // PANEL, panel=PANEL).compile().as_text()


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_stream_scopes_in_compiled_hlo(route):
    """Each stage of the route's scan body carries its scope in the compiled
    program's op names: the fused CountSketch scan, the per-panel body,
    Route B's kernel (forced on the CPU), and SP-SVD's per-panel body, whose
    sketched column update is ``stream.colsketch``."""
    assert _scopes_in(_stream_hlo(route)) == ROUTES[route][2]


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_scopes_change_only_metadata(route, monkeypatch):
    """The optimized stream program with its metadata stripped is the text
    it compiles to when every scope is a no-op."""
    scoped = _stream_hlo(route)

    def scan_panels(state, A, num_panels, panel):  # fresh: no trace cache
        return engine.scan_panels(state, A, num_panels, panel)

    bare_jit = jax.jit(scan_panels, static_argnames=("num_panels", "panel"),
                       donate_argnums=(0,))
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _stream_hlo(route, bare_jit)
    assert not _scopes_in(bare) and _scopes_in(scoped)
    assert _stripped(scoped) == _stripped(bare)


@pytest.mark.parametrize(
    "make,finalize",
    [
        (lambda: _fixed_state(False), streaming_cur_finalize),
        (_fixed_rows_state, adaptive_cur_finalize),
        (lambda: streaming_spsd_init(jax.random.key(9), N, CI, s=48, panel=PANEL),
         streaming_spsd_finalize),
        (lambda: adaptive_spsd_init(jax.random.key(10), N, 8, s=48, panel=PANEL),
         adaptive_spsd_finalize),
        (_svd_state, spsvd_engine_finalize),
    ],
    ids=["streaming_cur", "adaptive_cur", "streaming_spsd", "adaptive_spsd", "sp_svd"],
)
def test_finalize_solve_scope(make, finalize):
    """Each finalizer's core solve runs in ``finalize.solve``."""
    hlo = finalize.__wrapped__.lower(make()).compile().as_text()
    assert _scopes_in(hlo) == {"finalize.solve"}


_MESH_SCRIPT = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.stream.adaptive import adaptive_cur_init
from repro.stream.distributed import _mesh_stream
m, n, panel = 64, 256, 32
st = adaptive_cur_init(jax.random.key(0), m, n, 8, jnp.arange(8, dtype=jnp.int32),
                       sketch="countsketch", s_c=32, s_r=32, panel=panel, panel_cap=2)
st = st.__class__(**{**st.__dict__, "ctx": st.ops.prep_shard(st.ctx, 4)})
mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
A = jax.ShapeDtypeStruct((m, n), jnp.float32)
print(_mesh_stream.lower(st, A, panel=panel, mesh=mesh, axis="data").compile().as_text())
"""


def test_mesh_stream_scopes_in_compiled_hlo():
    """The mesh program (4 virtual devices) holds the scan's scopes and puts
    its collectives in ``stream.psum``."""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    hlo = proc.stdout
    assert _scopes_in(hlo) == {"stream.sketch", "stream.chunk_fold", "stream.mfold",
                               "stream.admit", "stream.psum"}
    psum_ops = [line for line in hlo.splitlines() if "all-reduce(" in line]
    assert psum_ops and all("stream.psum" in line for line in psum_ops)


def test_scope_vocabulary_is_covered():
    """Every scope of the vocabulary is one that a test above finds in a
    compiled program, and each is ``<layer>.<stage>``."""
    tested = set().union(*(route[2] for route in ROUTES.values()))
    assert set(SCOPES) == tested | {"stream.psum", "finalize.solve"}
    assert all(re.fullmatch(r"(?:stream|finalize)\.[a-z_]+", s) for s in SCOPES)


def test_spans_reach_profiler_with_registry_disabled(tmp_path):
    """With the registry disabled and a profiler session live, the engine's
    host spans are in the trace and no span is recorded."""
    from jax.profiler import ProfileData

    assert default_registry().enabled is False
    A = _A()
    jax.profiler.start_trace(str(tmp_path))
    try:
        st = stream_panels(_fixed_state(False), A, PANEL)
        jax.block_until_ready(streaming_cur_finalize(st))
    finally:
        jax.profiler.stop_trace()
    (pb,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(pb).planes
             for line in plane.lines for e in line.events}
    assert {"stream/streaming_cur/init", "stream/streaming_cur/scan",
            "stream/streaming_cur/finalize"} <= names
    assert not default_registry().spans


# ------------------------------------------------------------- host registry


def test_registry_instruments_and_jsonl(tmp_path):
    reg = MetricsRegistry()
    reg.inc("a/count")
    reg.inc("a/count", 4)
    reg.set_gauge("a/gauge", 2.5)
    for v in (1.0, 2.0, 3.0, 10.0):
        reg.observe("a/hist", v)
    summ = reg.histogram_summary("a/hist")
    assert summ["count"] == 4 and summ["min"] == 1.0 and summ["max"] == 10.0
    with span("outer", reg):
        with span("inner", reg):
            pass
    assert [s.name for s in reg.spans] == ["inner", "outer"]  # closed order
    assert reg.spans[0].parent == "outer" and reg.spans[1].parent is None
    path = tmp_path / "metrics.jsonl"
    reg.dump_jsonl(path)
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    assert {"counter", "gauge", "histogram", "span"} <= {r["type"] for r in recs}
    assert next(r for r in recs if r["name"] == "a/count")["value"] == 5
    spans = {r["name"]: r for r in recs if r["type"] == "span"}
    assert spans["inner"]["parent"] == "outer" and spans["outer"]["parent"] is None


def test_disabled_registry_is_inert():
    reg = MetricsRegistry(enabled=False)
    reg.inc("x")
    reg.set_gauge("x", 1.0)
    reg.observe("x", 1.0)
    with span("x", reg):
        pass
    assert not reg.counters and not reg.gauges and not reg.histograms and not reg.spans


def test_default_registry_swap_and_engine_spans():
    """Library spans are inert by default; an enabled process registry
    captures the engine's scan span without any plumbing."""
    assert default_registry().enabled is False
    prev = set_registry(MetricsRegistry())
    try:
        streaming_cur_finalize(stream_panels(_fixed_state(False), _A(), PANEL))
        names = [s.name for s in default_registry().spans]
        assert names == ["stream/streaming_cur/init", "stream/streaming_cur/scan",
                         "stream/streaming_cur/finalize"]
    finally:
        set_registry(prev)


def test_sp_svd_spans_where_the_host_calls():
    """SP-SVD's init and finalize carry ``stream/sp_svd/*`` spans where the
    host calls them, beside the engine's scan span; OSNAP's route counter is
    recorded once per trace."""
    prev = set_registry(MetricsRegistry())
    try:
        jax.clear_caches()
        st = stream_panels(_svd_state(), _A(), PANEL)
        jax.block_until_ready(spsvd_engine_finalize(st))
        reg = default_registry()
        assert [s.name for s in reg.spans] == ["stream/sp_svd/init", "stream/sp_svd/scan",
                                               "stream/sp_svd/finalize"]
        assert reg.counters.get("sketch.osnap.route.segment_sum", 0) > 0
    finally:
        set_registry(prev)
        jax.clear_caches()


def test_record_stream_telemetry():
    reg = MetricsRegistry()
    st = stream_panels(_adaptive_state(True), _A(), PANEL)
    reg.record_stream_telemetry(st)
    assert reg.counters["stream/admitted"] == int(st.ctx.n_filled)
    assert reg.counters["stream/panels"] == N // PANEL
    assert reg.histograms["stream/panel_energy"]
    assert "stream/energy_mass" in reg.gauges


# --------------------------------------------------- serve / train surfaces


def test_kv_compress_metrics():
    from repro.serve.kv_compress import KVCompressionConfig, compress_head_batch

    reg = MetricsRegistry()
    hist = jax.random.normal(jax.random.key(40), (1, 2, 64, 16))
    kc = KVCompressionConfig(rank=4, oversample=2, panel=32)
    compress_head_batch(jax.random.key(41), hist, kc, registry=reg)
    assert reg.counters["serve/kv_heads_compressed"] == 2
    assert len(reg.histograms["serve/kv_rel_err"]) == 2
    assert reg.gauges["serve/kv_compression_ratio"] > 1.0
    assert "serve/kv_compress/head_batch" in [s.name for s in reg.spans]


def test_grad_compress_stats():
    from repro.train.grad_compress import CompressionConfig, compressed_mean_grads

    ccfg = CompressionConfig(rank=8, sketch_factor=2, min_dim=64)
    g = {
        "w": jax.random.normal(jax.random.key(50), (128, 128)),
        "b": jnp.ones((16,)),
    }
    e = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), g)
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    stat_keys = (
        "comp/wire_floats", "comp/dense_floats", "comp/ratio",
        "comp/ef_norm", "comp/rel_err",
    )

    def f(g, e, key):
        _gbar, _ne, stats = compressed_mean_grads(
            g, e, key, ccfg, ("dp",), with_stats=True
        )
        return stats

    spec = jax.tree.map(lambda _: P(), g)
    fn = jax.shard_map(
        f, mesh=mesh, in_specs=(spec, spec, P()),
        out_specs={k: P() for k in stat_keys}, axis_names={"dp"}, check_vma=True,
    )
    stats = jax.jit(fn)(g, e, jax.random.key(51))
    wire, dense = float(stats["comp/wire_floats"]), float(stats["comp/dense_floats"])
    assert dense == 128 * 128 + 16
    assert 0 < wire < dense and float(stats["comp/ratio"]) > 1.0
    # a full-rank Gaussian "gradient" is the compressor's worst case — the
    # stat just has to be a finite, positive health signal
    assert 0.0 < float(stats["comp/rel_err"]) < 10.0
    assert np.isfinite(float(stats["comp/ef_norm"]))


# ---------------------------------------------------------- multi-device lane


@pytest.mark.slow
def test_multidev_telemetry_merge():
    """Real shard_map telemetry merge at 2/4 devices (subprocess with forced
    host devices — see tests/multidev_scenario.py)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    script = os.path.join(os.path.dirname(__file__), "multidev_scenario.py")
    proc = subprocess.run(
        [sys.executable, script, "telemetry"],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert proc.returncode == 0, f"\nSTDOUT:{proc.stdout[-2000:]}\nSTDERR:{proc.stderr[-3000:]}"
    assert "OK scenario_telemetry_mesh_merge" in proc.stdout
