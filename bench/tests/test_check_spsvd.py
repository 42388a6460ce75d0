"""The check of the single-pass SVD cell (``spsvd_32k.osnap``) at a small
size on the CPU, with the OSNAP sketches on the ``countsketch`` kernel in
interpret mode as on a TPU.

A sound run passes the cell's limits; both controls fail them (the
program's own bfloat16 path and the plain reference computed in bfloat16);
and a run with the timed path broken underneath comes out not correct.
"""

import copy
import time

import jax
import pytest

import calibrate
import harness
from conftest import FAKE_PEAK, cpu_devices

CELL = "spsvd_32k.osnap"
# sp_svd_sizes(k = 8, eps = 0.5) on a rank-8 matrix of 512 x 512
SMALL = {"panel": 64, "c": 48, "r": 48, "c0": 96, "r0": 96, "s_c": 68, "s_r": 68}


@pytest.fixture(autouse=True)
def kernel_route(monkeypatch):
    """OSNAP takes the ``countsketch`` kernel (interpret mode) as on a TPU;
    every test traces anew, so a fault patched in reaches the programs."""
    import repro.kernels.ops as ops

    monkeypatch.setattr(ops, "kernel_route_enabled", lambda: True)
    jax.clear_caches()
    yield
    jax.clear_caches()


def small_spec() -> dict:
    spec = copy.deepcopy(harness.load_cell(CELL))
    spec["config"]["data"].update(m=512, n=512, rank=8)
    spec["config"].update(SMALL)
    return spec


def run_cell(seed=5):
    return harness.run(small_spec(), seed, 0.3, False, cpu_devices(1), FAKE_PEAK,
                       time.perf_counter())


def failing(result):
    return sorted(k for k, c in result["checks"].items() if c["value"] > c["limit"])


def test_sound_run_is_correct():
    from repro.obs.metrics import MetricsRegistry, set_registry

    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        result = run_cell(seed=2**33 + 17)
    finally:
        set_registry(prev)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert reg.counters.get("sketch.osnap.route.kernel", 0) > 0
    assert "sketch.osnap.route.segment_sum" not in reg.counters


def test_control_fails_the_limits():
    spec = small_spec()
    out = calibrate.readings(spec, [1, 2, 3], [1, 2], cpu_devices(1), emit=lambda s: None)
    limits = spec["limits"]
    assert all(out["program"][k] <= v for k, v in limits.items()), out["program"]
    for control in ("program_bf16", "reference_bf16"):
        assert any(out[control][k] > v for k, v in limits.items()), out[control]


def test_fault_state_unchanged(monkeypatch):
    import repro.stream as stream

    monkeypatch.setattr(stream, "stream_panels", lambda state, *a, **k: state)
    assert not run_cell()["correct"]


def test_fault_half_the_panels(monkeypatch):
    import repro.stream as stream

    orig = stream.stream_panels
    monkeypatch.setattr(stream, "stream_panels", lambda state, A, panel, **k: orig(
        state, A, panel, stop=A.shape[1] // 2, **k))
    result = run_cell()
    assert not result["correct"] and failing(result)


def test_fault_answer_altered(monkeypatch):
    import repro.core.svd as svd

    orig = svd.spsvd_engine_finalize

    def altered(state, k=None):
        U, S, V = orig(state, k=k)
        return U, S * 1.1, V

    monkeypatch.setattr(svd, "spsvd_engine_finalize", altered)
    result = run_cell()
    assert not result["correct"] and "resid_excess" in failing(result)
