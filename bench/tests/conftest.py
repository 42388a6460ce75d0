"""Benchmark tests run on the CPU at small sizes, apart from the repository's
tests: ``python -m pytest bench/tests``. Four virtual CPU devices stand in
for a four-chip mesh."""

import copy
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4").strip()

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

# Sizes a test run holds; every other key of each configuration, and the
# algorithm, sketch family and entry points, are the cell's own.
SMALL = {
    "cur_32k": {"data": {"m": 1024, "n": 1024, "rank": 8}, "panel": 64, "c": 16, "r": 16,
                "s_c": 960, "s_r": 960, "panel_cap": 2},
    "cur_128k": {"data": {"m": 1024, "n": 1024, "rank": 8}, "panel": 64, "c": 16, "r": 16,
                 "s_c": 960, "s_r": 960, "panel_cap": 2},
    "spsd_rbf_32k": {"data": {"n": 512, "d": 8}, "panel": 64, "c": 16, "s": 160},
}
FAKE_PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9,
             "ici_bytes_per_s": 1e10}


def small_spec(cell: str) -> dict:
    """The cell as ``harness.load_cell`` gives it, cut to :data:`SMALL`."""
    import harness

    spec = copy.deepcopy(harness.load_cell(cell))
    cut = SMALL[spec["cell"]["config"]]
    for k, v in cut.items():
        if k == "data":
            spec["config"]["data"].update(v)
        else:
            spec["config"][k] = v
    return spec


def cpu_devices(chips: int):
    import jax

    return jax.devices()[:chips]
