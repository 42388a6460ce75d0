"""Sharding rules (single-device) + multi-device scenarios via subprocess."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.distributed.sharding import ParallelismRules, leaf_pspec, shard_act


class FakeMesh:
    """Minimal mesh stand-in for rule unit tests (axis sizes only)."""

    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def _spec(path_names, shape, rules, mesh):
    import jax.tree_util as jtu

    path = tuple(jtu.DictKey(n) for n in path_names)
    # leaf_pspec only reads .ndim/.shape — a ShapeDtypeStruct avoids
    # materializing multi-GB zero buffers for the large-tensor rule cases
    return leaf_pspec(path, jax.ShapeDtypeStruct(shape, jnp.float32), rules, mesh)


MESH = FakeMesh({"data": 16, "model": 16})
RULES = ParallelismRules(dp_axes=("data",))


def test_tp_rules_column_row_parallel():
    assert _spec(("mixer", "w_q"), (2048, 2048), RULES, MESH) == jax.sharding.PartitionSpec(None, "model")
    assert _spec(("mixer", "w_o"), (2048, 2048), RULES, MESH) == jax.sharding.PartitionSpec("model", None)
    assert _spec(("ffn", "w_down"), (8192, 2048), RULES, MESH) == jax.sharding.PartitionSpec("model", None)


def test_divisibility_fallback():
    # vocab 50280 is not divisible by 16 → replicated
    assert _spec(("embed", "tok"), (50280, 2048), RULES, MESH)[0] is None
    assert _spec(("embed", "tok"), (163840, 2048), RULES, MESH)[0] == "model"


def test_moe_expert_sharding():
    spec = _spec(("ffn", "w_up"), (384, 7168, 2048), RULES, MESH)
    assert spec[0] == "model"  # expert-parallel dim


def test_fsdp_adds_data_axis():
    rules = ParallelismRules(dp_axes=("data",), fsdp=True)
    spec = _spec(("mixer", "w_q"), (8192, 8192), rules, MESH)
    assert spec == jax.sharding.PartitionSpec(("data",), "model")


def test_stacked_leading_dims_unsharded():
    spec = _spec(("segments", "w_q"), (16, 2048, 2048), RULES, MESH)
    assert spec[0] is None and spec[2] == "model"


def test_norms_and_scalars():
    assert _spec(("norm1", "scale"), (2048,), RULES, MESH) == jax.sharding.PartitionSpec(None)
    assert _spec(("mixer", "gate"), (), RULES, MESH) == jax.sharding.PartitionSpec()


def test_shard_act_noop_outside_context():
    x = jnp.ones((4, 8, 16))
    assert shard_act(x, "btd") is x


def _run_scenario(name):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    script = os.path.join(os.path.dirname(__file__), "multidev_scenario.py")
    proc = subprocess.run(
        [sys.executable, script, name], capture_output=True, text=True, env=env, timeout=900
    )
    assert proc.returncode == 0, f"\nSTDOUT:{proc.stdout[-2000:]}\nSTDERR:{proc.stderr[-3000:]}"
    assert f"OK scenario" in proc.stdout


@pytest.mark.slow
def test_multidev_sharded_equals_single():
    _run_scenario("sharded")


@pytest.mark.slow
def test_multidev_compressed_converges():
    _run_scenario("compressed")


@pytest.mark.slow
def test_multidev_compressed_wire_bytes():
    _run_scenario("wire")
