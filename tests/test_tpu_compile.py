"""Ahead-of-time compiles of the four Pallas kernels for a described v5e chip.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: misaligned block layouts, in-kernel transposes Mosaic
cannot lower, blocks that overflow VMEM. These tests hand the real TPU
compiler shapes at the stream widths and assert each program holds a Mosaic
kernel (``tpu_custom_call``) — nothing runs, so no chip is needed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# (m, L, s_c, c): panel height, panel width, column-sketch size, column
# budget. The last row is the chip smoke's stream (32768² at panel 512,
# c = r = 256 with the Table-2 sketch sizes s_c = s_r = 3840).
WIDTHS = [(2048, 256, 160, 16), (32768, 256, 160, 16), (32768, 512, 3840, 256)]
KERNELS = ["twoside_sketch", "countsketch_apply", "panel_score", "panel_update"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_call(name, m, L, s_c, c, shape):
    """(function, argument shapes) compiling one ``ops`` wrapper as Mosaic."""
    f32, i32 = jnp.float32, jnp.int32
    if name == "twoside_sketch":
        return partial(ops.twoside_sketch, interpret=False), [
            shape((s_c, m), f32), shape((m, L), f32), shape((L, s_c), f32)]
    if name == "countsketch_apply":
        return partial(ops.countsketch_apply, s=s_c, interpret=False), [
            shape((m,), i32), shape((m,), f32), shape((m, L), f32)]
    if name == "panel_score":
        return partial(ops.panel_score, interpret=False), [
            shape((s_c, m), f32), shape((m, L), f32), shape((s_c, c), f32)]

    def update(sc, a_l, srt, q, C, M, sf, si):
        return ops.panel_update(
            sc, a_l, srt, q, C, M, min_gain=sf[0], run_mean=sf[1], true_cols=sf[2],
            n_filled=si[0], free=si[1], panel_cap=max(1, c // 8), interpret=False,
        )

    return update, [
        shape((s_c, m), f32), shape((m, L), f32), shape((L, s_c), f32),
        shape((s_c, c), f32), shape((m, c), f32), shape((s_c, s_c), f32),
        shape((3,), f32), shape((2,), i32)]


@pytest.mark.parametrize("width", WIDTHS, ids=lambda w: "m{}_L{}_sc{}_c{}".format(*w))
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name, width):
    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    fn, args = _kernel_call(name, *width, shape)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (m, n, s, dtype): the CountSketch kernel at the cells' shapes — the chunk
# sketch of a 32768² operand at s_c = 3840 (CUR) and s = 2560 (SPSD), and
# the per-panel M fold, 512 rows of sc_aᵀ into (s_r, s_c) = (3840, 3840);
# and a bfloat16 fold, whose rows the kernel loads as whole packed tiles
CS_CELL_SHAPES = [(32768, 32768, 3840, "float32"), (32768, 32768, 2560, "float32"),
                  (512, 3840, 3840, "float32"), (512, 3840, 3840, "bfloat16")]


@pytest.mark.parametrize("shape", CS_CELL_SHAPES, ids=lambda w: "m{}_n{}_s{}_{}".format(*w))
def test_countsketch_compiles_for_v5e_at_cell_shapes(one_chip, shape):
    m, n, s, dtype = shape

    def arg(sh, dt):
        return jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)

    fn = partial(ops.countsketch_apply, s=s, interpret=False)
    compiled = jax.jit(fn).lower(
        arg((m,), jnp.int32), arg((m,), jnp.float32), arg((m, n), jnp.dtype(dtype))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the operand is read in place: no copy, pad or slice of it
    short = {"float32": "f32", "bfloat16": "bf16"}[dtype]
    made = [line for line in text.splitlines()
            if f"= {short}[{m},{n}]{{" in line and "parameter(" not in line]
    assert not made, made


@pytest.mark.parametrize("where", ["mesh", "shard_map"])
def test_countsketch_route_compiles_on_four_chips(topo, where, monkeypatch):
    """``CountSketch.apply`` as on a TPU, with its operand laid out over a
    2x2 mesh: a jit over the mesh (the four-chip finalizer's program) takes
    ``segment_sum``, which XLA partitions, because XLA cannot partition a
    Mosaic kernel; inside ``shard_map`` each chip runs the kernel."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.sketching import CountSketch

    monkeypatch.setattr(ops, "kernel_route_enabled", lambda: True)
    monkeypatch.setattr(ops, "interpret_default", lambda: False)
    jax.clear_caches()
    mesh = Mesh(topo.devices, ("data",))
    m, n, s = 32768, 1024, 3840
    whole = NamedSharding(mesh, P())
    cols = NamedSharding(mesh, P(None, "data"))
    sketch = CountSketch(hashes=jax.ShapeDtypeStruct((m,), jnp.int32, sharding=whole),
                         signs=jax.ShapeDtypeStruct((m,), jnp.float32, sharding=whole), s=s)
    if where == "mesh":
        fn, a = (lambda S, a: S.apply(a)), jax.ShapeDtypeStruct((m, n), jnp.float32, sharding=whole)
    else:
        def fn(S, a):
            return jax.shard_map(S.apply, mesh=mesh, in_specs=P(None, "data"),
                                 out_specs=P(None, "data"))(a)

        a = jax.ShapeDtypeStruct((m, n), jnp.float32, sharding=cols)
    text = jax.jit(fn).lower(sketch, a).compile().as_text()
    jax.clear_caches()
    assert ("tpu_custom_call" in text) == (where == "shard_map")


# (operand rows, operand cols, s, apply or apply_t): OSNAP (p = 2) at the
# single-pass SVD cell's shapes — S_C (s = 544) and Psi (s = 1292) over the
# rows of a (32768, 512) panel, and an s = 1292 sketch over its columns,
# which apply_t sketches as the rows of the panel's (512, 32768) transpose
OSNAP_CELL_SHAPES = [(32768, 512, 544, "apply"), (32768, 512, 1292, "apply"),
                     (32768, 512, 1292, "apply_t")]


@pytest.mark.parametrize("shape", OSNAP_CELL_SHAPES, ids=lambda w: "m{}_n{}_s{}_{}".format(*w))
def test_osnap_route_compiles_for_v5e_at_cell_shapes(one_chip, shape, monkeypatch):
    """``OSNAPSketch.apply`` as on a TPU: one kernel call per hash row, and
    no operand-sized array made besides the panel's transpose that
    ``apply_t`` sketches."""
    from repro.core.sketching import OSNAPSketch

    monkeypatch.setattr(ops, "kernel_route_enabled", lambda: True)
    monkeypatch.setattr(ops, "interpret_default", lambda: False)
    jax.clear_caches()
    rows, cols, s, how = shape
    src = rows if how == "apply" else cols

    def arg(sh, dt):
        return jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)

    sketch = OSNAPSketch(hashes=arg((2, src), jnp.int32), signs=arg((2, src), jnp.float32),
                         s=s, p=2)
    text = jax.jit(lambda S, a: getattr(S, how)(a)).lower(
        sketch, arg((rows, cols), jnp.float32)).compile().as_text()
    jax.clear_caches()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # a bitcast is a view; the transpose is one relayout copy of the panel
    made = [line for line in text.splitlines()
            if ("= f32[32768,512]{" in line or "= f32[512,32768]{" in line)
            and "parameter(" not in line and " bitcast(" not in line]
    if how == "apply":
        assert not made, made
    else:
        assert len(made) == 1 and (" copy(" in made[0] or " transpose(" in made[0]), made


def test_spsvd_c_update_compiles_for_v5e_at_cell_shapes(one_chip, monkeypatch):
    """SP-SVD's C update at the single-pass SVD cell's shapes (a 512-column
    panel, c0 = 1292, c = 384), as on a TPU: ``A_L (G_C Omega_L)^T``, one
    matmul at HIGH — no kernel call, no transpose of the panel and no
    (m, c0) array made."""
    from repro.core.sketching import GaussianSketch, OSNAPSketch
    from repro.core.svd import SPSVDSketches, _svd_update_c

    monkeypatch.setattr(ops, "kernel_route_enabled", lambda: True)
    monkeypatch.setattr(ops, "interpret_default", lambda: False)
    jax.clear_caches()
    m, n, L, c, c0 = 32768, 32768, 512, 384, 1292

    def arg(sh, dt):
        return jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)

    omega = OSNAPSketch(hashes=arg((2, n), jnp.int32), signs=arg((2, n), jnp.float32),
                        s=c0, p=2)
    g_c = GaussianSketch(arg((c, c0), jnp.float32))
    sk = SPSVDSketches(psi=None, g_r=None, omega=omega, g_c=g_c, s_c=None, s_r=None)
    text = jax.jit(lambda sk, C, a, off: _svd_update_c(sk, C, a, None, off)[1]).lower(
        sk, arg((m, c), jnp.float32), arg((m, L), jnp.float32), arg((), jnp.int32)
    ).compile().as_text()
    jax.clear_caches()
    assert "tpu_custom_call" not in text
    made = [line for line in text.splitlines()
            if any(f"= f32[{sh}]{{" in line for sh in ("512,32768", "32768,1292", "1292,32768"))]
    assert not made, made
