"""jit'd public wrappers: shape padding, dtype policy, interpret fallback.

On non-TPU backends ``interpret=True`` executes the kernel bodies in
Python for correctness; on TPU the same code lowers to Mosaic. The
matmul wrappers pad every dim to its block multiple with zeros
(mathematically a no-op for every kernel: zero rows/cols contribute
nothing) and slice the result back; ``countsketch_apply`` clips its edge
blocks instead, so its operand is never copied.

The matmul kernels share one block scheme (:data:`LANE`/:data:`SUBLANE`
tile floor, :func:`pad_dims` zero-padding, :func:`interpret_default`
backend dispatch), so a re-tiling decision is made once here rather than
per kernel.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from .countsketch import countsketch_kernel
from .panel_score import panel_score_kernel
from .panel_update import panel_update_kernel
from .ref import countsketch_ref, panel_score_ref, panel_update_ref, twoside_sketch_ref
from .twoside_sketch import twoside_sketch_kernel

# The fp32 TPU register tile is (8, 128): every kernel operand's trailing
# two dims are padded to multiples of these (block sizes are themselves
# multiples, so padding to the block is padding to the tile).
SUBLANE = 8
LANE = 128

# Test hook (see kernel_route_enabled): force the Mosaic-route *dispatch
# decision* on a non-TPU backend so the engine's panel_kernel path can be
# exercised end-to-end in interpret mode. Never set in production code.
_FORCE_KERNEL_ROUTE = False


def interpret_default() -> bool:
    """Interpret unless the backend is actually TPU.

    Mosaic lowering exists only for TPU — ``interpret = not on_cpu`` would
    send a GPU (or any other) backend down a lowering path that fails, so
    the dispatch question is "is this a TPU?", not "is this a CPU?".
    """
    return jax.default_backend() != "tpu"


def kernel_route_enabled() -> bool:
    """Should engine hooks route panels through the Pallas kernels?

    True on TPU (Mosaic execution) and when tests force the route
    (interpret-mode execution of the same kernel bodies). Distinct from
    :func:`interpret_default`: this gates whether a *caller* picks the
    kernel at all, that gates how a picked kernel executes.
    """
    return _FORCE_KERNEL_ROUTE or jax.default_backend() == "tpu"


def _on_cpu() -> bool:  # retained for external callers of the old helper
    return jax.default_backend() == "cpu"


def _pad_to(x: jax.Array, mults) -> jax.Array:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if any(p for _, p in pads):
        return jnp.pad(x, pads)
    return x


def pad_dims(*pairs):
    """Shared padding step: ``pad_dims((x, mults), ...)`` zero-pads every
    array's dims to their block multiples (no-op when already aligned)."""
    return tuple(_pad_to(x, mults) for x, mults in pairs)


@partial(jax.jit, static_argnames=("block_sc", "block_sr", "block_m", "block_n", "interpret"))
def twoside_sketch(
    sc: jax.Array,
    a: jax.Array,
    srt: jax.Array,
    *,
    block_sc: int = 128,
    block_sr: int = 128,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """M = S_C · A · S_Rᵀ (fused, fp32 out). Shapes: (s_c,m)·(m,n)·(n,s_r)."""
    interpret = interpret_default() if interpret is None else interpret
    s_c, m = sc.shape
    n, s_r = srt.shape
    scp, ap, srtp = pad_dims(
        (sc, (block_sc, block_m)), (a, (block_m, block_n)), (srt, (block_n, block_sr))
    )
    out = twoside_sketch_kernel(
        scp, ap, srtp,
        block_sc=block_sc, block_sr=block_sr, block_m=block_m, block_n=block_n,
        interpret=interpret,
    )
    return out[:s_c, :s_r]


def kernel_partitionable(x: jax.Array) -> bool:
    """May a Pallas kernel take ``x`` where it is traced?

    XLA cannot partition a Mosaic kernel: a kernel call fails to lower in a
    program laid out over several devices by XLA (a jit over a mesh), and
    lowers on one device or inside ``shard_map``, where every mesh axis is
    manual. ``x``'s type carries the mesh it is laid out over.
    """
    mesh = jax.typeof(x).sharding.mesh
    return mesh.size <= 1 or all(t == AxisType.Manual for t in mesh.axis_types)


@partial(jax.jit, static_argnames=("s", "block_m", "block_n", "interpret"))
def countsketch_apply(
    hashes: jax.Array,
    signs: jax.Array,
    a: jax.Array,
    s: int,
    *,
    block_m: int = 1024,
    block_n: int = 2048,
    interpret: bool | None = None,
) -> jax.Array:
    """S·A for a CountSketch given (hash, sign) vectors. Returns (s, n) fp32.

    ``a`` is read in place: no dim is padded (the kernel clips its edge
    blocks), so the stream's 4 GiB chunk is never copied.
    """
    interpret = interpret_default() if interpret is None else interpret
    return countsketch_kernel(
        hashes.astype(jnp.int32), signs.astype(jnp.float32), a, s,
        block_m=block_m, block_n=block_n, interpret=interpret,
    )


@partial(jax.jit, static_argnames=("block_m", "block_l", "interpret"))
def panel_score(
    sc: jax.Array,
    a_l: jax.Array,
    q: jax.Array,
    *,
    block_m: int = 256,
    block_l: int = 128,
    interpret: bool | None = None,
) -> tuple:
    """Fused panel scoring: ``(S_C·A_L, resid2, energy)`` in one VMEM pass.

    Shapes: ``sc (s_c, m)``, ``a_l (m, L)``, ``q (s_c, c)`` where ``q`` is
    a (whitened or orthonormal) basis of the admitted columns' sketches —
    ``resid2 = energy − ‖qᵀ·‖²`` scores against ``span(q)``; all-zero
    columns of ``q`` are inert (see ``repro.stream.adaptive``). Returns
    ``(sc_a (s_c, L), resid2 (L,), energy (L,))`` fp32. Zero-padding every
    dim to its block multiple is mathematically a no-op for all three
    outputs.
    """
    interpret = interpret_default() if interpret is None else interpret
    s_c, m = sc.shape
    L = a_l.shape[1]
    scp, ap, qp = pad_dims(
        (sc, (SUBLANE, block_m)), (a_l, (block_m, block_l)), (q, (SUBLANE, LANE))
    )
    sc_a, stats = panel_score_kernel(
        scp, ap, qp, block_m=block_m, block_l=block_l, interpret=interpret
    )
    return sc_a[:s_c, :L], stats[0, :L], stats[1, :L]


@partial(jax.jit, static_argnames=("panel_cap", "block_m", "interpret"))
def panel_update(
    sc: jax.Array,
    a_l: jax.Array,
    srt: jax.Array,
    q: jax.Array,
    C: jax.Array,
    M: jax.Array,
    *,
    min_gain: jax.Array,
    run_mean: jax.Array,
    true_cols: jax.Array,
    n_filled: jax.Array,
    free: jax.Array,
    panel_cap: int,
    block_m: int = 256,
    interpret: bool | None = None,
) -> tuple:
    """Fused per-panel megakernel: sketch + scores + admission + C write.

    One VMEM pass per panel of the adaptive admission-only update
    (:mod:`repro.stream.adaptive`): computes ``sc_a = S_C·A_L`` and the
    per-column ``(resid2, energy)`` scores (the ``panel_score`` math),
    resolves the admission *inside the kernel* (eligibility threshold +
    rank-based slot assignment, provably the same selection as the XLA
    ``top_k``/cumsum path), and scatters the admitted panel columns into
    ``C`` via a one-hot matmul — each ``A_L`` tile is read at most twice
    (once for the sketch reduction, once for the C write of its row block).
    The ``M += sc_a · S_Rᵀ`` fold runs as one XLA matmul on the kernel's
    ``sc_a`` output (see :mod:`repro.kernels.panel_update`).

    Args:
        sc: ``(s_c, m)`` dense column sketch.
        a_l: ``(m, L)`` panel.
        srt: ``(L, s_r)`` dense transposed S_R window at this panel's offset.
        q: ``(s_c, c_local)`` whitened basis of the admitted sketches.
        C, M: accumulators; returned updated (``C`` is aliased through
            the kernel, so on TPU its update is in place).
        min_gain, run_mean, true_cols: admission threshold scalars —
            ``thresh = min_gain · max(run_mean, Σenergy/true_cols)``.
        n_filled, free: next free slot and remaining budget of the calling
            worker's slot range.
        panel_cap: static max admissions per panel.

    Returns:
        ``(C', M', sc_a (s_c, L) f32, resid2 (L,) f32, energy (L,) f32,
        slots (L,) int32)`` — ``slots[j]`` is the C slot column ``j`` was
        admitted into, or the ``C.shape[1]`` sentinel (OOB for the
        caller's ``mode='drop'`` index scatters) when it was not.
    """
    interpret = interpret_default() if interpret is None else interpret
    s_c, m = sc.shape
    L = a_l.shape[1]
    c_total = C.shape[1]
    scp, ap, qp, Cp = pad_dims(
        (sc, (SUBLANE, block_m)),
        (a_l, (block_m, LANE)),
        (q, (SUBLANE, LANE)),
        (C, (block_m, LANE)),
    )
    scal_f = jnp.zeros((8,), jnp.float32)
    scal_f = scal_f.at[0].set(min_gain).at[1].set(run_mean).at[2].set(true_cols)
    scal_i = jnp.zeros((8,), jnp.int32)
    scal_i = scal_i.at[0].set(n_filled).at[1].set(free)
    Cp, sc_a, stats, slots = panel_update_kernel(
        scp, ap, qp, Cp, scal_f, scal_i,
        L=L, c_total=c_total, panel_cap=min(panel_cap, L),
        block_m=block_m, interpret=interpret,
    )
    sc_a = sc_a[:s_c, :L]
    M = M + (sc_a @ srt.astype(jnp.float32)).astype(M.dtype)
    return (
        Cp[:C.shape[0], :c_total],
        M,
        sc_a,
        stats[0, :L],
        stats[1, :L],
        slots[0, :L],
    )


__all__ = [
    "LANE",
    "SUBLANE",
    "pad_dims",
    "interpret_default",
    "kernel_route_enabled",
    "kernel_partitionable",
    "twoside_sketch",
    "countsketch_apply",
    "panel_score",
    "panel_update",
    "twoside_sketch_ref",
    "countsketch_ref",
    "panel_score_ref",
    "panel_update_ref",
]
