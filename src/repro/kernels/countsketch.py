"""Pallas TPU kernel: CountSketch  S·A  as a row-accumulating scatter.

A CountSketch sends row ``i`` of ``A`` to bucket ``h_i`` with sign ``σ_i``:
``(S·A)[h, :] = Σ_{i : h_i = h} σ_i · A[i, :]``. The kernel does exactly
that O(nnz(A)) work. Grid ``(column blocks, row blocks)``, row blocks
fastest: the ``(s, block_n)`` output block stays resident in VMEM across the
row-block axis and is the accumulator; for each row ``i`` of a
``(block_m, block_n)`` block of ``A`` the kernel adds ``σ_i · A[i, :]`` into
accumulator row ``h_i`` through a dynamic single-sublane slice, in ascending
``i`` and in float32 — the summation order of a sequential segment sum, so
float32 results equal ``jax.ops.segment_sum``'s bit for bit. Hashes and signs
are scalars and travel in SMEM, one block per row block.

``A`` is read from HBM once, in place: ragged edge blocks are not padded
(the last row block's loop stops at ``m``; columns past ``n`` only reach
output columns the edge block's write-back drops). Each row costs a
single-sublane load of ``A``, a load and a store of the accumulator row per
128 columns, and the scalar work of addressing them; with 2048-column
blocks that keeps pace with HBM, so one read of ``A`` and one write of
``S·A`` bound the kernel (TPU v5e, f32 32768² at s = 3840: 6.46 ms, 91% of
the least time those bytes take at 819 GB/s).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_CAP_BYTES = 120 * 1024 * 1024  # of the chip's 128 MiB


def _kernel(h_ref, sg_ref, a_ref, out_ref, *, m: int, block_m: int, group: int):
    k = pl.program_id(1)
    # a packed dtype (bfloat16) has no single-row dynamic slice: load the
    # group's rows as one aligned tile and take its rows statically
    packed = jnp.dtype(a_ref.dtype).itemsize < 4

    @pl.when(k == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    def add(i, a_row):
        h = h_ref[0, i]
        out_ref[pl.ds(h, 1), :] += sg_ref[0, i] * a_row.astype(jnp.float32)

    def rows(g, n_rows=None):
        base = pl.multiple_of(g * group, group)
        if packed:
            tile = a_ref[pl.ds(base, group), :]
        for u in range(group):
            a_row = tile[u:u + 1, :] if packed else a_ref[pl.ds(base + u, 1), :]
            if n_rows is None:
                add(base + u, a_row)
            else:
                pl.when(base + u < n_rows)(partial(add, base + u, a_row))

    def groups(g, carry):
        rows(g)
        return carry

    if m % block_m == 0:
        jax.lax.fori_loop(0, block_m // group, groups, 0)
    else:  # the last row block stops at m
        n_rows = jnp.minimum(block_m, m - k * block_m)
        jax.lax.fori_loop(0, n_rows // group, groups, 0)
        rows(n_rows // group, n_rows)


def countsketch_kernel(
    hashes: jax.Array,  # (m,) int32 in [0, s)
    signs: jax.Array,  # (m,) float32
    a: jax.Array,  # (m, n)
    s: int,
    *,
    block_m: int = 1024,
    block_n: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """``(s, n)`` float32 CountSketch of ``a``; blocks clip to ``a``'s extent.

    ``block_m`` and ``block_n`` are multiples of 128. Column blocks narrow
    until the double-buffered ``(s, block_n)`` accumulator fits VMEM, and,
    where ``a`` has fewer rows than ``s`` buckets (the M fold: 512 rows into
    3840), to an eighth of ``n``, so that one block's write-back overlaps
    the next block's rows (v5e, 64 folds of ``(512, 3840)``: 13.3 ms in
    384-column blocks, 14.9 ms in 1920). Hashes must lie in ``[0, s)``: the
    kernel does not bounds-check its stores.
    """
    m, n = a.shape
    assert block_m % 128 == 0 and block_n % 128 == 0, (block_m, block_n)
    itemsize = jnp.dtype(a.dtype).itemsize
    group = max(16, 32 // itemsize)  # rows per unrolled step: whole packed tiles
    bm = min(block_m, pl.cdiv(m, 128) * 128)
    bn = block_n
    if m < s:
        bn = min(bn, max(128, n // 8 // 128 * 128))

    def vmem(bn):
        return 2 * s * bn * 4 + 2 * bm * bn * itemsize + (4 << 20)

    while bn > 128 and vmem(bn) > VMEM_CAP_BYTES:
        bn = max(128, bn // 2 // 128 * 128)
    bn = n if n <= bn else bn
    return pl.pallas_call(
        partial(_kernel, m=m, block_m=bm, group=group),
        grid=(pl.cdiv(n, bn), pl.cdiv(m, bm)),
        in_specs=[
            pl.BlockSpec((1, bm), lambda j, k: (0, k), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bm), lambda j, k: (0, k), memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bn), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((s, bn), lambda j, k: (0, j)),
        # inside shard_map the sums vary over every manual axis an operand does
        out_shape=jax.ShapeDtypeStruct(
            (s, n), jnp.float32,
            vma=frozenset().union(*(jax.typeof(x).vma for x in (hashes, signs, a)))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(vmem(bn), VMEM_CAP_BYTES),
        ),
        interpret=interpret,
        name="countsketch",
    )(hashes[None, :], signs[None, :], a)  # (1, m): vmap may add a leading dim
