"""Pallas TPU kernel: CountSketch  S·A  as a blocked one-hot MXU matmul.

The paper's input-sparsity CountSketch is a scatter-add — no TPU analogue
(no scatter units; see DESIGN.md §5). The TPU-native restatement: for each
(bm=128)-row block of A, materialize the signed one-hot slab
P = onehot(h[block]) ⊙ σ[block]  (s × bm) *inside VMEM* from the integer
hash/sign vectors (broadcasted-iota compare — the slab never exists in
HBM), and accumulate  P @ A_block  on the MXU into an (s, bn) scratch.

One HBM pass over A — bandwidth-bound, which is the O(nnz) insight
restated for a dense-tile machine.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(h_ref, sg_ref, a_ref, out_ref, acc_ref, *, s_pad: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    h = h_ref[...]  # (1, bm) int32
    sg = sg_ref[...]  # (1, bm)
    bm = h.shape[1]
    # signed one-hot slab (s_pad, bm) built in-register: rows=sketch buckets
    rows = jax.lax.broadcasted_iota(jnp.int32, (s_pad, bm), 0)
    slab = jnp.where(rows == h, sg, 0).astype(a_ref.dtype)
    acc_ref[...] += jnp.dot(slab, a_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def countsketch_kernel(
    hashes: jax.Array,  # (1, m) int32 in [0, s)
    signs: jax.Array,  # (1, m) ±1
    a: jax.Array,  # (m, n)
    s: int,
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """dims must be pre-padded to block multiples; s padded to 128 (ops.py).

    Hashes and signs travel as ``(1, m)`` rows: a 1-D ``(block_m,)`` block
    does not match the layout XLA gives a 1-D int32 array on TPU, while a
    ``(1, block_m)`` block is a full-extent sublane × lane-aligned tile.
    """
    m, n = a.shape
    assert m % block_m == 0 and n % block_n == 0 and s % 128 == 0
    grid = (n // block_n, m // block_m)

    import functools

    return pl.pallas_call(
        functools.partial(_kernel, s_pad=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m), lambda j, k: (0, k)),
            pl.BlockSpec((1, block_m), lambda j, k: (0, k)),
            pl.BlockSpec((block_m, block_n), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((s, block_n), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((s, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((s, block_n), jnp.float32)],
        interpret=interpret,
    )(hashes, signs, a)
