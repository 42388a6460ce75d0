"""Plain float32 helpers shared by the references of ``algorithms/``.

Copied from ``_rel_residual`` and ``rel`` in the repository's
``chip_smoke.py`` (the yardstick must not move with the program), with every
matmul at ``highest``: on a TPU a float32 matmul otherwise runs as one
bfloat16 pass. Nothing here imports the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = "highest"

# Pseudo-inverse cutoff of the references: about the floor of the system's
# floored least-squares solve at c = 256 (float32 eps x c), far below the
# smallest singular value of well-posed inputs, so pinv is the exact inverse.
RTOL = 1e-5


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def pinv(x):
    with jax.default_matmul_precision(HI):
        return jnp.linalg.pinv(x.astype(jnp.float32), rtol=RTOL)


def rel_residual(A, L, R, blocks: int = 8):
    """``||A - L R||_F / ||A||_F``, accumulated over row blocks so that the
    product ``L R`` is never held whole."""
    m = A.shape[0]
    while m % blocks:
        blocks //= 2
    bs = m // blocks

    def one(i):
        rows = jax.lax.dynamic_slice_in_dim(A, i * bs, bs, axis=0)
        Li = jax.lax.dynamic_slice_in_dim(L, i * bs, bs, axis=0)
        E = rows - mm(Li, R)
        return jnp.sum(E * E), jnp.sum(rows * rows)

    err, tot = jax.lax.map(one, jnp.arange(blocks))
    return jnp.sqrt(jnp.sum(err) / jnp.sum(tot))


def rel_diff(a, b):
    """``||a - b||_F / ||b||_F`` in float32."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30)


def max_abs_diff(a, b):
    return jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
