"""RBF kernel matrix of Gaussian points, made on the device from a key.

Copied from ``rbf_kernel`` in the repository's ``chip_smoke.py`` so that the
yardstick does not move when the program side changes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make(key, spec: dict, sharding=None):
    """RBF kernel of n Gaussian points in d dimensions with sigma^2 = d/2 (the
    median squared distance is about 2d): 256 uniform columns keep 99.7% of
    the spectral energy at condition number ~4e2, so the core solve is well
    posed in float32."""
    n, d = spec["n"], spec["d"]

    def gen(key):
        X = jax.random.normal(key, (n, d), jnp.float32)
        sq = jnp.sum(X * X, axis=1)
        G = jnp.matmul(X, X.T, precision="highest")
        D2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * G, 0.0)
        return jnp.exp(-D2 / d)

    return jax.jit(gen, out_shardings=sharding)(key)
