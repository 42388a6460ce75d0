"""Low-rank-plus-noise test matrix, made on the device from a key.

Copied from ``lowrank_matrix`` in the repository's ``chip_smoke.py`` so that
the yardstick does not move when the program side changes; the spike
height is a parameter here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make(key, spec: dict, sharding=None):
    """``U diag(1/(1+i)) V + diag-scaled noise``: Gaussian factors (no m x m
    QR) plus independent noise, ``spike``-sigma heavy in a ``spiked`` share of
    columns so that adaptive admission has directions outside the low-rank
    span to find.

    Made by two programs, the noise added in place: fused into one, the TPU
    compile takes about two minutes instead of seconds.
    """
    m, n, rank = spec["m"], spec["n"], spec["rank"]
    noise, spiked, spike = spec["noise"], spec["spiked"], spec["spike"]
    ku, kv, ke, kw = jax.random.split(key, 4)

    def signal(ku, kv):
        sigma = 1.0 / (1.0 + jnp.arange(rank, dtype=jnp.float32))
        U = jax.random.normal(ku, (m, rank), jnp.float32) * sigma
        return U @ jax.random.normal(kv, (rank, n), jnp.float32)

    def add_noise(A, ke, kw):
        scale = noise + jnp.where(jax.random.uniform(kw, (n,)) < spiked, spike, 0.0)
        return A + scale * jax.random.normal(ke, (m, n), jnp.float32)

    A = jax.jit(signal, out_shardings=sharding)(ku, kv)
    return jax.jit(add_noise, out_shardings=sharding, donate_argnums=0)(A, ke, kw)
