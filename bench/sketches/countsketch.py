"""CountSketch inputs: one +-1 entry per column, at a uniform row.

The benchmark draws the sketch itself and hands it to the program, so the
reference builds its dense matrix from the same draw without reading
anything the program made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def draw(key, s: int, m: int) -> dict:
    k_h, k_s = jax.random.split(key)
    return {
        "hashes": jax.random.randint(k_h, (m,), 0, s, jnp.int32),
        "signs": jax.random.rademacher(k_s, (m,), jnp.float32),
    }


def wrap(arrays: dict, s: int):
    """The program's sketch object for ``arrays``."""
    from repro.core.sketching import CountSketch

    return CountSketch(hashes=arrays["hashes"], signs=arrays["signs"], s=s)


def dense(arrays: dict, s: int):
    """``S[h_i, i] = sign_i`` as an ``(s, m)`` float32 matrix, built by a
    comparison so that it shards along ``m`` like its inputs."""
    rows = jnp.arange(s, dtype=jnp.int32)[:, None]
    return jnp.where(arrays["hashes"][None, :] == rows, arrays["signs"][None, :], 0.0)


def mul(S, X):
    """``S X`` for the dense form ``S`` of a CountSketch: signed sums of the
    float32 entries of ``X``, as the configurations state (no product
    rounds an entry)."""
    return jnp.matmul(S, X, precision="highest")


def flops(s: int, rows: int, cols: int) -> float:
    """Operations ``S X`` requires for an ``(s x rows)`` sketch and an
    ``(rows x cols)`` operand. Each entry of the operand is added into one row: ``rows cols`` adds."""
    return float(rows * cols)
