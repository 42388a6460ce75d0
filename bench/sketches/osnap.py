"""OSNAP inputs: ``p`` entries of +-1/sqrt(p) per column, each at a uniform
row (the "with replacement" variant the program implements).

The benchmark draws the sketch itself and hands it to the program, so the
reference builds its dense matrix from the same draw without reading
anything the program made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def draw(key, s: int, m: int, p: int = 2) -> dict:
    k_h, k_s = jax.random.split(key)
    return {
        "hashes": jax.random.randint(k_h, (p, m), 0, s, jnp.int32),
        "signs": jax.random.rademacher(k_s, (p, m), jnp.float32) * (1.0 / p**0.5),
    }


def wrap(arrays: dict, s: int):
    """The program's sketch object for ``arrays``."""
    from repro.core.sketching import OSNAPSketch

    return OSNAPSketch(hashes=arrays["hashes"], signs=arrays["signs"], s=s,
                       p=arrays["hashes"].shape[0])


def dense(arrays: dict, s: int):
    """``S[h_ji, i] += sign_ji`` over the ``p`` hash rows ``j`` as an
    ``(s, m)`` float32 matrix, built by comparisons."""
    rows = jnp.arange(s, dtype=jnp.int32)[:, None]
    out = 0.0
    for h, sg in zip(arrays["hashes"], arrays["signs"]):
        out = out + jnp.where(h[None, :] == rows, sg[None, :], 0.0)
    return out


def mul(S, X):
    """``S X`` for the dense form ``S`` of an OSNAP sketch: signed sums of the
    float32 entries of ``X``, as the configurations state (no product
    rounds an entry)."""
    return jnp.matmul(S, X, precision="highest")


def flops(s: int, rows: int, cols: int, p: int = 2) -> float:
    """Operations ``S X`` requires for an ``(s x rows)`` sketch and an
    ``(rows x cols)`` operand. Each entry of the operand is added into ``p``
    rows: ``p rows cols`` adds."""
    return float(p * rows * cols)
