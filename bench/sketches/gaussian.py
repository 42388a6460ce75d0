"""Dense Gaussian sketch inputs: iid N(0, 1/s) entries (paper section 2.3).

The benchmark draws the sketch itself and hands it to the program, so the
reference uses the same draw without reading anything the program made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def draw(key, s: int, m: int) -> dict:
    return {"mat": jax.random.normal(key, (s, m), jnp.float32) * (1.0 / s**0.5)}


def wrap(arrays: dict, s: int):
    """The program's sketch object for ``arrays``."""
    from repro.core.sketching import GaussianSketch

    return GaussianSketch(arrays["mat"])


def dense(arrays: dict, s: int):
    return arrays["mat"]


def mul(S, X):
    """``S X`` for the dense sketch ``S`` at the configurations' stated
    precision: a float32 matmul at the platform's default precision, which
    on a TPU is one bfloat16 pass (each operand rounded to bfloat16, the
    exact products summed in float32)."""
    return jnp.matmul(S, X, precision=jax.lax.Precision.DEFAULT)


def flops(s: int, rows: int, cols: int) -> float:
    """Operations ``S X`` requires for an ``(s x rows)`` sketch and an
    ``(rows x cols)`` operand. A dense product: ``2 s rows cols``."""
    return 2.0 * s * rows * cols
