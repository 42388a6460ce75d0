"""Work of one fixed-column streaming SPSD factorization."""

from __future__ import annotations

from . import F32, lstsq_flops, sketch_flops


def count(cfg: dict, family: str) -> dict:
    """One fixed-column streaming SPSD factorization of an ``(n x n)`` kernel:

    * ``S_1 K`` over all columns: :func:`sketch_flops` ``(s, n, n)``;
    * the ``M`` fold ``(S_1 K) S_2^T``: :func:`sketch_flops` ``(s, n, s)``;
    * finalize: ``S_1 C`` and ``S_2 C`` (:func:`sketch_flops` ``(s, n, c)``
      each), the two least-squares solves, and the PSD projection of the
      ``c x c`` core (symmetric eigendecomposition ``9 c^3``, rebuild
      ``2 c^3``).

    Bytes: ``K`` read once, ``C`` and ``M`` written once.
    """
    n, c, s = cfg["data"]["n"], cfg["c"], cfg["s"]
    flops = (sketch_flops(family, s, n, n) + sketch_flops(family, s, n, s)
             + 2 * sketch_flops(family, s, n, c)
             + lstsq_flops(s, c, s) + lstsq_flops(s, c, c) + 11.0 * c**3)
    nbytes = F32 * (n * n + n * c + s * s)
    return {"flops": flops, "bytes": float(nbytes)}
