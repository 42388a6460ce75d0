"""Work of one adaptive streaming CUR factorization."""

from __future__ import annotations

from . import F32, lstsq_flops, sketch_flops


def count(cfg: dict, family: str) -> dict:
    """One adaptive streaming CUR factorization of an ``(m x n)`` matrix:

    * ``S_C A`` over all columns: :func:`sketch_flops` ``(s_c, m, n)``;
    * the ``M`` fold ``(S_C A) S_R^T``: :func:`sketch_flops` ``(s_r, n, s_c)``;
    * admission scores ``Q^T (S_C a_j)`` for every column: ``2 s_c c n``;
    * finalize: ``R S_R^T`` (:func:`sketch_flops` ``(s_r, n, r)``) and the two
      least-squares solves ``(S_C C)^+ M`` and ``(.)(R S_R^T)^+``.

    Bytes: ``A`` read once, ``C``, ``R`` and ``M`` written once.
    """
    m, n = cfg["data"]["m"], cfg["data"]["n"]
    c, r, s_c, s_r = cfg["c"], cfg["r"], cfg["s_c"], cfg["s_r"]
    flops = (sketch_flops(family, s_c, m, n) + sketch_flops(family, s_r, n, s_c)
             + 2.0 * s_c * c * n + sketch_flops(family, s_r, n, r)
             + lstsq_flops(s_c, c, s_r) + lstsq_flops(s_r, r, c))
    nbytes = F32 * (m * n + m * c + r * n + s_c * s_r)
    return {"flops": flops, "bytes": float(nbytes)}
