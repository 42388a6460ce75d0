"""Work of one single-pass SVD (Algorithm 3)."""

from __future__ import annotations

import importlib

from . import F32, lstsq_flops


def qr_flops(rows: int, cols: int) -> float:
    """Householder QR of a ``(rows x cols)`` matrix with its thin ``Q``
    formed: ``2 rows cols^2 - 2 cols^3 / 3`` to factor, the same again to
    form ``Q``."""
    return 2 * (2 * rows * cols * cols - 2 * cols**3 / 3)


def count(cfg: dict, family: str) -> dict:
    """One single-pass SVD of an ``(m x n)`` matrix with the sketches of
    ``family`` (p nonzeros a column) inside Gaussian compositions:

    * the three sketches of ``A``: ``Psi A`` (``r0``), ``S_C A`` (``s_c``)
      and ``A Omega^T`` (``c0``), ``p m n`` adds each;
    * the compositions ``(A Omega^T) G_C^T`` (``2 m c0 c``) and
      ``G_R (Psi A)`` (``2 r r0 n``);
    * the ``M`` fold ``(S_C A) S_R^T``: ``p s_c n`` adds;
    * finalize: the QRs of ``C`` and ``R^T``, the sketches ``S_C Q_C`` and
      ``S_R Q_R`` (``p m c`` and ``p n r`` adds), the two least-squares
      solves of the core, its SVD (``22 c^3`` for a square core with both
      factors) and the products ``Q_C U_N``, ``Q_R V_N``.

    Bytes: ``A`` read once, ``C``, ``R`` and ``M`` written once.
    """
    m, n = cfg["data"]["m"], cfg["data"]["n"]
    c, r, c0, r0 = cfg["c"], cfg["r"], cfg["c0"], cfg["r0"]
    s_c, s_r = cfg["s_c"], cfg["s_r"]
    sk = importlib.import_module(f"sketches.{family}")
    p = cfg["osnap_p"]

    def sketch(s, rows, cols):
        return sk.flops(s, rows, cols, p)

    flops = (sketch(r0, m, n) + sketch(s_c, m, n) + sketch(c0, n, m)
             + 2.0 * m * c0 * c + 2.0 * r * r0 * n
             + sketch(s_r, n, s_c)
             + qr_flops(m, c) + qr_flops(n, r) + sketch(s_c, m, c) + sketch(s_r, n, r)
             + lstsq_flops(s_c, c, s_r) + lstsq_flops(s_r, r, c) + 22.0 * min(c, r)**3
             + 2.0 * m * c * c + 2.0 * n * r * r)
    nbytes = F32 * (m * n + m * c + r * n + s_c * s_r)
    return {"flops": flops, "bytes": float(nbytes)}
