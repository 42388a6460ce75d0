"""Work of one call of the ``panel_update`` Pallas kernel."""

from __future__ import annotations

from . import F32


def count(cfg: dict) -> dict:
    """One call of the ``panel_update`` Pallas kernel on an ``(m x L)`` panel:
    the panel sketch ``S_C A_L`` (``2 s_c m L``) and its scores
    ``Q^T (S_C A_L)`` (``2 s_c c L``). Bytes: ``S_C`` read once, ``A_L`` read
    once, ``C`` read and written."""
    m, L = cfg["data"]["m"], cfg["panel"]
    c, s_c = cfg["c"], cfg["s_c"]
    flops = 2.0 * s_c * m * L + 2.0 * s_c * c * L
    nbytes = F32 * (s_c * m + m * L + 2 * m * c)
    return {"flops": flops, "bytes": float(nbytes)}
