"""Work a factorization or a kernel call requires, counted from shapes.

Each count is what the algorithm needs, whatever route implements it: the
same configuration and sketch family give the same numbers whether the
program runs the fused scan, the Pallas kernel or a future path. A
multiply-add is two operations, an add one. Bytes are float32 (4 each) and
count every operand read once and every result written once.

The least time of a count on a chip is the larger of operations over the
peak rate and bytes over the peak bandwidth (:func:`least_seconds`).
"""

from __future__ import annotations

F32 = 4


def lstsq_flops(p: int, k: int, q: int) -> float:
    """``argmin_X ||B X - Y||`` for ``B`` (p x k), ``Y`` (p x q) by Householder
    QR: ``2pk^2 - 2k^3/3`` to factor, the same again to form the thin ``Q``,
    ``2pkq`` for ``Q^T Y`` and ``k^2 q`` for the triangular solve."""
    return 2 * (2 * p * k * k - 2 * k**3 / 3) + 2 * p * k * q + k * k * q


def sketch_flops(family: str, s: int, rows: int, cols: int) -> float:
    """``S X`` for an ``(s x rows)`` sketch of ``family`` and an
    ``(rows x cols)`` operand, as ``sketches/<family>.py`` counts it."""
    import importlib

    return importlib.import_module(f"sketches.{family}").flops(s, rows, cols)


def factorization(cfg: dict, traffic: dict) -> dict:
    """The count of one factorization of ``cfg`` under ``traffic``, from the
    module of this package named after the configuration's algorithm."""
    import importlib

    return importlib.import_module(f"{__name__}.{cfg['algorithm']}").count(cfg, traffic["sketch"])


def least_seconds(work: dict, peak: dict, chips: int = 1) -> float:
    """The least time ``chips`` chips of ``peak`` could take for ``work``."""
    return max(work["flops"] / (chips * peak["flops_per_s"]),
               work["bytes"] / (chips * peak["hbm_bytes_per_s"]))
