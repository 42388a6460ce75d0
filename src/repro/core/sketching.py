"""Matrix sketching library (paper §2.3).

Implements every sketching family the paper's Table 1/2 analyses cover:

* Gaussian projection
* Subsampled randomized Hadamard transform (SRHT)
* CountSketch (Clarkson & Woodruff, 2013)
* OSNAP (Nelson & Nguyen, 2013)
* Row sampling (uniform / leverage-score, Drineas et al. 2006b)
* Composed sketches ``S2 ∘ S1`` (e.g. Gaussian ∘ OSNAP as used by Algorithm 3)

Every sketch is a small pytree-registered dataclass with three operations:

* ``apply(A)``     — ``S @ A``          (A is (m, n), S is (s, m))
* ``apply_t(A)``   — ``A @ S.T``        (A is (n, m))
* ``materialize()``— dense ``S`` (tests/small problems only)

plus ``cols(offset, size)`` which restricts the *source* dimension to a
contiguous column window — the streaming primitive Algorithm 3 needs to
consume ``A`` in L-column panels (``M += S_C A_L S_R[:, cols]ᵀ``) — and
``pad_cols(total)`` which extends the source dimension with *zero-scaled*
columns so that ``cols()`` windows reaching past the true source dim stay
valid slices that contribute nothing (the contract zero-padded ragged tail
panels rely on; see ``repro.stream.engine``).

The hashed sketches (CountSketch, OSNAP) add ``transpose_apply(Y)`` —
``S.T @ Y`` (Y is (s, k)), a gather of the rows of ``Y`` their hashes
name.

All randomness is fully determined by an explicit ``jax.random`` key so that
sketches drawn on different data-parallel workers from a shared seed are
bit-identical (gradient compression relies on ``Σᵢ(Gᵢ Ω) = (Σᵢ Gᵢ) Ω``).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kernel_ops
from ..obs.metrics import default_registry

__all__ = [
    "GaussianSketch",
    "SRHTSketch",
    "CountSketch",
    "OSNAPSketch",
    "RowSampling",
    "ComposedSketch",
    "draw_sketch",
    "fwht",
    "SKETCH_KINDS",
]


def _register(cls, data: tuple, meta: tuple):
    return jax.tree_util.register_dataclass(cls, data_fields=list(data), meta_fields=list(meta))


def _bcast_vec(v: jax.Array, ndim: int) -> jax.Array:
    """Reshape a length-k vector to (k, 1, …, 1) for broadcasting against an
    ndim-dimensional operand (3.10-safe stand-in for ``v[:, *([None]*(ndim-1))]``)."""
    return v.reshape(v.shape[:1] + (1,) * (ndim - 1))


# ---------------------------------------------------------------------------
# Gaussian projection
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GaussianSketch:
    """Dense ``S ∈ R^{s×m}`` with iid N(0, 1/s) entries (paper §2.3)."""

    mat: jax.Array  # (s, m)

    @staticmethod
    def draw(key, s: int, m: int, dtype=jnp.float32) -> "GaussianSketch":
        mat = jax.random.normal(key, (s, m), dtype) * (1.0 / np.sqrt(s))
        return GaussianSketch(mat)

    @property
    def s(self) -> int:
        return self.mat.shape[0]

    @property
    def m(self) -> int:
        return self.mat.shape[1]

    def apply(self, A: jax.Array) -> jax.Array:
        return self.mat[:, : A.shape[0]] @ A  # [:m] slice: padded sketch on unpadded A

    def apply_t(self, A: jax.Array) -> jax.Array:
        return A @ self.mat[:, : A.shape[-1]].T

    def materialize(self) -> jax.Array:
        return self.mat

    def cols(self, offset: int, size: int) -> "GaussianSketch":
        return GaussianSketch(jax.lax.dynamic_slice_in_dim(self.mat, offset, size, axis=1))

    def pad_cols(self, total: int) -> "GaussianSketch":
        if total <= self.m:
            return self
        pad = jnp.zeros((self.s, total - self.m), self.mat.dtype)
        return GaussianSketch(jnp.concatenate([self.mat, pad], axis=1))


_register(GaussianSketch, ("mat",), ())


# ---------------------------------------------------------------------------
# SRHT
# ---------------------------------------------------------------------------


def fwht(x: jax.Array) -> jax.Array:
    """Unnormalised fast Walsh–Hadamard transform along axis 0.

    ``x.shape[0]`` must be a power of two. O(m log m) per column.
    """
    m = x.shape[0]
    if m & (m - 1):
        raise ValueError(f"FWHT needs a power-of-two leading dim, got {m}")
    tail = x.shape[1:]
    h = 1
    while h < m:
        x = x.reshape(m // (2 * h), 2, h, *tail)
        a, b = x[:, 0], x[:, 1]
        x = jnp.stack([a + b, a - b], axis=1).reshape(m, *tail)
        h *= 2
    return x


@dataclasses.dataclass(frozen=True)
class SRHTSketch:
    """``S = sqrt(m/s) · P · (H/√m) · D`` (paper §2.3, Tropp 2011).

    ``m`` is internally padded to the next power of two; padded rows of the
    source are treated as zeros.
    """

    signs: jax.Array  # (m_pad,) ±1
    row_idx: jax.Array  # (s,) sampled rows of the transformed matrix
    m: int  # true source dim (static)
    m_pad: int  # padded source dim (static)

    @staticmethod
    def draw(key, s: int, m: int, dtype=jnp.float32) -> "SRHTSketch":
        m_pad = 1 << int(np.ceil(np.log2(max(m, 2))))
        k_sign, k_row = jax.random.split(key)
        signs = jax.random.rademacher(k_sign, (m_pad,), dtype)
        row_idx = jax.random.randint(k_row, (s,), 0, m_pad)
        return SRHTSketch(signs=signs, row_idx=row_idx, m=m, m_pad=m_pad)

    @property
    def s(self) -> int:
        return self.row_idx.shape[0]

    def apply(self, A: jax.Array) -> jax.Array:
        m = A.shape[0]
        pad = self.m_pad - m
        x = A * _bcast_vec(self.signs[:m], A.ndim)
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad, *A.shape[1:]), A.dtype)], axis=0)
        x = fwht(x) * (1.0 / np.sqrt(self.s))
        return jnp.take(x, self.row_idx, axis=0)

    def apply_t(self, A: jax.Array) -> jax.Array:
        return self.apply(A.T).T

    def materialize(self) -> jax.Array:
        return self.apply(jnp.eye(self.m, dtype=self.signs.dtype))

    def cols(self, offset: int, size: int):  # pragma: no cover - structural
        raise NotImplementedError("SRHT is not column-sliceable; use CountSketch/OSNAP for streaming")


_register(SRHTSketch, ("signs", "row_idx"), ("m", "m_pad"))


# ---------------------------------------------------------------------------
# CountSketch
# ---------------------------------------------------------------------------


def _hashed_transpose_apply(hashes: jax.Array, signs: jax.Array, Y: jax.Array) -> jax.Array:
    """``Sᵀ Y`` for the hashed sketch with ``(p, m)`` ``hashes`` and
    ``signs``: row ``i`` is ``Σ_j signs[j, i] · Y[hashes[j, i]]``, the ``p``
    terms added in hash-row order in float32 (at least). A gather of ``p·m``
    rows of ``Y``; zero-signed ``pad_cols`` columns give zero rows."""
    dt = jnp.result_type(Y.dtype, signs.dtype)
    acc = jnp.promote_types(dt, jnp.float32)
    out = None
    for h, sg in zip(hashes, signs):
        term = _bcast_vec(sg.astype(acc), Y.ndim) * Y[h].astype(acc)
        out = term if out is None else out + term
    return out.astype(dt)


def _takes_kernel(A: jax.Array) -> bool:
    """Does a hashed sketch (CountSketch, OSNAP) apply to ``A`` through the
    ``countsketch`` Pallas kernel? On a TPU (``kernel_route_enabled``), for a
    2-D operand that XLA need not partition (``kernel_partitionable``)."""
    return (A.ndim == 2 and kernel_ops.kernel_route_enabled()
            and kernel_ops.kernel_partitionable(A))


@dataclasses.dataclass(frozen=True)
class CountSketch:
    """One ±1 entry per column, position uniform (Clarkson & Woodruff 2013).

    ``apply`` is a signed segment-sum — the O(nnz(A)) input-sparsity
    algorithm. On a TPU a 2-D operand takes the row-accumulating Pallas
    kernel (``repro.kernels.countsketch``), which XLA's serial scatter loop
    would otherwise run; other backends, operands of other rank, and
    operands laid out over several devices outside ``shard_map`` (which XLA
    must partition, and cannot partition a kernel) take
    ``jax.ops.segment_sum``. The kernel adds the signed rows in ascending
    order in float32, as ``segment_sum`` does for a float32 result, so the
    two agree bit for bit there. The route taken is counted at trace time
    under ``sketch.countsketch.route.kernel`` /
    ``sketch.countsketch.route.segment_sum``.
    """

    hashes: jax.Array  # (m,) int32 in [0, s)
    signs: jax.Array  # (m,) ±1
    s: int  # static

    @staticmethod
    def draw(key, s: int, m: int, dtype=jnp.float32) -> "CountSketch":
        k_h, k_s = jax.random.split(key)
        hashes = jax.random.randint(k_h, (m,), 0, s)
        signs = jax.random.rademacher(k_s, (m,), dtype)
        return CountSketch(hashes=hashes, signs=signs, s=s)

    @property
    def m(self) -> int:
        return self.hashes.shape[0]

    def apply(self, A: jax.Array) -> jax.Array:
        m = A.shape[0]
        reg = default_registry()
        if _takes_kernel(A):
            reg.inc("sketch.countsketch.route.kernel")
            out = kernel_ops.countsketch_apply(self.hashes[:m], self.signs[:m], A, self.s)
            return out.astype(jnp.result_type(A.dtype, self.signs.dtype))
        reg.inc("sketch.countsketch.route.segment_sum")
        signed = A * _bcast_vec(self.signs[:m], A.ndim)
        return jax.ops.segment_sum(signed, self.hashes[:m], num_segments=self.s)

    def apply_t(self, A: jax.Array) -> jax.Array:
        return self.apply(A.T).T

    def transpose_apply(self, Y: jax.Array) -> jax.Array:
        """``Sᵀ Y`` for ``Y`` (s, k): row ``i`` is ``signs[i] · Y[hashes[i]]``."""
        return _hashed_transpose_apply(self.hashes[None], self.signs[None], Y)

    def materialize(self) -> jax.Array:
        S = jnp.zeros((self.s, self.m), self.signs.dtype)
        return S.at[self.hashes, jnp.arange(self.m)].set(self.signs)

    def cols(self, offset: int, size: int) -> "CountSketch":
        return CountSketch(
            hashes=jax.lax.dynamic_slice_in_dim(self.hashes, offset, size),
            signs=jax.lax.dynamic_slice_in_dim(self.signs, offset, size),
            s=self.s,
        )

    def pad_cols(self, total: int) -> "CountSketch":
        if total <= self.m:
            return self
        pad = total - self.m
        return CountSketch(
            hashes=jnp.concatenate([self.hashes, jnp.zeros((pad,), self.hashes.dtype)]),
            signs=jnp.concatenate([self.signs, jnp.zeros((pad,), self.signs.dtype)]),
            s=self.s,
        )


_register(CountSketch, ("hashes", "signs"), ("s",))


# ---------------------------------------------------------------------------
# OSNAP
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OSNAPSketch:
    """``p`` ±1/√p entries per column (Nelson & Nguyen 2013).

    Implemented as the sum of ``p`` independent CountSketches scaled by
    1/√p (the "with replacement" OSNAP variant standard in practice; the
    subspace-embedding property is preserved, validated in tests).

    ``apply`` takes the route ``CountSketch.apply`` takes: on a TPU a 2-D
    operand goes through the ``countsketch`` Pallas kernel once per hash
    row, each term summed in ascending row order in float32 as
    ``segment_sum`` sums it, and the ``p`` terms added in hash-row order;
    elsewhere the ``p`` signed segment sums run vmapped. The route is
    counted at trace time under ``sketch.osnap.route.kernel`` /
    ``sketch.osnap.route.segment_sum``.
    """

    hashes: jax.Array  # (p, m)
    signs: jax.Array  # (p, m)
    s: int
    p: int

    @staticmethod
    def draw(key, s: int, m: int, p: int = 2, dtype=jnp.float32) -> "OSNAPSketch":
        k_h, k_s = jax.random.split(key)
        hashes = jax.random.randint(k_h, (p, m), 0, s)
        signs = jax.random.rademacher(k_s, (p, m), dtype) * (1.0 / np.sqrt(p))
        return OSNAPSketch(hashes=hashes, signs=signs, s=s, p=p)

    @property
    def m(self) -> int:
        return self.hashes.shape[1]

    def apply(self, A: jax.Array) -> jax.Array:
        m = A.shape[0]
        reg = default_registry()
        if _takes_kernel(A):
            reg.inc("sketch.osnap.route.kernel")
            out = kernel_ops.countsketch_apply(self.hashes[0, :m], self.signs[0, :m], A, self.s)
            for j in range(1, self.p):
                out = out + kernel_ops.countsketch_apply(
                    self.hashes[j, :m], self.signs[j, :m], A, self.s)
            return out.astype(jnp.result_type(A.dtype, self.signs.dtype))
        reg.inc("sketch.osnap.route.segment_sum")

        def one(h, sg):
            signed = A * _bcast_vec(sg[:m], A.ndim)
            return jax.ops.segment_sum(signed, h[:m], num_segments=self.s)

        return jnp.sum(jax.vmap(one)(self.hashes, self.signs), axis=0)

    def apply_t(self, A: jax.Array) -> jax.Array:
        return self.apply(A.T).T

    def transpose_apply(self, Y: jax.Array) -> jax.Array:
        """``Sᵀ Y`` for ``Y`` (s, k): row ``i`` is ``Σ_j signs[j, i] ·
        Y[hashes[j, i]]``, the ``p`` terms added in hash-row order in
        float32. No kernel: a gather of ``p·m`` rows."""
        return _hashed_transpose_apply(self.hashes, self.signs, Y)

    def materialize(self) -> jax.Array:
        S = jnp.zeros((self.s, self.m), self.signs.dtype)
        for i in range(self.p):
            S = S.at[self.hashes[i], jnp.arange(self.m)].add(self.signs[i])
        return S

    def cols(self, offset: int, size: int) -> "OSNAPSketch":
        return OSNAPSketch(
            hashes=jax.lax.dynamic_slice_in_dim(self.hashes, offset, size, axis=1),
            signs=jax.lax.dynamic_slice_in_dim(self.signs, offset, size, axis=1),
            s=self.s,
            p=self.p,
        )

    def pad_cols(self, total: int) -> "OSNAPSketch":
        if total <= self.m:
            return self
        pad = total - self.m
        return OSNAPSketch(
            hashes=jnp.concatenate([self.hashes, jnp.zeros((self.p, pad), self.hashes.dtype)], axis=1),
            signs=jnp.concatenate([self.signs, jnp.zeros((self.p, pad), self.signs.dtype)], axis=1),
            s=self.s,
            p=self.p,
        )


_register(OSNAPSketch, ("hashes", "signs"), ("s", "p"))


# ---------------------------------------------------------------------------
# Row sampling (uniform / leverage-score)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RowSampling:
    """Sample-and-rescale sketch: row i w.p. pᵢ, scaled 1/√(s pᵢ) (paper §2.3)."""

    idx: jax.Array  # (s,)
    scale: jax.Array  # (s,)
    m: int

    @staticmethod
    def draw(key, s: int, m: int, probs: Optional[jax.Array] = None, dtype=jnp.float32) -> "RowSampling":
        if probs is None:
            probs = jnp.full((m,), 1.0 / m, dtype)
        else:
            probs = probs.astype(dtype) / jnp.sum(probs)
        idx = jax.random.choice(key, m, (s,), replace=True, p=probs)
        scale = 1.0 / jnp.sqrt(s * probs[idx])
        return RowSampling(idx=idx, scale=scale, m=m)

    @property
    def s(self) -> int:
        return self.idx.shape[0]

    def apply(self, A: jax.Array) -> jax.Array:
        rows = jnp.take(A, self.idx, axis=0)
        return rows * _bcast_vec(self.scale, A.ndim)

    def apply_t(self, A: jax.Array) -> jax.Array:
        return jnp.take(A, self.idx, axis=1) * self.scale[None, :]

    def materialize(self) -> jax.Array:
        S = jnp.zeros((self.s, self.m), self.scale.dtype)
        return S.at[jnp.arange(self.s), self.idx].add(self.scale)

    def cols(self, offset: int, size: int) -> "RowSampling":
        """Restrict to the source-column window ``[offset, offset+size)``.

        A sampling matrix has one nonzero per row (at column ``idx[i]``), so
        the window restriction re-bases in-window indices and zero-scales
        out-of-window rows — samples outside the window contribute nothing,
        which is exactly the ``S[:, offset:offset+size]`` slice. ``offset``
        may be traced (the streaming engine slides the window per panel).
        """
        rel = self.idx - offset
        in_window = (rel >= 0) & (rel < size)
        return RowSampling(
            idx=jnp.clip(rel, 0, size - 1),
            scale=jnp.where(in_window, self.scale, jnp.zeros((), self.scale.dtype)),
            m=size,
        )

    def pad_cols(self, total: int) -> "RowSampling":
        """Extend the source dim with zero columns (never sampled).

        Sampled indices always lie in ``[0, m)``, so windows past the true
        source dim contain no samples and ``cols()`` zero-scales them — the
        exact ragged-tail contract of :mod:`repro.stream.engine` holds with
        no stored-array change.
        """
        if total <= self.m:
            return self
        return RowSampling(idx=self.idx, scale=self.scale, m=total)


_register(RowSampling, ("idx", "scale"), ("m",))


# ---------------------------------------------------------------------------
# Composition (e.g. Gaussian ∘ OSNAP used by Algorithm 3)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ComposedSketch:
    """``S = outer ∘ inner`` — apply ``inner`` first, then ``outer``.

    The paper's Remark 1 / Algorithm 3 pattern: a cheap input-sparsity
    sketch (OSNAP) followed by a Gaussian projection to compact size.
    """

    inner: object
    outer: object

    @property
    def s(self) -> int:
        return self.outer.s

    @property
    def m(self) -> int:
        return self.inner.m

    def apply(self, A: jax.Array) -> jax.Array:
        return self.outer.apply(self.inner.apply(A))

    def apply_t(self, A: jax.Array) -> jax.Array:
        return self.outer.apply_t(self.inner.apply_t(A))

    def materialize(self) -> jax.Array:
        return self.outer.apply(self.inner.materialize())

    def cols(self, offset: int, size: int) -> "ComposedSketch":
        return ComposedSketch(inner=self.inner.cols(offset, size), outer=self.outer)

    def pad_cols(self, total: int) -> "ComposedSketch":
        return ComposedSketch(inner=self.inner.pad_cols(total), outer=self.outer)


_register(ComposedSketch, ("inner", "outer"), ())


# ---------------------------------------------------------------------------
# Factory
# ---------------------------------------------------------------------------

SKETCH_KINDS = ("gaussian", "srht", "countsketch", "osnap", "uniform", "leverage")


def draw_sketch(key, kind: str, s: int, m: int, *, probs=None, p: int = 2, dtype=jnp.float32):
    """Draw an ``(s, m)`` sketch of the requested family.

    ``probs`` is required for kind="leverage" (the leverage-score
    distribution of the matrix being protected, per Tables 2/3).
    """
    if kind == "gaussian":
        return GaussianSketch.draw(key, s, m, dtype)
    if kind == "srht":
        return SRHTSketch.draw(key, s, m, dtype)
    if kind == "countsketch":
        return CountSketch.draw(key, s, m, dtype)
    if kind == "osnap":
        return OSNAPSketch.draw(key, s, m, p=p, dtype=dtype)
    if kind == "uniform":
        return RowSampling.draw(key, s, m, probs=None, dtype=dtype)
    if kind == "leverage":
        if probs is None:
            raise ValueError("leverage sampling requires `probs`")
        return RowSampling.draw(key, s, m, probs=probs, dtype=dtype)
    if kind == "osnap+gaussian":
        k1, k2 = jax.random.split(key)
        s0 = min(m, max(2 * s, s + 8))
        inner = OSNAPSketch.draw(k1, s0, m, p=p, dtype=dtype)
        outer = GaussianSketch.draw(k2, s, s0, dtype)
        return ComposedSketch(inner=inner, outer=outer)
    raise ValueError(f"unknown sketch kind {kind!r}; expected one of {SKETCH_KINDS + ('osnap+gaussian',)}")
