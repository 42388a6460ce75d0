"""Adaptive streaming CUR as a benchmark job, and its plain reference.

A job is one whole factorization through the library's entry points:
``adaptive_cur_init`` on sketches and fixed rows that the benchmark draws,
then ``stream_panels`` (one chip) or ``mesh_sharded_stream`` (a mesh), then
``adaptive_cur_finalize``. The reference recomputes, in plain float32 and
from the same draw, the columns and rows the job chose, the core sketch
``M = S_C A S_R^T``, the sketched core ``(S_C C)^+ M (R S_R^T)^+`` and the
exact core ``C^+ A R^+`` (copied from ``cur_reference`` in the repository's
``chip_smoke.py``, extended to a column-sharded ``A``): each sketch product
at the precision the configuration states, every other product and solve
at ``highest``. It checks the numbers of the chosen columns and rows, not
which ones were chosen.

The numbers of the check: ``C_err`` and ``R_err``, the largest entry by which
the job's C and R differ from exact copies; ``M_diff`` and ``U_diff``, the
relative Frobenius distance of its ``M`` and core from the plain ones;
``gmr_excess``, its residual ``||A - C U R||_F`` over that of the exact core,
less one (Theorem 1 bounds it by the configuration's ``eps``); and
``resid_excess``, its residual over that of the plain sketched core, less
one.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import reference as ref

AXIS = "data"


def take_cols(A, idx, mesh):
    """``A[:, idx]`` with ``-1`` slots zero; on a mesh each chip copies the
    columns it holds and one ``psum`` of exact copies and zeros joins them."""
    if mesh is None:
        return jnp.where((idx >= 0)[None, :], A[:, jnp.clip(idx, 0)], 0)

    def local(a, idx):
        nl = a.shape[1]
        rel = idx - jax.lax.axis_index(AXIS) * nl
        here = (rel >= 0) & (rel < nl) & (idx >= 0)
        got = jnp.where(here[None, :], a[:, jnp.clip(rel, 0, nl - 1)], 0)
        return jax.lax.psum(got, AXIS)

    return jax.shard_map(local, mesh=mesh, in_specs=(P(None, AXIS), P()), out_specs=P(),
                         check_vma=False)(A, idx)


class Job:
    def __init__(self, cfg: dict, traffic: dict, mesh=None, dtype=jnp.float32):
        d = cfg["data"]
        self.cfg, self.mesh, self.dtype = cfg, mesh, dtype
        self.m, self.n = d["m"], d["n"]
        self.c, self.r, self.panel = cfg["c"], cfg["r"], cfg["panel"]
        self.s_c, self.s_r = cfg["s_c"], cfg["s_r"]
        self.sk = importlib.import_module(f"sketches.{traffic['sketch']}")
        self.cols_per_job = self.n
        self.A = None
        rep = NamedSharding(mesh, P()) if mesh is not None else None
        self._draw = jax.jit(self._draw_impl, out_shardings=rep)
        self._reference = jax.jit(self._reference_impl, static_argnames=("low",))
        self._numbers = jax.jit(self._numbers_impl)
        self._healthy = jax.jit(self._healthy_impl)

    # -- data and inputs ----------------------------------------------------

    def make_data(self, key):
        gen = importlib.import_module(f"datagen.{self.cfg['data']['kind']}")
        sharding = NamedSharding(self.mesh, P(None, AXIS)) if self.mesh is not None else None
        self.A = gen.make(key, self.cfg["data"], sharding)
        return self.A

    def _draw_impl(self, key):
        k_c, k_r, k_rows = jax.random.split(key, 3)
        rows = jax.random.choice(k_rows, self.m, (self.r,), replace=False)
        return {"S_C": self.sk.draw(k_c, self.s_c, self.m),
                "S_R": self.sk.draw(k_r, self.s_r, self.n),
                "row_idx": jnp.sort(rows).astype(jnp.int32)}

    # -- the timed path ---------------------------------------------------------

    def init(self, key):
        from repro.stream.adaptive import adaptive_cur_init

        inp = self._draw(key)
        sketches = (self.sk.wrap(inp["S_C"], self.s_c), self.sk.wrap(inp["S_R"], self.s_r))
        return adaptive_cur_init(key, self.m, self.n, self.c, inp["row_idx"], sketches=sketches,
                                 panel=self.panel, min_gain=self.cfg["min_gain"],
                                 panel_cap=self.cfg["panel_cap"], dtype=self.dtype)

    def stream(self, state):
        if self.mesh is None:
            from repro.stream import stream_panels

            return stream_panels(state, self.A, self.panel)
        from repro.stream import mesh_sharded_stream

        return mesh_sharded_stream(state, self.A, self.panel, self.mesh, axis=AXIS)

    def finalize(self, state):
        from repro.stream.adaptive import adaptive_cur_finalize

        return adaptive_cur_finalize(state)

    # -- what is kept and checked -------------------------------------------

    def keep(self, state, res) -> dict:
        """What the check reads of a job: its factors, indices and ``M``."""
        return {"C": res.C, "R": res.R, "U": res.U, "M": state.M,
                "col_idx": res.col_idx, "row_idx": res.row_idx}

    def summary(self, res):
        """What is kept of every job for :meth:`healthy` (small)."""
        return res.U, res.col_idx

    def _healthy_impl(self, U, col_idx):
        filled = col_idx >= 0
        return (jnp.all(jnp.isfinite(U)) & jnp.all(col_idx < self.n) & jnp.any(filled))

    def healthy(self, summary) -> bool:
        return bool(self._healthy(*summary))

    def _reference_impl(self, A, inp, col_idx, row_idx, low: bool):
        """Plain C, R, M and core on the job's indices, each sketch product
        at the configuration's stated precision (``sketches/<family>.py``
        ``mul``), the solves at ``highest``; ``low`` computes it all in
        bfloat16 (the control), solving the core in float32 on the bfloat16
        sketches and storing it in bfloat16."""
        dt = jnp.bfloat16 if low else jnp.float32
        f32 = jnp.float32
        A_ = A.astype(dt)
        Sc = self.sk.dense(inp["S_C"], self.s_c).astype(dt)
        Sr = self.sk.dense(inp["S_R"], self.s_r)
        if self.mesh is not None:
            Sr = jax.lax.with_sharding_constraint(Sr, NamedSharding(self.mesh, P(None, AXIS)))
        Sr = Sr.astype(dt)
        C = take_cols(A_, col_idx, self.mesh)
        R = A_[row_idx, :]
        if low:
            with jax.default_matmul_precision(ref.HI):
                M = (Sc @ A_) @ Sr.T
                ScC = Sc @ C
                RSr = R @ Sr.T
        else:
            mul = self.sk.mul
            M = mul(Sr, mul(Sc, A_).T).T  # (S_C A) S_R^T
            ScC = mul(Sc, C)
            RSr = mul(Sr, R.T).T
        U = ref.mm(ref.mm(ref.pinv(ScC), M.astype(f32)), ref.pinv(RSr))
        U = jnp.where((col_idx >= 0)[:, None], U, 0.0).astype(dt)
        out = {k: v.astype(f32) for k, v in dict(C=C, R=R, M=M, U=U).items()}
        if not low:
            out["U_exact"] = ref.mm(ref.mm(ref.pinv(C), A), ref.pinv(R))
        return out

    def _numbers_impl(self, A, got, want):
        return {
            "C_err": ref.max_abs_diff(got["C"], want["C"]),
            "R_err": ref.max_abs_diff(got["R"], want["R"]),
            "M_diff": ref.rel_diff(got["M"], want["M"]),
            "U_diff": ref.rel_diff(got["U"], want["U"]),
            "resid": ref.rel_residual(A, ref.mm(got["C"], got["U"]), got["R"]),
            "resid_ref": ref.rel_residual(A, ref.mm(want["C"], want["U"]), want["R"]),
            "resid_exact": ref.rel_residual(A, ref.mm(want["C"], want["U_exact"]), want["R"]),
        }

    def compare(self, key, kept: dict, control: bool = False) -> dict:
        """The numbers of the check for the job drawn from ``key``: the job's
        own outputs ``kept`` against the plain reference, or (``control``)
        the reference computed in bfloat16 put in the job's place."""
        inp = self._draw(key)
        want = self._reference(self.A, inp, kept["col_idx"], kept["row_idx"], low=False)
        got = kept
        if control:
            got = self._reference(self.A, inp, kept["col_idx"], kept["row_idx"], low=True)
        out = {k: float(v) for k, v in self._numbers(self.A, got, want).items()}
        resid = out.pop("resid")
        out["resid_excess"] = resid / out.pop("resid_ref") - 1.0
        out["gmr_excess"] = resid / out.pop("resid_exact") - 1.0
        return out
