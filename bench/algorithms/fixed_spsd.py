"""Fixed-column streaming SPSD as a benchmark job, and its plain reference.

A job is one whole factorization through the library's entry points:
``streaming_spsd_init`` on uniform columns and a sketch pair that the
benchmark draws, then ``stream_panels``, then ``streaming_spsd_finalize``.
The reference recomputes, in plain float32 from the same draw (each sketch
product at the configuration's stated precision, the rest at ``highest``),
``C = K[:, idx]``, ``M = S_1 K S_2^T`` and the PSD-projected core
``(S_1 C)^+ M ((S_2 C)^T)^+`` (copied from ``spsd_reference`` in the
repository's ``chip_smoke.py``).

The numbers of the check: ``C_err``, the largest entry by which the job's C
differs from exact copies; ``M_diff`` and ``X_diff``, the relative Frobenius
distance of its ``M`` and core from the plain ones; ``resid_excess``, its
residual ``||K - C X C^T||_F`` over that of the plain core, less one; and
``gmr_excess``, its residual over that of the exact core ``C^+ K C^+T``,
less one.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

import reference as ref


class Job:
    def __init__(self, cfg: dict, traffic: dict, mesh=None, dtype=jnp.float32):
        if mesh is not None:
            raise ValueError("the fixed-column SPSD job runs on one chip")
        self.cfg, self.dtype = cfg, dtype
        self.n, self.c, self.s, self.panel = cfg["data"]["n"], cfg["c"], cfg["s"], cfg["panel"]
        self.sk = importlib.import_module(f"sketches.{traffic['sketch']}")
        self.cols_per_job = self.n
        self.A = None
        self._draw = jax.jit(self._draw_impl)
        self._reference = jax.jit(self._reference_impl, static_argnames=("low",))
        self._numbers = jax.jit(self._numbers_impl)
        self._healthy = jax.jit(self._healthy_impl)

    def make_data(self, key):
        gen = importlib.import_module(f"datagen.{self.cfg['data']['kind']}")
        self.A = gen.make(key, self.cfg["data"])
        return self.A

    def _draw_impl(self, key):
        k1, k2, k_cols = jax.random.split(key, 3)
        cols = jax.random.choice(k_cols, self.n, (self.c,), replace=False)
        return {"S1": self.sk.draw(k1, self.s, self.n), "S2": self.sk.draw(k2, self.s, self.n),
                "col_idx": jnp.sort(cols).astype(jnp.int32)}

    # -- the timed path ---------------------------------------------------------

    def init(self, key):
        from repro.spsd.streaming import streaming_spsd_init

        inp = self._draw(key)
        sketches = (self.sk.wrap(inp["S1"], self.s), self.sk.wrap(inp["S2"], self.s))
        return streaming_spsd_init(key, self.n, inp["col_idx"], sketches=sketches,
                                   panel=self.panel, dtype=self.dtype)

    def stream(self, state):
        from repro.stream import stream_panels

        return stream_panels(state, self.A, self.panel)

    def finalize(self, state):
        from repro.spsd.streaming import streaming_spsd_finalize

        return streaming_spsd_finalize(state)

    # -- what is kept and checked -------------------------------------------

    def keep(self, state, res) -> dict:
        return {"C": res.C, "X": res.X, "M": state.M, "col_idx": res.col_idx}

    def summary(self, res):
        return res.X, res.col_idx

    def _healthy_impl(self, X, col_idx):
        return jnp.all(jnp.isfinite(X)) & jnp.all((col_idx >= 0) & (col_idx < self.n))

    def healthy(self, summary) -> bool:
        return bool(self._healthy(*summary))

    def _reference_impl(self, K, inp, low: bool):
        """Plain C, M and PSD core on the drawn columns, each sketch product at
        the configuration's stated precision (``sketches/<family>.py``
        ``mul``), the solves at ``highest``; ``low`` computes it all in
        bfloat16 (the control), solving and projecting the core in
        float32 on the bfloat16 sketches and storing it in bfloat16."""
        dt = jnp.bfloat16 if low else jnp.float32
        f32 = jnp.float32
        K_ = K.astype(dt)
        S1 = self.sk.dense(inp["S1"], self.s).astype(dt)
        S2 = self.sk.dense(inp["S2"], self.s).astype(dt)
        C = K_[:, inp["col_idx"]]
        if low:
            with jax.default_matmul_precision(ref.HI):
                M = (S1 @ K_) @ S2.T
                S1C = S1 @ C
                S2C = S2 @ C
        else:
            mul = self.sk.mul
            M = mul(S2, mul(S1, K_).T).T  # (S_1 K) S_2^T
            S1C = mul(S1, C)
            S2C = mul(S2, C)
        with jax.default_matmul_precision(ref.HI):
            X = ref.mm(ref.mm(ref.pinv(S1C), M.astype(f32)), ref.pinv(S2C.astype(f32).T))
            X = 0.5 * (X + X.T)
            w, V = jnp.linalg.eigh(X)
            X = ref.mm(V * jnp.maximum(w, 0.0), V.T).astype(dt)
        out = {k: v.astype(f32) for k, v in dict(C=C, M=M, X=X).items()}
        if not low:
            Cp = ref.pinv(C)
            out["X_exact"] = ref.mm(ref.mm(Cp, K), Cp.T)
        return out

    def _numbers_impl(self, K, got, want):
        return {
            "C_err": ref.max_abs_diff(got["C"], want["C"]),
            "M_diff": ref.rel_diff(got["M"], want["M"]),
            "X_diff": ref.rel_diff(got["X"], want["X"]),
            "resid": ref.rel_residual(K, ref.mm(got["C"], got["X"]), got["C"].T),
            "resid_ref": ref.rel_residual(K, ref.mm(want["C"], want["X"]), want["C"].T),
            "resid_exact": ref.rel_residual(K, ref.mm(want["C"], want["X_exact"]), want["C"].T),
        }

    def compare(self, key, kept: dict, control: bool = False) -> dict:
        """The numbers of the check for the job drawn from ``key``: the job's
        own outputs ``kept`` against the plain reference, or (``control``)
        the reference computed in bfloat16 put in the job's place."""
        inp = self._draw(key)
        want = self._reference(self.A, inp, low=False)
        got = self._reference(self.A, inp, low=True) if control else kept
        out = {k: float(v) for k, v in self._numbers(self.A, got, want).items()}
        resid = out.pop("resid")
        out["resid_excess"] = resid / out.pop("resid_ref") - 1.0
        out["gmr_excess"] = resid / out.pop("resid_exact") - 1.0
        return out
