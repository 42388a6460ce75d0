"""Single-pass SVD (Algorithm 3) as a benchmark job, and its plain reference.

A job is one whole factorization through the library's entry points:
``spsvd_engine_init`` on the six operators of Algorithm 3 step 3 that the
benchmark draws (``Psi``, ``Omega``, ``S_C`` and ``S_R`` of the traffic's
sketch family, ``G_C`` and ``G_R`` Gaussian), then ``stream_panels``, then
``spsvd_engine_finalize``. The reference rebuilds, in plain float32 from
the same draw, ``C = (A Omega^T) G_C^T``, ``R = G_R (Psi A)`` and
``M = S_C A S_R^T``, each sketch product at the precision the configuration
states (``sketches/<family>.py`` ``mul``), and then Algorithm 3 steps 10-13:
the bases ``Q_C``, ``Q_R`` of ``C`` and ``R^T``, the core
``N = (S_C Q_C)^+ M (Q_R^T S_R^T)^+`` and its SVD, every QR and solve at
``highest``.

The numbers of the check: ``C_diff``, ``R_diff`` and ``M_diff``, the
relative Frobenius distance of the job's C, R and M from the plain ones;
``resid_excess``, its residual ``||A - U diag(S) V^T||_F`` over the
reference's, less one; and ``gmr_excess``, its residual over that of the
exact core on the reference's bases, ``||A - Q_C (Q_C^T A Q_R) Q_R^T||_F``,
less one (the GMR core's bound is the configuration's ``eps``). The
factors themselves are not compared: their signs and rotations are not
unique. ``||A - A_k||_F`` is not computed (a full SVD of ``A``).
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

import reference as ref


class Job:
    def __init__(self, cfg: dict, traffic: dict, mesh=None, dtype=jnp.float32):
        if mesh is not None:
            raise ValueError("the single-pass SVD job runs on one chip")
        self.cfg, self.dtype = cfg, dtype
        self.m, self.n, self.panel = cfg["data"]["m"], cfg["data"]["n"], cfg["panel"]
        self.p = cfg["osnap_p"]
        self.sizes = {k: cfg[k] for k in ("c", "r", "c0", "r0", "s_c", "s_r")}
        self.sk = importlib.import_module(f"sketches.{traffic['sketch']}")
        self.gauss = importlib.import_module("sketches.gaussian")
        z = self.sizes
        # operator -> (rows, source dim, Gaussian?)
        self.operators = {
            "psi": (z["r0"], self.m, False), "g_r": (z["r"], z["r0"], True),
            "omega": (z["c0"], self.n, False), "g_c": (z["c"], z["c0"], True),
            "s_c": (z["s_c"], self.m, False), "s_r": (z["s_r"], self.n, False),
        }
        self.cols_per_job = self.n
        self.A = None
        self._draw = jax.jit(self._draw_impl)
        self._reference = jax.jit(self._reference_impl, static_argnames=("low",))
        self._numbers = jax.jit(self._numbers_impl)
        self._healthy = jax.jit(self._healthy_impl)

    def _family(self, name: str):
        return self.gauss if self.operators[name][2] else self.sk

    # -- data and inputs ----------------------------------------------------

    def make_data(self, key):
        gen = importlib.import_module(f"datagen.{self.cfg['data']['kind']}")
        self.A = gen.make(key, self.cfg["data"])
        return self.A

    def _draw_impl(self, key):
        keys = jax.random.split(key, len(self.operators))
        out = {}
        for k, (name, (rows, cols, gaussian)) in zip(keys, self.operators.items()):
            out[name] = (self.gauss.draw(k, rows, cols) if gaussian
                         else self.sk.draw(k, rows, cols, self.p))
        return out

    # -- the timed path ---------------------------------------------------------

    def init(self, key):
        from repro.core.svd import SPSVDSketches, spsvd_engine_init

        inp = self._draw(key)
        sketches = SPSVDSketches(**{name: self._family(name).wrap(inp[name], rows)
                                    for name, (rows, _, _) in self.operators.items()})
        return spsvd_engine_init(key, self.m, self.n, sizes=self.sizes, dtype=self.dtype,
                                 osnap_p=self.p, panel=self.panel, sketches=sketches)

    def stream(self, state):
        from repro.stream import stream_panels

        return stream_panels(state, self.A, self.panel)

    def finalize(self, state):
        from repro.core.svd import spsvd_engine_finalize

        return spsvd_engine_finalize(state, k=self.cfg["fixed_rank"])

    # -- what is kept and checked -------------------------------------------

    def keep(self, state, res) -> dict:
        """What the check reads of a job: its accumulators and factors."""
        U, S, V = res
        return {"C": state.C, "R": state.R, "M": state.M, "U": U, "S": S, "V": V}

    def summary(self, res):
        """What is kept of every job for :meth:`healthy`: the singular values."""
        return res[1]

    def _healthy_impl(self, S):
        return jnp.all(jnp.isfinite(S)) & (S[0] > 0)

    def healthy(self, summary) -> bool:
        return bool(self._healthy(summary))

    def _reference_impl(self, A, inp, low: bool):
        """Plain C, R, M and Algorithm 3 steps 10-13 on them, each sketch
        product at the configuration's stated precision
        (``sketches/<family>.py`` ``mul``), every QR and solve at
        ``highest``; ``low`` computes the sketch products in bfloat16 (the
        control), the bases and the core in float32 on them, and stores the
        factors in bfloat16."""
        dt = jnp.bfloat16 if low else jnp.float32
        f32 = jnp.float32
        A_ = A.astype(dt)
        D = {name: self._family(name).dense(inp[name], rows).astype(dt)
             for name, (rows, _, _) in self.operators.items()}
        if low:
            def mul(S, X):
                return jnp.matmul(S, X, precision=ref.HI)

            gmul = mul
        else:
            mul, gmul = self.sk.mul, self.gauss.mul
        C = gmul(D["g_c"], mul(D["omega"], A_.T)).T  # (A Omega^T) G_C^T
        R = gmul(D["g_r"], mul(D["psi"], A_))  # G_R (Psi A)
        M = mul(D["s_r"], mul(D["s_c"], A_).T).T  # (S_C A) S_R^T
        with jax.default_matmul_precision(ref.HI):
            Q_C, _ = jnp.linalg.qr(C.astype(f32))
            Q_R, _ = jnp.linalg.qr(R.astype(f32).T)
        ScQ = mul(D["s_c"], Q_C.astype(dt))
        SrQ = mul(D["s_r"], Q_R.astype(dt))
        N = ref.mm(ref.mm(ref.pinv(ScQ), M.astype(f32)), ref.pinv(SrQ).T)
        with jax.default_matmul_precision(ref.HI):
            U_N, S, V_Nt = jnp.linalg.svd(N, full_matrices=False)
        U, V = ref.mm(Q_C, U_N), ref.mm(Q_R, V_Nt.T)
        out = {k: v.astype(f32) for k, v in dict(C=C, R=R, M=M).items()}
        out.update(U=U.astype(dt), S=S.astype(dt), V=V.astype(dt))
        if not low:
            out.update(Q_C=Q_C, Q_R=Q_R, N_exact=ref.mm(ref.mm(Q_C.T, A), Q_R))
        return out

    def _numbers_impl(self, A, got, want):
        f32 = jnp.float32

        def resid(U, S, V):
            return ref.rel_residual(A, U.astype(f32) * S.astype(f32)[None, :], V.astype(f32).T)

        return {
            "C_diff": ref.rel_diff(got["C"], want["C"]),
            "R_diff": ref.rel_diff(got["R"][:, : self.n], want["R"]),
            "M_diff": ref.rel_diff(got["M"], want["M"]),
            "resid": resid(got["U"], got["S"], got["V"]),
            "resid_ref": resid(want["U"], want["S"], want["V"]),
            "resid_exact": ref.rel_residual(A, ref.mm(want["Q_C"], want["N_exact"]),
                                            want["Q_R"].T),
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
