#!/usr/bin/env python3
"""Bring-up smoke of the main paths on a TPU, through the normal entry points.

  python chip_smoke.py [--seed 0]    # one chip: kernels, three stream routes, serving
  python chip_smoke.py --chips 4     # four chips: data-parallel ingestion only

One chip runs, in order:

* the four Pallas kernels of ``repro.kernels.ops`` at stream widths, each
  compiled to Mosaic (``tpu_custom_call``) and compared with
  ``repro.kernels.ref``;
* a 32768 x 32768 float32 matrix (4 GiB) made on the device from ``--seed``,
  streamed through ``stream_panels`` at panel 512 with c = r = 256 by
  adaptive CUR on CountSketch (the fused scan), adaptive CUR on Gaussian
  sketches (the ``panel_update`` kernel) and fixed-column streaming SPSD on a
  32768-point RBF kernel, each checked against a plain ``jnp.linalg``
  recomputation of its core from the same sketches and chosen indices;
* ``repro.launch.serve.main`` on llama3.2-1b at its published widths with
  random weights, dense KV and rank-16 compressed KV.

Four chips run ``mesh_sharded_stream`` on the same matrix sharded by columns
over the chips, against the one-chip drivers on the same data.

Every phase prints one JSON line of facts: compile and run seconds, the
error against its reference with the tolerance it must meet, and the
device's ``peak_bytes_in_use``. The last line is the result object. Any
failed phase, or a backend that is not a TPU, ends the run with a non-zero
exit code and no result line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# repro modules are imported inside the phases, after main() has set up the
# persistent compile cache: a compile before that would leave it off
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# Stream widths: a 4 GiB float32 matrix, 512-column panels, c = r = 256.
N = 32768
PANEL = 512
BUDGET = 256
# Mosaic kernels lower to this custom call; interpret mode never does.
MOSAIC_MARK = "tpu_custom_call"


class PhaseError(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok, what: str) -> None:
    if not ok:
        raise PhaseError(what)


def report(phase: str, **facts) -> None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    facts["peak_bytes_in_use"] = max((p for p in peaks if p is not None), default=None)
    print(json.dumps({"phase": phase, **facts}), flush=True)


def timed(fn, *args):
    """(result, seconds) with the result's device work finished."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def free(*arrays) -> None:
    for leaf in jax.tree.leaves(arrays):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


def rel(a, b) -> float:
    """‖a − b‖_F / ‖b‖_F in float32."""
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


# ---------------------------------------------------------------------------
# data, made on the device
# ---------------------------------------------------------------------------


def lowrank_matrix(key, m: int, n: int, *, rank: int = 64, noise: float = 0.05,
                   spiked: float = 0.02, sharding=None):
    """``U diag(1/(1+i)) V + diag-scaled noise``: Gaussian factors (no m×m
    QR) plus independent noise, 3σ-heavy in a ``spiked`` share of columns so
    that adaptive admission has directions outside the low-rank span to find.

    Made by two programs, the noise added in place: fused into one, the
    TPU compile takes about two minutes instead of seconds.
    """
    ku, kv, ke, kw = jax.random.split(key, 4)

    def signal(ku, kv):
        sigma = 1.0 / (1.0 + jnp.arange(rank, dtype=jnp.float32))
        U = jax.random.normal(ku, (m, rank), jnp.float32) * sigma
        return U @ jax.random.normal(kv, (rank, n), jnp.float32)

    def add_noise(A, ke, kw):
        scale = noise + jnp.where(jax.random.uniform(kw, (n,)) < spiked, 3.0, 0.0)
        return A + scale * jax.random.normal(ke, (m, n), jnp.float32)

    A = jax.jit(signal, out_shardings=sharding)(ku, kv)
    return jax.jit(add_noise, out_shardings=sharding, donate_argnums=0)(A, ke, kw)


def rbf_kernel(key, n: int, *, d: int = 32):
    """RBF kernel of n Gaussian points in d dimensions with σ² = d/2 (the
    median squared distance is about 2d): 256 uniform columns keep 99.7% of
    the spectral energy at condition number ~4e2, so the core solve is
    well posed in float32."""
    def gen(key):
        X = jax.random.normal(key, (n, d), jnp.float32)
        sq = jnp.sum(X * X, axis=1)
        G = jnp.matmul(X, X.T, precision="highest")
        D2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * G, 0.0)
        return jnp.exp(-D2 / d)

    return jax.jit(gen)(key)


# ---------------------------------------------------------------------------
# plain float32 references (jnp.linalg only)
# ---------------------------------------------------------------------------

# Pseudo-inverse cutoff of the references: about the floor of the system's
# floored least-squares solve at c = 256 (float32 eps x c), far below the
# smallest singular value of well-posed inputs, so pinv is the exact inverse.
RTOL = 1e-5


def _rel_residual(A, C, U, R, blocks: int = 8):
    """‖A − C U R‖_F / ‖A‖_F, accumulated over row blocks (never holds CUR)."""
    m = A.shape[0]
    bs = m // blocks
    CU = jnp.matmul(C, U, precision="highest")

    def one(i):
        rows = jax.lax.dynamic_slice_in_dim(A, i * bs, bs, axis=0)
        Li = jax.lax.dynamic_slice_in_dim(CU, i * bs, bs, axis=0)
        E = rows - jnp.matmul(Li, R, precision="highest")
        return jnp.sum(E * E), jnp.sum(rows * rows)

    err, tot = jax.lax.map(one, jnp.arange(blocks))
    return jnp.sqrt(jnp.sum(err) / jnp.sum(tot))


def rel_residual(A, C, U, R) -> float:
    return float(jax.jit(_rel_residual)(A, C, U, R))


def cur_reference(A, S_C, S_R, col_idx, row_idx):
    """Plain float32 core ``(S_C C)† (S_C A S_Rᵀ) (R S_Rᵀ)†`` and the exact
    core ``C† A R†`` on the stream's indices; unfilled slots (−1) are zero."""
    hi = "highest"
    filled = col_idx >= 0
    C = jnp.where(filled[None, :], A[:, jnp.clip(col_idx, 0)], 0.0)
    R = A[row_idx, :]
    ScA = jnp.matmul(S_C, A, precision=hi)
    M = jnp.matmul(ScA, S_R.T, precision=hi)
    U = jnp.linalg.pinv(jnp.matmul(S_C, C, precision=hi), rtol=RTOL) @ M
    U = jnp.matmul(U, jnp.linalg.pinv(jnp.matmul(R, S_R.T, precision=hi), rtol=RTOL), precision=hi)
    U = jnp.where(filled[:, None], U, 0.0)
    CtA = jnp.matmul(jnp.linalg.pinv(C, rtol=RTOL), A, precision=hi)
    U_exact = jnp.matmul(CtA, jnp.linalg.pinv(R, rtol=RTOL), precision=hi)
    return C, R, U, U_exact


def spsd_reference(K, S1, S2, col_idx):
    """Plain float32 symmetric core ``Π₊((S₁C)† S₁KS₂ᵀ (CᵀS₂ᵀ)†)`` and the
    exact core ``C† K C†ᵀ``."""
    hi = "highest"
    C = K[:, col_idx]
    M = jnp.matmul(jnp.matmul(S1, K, precision=hi), S2.T, precision=hi)
    X = jnp.linalg.pinv(jnp.matmul(S1, C, precision=hi), rtol=RTOL) @ M
    X = jnp.matmul(X, jnp.linalg.pinv(jnp.matmul(S2, C, precision=hi).T, rtol=RTOL), precision=hi)
    X = 0.5 * (X + X.T)
    w, V = jnp.linalg.eigh(X)
    X = jnp.matmul(V * jnp.maximum(w, 0.0), V.T, precision=hi)
    Cp = jnp.linalg.pinv(C, rtol=RTOL)
    X_exact = jnp.matmul(jnp.matmul(Cp, K, precision=hi), Cp.T, precision=hi)
    return C, X, X_exact


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def kernels_phase(key, *, m: int = N, L: int = PANEL, s_c: int = 3840, c: int = BUDGET):
    """The four ``ops`` wrappers on the chip against ``kernels/ref.py``."""
    from repro.kernels import ops, ref

    ks = jax.random.split(key, 8)
    f32 = jnp.float32
    sc = jax.random.normal(ks[0], (s_c, m), f32)
    a = jax.random.normal(ks[1], (m, L), f32)
    srt = jax.random.normal(ks[2], (L, s_c), f32)
    hashes = jax.random.randint(ks[3], (m,), 0, s_c)
    signs = jax.random.rademacher(ks[4], (m,), f32)
    filled = c // 2
    Q, _ = jnp.linalg.qr(jax.random.normal(ks[5], (s_c, c), f32))
    q = Q * (jnp.arange(c) < filled)
    # eight spiked columns with well-separated scores: the admitted set and
    # its slot order must not hinge on the last bits of a residual
    spikes = jnp.arange(8) * (L // 8) + 3
    a_spiked = a.at[:, spikes].multiply(10.0 + 2.0 * jnp.arange(8))
    C = jax.random.normal(ks[6], (m, c), f32) * (jnp.arange(c) < filled)
    M = jax.random.normal(ks[7], (s_c, s_c), f32)
    adm = dict(min_gain=4.0, run_mean=0.0, true_cols=float(L), n_filled=filled,
               free=c - filled)

    def update(sc, a_l, srt, q, C, M):
        return ops.panel_update(sc, a_l, srt, q, C, M, panel_cap=max(1, c // 8), **adm)

    def update_ref(sc, a_l, srt, q, C, M):
        return ref.panel_update_ref(sc, a_l, srt, q, C, M, panel_cap=max(1, c // 8), **adm)

    cases = {
        "twoside_sketch": (ops.twoside_sketch, ref.twoside_sketch_ref, (sc, a, srt)),
        "countsketch_apply": (
            lambda h, g, x: ops.countsketch_apply(h, g, x, s_c),
            lambda h, g, x: ref.countsketch_ref(h, g, x, s_c),
            (hashes, signs, a),
        ),
        "panel_score": (ops.panel_score, ref.panel_score_ref, (sc, a, q)),
        "panel_update": (update, update_ref, (sc, a_spiked, srt, q, C, M)),
    }
    tol = 1e-2  # float32 operands through the MXU's bf16 passes
    for name, (fn, ref_fn, args) in cases.items():
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        check(MOSAIC_MARK in compiled.as_text(), f"{name}: no Mosaic kernel in the program")
        out, run_s = timed(compiled, *args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref_fn)(*args)
        outs = out if isinstance(out, (tuple, list)) else (out,)
        wants = want if isinstance(want, (tuple, list)) else (want,)
        errs = {}
        for i, (g, w) in enumerate(zip(outs, wants)):
            if jnp.issubdtype(w.dtype, jnp.integer):
                errs[f"out{i}_mismatches"] = int(jnp.sum(g != w))
            else:
                scale = float(jnp.max(jnp.abs(w))) + 1e-30
                errs[f"out{i}_max_err"] = float(jnp.max(jnp.abs(g - w))) / scale
        if name == "panel_update":
            errs["admitted"] = int(jnp.sum(outs[5] < c))
            check(errs["admitted"] == 8, f"{name}: admitted {errs['admitted']} of 8 spikes")
            check(errs["out0_max_err"] <= 1e-6, f"{name}: C copies are not exact")
        worst = max((v for k, v in errs.items() if k.endswith("max_err")), default=0.0)
        mismatches = sum(v for k, v in errs.items() if k.endswith("mismatches"))
        report(f"kernel/{name}", compile_s=compile_s, run_s=run_s, tol=tol, **errs)
        check(worst <= tol and mismatches == 0, f"{name}: error {worst} (tol {tol}), "
              f"{mismatches} integer mismatches")
        free(out, want)
    free(sc, a, srt, hashes, signs, q, a_spiked, C, M, Q)


def _stream_twice(init, A, panel):
    """First call (compile + run) and a warm call of ``stream_panels``."""
    from repro.stream import stream_panels

    state, first_s = timed(lambda: stream_panels(init(), A, panel))
    free(state)
    state0 = jax.block_until_ready(init())
    state, warm_s = timed(stream_panels, state0, A, panel)
    return state, first_s, warm_s


def _route_has_kernel(init, A, panel) -> bool:
    """Does the compiled whole-stream program launch a Mosaic kernel?"""
    from repro.stream.engine import scan_panels

    lowered = jax.jit(scan_panels, static_argnames=("num_panels", "panel")).lower(
        init(), A, num_panels=A.shape[1] // panel, panel=panel)
    return MOSAIC_MARK in lowered.as_text()


def stream_cur_phase(name, A, key, *, sketch: str, panel: int = PANEL, c: int = BUDGET,
                     r: int = BUDGET, tol: float = 1e-2):
    """Adaptive streaming CUR through ``stream_panels`` against the plain core."""
    from repro.stream.adaptive import adaptive_cur_finalize, adaptive_cur_init

    m, n = A.shape
    k_init, k_rows = jax.random.split(key)
    row_idx = jnp.sort(jax.random.choice(k_rows, m, (r,), replace=False)).astype(jnp.int32)

    def init():
        return adaptive_cur_init(k_init, m, n, c, row_idx, sketch=sketch, panel=panel)

    on_tpu = jax.default_backend() == "tpu"
    kernel = _route_has_kernel(init, A, panel)
    # on a TPU every route launches a kernel: Gaussian admission-only streams
    # the panel_update kernel, CountSketch streams (the fused scan) the
    # countsketch kernel
    check(kernel == on_tpu,
          f"{name}: kernel launched={kernel} on {jax.default_backend()}")
    state, first_s, warm_s = _stream_twice(init, A, panel)
    res = jax.block_until_ready(adaptive_cur_finalize(state))
    S_C = state.ctx.S_C.materialize()
    S_R = state.ctx.S_R.materialize()[:, :n]
    C_ref, R_ref, U_ref, U_exact = jax.jit(cur_reference)(A, S_C, S_R, res.col_idx, res.row_idx)
    err = rel_residual(A, res.C, res.U, res.R)
    err_ref = rel_residual(A, C_ref, U_ref, R_ref)
    err_exact = rel_residual(A, C_ref, U_exact, R_ref)
    facts = dict(
        compile_s=first_s - warm_s, first_s=first_s, run_s=warm_s,
        cols_per_s=n / warm_s, kernel=kernel,
        filled=int(jnp.sum(res.col_idx >= 0)),
        C_max_err=float(jnp.max(jnp.abs(res.C - C_ref))),
        R_max_err=float(jnp.max(jnp.abs(res.R - R_ref))),
        U_rel_diff=rel(res.U, U_ref),
        err=err, err_ref=err_ref, err_exact=err_exact, tol=tol,
    )
    report(f"stream/{name}", **facts)
    check(facts["C_max_err"] == 0.0 and facts["R_max_err"] == 0.0,
          f"{name}: C/R are not exact copies of the chosen columns/rows")
    check(facts["U_rel_diff"] <= tol,
          f"{name}: core differs from the plain core by {facts['U_rel_diff']} (tol {tol})")
    # one-sided: the stream accumulates its sketches at the backend's default
    # matmul precision, and that can land its core nearer the exact C† A R†
    # than the plain float32 one — a better residual is not a fault
    check(err <= (1 + tol) * err_ref,
          f"{name}: residual {err} exceeds the plain-core residual {err_ref} (tol {tol})")
    free(state, res, S_C, S_R, C_ref, R_ref, U_ref, U_exact)


def stream_spsd_phase(K, key, *, panel: int = PANEL, c: int = BUDGET, tol: float = 1e-2):
    """Fixed-column streaming SPSD through ``stream_panels`` against the plain core."""
    from repro.spsd.streaming import streaming_spsd_finalize, streaming_spsd_init

    n = K.shape[0]
    k_init, k_cols = jax.random.split(key)
    col_idx = jnp.sort(jax.random.choice(k_cols, n, (c,), replace=False)).astype(jnp.int32)

    def init():
        return streaming_spsd_init(k_init, n, col_idx, panel=panel)

    state, first_s, warm_s = _stream_twice(init, K, panel)
    res = jax.block_until_ready(streaming_spsd_finalize(state))
    S1 = state.ctx.S1.materialize()
    S2 = state.ctx.S2.materialize()[:, :n]
    C_ref, X_ref, X_exact = jax.jit(spsd_reference)(K, S1, S2, col_idx)
    err = rel_residual(K, res.C, res.X, res.C.T)
    err_ref = rel_residual(K, C_ref, X_ref, C_ref.T)
    err_exact = rel_residual(K, C_ref, X_exact, C_ref.T)
    facts = dict(
        compile_s=first_s - warm_s, first_s=first_s, run_s=warm_s, cols_per_s=n / warm_s,
        C_max_err=float(jnp.max(jnp.abs(res.C - C_ref))),
        X_rel_diff=rel(res.X, X_ref),
        err=err, err_ref=err_ref, err_exact=err_exact, tol=tol,
    )
    report("stream/spsd_fixed_rbf", **facts)
    check(facts["C_max_err"] == 0.0, "spsd: C is not an exact copy of the chosen columns")
    check(facts["X_rel_diff"] <= tol,
          f"spsd: core differs from the plain core by {facts['X_rel_diff']} (tol {tol})")
    check(err <= (1 + tol) * err_ref,
          f"spsd: residual {err} exceeds the plain-core residual {err_ref} (tol {tol})")
    free(state, res, S1, S2, C_ref, X_ref, X_exact)


def serve_phase(argv, *, vocab: int):
    """``launch/serve.py``'s ``main`` with dense and rank-16 compressed KV."""
    from repro.launch import serve

    outs = {}
    for mode, extra in (("dense", []), ("kv16", ["--kv-compress", "16"])):
        out, first_s = timed(serve.main, argv + extra)
        out, warm_s = timed(serve.main, argv + extra)
        valid = bool(jnp.all((out >= 0) & (out < vocab)))
        report(f"serve/{mode}", compile_s=first_s - warm_s, first_s=first_s, run_s=warm_s,
               shape=list(out.shape), valid_ids=valid)
        check(valid, f"serve/{mode}: token outside [0, {vocab}) — a non-finite logit row")
        outs[mode] = out
    same_first = bool(jnp.all(outs["dense"][:, 0] == outs["kv16"][:, 0]))
    agree = float(jnp.mean(outs["dense"] == outs["kv16"]))
    report("serve/dense_vs_kv16", first_token_equal=same_first, token_agreement=agree)
    check(same_first, "serve: first token differs between dense and compressed KV")


def sharded_phase(key, *, m: int = N, n: int = N, panel: int = PANEL, c: int = BUDGET,
                  r: int = BUDGET, chips: int = 4, tol: float = 1e-4):
    """``mesh_sharded_stream`` over ``chips`` against the one-chip drivers."""
    from repro.cur.streaming import streaming_cur_init
    from repro.stream import mesh_sharded_stream, simulate_sharded_stream, stream_panels
    from repro.stream.adaptive import adaptive_cur_init

    mesh = Mesh(np.array(jax.devices()[:chips]), ("data",))
    k_a, k_init, k_cols, k_rows = jax.random.split(key, 4)
    A = lowrank_matrix(k_a, m, n, sharding=NamedSharding(mesh, P(None, "data")))
    shard_cols = {s.data.shape[1] for s in A.addressable_shards}
    check(shard_cols == {n // chips}, f"A is not column-sharded: {shard_cols}")
    row_idx = jnp.sort(jax.random.choice(k_rows, m, (r,), replace=False)).astype(jnp.int32)
    col_idx = jnp.sort(jax.random.choice(k_cols, n, (c,), replace=False)).astype(jnp.int32)

    def adaptive():
        return adaptive_cur_init(k_init, m, n, c, row_idx, sketch="countsketch", panel=panel)

    def fixed():
        return streaming_cur_init(k_init, m, n, col_idx, row_idx, panel=panel)

    got = {}
    for name, init in (("adaptive", adaptive), ("fixed", fixed)):
        st, first_s = timed(mesh_sharded_stream, init(), A, panel, mesh)
        free(st)
        st, warm_s = timed(mesh_sharded_stream, init(), A, panel, mesh)
        got[name] = (st, first_s, warm_s)
    # one-chip references on the same data, gathered onto chip 0
    A1 = jax.device_put(A, jax.devices()[0])
    free(A)
    want = {
        "adaptive": timed(simulate_sharded_stream, adaptive(), A1, panel, chips),
        "fixed": timed(stream_panels, fixed(), A1, panel),
    }
    for name in ("adaptive", "fixed"):
        st, first_s, warm_s = got[name]
        st = jax.device_put(st, jax.devices()[0])  # replicated result → chip 0
        ref, ref_s = want[name]
        facts = dict(compile_s=first_s - warm_s, first_s=first_s, run_s=warm_s,
                     cols_per_s=n / warm_s, one_chip_first_s=ref_s,
                     col_idx_equal=bool(jnp.all(st.ctx.col_idx == ref.ctx.col_idx)),
                     C_max_err=float(jnp.max(jnp.abs(st.C - ref.C))),
                     R_max_err=float(jnp.max(jnp.abs(st.R - ref.R))),
                     M_rel_diff=rel(st.M, ref.M), tol=tol)
        report(f"sharded/{name}_w{chips}", **facts)
        check(facts["col_idx_equal"], f"sharded/{name}: chosen columns differ")
        check(facts["C_max_err"] == 0.0 and facts["R_max_err"] == 0.0,
              f"sharded/{name}: C/R differ from the one-chip run")
        check(facts["M_rel_diff"] <= tol, f"sharded/{name}: M differs by "
              f"{facts['M_rel_diff']} (tol {tol})")
    free(A1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel ingestion phase on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (backend {devices[0].platform}); refusing to run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} devices", file=sys.stderr)
        return 2
    print(json.dumps({"phase": "start", "cache_dir": cache, "jax": jax.__version__,
                      "kind": devices[0].device_kind, "devices": len(devices)}), flush=True)

    t0 = time.perf_counter()
    key = jax.random.key(args.seed)
    k_kern, k_a, k_cs, k_g, k_k, k_spsd, k_sh = jax.random.split(key, 7)
    try:
        if args.chips == 4:
            sharded_phase(k_sh, chips=4)
        else:
            kernels_phase(k_kern)
            A = lowrank_matrix(k_a, N, N)
            stream_cur_phase("adaptive_countsketch", A, k_cs, sketch="countsketch")
            stream_cur_phase("adaptive_gaussian", A, k_g, sketch="gaussian")
            free(A)
            K = rbf_kernel(k_k, N)
            stream_spsd_phase(K, k_spsd)
            free(K)
            serve_phase(["--arch", "llama3.2-1b", "--batch", "8", "--prompt-len", "1024",
                         "--gen", "32", "--mesh", "1x1", "--seed", str(args.seed)],
                        vocab=128256)
    except Exception:  # noqa: BLE001 — any phase failure fails the smoke
        traceback.print_exc()
        return 1
    report("total", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {"platform": devices[0].platform,
                                             "kind": devices[0].device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
