"""End-to-end training driver (CPU-host scale; a larger mesh runs the same
code).

Example — the ~100M run used by examples/train_lm.py:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.train \\
      --arch llama3.2-1b --d-model 512 --layers 12 --heads 8 --kv-heads 4 \\
      --d-ff 2048 --vocab 8192 --batch 16 --seq 256 --steps 200 \\
      --mesh 4x2 [--grad-compress --compress-rank 32]
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding

from repro.checkpoint import run_resilient_loop
from repro.configs import get_arch
from repro.data import DataConfig, SyntheticLM
from repro.distributed.sharding import (
    ParallelismRules,
    activation_sharding,
    batch_pspec,
    param_shardings,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params, param_count
from repro.train import (
    CompressionConfig,
    OptimizerConfig,
    compression_ratio,
    init_opt_state,
    make_compressed_train_step,
    make_train_step,
)


def build_config(args):
    cfg = get_arch(args.arch).smoke_config() if args.smoke else get_arch(args.arch).full_config()
    over = {}
    if args.d_model:
        over.update(d_model=args.d_model, d_ff=args.d_ff or 4 * args.d_model)
    if args.layers:
        mod = get_arch(args.arch)
        base = mod.full_config()
        # rebuild the pattern at the requested depth with the same block mix
        unit = base.pattern[: max(1, len(base.pattern) // base.n_layers)]
        reps = base.pattern * ((args.layers // len(base.pattern)) + 1)
        over.update(n_layers=args.layers, pattern=tuple(reps[: args.layers]))
    if args.heads:
        over.update(n_heads=args.heads)
    if args.kv_heads:
        over.update(n_kv_heads=args.kv_heads)
    if args.head_dim:
        over.update(head_dim=args.head_dim)
    if args.vocab:
        over.update(vocab_size=args.vocab)
    if args.dtype:
        over.update(dtype=args.dtype)
    if over:
        cfg = dataclasses.replace(cfg, **over)
    return cfg


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--d-ff", type=int, default=0)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--heads", type=int, default=0)
    ap.add_argument("--kv-heads", type=int, default=0)
    ap.add_argument("--head-dim", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=0)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh", default=None, help="dataxmodel, e.g. 4x2 (default: Nx1)")
    ap.add_argument("--remat", default="dots", choices=["dots", "full", "none"])
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--compress-rank", type=int, default=32)
    ap.add_argument("--compress-factor", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fail-at-step", type=int, default=-1, help="inject a crash (FT demo)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    d, m = (int(x) for x in (args.mesh or f"{jax.device_count()}x1").split("x"))
    # Auto axes: the sharding rules place parameters and constrain
    # activations; explicit-typed axes would reject the embedding gather
    mesh = jax.make_mesh((d, m), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    rules = ParallelismRules(dp_axes=("data",))
    cfg = build_config(args)

    params = init_params(jax.random.key(args.seed), cfg)
    pshard = param_shardings(params, rules, mesh)
    params = jax.device_put(params, pshard)
    oc = OptimizerConfig(lr=args.lr, warmup_steps=min(20, args.steps // 10 + 1), total_steps=args.steps)
    state = {"params": params, "opt": init_opt_state(params, oc)}
    print(f"[train] {cfg.name}: {param_count(params)/1e6:.1f}M params, mesh {d}x{m}, "
          f"{args.steps} steps @ batch {args.batch}x{args.seq}")

    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, batch=args.batch, seq_len=args.seq, seed=args.seed))
    bshard = {"tokens": NamedSharding(mesh, batch_pspec(rules))}
    remat = None if args.remat == "none" else args.remat

    if args.grad_compress:
        ccfg = CompressionConfig(rank=args.compress_rank, sketch_factor=args.compress_factor,
                                 min_dim=min(512, cfg.d_model))
        print(f"[train] GMR gradient compression: rank={ccfg.rank} s={ccfg.s} "
              f"DP volume ratio={compression_ratio(params, ccfg):.1f}x")
        cstep, init_err = make_compressed_train_step(cfg, oc, ccfg, mesh, rules, remat=remat)
        state["err"] = init_err(params)

        def step_fn(state, batch, step):
            with activation_sharding(mesh, rules):
                return cstep(state, batch, jax.random.fold_in(jax.random.key(9), step))
    else:
        base_step = make_train_step(cfg, oc, remat=remat, microbatch=args.microbatch)

        def traced(state, batch):
            with activation_sharding(mesh, rules):
                return base_step(state, batch)

        jstep = jax.jit(traced, donate_argnums=(0,))

        def step_fn(state, batch, step):
            return jstep(state, batch)

    ckpt_dir = args.ckpt_dir or os.path.join("/tmp", f"repro_ckpt_{cfg.name}")
    t0 = time.time()
    report = run_resilient_loop(
        state=state,
        step_fn=step_fn,
        batch_fn=lambda s: jax.device_put(data.batch_at(s), bshard),
        n_steps=args.steps,
        ckpt_dir=ckpt_dir,
        ckpt_every=args.ckpt_every,
        fail_at_step=args.fail_at_step if args.fail_at_step >= 0 else None,
    )
    dt = time.time() - t0
    print(f"[train] done: {report.steps_run} steps in {dt:.1f}s "
          f"({dt/max(report.steps_run,1)*1e3:.0f} ms/step), restarts={report.restarts}, "
          f"loss {report.losses[0]:.4f} -> {report.losses[-1]:.4f}")
    return report


if __name__ == "__main__":
    main()
