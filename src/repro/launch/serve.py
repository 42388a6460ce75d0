"""Batched serving driver: prefill + decode loop with timing.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \\
      --batch 8 --prompt-len 1024 --gen 32 [--kv-compress 16]

``--smoke`` swaps the published widths for the architecture's smoke preset.
The mesh defaults to every visible device on the data axis (``Nx1``).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.configs import get_arch
from repro.distributed.sharding import ParallelismRules, activation_sharding, param_shardings
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params, param_count
from repro.models.modality import synth_patch_embeddings
from repro.serve import KVCompressionConfig, generate


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--mesh", default=None, help="dataxmodel, e.g. 4x1 (default: Nx1)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-compress", type=int, default=0, metavar="RANK",
                    help="compress full-attention KV caches at this rank "
                         "(decode-native streaming SVD; 0 = dense caches)")
    ap.add_argument("--kv-adaptive", action="store_true",
                    help="share the rank budget adaptively across heads")
    args = ap.parse_args(argv)

    kc = None
    if args.kv_compress:
        kc = KVCompressionConfig(rank=args.kv_compress, oversample=2, panel=32,
                                 decode_panel=8, refresh_every=32,
                                 adaptive=args.kv_adaptive,
                                 min_rank=max(1, args.kv_compress // 4))

    enable_compile_cache()
    d, m = (int(x) for x in (args.mesh or f"{jax.device_count()}x1").split("x"))
    # Auto axes: the sharding rules place parameters and constrain
    # activations; explicit-typed axes would reject the embedding gather
    mesh = jax.make_mesh((d, m), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    rules = ParallelismRules(dp_axes=("data",))
    mod = get_arch(args.arch)
    cfg = mod.smoke_config() if args.smoke else mod.full_config()

    params = init_params(jax.random.key(args.seed), cfg)
    params = jax.device_put(params, param_shardings(params, rules, mesh))
    print(f"[serve] {cfg.name}: {param_count(params)/1e6:.2f}M params")

    key = jax.random.key(args.seed + 1)
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0, cfg.vocab_size, jnp.int32)
    vision = synth_patch_embeddings(key, cfg, args.batch) if cfg.d_vision else None

    with mesh, activation_sharding(mesh, rules):
        t0 = time.time()
        out = generate(params, cfg, prompt, args.gen, key=key,
                       temperature=args.temperature, vision=vision, dense_moe=True,
                       kv_compress=kc)
        out.block_until_ready()
    dt = time.time() - t0
    n_tok = args.batch * args.gen
    mode = f"compressed kv @ rank {kc.rank}" + (" adaptive" if kc.adaptive else "") \
        if kc else "dense kv"
    print(f"[serve] generated {out.shape} in {dt:.2f}s ({n_tok/dt:.1f} tok/s incl. compile, {mode})")
    print("[serve] sample:", out[0, :16].tolist())
    return out


if __name__ == "__main__":
    main()
