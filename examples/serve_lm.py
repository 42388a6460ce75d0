"""Batched serving example: prefill + decode a smoke-scale model on every
visible device (data-parallel mesh).

  PYTHONPATH=src python examples/serve_lm.py [--arch gemma3-12b]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch import serve as serve_mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-12b")
    ap.add_argument("--kv-compress", type=int, default=8, metavar="RANK",
                    help="KV compression rank for full-attention layers (0 = dense)")
    args = ap.parse_args()
    serve_mod.main([
        "--arch", args.arch, "--smoke",
        "--batch", "4", "--prompt-len", "48", "--gen", "24",
        "--kv-compress", str(args.kv_compress),
    ])
    print("serve_lm example OK")


if __name__ == "__main__":
    main()
