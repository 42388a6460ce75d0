"""The paper's accuracy experiments: one module per table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only gmr_error,...]

Prints ``name,us_per_call,derived`` CSV rows (the skeleton contract). A
module that raises prints a ``<module>/ERROR`` row, the remaining modules
still run, and the harness then exits non-zero. ``us_per_call`` is CPU
wall-clock; speed is measured on the chip by ``bench/``.
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced sweeps for CI")
    ap.add_argument("--only", default="", help="comma-separated module subset")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import cur_decomp, gmr_error, single_pass_svd, spsd_approx, stream_bench

    modules = {
        "gmr_error": gmr_error,        # paper Fig. 1  (§6.1)
        "cur_decomp": cur_decomp,      # paper §1 application 1 (repro/cur/)
        "spsd_approx": spsd_approx,    # paper Fig. 2 + Table 7 (§6.2)
        "single_pass_svd": single_pass_svd,  # paper Fig. 3 (§6.3)
        "stream_bench": stream_bench,  # streaming engine: adaptive/evict/rows + DP parity
    }
    if args.only:
        keep = set(args.only.split(","))
        modules = {k: v for k, v in modules.items() if k in keep}

    print("name,us_per_call,derived")
    failed = []
    for name, mod in modules.items():
        t0 = time.time()
        try:
            rows = mod.run(quick=args.quick)
        except Exception as e:  # noqa: BLE001 — surface per-module failures in CSV
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}")
            failed.append(name)
            continue
        for row in rows:
            derived = str(row["derived"]).replace(",", ";")
            print(f"{row['name']},{row['us_per_call']},{derived}")
        print(f"{name}/_total,{(time.time()-t0)*1e6:.0f},module_wall_time", file=sys.stderr)
    if failed:
        sys.exit(f"benchmark modules failed: {','.join(failed)}")


if __name__ == "__main__":
    main()
