"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. See docs/architecture.md for the
figure-to-module index; absolute TPU numbers come from the dry-run
roofline (bench_roofline reads its cache), wall-times here are CPU-host
calibrations of the paper's *relative* claims.
"""
from __future__ import annotations

import sys
import traceback
from pathlib import Path

from repro.launch.compile_cache import use_compile_cache

MODULES = [
    "benchmarks.bench_stepwise",       # Fig 7
    "benchmarks.bench_batched",        # batched many-problem path (ISSUE 5)
    "benchmarks.bench_init",           # fused k-means++ seeding (ISSUE 8)
    "benchmarks.bench_shapes",         # Fig 8-11 / 19-20
    "benchmarks.bench_speedup_grid",   # Fig 12
    "benchmarks.bench_params",         # Fig 13/14 + Table I
    "benchmarks.bench_ft_overhead",    # Fig 15/16
    "benchmarks.bench_injection",      # Fig 17/18/21
    "benchmarks.bench_roofline",       # EXPERIMENTS §Roofline
]


def main() -> None:
    use_compile_cache(Path(__file__).resolve().parent.parent)
    print("name,us_per_call,derived")
    failed = 0
    for modname in MODULES:
        try:
            mod = __import__(modname, fromlist=["run"])
            for line in mod.run():
                print(line, flush=True)
        except Exception:
            failed += 1
            print(f"{modname},ERROR,", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
