"""Find the knee of a serving cell once: offer a list of fixed rates to one
set-up and print, per rate, the latency percentiles, the rows answered per
second and whether the tail grew between the window's halves.

    python -m chipbench.sweep --config <config> --traffic <mix> --seed <n> \\
        --seconds <s> --rates 100,200,400

The knee is the highest rate at which completions keep up with offers and
p99 does not grow between the halves; the cell's traffic file then fixes
its rate as a number. The mix need not be a cell of ``BENCHMARK.json``
yet: its rate is what the sweep is for. Needs the chip, like
``chipbench.run``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from chipbench import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = run.Cell(args.traffic,
                    run.load_json(run.HERE / "configs" / f"{args.config}.json"),
                    run.load_json(run.HERE / "traffic" / f"{args.traffic}.json"),
                    1, args.seed, args.seconds)
    import jax
    sys.path.insert(0, str(run.ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache(run.ROOT)
    if jax.default_backend() != "tpu":
        print("chipbench.sweep: needs a TPU", file=sys.stderr)
        return 2
    driver = run.load_module(run.HERE / "drivers" / "serve.py")
    compiles = run.CompileCounter(jax)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell.traffic["rate_per_s"] = rate
        if i == 0:
            state = driver.setup(cell)
        else:
            state.mix = cell.traffic
            state.svc.start()
        mark = compiles.mark()
        t = time.perf_counter()
        rec = driver.window(state, args.seconds)
        print(json.dumps({"rate_per_s": rate, **rec["end_to_end"],
                          **rec["log"], "compiles": compiles.since(mark),
                          "wall_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
