"""Read a cell's fit loop by the program's own spans and scopes, and what
the profiler costs.

    python -m chipbench.probe --workload <cell> --seeds <n,n,...> \\
        --seconds <s>

For each seed: the cell driver's set-up, one window with the profiler off
and one with it on (the order alternates from seed to seed), then the
traced window reduced twice: as ``chipbench.trace`` reduces it for the
per-layer metrics, and by ``chipbench.scopes`` (device time per
``kmeans.*`` scope, idle time by ``kmeans.*`` host span, inside and between
programs, the one-clock check). One JSON line per seed on standard output.
Nothing is compared with the reference: ``chipbench.run`` does that.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

from chipbench import run, scopes, trace as tr


def _window(jax, driver, state, seconds: float, trace_dir) -> dict:
    if trace_dir is not None:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            return driver.window(state, seconds)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()


def probe(cell: run.Cell, spec: dict, jax, out_dir: Path) -> dict:
    """One seed of one cell: both windows and the traced window's
    reductions."""
    driver = run.load_module(run.HERE / "drivers"
                             / f"{cell.traffic['kind']}.py")
    metric = next(m["name"] for m in run.metrics_for(
        spec, cell.name, "end_to_end") if m["name"] != "setup_s")
    state = driver.setup(cell)
    trace_dir = out_dir / "probe" / f"{cell.name}-{cell.seed}"
    line = {"cell": cell.name, "seed": cell.seed, "metric": metric}
    for mode in (("off", "on") if cell.seed % 2 else ("on", "off")):
        record = _window(jax, driver, state, cell.seconds,
                         trace_dir if mode == "on" else None)
        line[mode] = record["end_to_end"][metric]
        if mode == "on":
            traced = record
    path = tr.find_xplane(str(trace_dir))
    loaded = tr.load(path, driver.SPANS)
    costs = run.costs_for(cell)
    modules, spans, op_scopes = scopes.load_program(path)
    shutil.rmtree(trace_dir, ignore_errors=True)
    reduced = tr.reduce(loaded, costs)
    lo, hi = tr.window_of(loaded)
    ops = tr.clip(tr.leaves(loaded.devices[0]), lo, hi)
    breakdown, _ = tr.breakdown(ops, tr.idle_gaps(ops, lo, hi),
                                loaded.spans + spans)
    line.update(
        iterations=traced["iterations"],
        trace={k: reduced[k] for k in ("window_s", "busy_s", "other_s",
                                       "idle_by_span")},
        program=scopes.reduce_program(loaded, modules, spans, op_scopes,
                                      costs),
        breakdown=breakdown)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one set-up each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=str(run.HERE / "out"),
                    help="directory for traces")
    args = ap.parse_args(argv)
    try:
        spec = run.load_json(run.ROOT / "BENCHMARK.json")
        import jax
        sys.path.insert(0, str(run.ROOT / "src"))
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache(run.ROOT)
        if jax.default_backend() != "tpu":
            raise run.BenchError(f"needs a TPU; JAX found "
                                 f"{jax.default_backend()!r}")
        for seed in (int(s) for s in args.seeds.split(",")):
            cell = run.cell_from_spec(spec, args.workload, seed,
                                      args.seconds, True)
            print(json.dumps(probe(cell, spec, jax, Path(args.out))),
                  flush=True)
    except (run.BenchError, ImportError) as e:
        print(f"chipbench.probe: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
