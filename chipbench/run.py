"""Run one benchmark cell on the chip and print its result line.

    python -m chipbench.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell names a
configuration (``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<traffic>.json``); the mix's ``kind`` names its driver
(``chipbench/drivers/<kind>.py``); each per-layer metric has its reader
(``chipbench/metrics/<metric>.py``) and each kernel a cost model
(``chipbench/costs/<kernel>.py``).

A run: compile cache first, then the chip check (no TPU, or fewer chips
than the cell asks for, exits non-zero with no result), the cell driver's set-up
(data, estimator, warm-up: ``setup_s`` counts from process start), the
measured window of ``--seconds``, the peak device memory, and then the
comparison with the plain reference that decides ``correct``. With
``--trace 1`` the window runs under the profiler and the line carries the
cell's per-layer metrics instead of its end-to-end ones. The last line of
standard output is one JSON object; the numbers compared, each beside its
limit, are the last lines of standard error and the last key of that
object.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path


def _process_start() -> float:
    """Wall-clock time at which this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


PROCESS_START = _process_start()
ROOT = Path(__file__).resolve().parent.parent
HERE = ROOT / "chipbench"


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, a bad name, ...)."""


def load_module(path: Path):
    """Import one data-named file (names may hold dots and dashes)."""
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    name = "chipbench._" + path.parent.name + "_" + \
        path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One cell as a run sees it."""
    name: str
    config: dict
    traffic: dict
    chips: int
    seed: int
    seconds: float
    trace: bool = False
    control: str | None = None      # a lower precision, for the control


def cell_from_spec(spec: dict, workload: str, seed: int, seconds: float,
                   trace: bool, control: str | None = None) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    w = cells[workload]
    config = next(c for c in spec["configs"] if c["name"] == w["config"])
    return Cell(workload, load_json(ROOT / config["file"]),
                load_json(HERE / "traffic" / f"{w['traffic']}.json"),
                int(w["chips"]), seed, seconds, trace, control)


def metrics_for(spec: dict, workload: str, group: str) -> list[dict]:
    return [m for m in spec[group]
            if workload in m.get("workloads", [workload])]


class CompileCounter:
    """Backend compiles and their seconds, from JAX's own monitoring
    events; ``mark`` / ``since`` count those inside a window."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax) -> None:
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += secs

    def mark(self) -> tuple[int, float]:
        return self.count, self.seconds

    def since(self, mark) -> tuple[int, float]:
        return self.count - mark[0], self.seconds - mark[1]


class GcClock:
    """Python's garbage collections and their seconds, from
    ``gc.callbacks``; ``mark`` / ``since`` count those inside a window."""

    def __init__(self) -> None:
        self.count, self.seconds, self.longest = 0, 0.0, 0.0
        self._start = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, _info) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            took = time.perf_counter() - self._start
            self.count += 1
            self.seconds += took
            self.longest = max(self.longest, took)
            self._start = None

    def mark(self) -> tuple[int, float]:
        self.longest = 0.0
        return self.count, self.seconds

    def since(self, mark) -> dict:
        return {"count": self.count - mark[0],
                "seconds": self.seconds - mark[1], "longest_s": self.longest}


def device_info(jax) -> dict:
    devices = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def peaks_for(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"chipbench/peaks.json")
    return table[kind]


def costs_for(cell: Cell) -> dict:
    return {k: load_module(HERE / "costs" / f"{k}.py")
            for k in cell.traffic.get("kernels", [])}


def require_launches(reduced: dict, ops_path) -> None:
    """Every kernel the traffic mix names ran in the traced window: a cost
    model whose pattern matches nothing is a fault of the benchmark, not a
    roofline to leave out."""
    missing = sorted(k for k, r in reduced["kernels"].items()
                     if not r["launches"])
    if missing:
        raise BenchError(f"no launch of {', '.join(missing)} in the traced "
                         f"window; its ops are in {ops_path}")


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """Every number compared, beside its limit; correct when each is at or
    under its limit and none is missing."""
    checks = {name: {"value": values.get(name), "limit": limit}
              for name, limit in limits.items()}
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


def run_cell(cell: Cell, spec: dict, jax, out_dir: Path,
             process_start: float) -> dict:
    """Set-up, window, memory, comparison and metrics of one cell: the
    whole run after the chip check. Returns the result line's object."""
    driver = load_module(HERE / "drivers" / f"{cell.traffic['kind']}.py")
    compiles = CompileCounter(jax)
    collections = GcClock()
    state = driver.setup(cell)
    setup_s = time.time() - process_start

    trace_dir = out_dir / "trace" / f"{cell.name}-{cell.seed}"
    if cell.trace:
        # device ops and the harness's own spans; no Python call tracer,
        # which would slow the host code under test
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    mark, gc_mark = compiles.mark(), collections.mark()
    try:
        with jax.profiler.TraceAnnotation("window"):
            record = driver.window(state, cell.seconds)
    finally:
        if cell.trace:
            jax.profiler.stop_trace()
    record["compiles_in_window"], record["compile_s_in_window"] = \
        compiles.since(mark)
    record["log"]["gc_in_window"] = collections.since(gc_mark)
    record["log"]["compiles_in_window"] = record["compiles_in_window"]
    device = device_info(jax)

    values = driver.check(state, record)
    correct, checks = judge(values, cell.traffic["limits"])
    # a wrong answer is not pinned to one fit or request: none is vouched for
    result = {"correct": correct, "attempted": record["attempted"],
              "failed": record["failed"] if correct else record["attempted"]}
    if cell.trace:
        from chipbench import trace as tr
        costs = costs_for(cell)
        loaded = tr.load(tr.find_xplane(str(trace_dir)), driver.SPANS)
        reduced = tr.reduce(loaded, costs)
        ops_path = out_dir / f"{cell.name}-{cell.seed}.ops.json"
        with open(ops_path, "w") as f:
            json.dump(tr.op_table(loaded), f, indent=1)
        shutil.rmtree(trace_dir, ignore_errors=True)
        require_launches(reduced, ops_path)
        ctx = {"record": record, "trace": reduced, "cell": cell,
               "costs": costs, "peaks": peaks_for(device["kind"])}
        metrics = {}
        for m in metrics_for(spec, cell.name, "per_layer"):
            value = load_module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
            if value is None:
                print(f"metric {m['name']}: nothing to read", file=sys.stderr)
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        print("trace: " + json.dumps({
            k: reduced[k] for k in ("kernels", "other_s", "idle_by_span")}),
            file=sys.stderr)
        result.update(metrics=metrics, device=device,
                      breakdown=reduced["breakdown"])
    else:
        record["end_to_end"]["setup_s"] = setup_s
        result.update(metrics={
            m["name"]: {"value": record["end_to_end"][m["name"]],
                        "unit": m["unit"]}
            for m in metrics_for(spec, cell.name, "end_to_end")},
            device=device)
    print("record: " + json.dumps(record.get("log", {})), file=sys.stderr)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("bfloat16",), default=None,
                    help="run the program at this lower precision: the "
                         "control that the comparison must fail")
    ap.add_argument("--out", default=str(HERE / "out"),
                    help="directory for traces")
    args = ap.parse_args(argv)

    try:
        spec = load_json(ROOT / "BENCHMARK.json")
        cell = cell_from_spec(spec, args.workload, args.seed, args.seconds,
                              bool(args.trace), args.control)
        import jax
        sys.path.insert(0, str(ROOT / "src"))
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache(ROOT)
        if jax.default_backend() != "tpu":
            raise BenchError(f"needs a TPU; JAX found "
                             f"{jax.default_backend()!r}")
        if len(jax.devices()) < cell.chips:
            raise BenchError(f"cell needs {cell.chips} chips; JAX found "
                             f"{len(jax.devices())}")
        from repro.kernels import ops
        if not ops.on_tpu():
            raise BenchError("ops.on_tpu() is False: kernels would run in "
                             "interpret mode")
        result = run_cell(cell, spec, jax, Path(args.out), PROCESS_START)
    except (BenchError, ImportError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} <= {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
