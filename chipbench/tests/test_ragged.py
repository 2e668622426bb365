"""The ragged KV-key cell at a test size on the CPU: `drivers/ragged_fit`
through the whole run after the chip check, its control and a planted
fault, the cost model, and the three readers that only this cell has."""
import time

import jax
import pytest

from chipbench import run

SEED = 2**33 + 7      # wider than 32 bits, as a run's --seed may be
CELL = "kvkeys-ragged-fit"
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _cell(control=None):
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = run.cell_from_spec(spec, CELL, SEED, 0.5, False, control)
    cfg = cell.config
    # 12 iterations: a 10-step chunk and a 2-step one, two programs
    cfg.update(prompt_lengths=[37, 150, 90], kv_heads=2, full_layers=1,
               rows=2 * (37 + 150 + 90), clusters=16, iterations=12)
    cfg["data"]["components"] = 8
    return spec, cell


def _run(tmp_path, control=None):
    spec, cell = _cell(control)
    return run.run_cell(cell, spec, jax, tmp_path, time.time())


@pytest.fixture
def pallas(monkeypatch):
    """Route the estimator to the ragged Pallas kernel (interpret mode)."""
    from repro.api import get_backend
    from repro.batch.estimator import BatchedKMeans
    monkeypatch.setattr(BatchedKMeans, "_resolve_backend",
                        staticmethod(lambda name: get_backend(
                            "lloyd_batched")))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_sound_run_is_correct_and_well_formed(backend, tmp_path, capsys,
                                              request):
    if backend == "pallas":
        request.getfixturevalue("pallas")
    res = _run(tmp_path)
    assert list(res) == KEYS
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"batched_fit_iter_ms", "setup_s"}
    err = capsys.readouterr().err
    log = run.json.loads(next(line for line in err.splitlines()
                              if line.startswith("record: "))[8:])
    assert log["judged_steps"] == [10, 12]
    assert log["rows_valid"] == 554
    if backend == "pallas":
        assert log["rows_padded"] > 0 and log["row_tiles"] > 0


def test_bf16_control_is_not_correct(tmp_path):
    assert not _run(tmp_path, control="bfloat16")["correct"]


def test_padded_row_counted_is_not_correct(tmp_path, pallas, monkeypatch):
    """A kernel that counts one padded row of each problem's tail tile
    into that problem's clusters fails the comparison."""
    import dataclasses

    from repro.kernels import ops
    orig = ops.plan_data_batched

    def leaky(x, params=None, lengths=None):
        plan = orig(x, params, lengths)
        rows = plan.tile_rows
        return dataclasses.replace(
            plan, tile_rows=jax.numpy.where(rows < plan.block, rows + 1,
                                            rows))
    monkeypatch.setattr(ops, "plan_data_batched", leaky)
    assert not _run(tmp_path)["correct"]


def test_cost_counts_each_problem_rows_not_padding():
    _, cell = _cell()
    mod = run.load_module(run.HERE / "costs" / "lloyd_step_ragged.py")
    flops, nbytes = mod.cost(cell)
    lengths = [37, 37, 150, 150, 90, 90]
    n, b, k, f = sum(lengths), 6, 16, 128
    assert flops == sum(2.0 * m * k * f + m * f for m in lengths)
    assert nbytes == 4.0 * (n * f + 2 * b * k * f + 2 * n + b * k)
    assert n == cell.config["rows"]


def _reader(name):
    return run.load_module(run.HERE / "metrics" / f"{name}.py").read


def test_readers():
    log = {"rows_valid": 800, "rows_padded": 200, "fits": 4,
           "wall_s": 2.0, "iterations": 100}
    trace = {"idle_by_span": [["kmeans.pack", 0.02], ["fit", 0.5],
                              ["kmeans.pack", 0.01]],
             "kernels": {"lloyd_step_ragged": {"launches": 2,
                                                "seconds": 1.0}}}
    _, cell = _cell()
    ctx = {"record": {"log": log}, "trace": trace, "cell": cell,
           "costs": {"lloyd_step_ragged": run.load_module(
               run.HERE / "costs" / "lloyd_step_ragged.py")},
           "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}
    assert _reader("pad_rows_share.ragged")(ctx) == 25.0
    assert _reader("pack_idle_ms_per_fit.ragged")(ctx) == pytest.approx(7.5)
    flops, nbytes = ctx["costs"]["lloyd_step_ragged"].cost(cell)
    want = 100.0 * 2 * max(flops / 1e12, nbytes / 1e9) / 1.0
    assert _reader("lloyd_step_ragged_roofline")(ctx) == pytest.approx(want)
    # a program without the ragged counters: nothing to read, no error
    bare = dict(ctx, record={"log": {"fits": 4}})
    assert _reader("pad_rows_share.ragged")(bare) is None
    assert _reader("pack_idle_ms_per_fit.ragged")(bare) is None
