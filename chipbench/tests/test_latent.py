"""The MLA latent cell at a test size on the CPU: `drivers/latent_fit`
(bf16-stored rows at f32 arithmetic) through the whole run after the chip
check, its control and a planted fault, the cost model, and the two
readers that only this cell has."""
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import run

SEED = 2**33 + 7      # wider than 32 bits, as a run's --seed may be
CELL = "mla-latent-ragged-fit"
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]
LENGTHS = [40, 150, 90]


def _cell(control=None):
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = run.cell_from_spec(spec, CELL, SEED, 0.5, False, control)
    cfg = cell.config
    # 12 iterations: a 10-step chunk and a 2-step one, two programs; 300
    # features pad to 384 lanes
    cfg.update(prompt_lengths=LENGTHS, full_layers=2, rows=2 * sum(LENGTHS),
               features=300, clusters=16, iterations=12)
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
    assert log["rows_valid"] == 560
    assert log["features"] == 300 and log["distance_passes"] == 3
    assert log["feature_lanes"] == (384 if backend == "pallas" else 300)


def test_rows_are_made_in_their_storage_dtype():
    from chipbench.drivers import latent_fit
    _, cell = _cell()
    cfg = cell.config
    x = latent_fit.make_rows(jax.random.PRNGKey(0), (40, 150), 300,
                             cfg["data"], jnp.bfloat16)
    assert x.shape == (190, 300) and x.dtype == jnp.bfloat16
    # each problem is its own mixture, drawn whole
    assert float(jnp.min(jnp.sum(jnp.abs(x.astype(jnp.float32)),
                                 axis=1))) > 0


def test_bf16_control_is_not_correct(tmp_path):
    assert not _run(tmp_path, control="bfloat16")["correct"]


def test_update_fault_is_not_correct(tmp_path, pallas, monkeypatch):
    """A kernel whose update keeps its per-tile partial sums in bf16 (the
    rows' storage dtype) in place of f32 fails the comparison."""
    from repro.kernels import ops
    orig = ops._segment_tree_sum

    def bf16_partials(a, counts):
        if a.ndim == 3:                 # the sums, not the counts
            a = a.astype(jnp.bfloat16).astype(a.dtype)
        return orig(a, counts)
    monkeypatch.setattr(ops, "_segment_tree_sum", bf16_partials)
    res = _run(tmp_path)
    assert not res["correct"]
    assert res["checks"]["update_err"]["value"] > \
        10 * res["checks"]["update_err"]["limit"]


def test_cost_counts_two_byte_rows_at_the_true_width():
    _, cell = _cell()
    mod = run.load_module(run.HERE / "costs" /
                          "lloyd_step_ragged_bf16rows.py")
    flops, nbytes = mod.cost(cell)
    lengths = [40, 40, 150, 150, 90, 90]
    n, b, k, f = 560, 6, 16, 300
    # by hand: 2*N*K*F + N*F flops; 2-byte rows, f32 C in and sums out,
    # f32 distance and i32 label per row, f32 counts
    assert flops == 2.0 * n * k * f + n * f == 5_544_000.0
    assert nbytes == 2 * n * f + 4 * (2 * b * k * f + 2 * n + b * k) \
        == 571_264.0
    assert sum(lengths) == n == cell.config["rows"]
    assert mod.PATTERN == r"^lloyd_step_ragged\b"


def _reader(name):
    return run.load_module(run.HERE / "metrics" / f"{name}.py").read


def test_readers():
    log = {"features": 576, "feature_lanes": 640, "rows_valid": 800,
           "rows_padded": 200, "fits": 4, "wall_s": 2.0, "iterations": 100}
    trace = {"kernels": {"lloyd_step_ragged_bf16rows": {"launches": 2,
                                                         "seconds": 1.0}}}
    _, cell = _cell()
    cost = run.load_module(run.HERE / "costs" /
                           "lloyd_step_ragged_bf16rows.py")
    ctx = {"record": {"log": log}, "trace": trace, "cell": cell,
           "costs": {"lloyd_step_ragged_bf16rows": cost},
           "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}
    assert _reader("pad_lanes_share.ragged")(ctx) == pytest.approx(
        100 * 64 / 576)
    flops, nbytes = cost.cost(cell)
    want = 100.0 * 2 * max(flops / 1e12, nbytes / 1e9) / 1.0
    assert _reader("lloyd_step_ragged_bf16rows_roofline")(ctx) == \
        pytest.approx(want)
    # a program without the counter, or a trace without the kernel:
    # nothing to read, no error
    bare = dict(ctx, record={"log": {"features": 576, "feature_lanes": None,
                                     "fits": 4}},
                trace={"kernels": {}})
    assert _reader("pad_lanes_share.ragged")(bare) is None
    assert _reader("lloyd_step_ragged_bf16rows_roofline")(bare) is None
