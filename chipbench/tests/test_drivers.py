"""Each driver at tiny shapes on the CPU, through the whole run after the
chip check: the result line's keys, a sound run judged correct, and the
runs that must be judged not correct — the bf16 control, and the timed path
broken underneath (a step that returns its state unchanged, half of the
batch left out of the update, an answer altered where it is produced)."""
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import fits, run

SEED = 2**33 + 5      # wider than 32 bits, as a run's --seed may be
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


# the serving cell waits for its knee to be measured on the chip; its
# driver is tested here as the cell will name it
SERVE = {"name": "ivf4096-assign", "config": "sift1m-ivf4096",
         "traffic": "ivf4096-assign", "chips": 1}


def _cell(workload, control=None):
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    if workload == SERVE["name"]:
        spec["workloads"].append(SERVE)
    cell = run.cell_from_spec(spec, workload, SEED, 0.5, False, control)
    cfg = cell.config
    cfg["data"]["components"] = 48
    # 12 iterations: a 10-step chunk and a 2-step one, two programs
    if cell.traffic["kind"] == "batched_fit":
        cfg.update(rows=1024, clusters=16, iterations=12)
    elif cell.traffic["kind"] == "serve":
        cfg.update(clusters=48)
        cell.traffic.update(query_pool_rows=4096, warmup_s=0.5,
                            rate_per_s=40, rows_max=200, callers=4)
    else:
        cfg.update(rows=3000, clusters=24, iterations=12)
    return spec, cell


def _run(workload, tmp_path, control=None):
    spec, cell = _cell(workload, control)
    return run.run_cell(cell, spec, jax, tmp_path, time.time())


FIT_CELLS = ["ivf4096-fit", "ivf4096-fit-ft-seu", "pq16x8-fit"]
ALL_CELLS = FIT_CELLS + ["ivf4096-assign"]


def test_a_step_of_every_chunk_program_is_judged():
    assert fits.judged_lengths(25, 10) == [10, 25]
    assert fits.judged_lengths(20, 10) == [10, 20]
    assert fits.judged_lengths(3, 10) == [3]


@pytest.mark.parametrize("workload", ALL_CELLS)
def test_sound_run_is_correct_and_well_formed(workload, tmp_path, capsys):
    res = _run(workload, tmp_path)
    assert list(res) == KEYS
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    if workload in FIT_CELLS:
        log = next(line for line in capsys.readouterr().err.splitlines()
                   if line.startswith("record: "))
        assert json.loads(log[len("record: "):])["judged_steps"] == [10, 12]
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    want = {m["name"] for m in run.metrics_for(spec, workload, "end_to_end")}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(res["device"])
    json.dumps(res)


@pytest.mark.parametrize("workload", ALL_CELLS)
def test_bf16_control_is_not_correct(workload, tmp_path):
    res = _run(workload, tmp_path, control="bfloat16")
    assert not res["correct"], res["checks"]


def _wrap_backend(monkeypatch, names, change):
    """Replace registered backends by ones whose outputs ``change`` edits."""
    from repro.api import registry
    registry._ensure_builtin_backends()
    for name in names:
        b = registry._REGISTRY[name]
        orig = b.fn

        def fn(x, c, *a, _orig=orig, **kw):
            return change(x, _orig(x, c, *a, **kw))
        monkeypatch.setitem(registry._REGISTRY, name,
                            dataclasses.replace(b, fn=fn))


def _half_sums(x, out):
    """Sums and counts of the first half of the rows only."""
    am, md, det, sums, counts = out[:5]
    xs = getattr(x, "x", x)
    k = sums.shape[-2]
    h = am.shape[-1] // 2
    onehot = jax.nn.one_hot(am[..., :h], k, dtype=jnp.float32)
    xh = xs[..., :h, :].astype(jnp.float32)
    sums = jnp.einsum("...nk,...nf->...kf", onehot, xh,
                      precision=jax.lax.Precision.HIGHEST)
    return (am, md, det, sums, jnp.sum(onehot, axis=-2)) + tuple(out[5:])


def _alter_label(x, out):
    am = out[0]
    k = out[3].shape[-2] if len(out) > 3 else 2
    flat = am.reshape(-1)
    flat = flat.at[0].set((flat[0] + 1) % k)
    return (flat.reshape(am.shape),) + tuple(out[1:])


@pytest.mark.parametrize("workload", FIT_CELLS)
def test_state_left_unchanged_is_not_correct(workload, tmp_path,
                                             monkeypatch):
    from repro.core import kmeans
    monkeypatch.setattr(kmeans, "means_from_sums",
                        lambda sums, counts, prev: prev)
    assert not _run(workload, tmp_path)["correct"]


def test_half_batch_two_pass_is_not_correct(tmp_path, monkeypatch):
    from repro.kernels import ref
    orig = ref.centroid_update
    monkeypatch.setattr(ref, "centroid_update", lambda x, a, k: orig(
        x[:x.shape[0] // 2], a[:a.shape[0] // 2], k))
    assert not _run("ivf4096-fit", tmp_path)["correct"]


@pytest.mark.parametrize("workload,backend", [
    ("ivf4096-fit-ft-seu", "lloyd_ft"),
    ("pq16x8-fit", "lloyd_batched_xla")])
def test_half_batch_one_pass_is_not_correct(workload, backend, tmp_path,
                                            monkeypatch):
    _wrap_backend(monkeypatch, [backend], _half_sums)
    assert not _run(workload, tmp_path)["correct"]


@pytest.mark.parametrize("workload,backend", [
    ("ivf4096-fit", "gemm_fused"),
    ("ivf4096-fit-ft-seu", "lloyd_ft"),
    ("pq16x8-fit", "lloyd_batched_xla")])
def test_altered_label_in_a_fit_is_not_correct(workload, backend, tmp_path,
                                               monkeypatch):
    _wrap_backend(monkeypatch, [backend], _alter_label)
    assert not _run(workload, tmp_path)["correct"]


def test_altered_answer_in_serving_is_not_correct(tmp_path, monkeypatch):
    from repro.serve.compiler import ServeCompiler
    orig = ServeCompiler.dispatch

    def dispatch(self, x, centroids):
        am, md, det = orig(self, x, centroids)
        return am.at[0].set((am[0] + 1) % self.n_clusters), md, det
    monkeypatch.setattr(ServeCompiler, "dispatch", dispatch)
    assert not _run("ivf4096-assign", tmp_path)["correct"]
