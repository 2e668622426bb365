"""Launcher + optimizer + autotune-table coverage."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_module(mod, args, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-m", mod] + args,
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-2500:]
    return out.stdout


class TestTrainLauncher:
    def test_train_and_restore(self, tmp_path):
        common = ["--arch", "internlm2-1.8b", "--smoke", "--lr", "1e-3",
                  "--ckpt-dir", str(tmp_path), "--ckpt-every", "5"]
        out = _run_module("repro.launch.train", common + ["--steps", "10"])
        assert "done; snapshots:" in out
        # crash/restore: continue to 15 from the step-10 snapshot
        out2 = _run_module("repro.launch.train",
                           common + ["--steps", "15", "--restore"])
        assert "restored checkpoint at step 10" in out2

    def test_serve_launcher(self):
        out = _run_module("repro.launch.serve",
                          ["--arch", "whisper-medium", "--requests", "2",
                           "--batch", "2", "--gen", "4", "--prompt-len", "8"])
        assert "served 2/2" in out


class TestSchedules:
    def test_wsd_shape(self):
        from repro.train.optimizer import TrainConfig, lr_at
        cfg = TrainConfig(learning_rate=1.0, warmup_steps=10,
                          total_steps=100, schedule="wsd",
                          wsd_decay_frac=0.2, min_lr_frac=0.1)
        assert float(lr_at(cfg, 0)) == 0.0
        assert abs(float(lr_at(cfg, 10)) - 1.0) < 1e-6       # warm
        assert abs(float(lr_at(cfg, 50)) - 1.0) < 1e-6       # stable
        assert float(lr_at(cfg, 90)) < 1.0                   # decaying
        assert abs(float(lr_at(cfg, 100)) - 0.1) < 1e-6      # floor

    def test_cosine_monotone_after_warmup(self):
        from repro.train.optimizer import TrainConfig, lr_at
        cfg = TrainConfig(learning_rate=1.0, warmup_steps=5,
                          total_steps=50, schedule="cosine")
        vals = [float(lr_at(cfg, s)) for s in range(5, 51)]
        assert all(a >= b - 1e-9 for a, b in zip(vals, vals[1:]))

    def test_adamw_descends_quadratic(self):
        from repro.train.optimizer import (TrainConfig, adamw_update,
                                           init_opt_state)
        cfg = TrainConfig(learning_rate=0.1, warmup_steps=0, total_steps=50,
                          weight_decay=0.0, schedule="constant")
        params = {"w": jnp.asarray([3.0, -2.0])}
        opt = init_opt_state(params, cfg)
        for _ in range(50):
            grads = {"w": 2 * params["w"]}
            params, opt, m = adamw_update(params, grads, opt, cfg)
        assert float(jnp.max(jnp.abs(params["w"]))) < 0.5


class TestAutotuneTable:
    def test_build_and_lookup_roundtrip(self, tmp_path):
        from repro.api import AutotuneCache, shape_bucket
        from repro.api.cache import SCHEMA_VERSION
        path = str(tmp_path / "table.json")
        cache = AutotuneCache(path)
        table = cache.build([(16384, 64, 64), (131072, 128, 128)],
                            mode="model")
        assert len(table["assign/float32/b0"]) == 2
        v, p = cache.lookup(16384, 64, 64)
        assert [v, p.block_m, p.block_k, p.block_f] == \
            table["assign/float32/b0"]["14-6-6"]
        # a fresh cache instance reloads the persisted winners
        fresh = AutotuneCache(path)
        w, q = fresh.lookup(131072, 128, 128)
        assert [w, q.block_m, q.block_k, q.block_f] == \
            table["assign/float32/b0"][shape_bucket(131072, 128, 128)]
        with open(path) as fh:
            assert json.load(fh) == {"schema": SCHEMA_VERSION,
                                     "kinds": table}

    def test_legacy_v1_table_loads_as_assign_kind(self, tmp_path):
        """v1 files (flat bucket -> blocks) keep working: their winners
        were tuned for the f32 assignment-only kernel (generic template)
        and must serve it — and only it."""
        from repro.api import AutotuneCache, shape_bucket
        path = str(tmp_path / "v1.json")
        with open(path, "w") as fh:
            json.dump({shape_bucket(1024, 64, 64): [64, 128, 128]}, fh)
        cache = AutotuneCache(path)
        v, p = cache.lookup(1024, 64, 64)               # kind="assign"
        assert v == "generic"
        assert [p.block_m, p.block_k, p.block_f] == [64, 128, 128]
        # the lloyd kernel never inherits an assignment-only winner; it
        # falls through to its own analytical selection
        q = cache.lookup(1024, 64, 64, kind="lloyd")
        assert q is not None
        # upgrading on save leaves the entry under the assign kind, f32
        cache.save()
        with open(path) as fh:
            on_disk = json.load(fh)
        assert on_disk["schema"] >= 3
        assert on_disk["kinds"]["assign/float32/b0"][
            shape_bucket(1024, 64, 64)] == ["generic", 64, 128, 128]

    def test_kinds_are_isolated(self, tmp_path):
        from repro.api import AutotuneCache
        from repro.kernels.ops import KernelParams
        cache = AutotuneCache()
        # a distinctive winner stored for the assignment kernel only
        cache.put(2048, 128, 256, KernelParams(1024, 512, 1024))
        _, pa = cache.lookup(2048, 128, 256)
        _, pl = cache.lookup(2048, 128, 256, kind="lloyd")
        assert [pa.block_m, pa.block_k, pa.block_f] == [1024, 512, 1024]
        assert (pl.block_m, pl.block_k, pl.block_f) != (1024, 512, 1024)

    def test_dtypes_are_isolated(self, tmp_path):
        """A winner tuned for f32 tiles must never serve the bf16/fp16
        templates — byte sizing and sublane alignment differ."""
        import jax.numpy as jnp
        from repro.api import AutotuneCache
        from repro.kernels.ops import KernelParams
        cache = AutotuneCache()
        cache.put(2048, 128, 256, KernelParams(1024, 512, 1024),
                  variant="generic")                     # f32 entry
        _, p32 = cache.lookup(2048, 128, 256)
        _, pbf = cache.lookup(2048, 128, 256, dtype=jnp.bfloat16)
        assert [p32.block_m, p32.block_k, p32.block_f] == [1024, 512, 1024]
        assert (pbf.block_m, pbf.block_k, pbf.block_f) != (1024, 512, 1024)

    def test_caches_are_isolated_per_instance(self, tmp_path):
        from repro.api import AutotuneCache
        from repro.kernels.ops import KernelParams
        a = AutotuneCache(str(tmp_path / "a.json"))
        b = AutotuneCache()               # in-memory only
        a.put(1024, 64, 64, KernelParams(64, 128, 128))
        _, pa = a.lookup(1024, 64, 64)
        _, pb = b.lookup(1024, 64, 64)    # falls back to the model winner
        assert [pa.block_m, pa.block_k, pa.block_f] == [64, 128, 128]
        assert (pb.block_m, pb.block_k, pb.block_f) != (0, 0, 0)


class TestChipEntryPoints:
    def test_compile_cache_follows_the_environment(self, monkeypatch,
                                                   tmp_path):
        import jax
        from repro.launch.compile_cache import use_compile_cache
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
        assert use_compile_cache(tmp_path) == str(tmp_path / "c")
        assert jax.config.jax_compilation_cache_dir == before

    def test_compile_cache_defaults_to_the_checkout(self, monkeypatch,
                                                    tmp_path):
        import jax
        from repro.launch.compile_cache import use_compile_cache
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            path = use_compile_cache(tmp_path)
            assert path == str(tmp_path.resolve() / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

    def test_chip_smoke_refuses_to_run_without_a_tpu(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run([sys.executable,
                              os.path.join(REPO, "chip_smoke.py")],
                             capture_output=True, text=True, env=env,
                             timeout=300)
        assert out.returncode != 0
        assert "needs a TPU" in out.stderr
        assert '"ok"' not in out.stdout

    @pytest.mark.parametrize("use_dmr", [False, True])
    @pytest.mark.parametrize("name", ["fused", "lloyd"])
    def test_chip_smoke_step_agrees_with_ref(self, name, use_dmr):
        """The smoke's compiled step, for a two-pass and a one-pass
        backend, run on the CPU at a small shape: it returns what its
        reference check reads, and that check passes."""
        import importlib.util

        import jax
        from repro.api.registry import get_backend
        from repro.kernels.ops import KernelParams
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        x = jax.random.normal(jax.random.key(0), (512, 16))
        c = x[:8]
        _, (am, md, sums, counts) = smoke.compiled_step(
            get_backend(name), KernelParams(128, 128, 128), use_dmr, x, c)
        agree = smoke.ref_agreement(x, c, am, md, sums, counts)
        assert agree["label_mismatches_off_tie"] == 0
        assert agree["counts_equal"]
        assert agree["sums_max_rel_err"] <= smoke.SUM_RTOL
        assert agree["inertia_err_over_scale"] <= smoke.INERTIA_RTOL
