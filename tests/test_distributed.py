"""Distributed runtime tests — run in a subprocess with 8 host devices so
the single-device test session isn't polluted (jax locks device count on
first init). The subprocess rig lives in ``tests/_mesh.py``, shared with
the 2D-mesh and fault-drill suites."""
import json

import pytest

from _mesh import run_with_devices

pytestmark = pytest.mark.multidevice


class TestDistributedKMeans:
    def test_one_pass_backend_shards(self):
        """fuses_update backends psum the kernel's own (sums, counts) —
        no second pass over the shard — and match the single-device fit."""
        out = run_with_devices("""
        import jax
        from repro.api import KMeans
        from repro.dist.kmeans_dist import DistributedKMeans
        from repro.data.blobs import make_blobs

        mesh = jax.make_mesh((8,), ("data",))
        x, _ = make_blobs(4096, 16, 8, seed=3)
        est = KMeans(8, max_iter=20, backend="lloyd_xla", random_state=0)
        c0 = est.init_centroids(x)
        dk = DistributedKMeans(est, mesh)
        c, am, inertia, iters, det = dk.fit(dk.shard_data(x), c0)
        ref = KMeans(8, max_iter=20, random_state=0).fit(x, centroids=c0)
        rel = abs(float(inertia) - ref.inertia_) / abs(ref.inertia_)
        print("REL", rel)
        """)
        rel = float(out.split("REL ")[1].split()[0])
        assert rel < 1e-3

    def test_one_pass_ft_backend_shards_with_reduce_checksums(self):
        """The protected one-pass path composes with sharding: off-TPU the
        lloyd_ft backend maps to its XLA analogue, the shard-local update
        checksums psum alongside the partial (sums, counts), and a clean
        run re-verifies them after the reduce with zero detections while
        matching the single-device solution."""
        out = run_with_devices("""
        import jax
        from repro.api import FaultPolicy, KMeans
        from repro.dist.kmeans_dist import DistributedKMeans
        from repro.data.blobs import make_blobs

        mesh = jax.make_mesh((8,), ("data",))
        x, _ = make_blobs(4096, 16, 8, seed=3)
        est = KMeans(8, max_iter=20,
                     fault=FaultPolicy.correct(update_dmr=False),
                     random_state=0)
        c0 = est.init_centroids(x)
        dk = DistributedKMeans(est, mesh)
        assert dk._shard_backend().name == "lloyd_ft_xla"
        c, am, inertia, iters, det = dk.fit(dk.shard_data(x), c0)
        ref = KMeans(8, max_iter=20, random_state=0).fit(x, centroids=c0)
        rel = abs(float(inertia) - ref.inertia_) / abs(ref.inertia_)
        print("REL", rel)
        print("DET", int(det))
        """)
        rel = float(out.split("REL ")[1].split()[0])
        assert rel < 1e-3
        assert int(out.split("DET ")[1].split()[0]) == 0

    def test_matches_single_device_and_checkpoints(self, tmp_path):
        out = run_with_devices(f"""
        import jax, jax.numpy as jnp
        from repro.dist.kmeans_dist import DistributedKMeans
        from repro.api import FaultPolicy, KMeans
        from repro.data.blobs import make_blobs
        from repro.ft.checkpoint import Checkpointer

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        x, _ = make_blobs(4096, 32, 8, seed=3)
        est = KMeans(8, max_iter=25, fault=FaultPolicy.correct(),
                     backend="fused_ft", random_state=0)
        dk = DistributedKMeans(est, mesh)
        c0 = est.init_centroids(x)
        ck = Checkpointer(r'{tmp_path}', async_write=True)
        c, am, inertia, iters, det = dk.fit(
            dk.shard_data(x), c0, checkpointer=ck, checkpoint_interval=2)
        ck.wait()
        ref = KMeans(8, max_iter=25, backend="gemm_fused",
                     random_state=0).fit(x, centroids=c0)
        rel = abs(float(inertia) - ref.inertia_) / ref.inertia_
        print("REL", rel)
        print("STEPS", ck.available_steps())
        st = ck.restore()
        print("RESTORED", st["_step"], st["centroids"].shape)
        """)
        assert "REL" in out
        rel = float(out.split("REL ")[1].split()[0])
        assert rel < 1e-3
        assert "RESTORED" in out

    def test_restart_from_checkpoint_resumes(self, tmp_path):
        out = run_with_devices(f"""
        import jax, jax.numpy as jnp
        from repro.dist.kmeans_dist import DistributedKMeans
        from repro.api import KMeans
        from repro.data.blobs import make_blobs
        from repro.ft.checkpoint import Checkpointer

        mesh = jax.make_mesh((8, 1), ("data", "model"))
        x, _ = make_blobs(2048, 16, 4, seed=9)
        est = KMeans(4, max_iter=12, tol=0.0, backend="gemm_fused",
                     random_state=0)
        dk = DistributedKMeans(est, mesh)
        c0 = est.init_centroids(x)
        xs = dk.shard_data(x)
        ck = Checkpointer(r'{tmp_path}', async_write=False)
        # run 1: "crashes" after 6 iterations (simulated by max_iters)
        dk.fit(xs, c0, max_iters=6, checkpointer=ck, checkpoint_interval=3)
        st = ck.restore()
        # run 2: restart from snapshot, finish
        c, am, inertia, iters, det = dk.fit(
            xs, jnp.asarray(st["centroids"]),
            start_iteration=int(st["iteration"]))
        full, *_ = dk.fit(xs, c0)[:1]
        import numpy as np
        print("DIFF", float(jnp.max(jnp.abs(c - full))))
        """)
        diff = float(out.split("DIFF ")[1].split()[0])
        assert diff < 1e-3   # restart converges to the same solution

    def test_compressed_psum_error_feedback(self):
        out = run_with_devices("""
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType, PartitionSpec as P
        from repro.dist.compression import compressed_psum, quantize, dequantize

        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(AxisType.Auto,))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 1024))

        def f(gl):
            gl = gl.reshape(1024)
            red, res = compressed_psum(gl, "data")
            return red[None], res[None]

        red, res = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P("data", None),
            out_specs=(P("data", None), P("data", None)),
            check_vma=False))(g)
        exact = jnp.sum(g, axis=0)
        err = float(jnp.max(jnp.abs(red[0] - exact)) /
                    jnp.max(jnp.abs(exact)))
        print("ERR", err)
        # error feedback residual is bounded by the quantization step
        print("RES", float(jnp.max(jnp.abs(res))))
        """)
        err = float(out.split("ERR ")[1].split()[0])
        assert err < 0.05    # int8 blockwise: ~1% typical

    def test_lm_train_step_runs_sharded(self):
        """End-to-end: the REAL train step (same code the dry-run lowers)
        executes on an 8-device mesh with a smoke config."""
        out = run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.configs.base import ShapeConfig
        from repro.train.steps import build_train_step
        from repro.train.optimizer import TrainConfig
        from repro.data.synthetic import TokenPipeline

        from jax.sharding import AxisType

        cfg = get_config("internlm2-1.8b", smoke=True)
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        shape = ShapeConfig("tiny", 32, 8, "train")
        # 4-step smoke: no warmup, lr high enough that descent beats noise
        tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=0,
                           total_steps=4, grad_accum=2)
        b = build_train_step(cfg, mesh, shape, tcfg)
        lm = b.lm
        params, axes = lm.init(jax.random.PRNGKey(0))
        from repro.dist.sharding import shard_params
        params = shard_params(mesh, params, axes)
        from repro.train.optimizer import init_opt_state
        opt = init_opt_state(params, tcfg)
        pipe = TokenPipeline(cfg.vocab_size, 32, 8)
        batch = pipe.next_batch(0)   # fixed batch: loss must descend
        losses = []
        for step in range(4):
            params, opt, m = b.step_fn(params, opt, batch)
            losses.append(float(m["loss"]))
        print("LOSSES", losses)
        """)
        losses = json.loads(out.split("LOSSES ")[1].replace("'", '"'))
        assert all(l == l for l in losses)  # finite
        assert losses[-1] < losses[0]       # structured data -> learnable


class TestElastic:
    def test_plan_rescale_drops_to_whole_tp_groups(self):
        from repro.ft.elastic import plan_rescale
        plan = plan_rescale(list(range(61)), model_parallel=8)
        assert plan.mesh_shape == (7, 8)
        assert plan.data_shards == 7

    def test_straggler_policy_two_strikes(self):
        from repro.ft.elastic import StragglerPolicy
        p = StragglerPolicy(deadline_factor=2.0, strikes=2)
        assert not p.observe(3, step_time=5.0, median_time=1.0)
        assert p.observe(3, step_time=5.0, median_time=1.0)
        p2 = StragglerPolicy(deadline_factor=2.0, strikes=2)
        assert not p2.observe(1, 5.0, 1.0)
        assert not p2.observe(1, 1.0, 1.0)   # recovered -> streak resets
        assert not p2.observe(1, 5.0, 1.0)
