"""Compile rehearsal for the TPU v5e: the main-path kernels, lowered by
Mosaic for a described (not attached) chip.

Interpret mode runs a kernel body in Python and enforces none of Mosaic's
rules — block shapes whose last two dims are neither (8, 128)-aligned nor
equal to the array's, vector ops without a lowering, working sets over the
scoped-VMEM limit. Each test here compiles one ``ops`` wrapper with
``interpret=False`` at the chip smoke's widths (SIFT1M: N=1,048,576,
F=128; the batched stack B=16, N=65,536, K=256; the ragged KV-key batch,
256 problems of 5,087 to 105,545 rows, K=256) with the tiles the
autotuner picks, and asserts the kernel reached the compiled program
(``tpu_custom_call``). A compile that passes is not a chip run:
``chip_smoke.py`` is.

The topology is described inside a module fixture — never at import —
because only one process at a time may load the TPU library, and every
pytest-xdist worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api.cache import AutotuneCache
from repro.kernels import cluster_sums as cs_kernel
from repro.kernels import ops
from repro.kernels.kmeanspp_init import init_kmeanspp_fused

N, F = 1_048_576, 128
B, BN, BK = 16, 65_536, 256
# the ragged cell: 8 prompts' keys x 8 full-attention layers x 4 KV heads
PROMPTS = (5087, 7845, 12098, 18658, 28774, 44376, 68438, 105545)
RAGGED = tuple(n for n in PROMPTS for _ in range(32))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _tuned(kind, m, k, f, *, dtype=jnp.float32, batch=1):
    """The autotuner's pick, from an in-memory table (no file is read)."""
    variant, p = AutotuneCache(None).lookup(m, k, f, kind=kind, dtype=dtype,
                                            batch=batch)
    return variant, ops.clamp_params(m, k, f, p, dtype=dtype)


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("k,variant", [(1024, "generic"), (128, "smallk")])
def test_fused_assign(one_chip, k, variant):
    _, p = _tuned("assign", N, k, F)
    assert ops.resolve_variant(k, p) == variant
    txt = _compiled_text(
        lambda x, c: ops.fused_assign(x, c, p, variant=variant,
                                      interpret=False),
        _sds(one_chip, (N, F)), _sds(one_chip, (k, F)))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("k,variant", [(1024, "generic"), (128, "smallk")])
def test_fused_lloyd(one_chip, k, variant):
    _, p = _tuned("lloyd", N, k, F)
    assert ops.resolve_variant(k, p) == variant
    txt = _compiled_text(
        lambda x, c: ops.fused_lloyd(x, c, p, variant=variant,
                                     interpret=False),
        _sds(one_chip, (N, F)), _sds(one_chip, (k, F)))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("k,variant", [(1024, "generic"), (128, "smallk")])
def test_fused_lloyd_pruned(one_chip, k, variant):
    _, p = _tuned("pruned", N, k, F)
    assert ops.resolve_variant(k, p) == variant
    txt = _compiled_text(
        lambda x, c: ops.fused_lloyd_pruned(x, c, p, variant=variant,
                                            interpret=False),
        _sds(one_chip, (N, F)), _sds(one_chip, (k, F)))
    assert "tpu_custom_call" in txt


def test_fused_lloyd_ft(one_chip):
    _, p = _tuned("lloyd_ft", N, 1024, F)
    txt = _compiled_text(
        lambda x, c: ops.fused_lloyd_ft(x, c, p, interpret=False),
        _sds(one_chip, (N, F)), _sds(one_chip, (1024, F)))
    assert "tpu_custom_call" in txt


def test_fused_lloyd_ft_ivf4096(one_chip):
    """The IVF4096 fit's K: the update epilogue's one-hot tile is
    (1024, 4096), and the tile recompute must still fit its VMEM."""
    _, p = _tuned("lloyd_ft", 1_000_000, 4096, F)
    txt = _compiled_text(
        lambda x, c: ops.fused_lloyd_ft(x, c, p, interpret=False),
        _sds(one_chip, (1_000_000, F)), _sds(one_chip, (4096, F)))
    assert "tpu_custom_call" in txt


def test_fused_assign_ft(one_chip):
    _, p = _tuned("assign", N, 1024, F)
    txt = _compiled_text(
        lambda x, c: ops.fused_assign_ft(x, c, p, interpret=False),
        _sds(one_chip, (N, F)), _sds(one_chip, (1024, F)))
    assert "tpu_custom_call" in txt


def test_fused_assign_int8(one_chip):
    _, p = _tuned("int8", N, 1024, F, dtype=jnp.int8)
    txt = _compiled_text(
        lambda x, c: ops.fused_assign_int8(x, c, p, interpret=False),
        _sds(one_chip, (N, F)), _sds(one_chip, (1024, F)))
    assert "tpu_custom_call" in txt


def test_fused_lloyd_batched(one_chip):
    _, p = _tuned("batched", BN, BK, F, batch=B)
    txt = _compiled_text(
        lambda x, c: ops.fused_lloyd_batched(x, c, p, interpret=False),
        _sds(one_chip, (B, BN, F)), _sds(one_chip, (B, BK, F)))
    assert "lloyd_step_batched" in txt and "tpu_custom_call" in txt


def test_fused_lloyd_ragged(one_chip):
    """The ragged step at the KV-key cell's shapes: B = 256 problems,
    sum N = 9,306,272 rows, K = 256, F = 128, the pack included."""
    _, p = _tuned("batched", max(RAGGED), BK, F, batch=len(RAGGED))
    p = ops.ragged_params(p, max(RAGGED), BK, F)
    txt = _compiled_text(
        lambda x, c: ops.fused_lloyd_batched(
            ops.plan_data_batched(x, p, RAGGED), c, interpret=False),
        _sds(one_chip, (sum(RAGGED), F)),
        _sds(one_chip, (len(RAGGED), BK, F)))
    assert "lloyd_step_ragged" in txt and "tpu_custom_call" in txt


def test_ragged_pack_needs_no_temporaries(one_chip):
    """The pack at the KV-key cell's shapes writes X's padded copy and
    nothing else: no problem's rows are sliced ahead into temporaries."""
    m = sum(RAGGED)
    mem = ops._pack_rows.lower(
        _sds(one_chip, (m, F)), lengths=RAGGED, block=4096,
        fp=F).compile().memory_analysis()
    assert mem.temp_size_in_bytes <= max(RAGGED) * F * 4


def test_cluster_sums_ivf4096(one_chip):
    """The sorted-window update kernel at the IVF4096 fit's shape: 1 M
    label-sorted rows of 128 in 1024-row tiles, K = 4096, its (4224, 128)
    sums block resident in VMEM."""
    m, k, bm = 1_000_000, 4096, ops.SORTED_BLOCK_M
    assert ops.update_path(m, k, F) == "sorted"
    t = -(-m // bm)
    txt = _compiled_text(
        lambda s, n, lab, x: cs_kernel.cluster_sums(s, n, lab, x, k=k,
                                                    block_m=bm),
        _sds(one_chip, (t,), jnp.int32), _sds(one_chip, (t,), jnp.int32),
        _sds(one_chip, (1, t * bm), jnp.int32),
        _sds(one_chip, (t * bm, F)))
    assert "cluster_sums" in txt and "tpu_custom_call" in txt


def test_cluster_sums_bf16(one_chip):
    """The kernel on a bf16 X, from the bf16 threshold on."""
    m, k, bm = 1_000_000, 6144, ops.SORTED_BLOCK_M
    assert ops.update_path(m, k, F, jnp.bfloat16) == "sorted"
    t = -(-m // bm)
    txt = _compiled_text(
        lambda s, n, lab, x: cs_kernel.cluster_sums(s, n, lab, x, k=k,
                                                    block_m=bm),
        _sds(one_chip, (t,), jnp.int32), _sds(one_chip, (t,), jnp.int32),
        _sds(one_chip, (1, t * bm), jnp.int32),
        _sds(one_chip, (t * bm, F), jnp.bfloat16))
    assert "cluster_sums" in txt and "tpu_custom_call" in txt


def test_distributed_row_step_sorted_update(topo, monkeypatch):
    """``DistributedKMeans`` row mode on the four-chip mesh at the
    IVF4096 fit's K: each shard of 1 M rows runs the sorted-window update
    (sort, gather, ``cluster_sums``) inside the sharded step."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.api import KMeans
    from repro.core.fault import no_step_injection
    from repro.dist.kmeans_dist import DistributedKMeans
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    m_local, k = N, 4096
    assert ops.update_path(m_local, k, F) == "sorted"
    mesh = Mesh(np.array(topo.devices), ("data",))
    est = KMeans(k, tol=0.0, init="random", autotune=AutotuneCache(None))
    dk = DistributedKMeans(est, mesh)
    backend = dk._shard_backend()
    assert not backend.fuses_update
    inj = no_step_injection(backend.kernel_kind)

    def sds(shape, spec, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))
    txt = dk._build_step(m_local, F).lower(
        sds((len(topo.devices) * m_local, F), P("data", None)),
        sds((k, F), P(None, None)), sds(inj.shape, P(None), inj.dtype),
        sds((1, k, F), P(None, None, None))).compile().as_text()
    assert "cluster_sums" in txt and "tpu_custom_call" in txt


def test_kmeanspp_round(one_chip):
    _, p = _tuned("init", BN, BK, F)
    txt = _compiled_text(
        lambda keys, x: init_kmeanspp_fused(keys, x, BK, params=p,
                                            use_kernel=True,
                                            interpret=False),
        _sds(one_chip, (B, 2), jnp.uint32), _sds(one_chip, (B, BN, F)))
    assert "tpu_custom_call" in txt


# the MLA latent cell: 4 prompts' [c_kv; k_pe] x 7 MLA layers, F = 576
LATENT = tuple(n for n in (42495, 71468, 120194, 202141) for _ in range(7))
LATENT_F = 576


def test_fused_lloyd_ragged_bf16_rows(one_chip):
    """The ragged step at the MLA latent cell's shapes: 28 problems, sum N
    = 3,054,086 bf16 rows of 576 features against f32 centroids (three
    bf16 passes), the pack included. Features pad to 640 lanes, and no
    f32 copy of the rows is made: the program's temporaries hold the
    640-lane bf16 pack (4.0 GB) and stay below the 7.04 GB that an f32
    copy of the rows alone would take."""
    _, p = _tuned("batched", max(LATENT), BK, LATENT_F, batch=len(LATENT))
    p = ops.ragged_params(p, max(LATENT), BK, LATENT_F, jnp.bfloat16)
    assert ops._round_up(LATENT_F, p.block_f) == 640
    rows = sum(LATENT)
    compiled = jax.jit(
        lambda x, c: ops.fused_lloyd_batched(
            ops.plan_data_batched(x, p, LATENT), c, interpret=False)).lower(
        _sds(one_chip, (rows, LATENT_F), jnp.bfloat16),
        _sds(one_chip, (len(LATENT), BK, LATENT_F))).compile()
    txt = compiled.as_text()
    assert "lloyd_step_ragged" in txt and "tpu_custom_call" in txt
    pack = sum(-(-n // p.block_m) for n in LATENT) * p.block_m * 640 * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert pack <= temp < rows * LATENT_F * 4
