"""The two-pass update's per-cluster sums (``ops.cluster_sums``): the
sorted-window kernel (interpret mode) and the dense one-hot product against
the ``ref.centroid_update`` oracle, the K/M rule that picks between them,
DMR around either, and the callers that run the update."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KMeans
from repro.core.kmeans import protected_sums
from repro.data.blobs import make_blobs
from repro.kernels import cluster_sums as _cs
from repro.kernels import ops, ref


def _sorted(x, am, k):
    return ops._sorted_sums(x, am, k, interpret=True)


def _labels(case, m, k, key):
    if case == "random":
        return jax.random.randint(key, (m,), 0, k)
    if case == "one_cluster":
        return jnp.full((m,), k // 3, jnp.int32)
    # two bands of labels with a run of empty clusters between them wider
    # than a window: every tile holding both bands spans two windows
    lo = jax.random.randint(key, (m,), 0, 10)
    return jnp.where(jnp.arange(m) % 2 == 0, lo, lo + 3 * _cs.WINDOW)


CASES = [
    # (labels, m, f, k, dtype)
    ("random", 3000, 16, 300, jnp.float32),   # K % 128, M % tile != 0
    ("random", 1000, 128, 1000, jnp.float32),  # a tile spans every window
    ("one_cluster", 2100, 24, 200, jnp.float32),
    ("empty_run", 2500, 8, 3 * _cs.WINDOW + 10, jnp.float32),
    ("random", 2500, 24, 130, jnp.bfloat16),
]


@pytest.mark.parametrize("case,m,f,k,dtype", CASES)
def test_sorted_sums_match_the_oracle(case, m, f, k, dtype):
    kx, ka = jax.random.split(jax.random.PRNGKey(m + k))
    x = (3.0 * jax.random.normal(kx, (m, f))).astype(dtype)
    am = _labels(case, m, k, ka)
    got = _sorted(x, am, k)
    sums, counts = ref.centroid_update(x, am, k)
    np.testing.assert_array_equal(np.asarray(got.counts), np.asarray(counts))
    np.testing.assert_allclose(np.asarray(got.sums), np.asarray(sums),
                               rtol=1e-5, atol=1e-4)
    assert got.sums.shape == (k, f) and got.sums.dtype == jnp.float32
    if case == "empty_run":
        assert float(got.windows) > 1.0     # the window loop ran twice
    if case == "one_cluster":
        assert float(got.windows) == 1.0


def test_sorted_sums_are_deterministic():
    x, _ = make_blobs(3000, 16, 6, seed=2)
    am = jax.random.randint(jax.random.PRNGKey(1), (3000,), 0, 257)
    a, b = _sorted(x, am, 257), _sorted(x, am, 257)
    assert jnp.array_equal(a.sums, b.sums)
    assert jnp.array_equal(a.counts, b.counts)


def test_label_sort_key_and_two_operand_sort_agree():
    """The packed one-operand key and the two-operand stable sort (taken
    when ``label·2^b + row`` overflows 32 bits) give one order."""
    am = jax.random.randint(jax.random.PRNGKey(6), (5000,), 0, 300)
    packed = ops._sort_labels(am, 300)
    wide = ops._sort_labels(am, 2 ** 20)     # 2^20 · 2^13 > 2^32
    want = jnp.argsort(am, stable=True)
    for labels, order in (packed, wide):
        assert jnp.array_equal(order, want)
        assert jnp.array_equal(labels, am[want])


def test_singleton_clusters_sum_exactly():
    """Each cluster holds one row: its sum is the row, bit for bit (three
    exact bf16 slices, nothing rounded)."""
    m, f = 1500, 32
    x = jax.random.normal(jax.random.PRNGKey(4), (m, f)) * 1e3
    am = jax.random.permutation(jax.random.PRNGKey(5), m).astype(jnp.int32)
    got = _sorted(x, am, m)
    assert jnp.array_equal(got.sums[am], x)


def test_dense_sums_are_the_oracle_product():
    x, _ = make_blobs(2000, 16, 6, seed=3)
    am = jax.random.randint(jax.random.PRNGKey(2), (2000,), 0, 40)
    got = ops._dense_sums(x, am, 40)
    sums, counts = ref.centroid_update(x, am, 40)
    assert jnp.array_equal(got.sums, sums)
    assert jnp.array_equal(got.counts, counts)
    assert float(got.windows) == 0.0


F32_MIN_K = ops.SORTED_MIN_K[jnp.dtype(jnp.float32)]


@pytest.mark.parametrize("m,k,f,path", [
    (1_000_000, 4096, 128, "sorted"),      # the IVF4096 cell
    (1_000_000, F32_MIN_K, 128, "sorted"),
    (1_000_000, 2048, 128, "dense"),       # the dense product is cheaper
    (1_000_000, F32_MIN_K - 1, 128, "dense"),
    (ops.SORTED_BLOCK_M - 1, 4096, 128, "dense"),   # under one tile
    (1_000_000, 262_144, 128, "dense"),    # its sums block outgrows VMEM
])
def test_update_path_rule(m, k, f, path):
    assert ops.update_path(m, k, f) == path


@pytest.mark.parametrize("dtype,k,path", [
    # bf16 takes the dense product in one MXU pass, not three
    (jnp.bfloat16, 4096, "dense"),
    (jnp.bfloat16, 5375, "dense"),
    (jnp.bfloat16, 5376, "sorted"),
    (jnp.bfloat16, 8192, "sorted"),
    # no float16 row load in Mosaic; int8 fits hand the update an f32 X
    (jnp.float16, 8192, "dense"),
    (jnp.int8, 8192, "dense"),
])
def test_update_path_by_dtype(dtype, k, path):
    assert ops.update_path(1_000_000, k, 128, dtype) == path


def test_cluster_sums_off_the_chip_is_dense():
    k = F32_MIN_K
    x, _ = make_blobs(2000, 16, 6, seed=3)
    am = jax.random.randint(jax.random.PRNGKey(2), (2000,), 0, k)
    assert not ops.on_tpu()
    assert ops.update_path(2000, k, 16) == "sorted"
    got = ops.cluster_sums(x, am, k)
    assert float(got.windows) == 0.0
    assert jnp.array_equal(got.sums, ops._dense_sums(x, am, k).sums)


def test_dmr_recomputes_on_a_planted_mismatch(monkeypatch):
    """The first (primary) pass is corrupted; DMR sees it disagree with the
    replica and returns the recompute, which equals the oracle."""
    x, _ = make_blobs(1500, 16, 5, seed=4)
    am = jax.random.randint(jax.random.PRNGKey(3), (1500,), 0, 300)
    calls = []

    def planted(x, a, k):
        calls.append(1)
        out = _sorted(x, a, k)
        if len(calls) == 1:
            return out._replace(sums=out.sums.at[7, 3].add(1.0))
        return out
    monkeypatch.setattr(ops, "cluster_sums", planted)
    got = jax.jit(lambda x, a: protected_sums(x, a, 300, use_dmr=True))(
        x, am)
    assert len(calls) == 3                  # primary, replica, recompute
    sums, counts = ref.centroid_update(x, am, 300)
    np.testing.assert_allclose(np.asarray(got.sums), np.asarray(sums),
                               rtol=1e-5, atol=1e-4)
    assert jnp.array_equal(got.counts, counts)
    monkeypatch.setattr(ops, "cluster_sums", _sorted)
    plain = protected_sums(x, am, 300, use_dmr=False)
    assert jnp.array_equal(plain.sums, _sorted(x, am, 300).sums)


def _fit(x, **kw):
    return KMeans(40, max_iter=6, tol=0.0, init="random", random_state=0,
                  **kw).fit(x)


def test_fit_reports_windows_and_matches_the_dense_fit(monkeypatch):
    x, _ = make_blobs(3000, 16, 40, seed=5)
    dense = _fit(x)
    assert dense.update_windows_ == 0.0
    monkeypatch.setattr(ops, "cluster_sums", _sorted)
    srt = _fit(x)
    assert srt.update_windows_ >= 1.0
    assert jnp.array_equal(srt.labels_, dense.labels_)
    np.testing.assert_allclose(np.asarray(srt.cluster_centers_),
                               np.asarray(dense.cluster_centers_),
                               rtol=1e-5, atol=1e-5)
    mini = _fit(x, batch_size=1024)
    assert mini.update_windows_ >= 1.0


def test_one_pass_fit_reports_no_windows():
    x, _ = make_blobs(1000, 8, 4, seed=6)
    km = KMeans(4, max_iter=3, backend="lloyd_xla").fit(x)
    assert km.update_windows_ is None


def test_partial_fit_matches_the_dense_update(monkeypatch):
    x, _ = make_blobs(4000, 16, 40, seed=7)
    blocks = [x[i:i + 1000] for i in range(0, 4000, 1000)]

    def stream():
        km = KMeans(40, init="random", random_state=0)
        for b in blocks:
            km.partial_fit(b)
        return km.cluster_centers_
    dense = stream()
    monkeypatch.setattr(ops, "cluster_sums", _sorted)
    np.testing.assert_allclose(np.asarray(stream()), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)


def test_distributed_row_mode_matches_the_dense_update(monkeypatch):
    from repro.dist.kmeans_dist import DistributedKMeans
    x, _ = make_blobs(2048, 16, 40, seed=8)
    mesh = jax.make_mesh((1,), ("data",))

    def fit():
        est = KMeans(40, max_iter=5, tol=0.0, init="random", random_state=0)
        dk = DistributedKMeans(est, mesh)
        return dk.fit(dk.shard_data(x), est.init_centroids(x))
    c_d, am_d, inertia_d = fit()[:3]
    monkeypatch.setattr(ops, "cluster_sums", _sorted)
    c_s, am_s, inertia_s = fit()[:3]
    assert jnp.array_equal(am_s, am_d)
    np.testing.assert_allclose(np.asarray(c_s), np.asarray(c_d),
                               rtol=1e-5, atol=1e-5)
    assert abs(float(inertia_s) - float(inertia_d)) <= 1e-5 * abs(
        float(inertia_d))
