"""Ragged batched fits: B problems of different row counts, packed back to
back as (sum N, F) rows with ``lengths``, through ``BatchedKMeans`` and
the ``lloyd_batched`` / ``lloyd_batched_xla`` backends.

Each problem is judged on its own rows against ``kernels/ref.py``; equal
lengths must equal the stacked fit bit for bit; padded rows must change
nothing. Pallas kernels run interpret=True (kernel bodies in Python).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import BatchedKMeans
from repro.data.blobs import make_blobs
from repro.kernels import ops, ref
from repro.kernels.ops import KernelParams

BACKENDS = ["lloyd_batched", "lloyd_batched_xla"]
# 64-row tiles: lengths below a tile, off the tile grid, on it, B = 1, and
# runs of problems with one tile count but different lengths
LENGTHS = [(5, 130, 77), (64, 128, 1, 200), (300,), (100, 120, 30, 60)]
TILES = KernelParams(64, 128, 128)
F, K = 12, 4


def _rows(lengths, seed=0):
    return jnp.concatenate([make_blobs(n, F, K, seed=seed + i)[0]
                            for i, n in enumerate(lengths)])


def _split(rows, lengths):
    ends = np.cumsum(lengths)
    return [rows[e - n:e] for n, e in zip(lengths, ends)]


def _centroids(rows, lengths, seed=1):
    """K rows of each problem, drawn with replacement (a problem may hold
    fewer rows than K)."""
    key = jax.random.PRNGKey(seed)
    return jnp.stack([xb[jax.random.randint(k, (K,), 0, xb.shape[0])]
                      for k, xb in zip(jax.random.split(key, len(lengths)),
                                       _split(rows, lengths))])


def _plan(backend, rows, lengths):
    return ops.plan_data_batched(
        rows, TILES if backend == "lloyd_batched" else None, lengths)


def _step(backend, plan, c):
    if backend == "lloyd_batched":
        return ops.fused_lloyd_batched(plan, c, interpret=True)
    from repro.core.assignment import assign_lloyd_batched_xla
    am, md, _, sums, counts = assign_lloyd_batched_xla(plan, c)
    return am, md, sums, counts


@pytest.mark.parametrize("lengths", LENGTHS, ids=str)
@pytest.mark.parametrize("backend", BACKENDS)
def test_step_matches_reference_per_problem(backend, lengths):
    """One ragged step: each problem's labels, distances, sums and counts
    are those of ``kernels/ref.py`` on that problem's rows alone; slots
    past a problem's rows hold 0."""
    rows = _rows(lengths)
    c = _centroids(rows, lengths)
    am, md, sums, counts = _step(backend, _plan(backend, rows, lengths), c)
    n_max = max(lengths)
    assert am.shape == md.shape == (len(lengths), n_max)
    for b, (xb, n) in enumerate(zip(_split(rows, lengths), lengths)):
        md_r, am_r, sums_r, counts_r = ref.lloyd_step(xb, c[b])
        np.testing.assert_array_equal(np.asarray(am[b, :n]),
                                      np.asarray(am_r))
        # distances round on the scale of |x|^2 + |c|^2, not of themselves
        scale = float(jnp.max(jnp.sum(xb * xb, axis=1))
                      + jnp.max(jnp.sum(c[b] * c[b], axis=1)))
        np.testing.assert_allclose(
            np.asarray(md[b, :n]),
            np.asarray(md_r + jnp.sum(xb * xb, axis=1)), rtol=1e-5,
            atol=1e-6 * scale)
        np.testing.assert_allclose(np.asarray(sums[b]), np.asarray(sums_r),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(counts[b]),
                                      np.asarray(counts_r))
        assert not np.any(np.asarray(am[b, n:]))
        assert not np.any(np.asarray(md[b, n:]))


@pytest.mark.parametrize("lengths", LENGTHS, ids=str)
@pytest.mark.parametrize("backend", BACKENDS)
def test_fit_matches_reference_per_problem(backend, lengths):
    """A ragged fit of one iteration: packed labels, inertia and new
    centroids of every problem from its own rows."""
    rows = _rows(lengths, seed=5)
    c = _centroids(rows, lengths, seed=6)
    bkm = BatchedKMeans(K, max_iter=1, backend=backend, params=TILES,
                        random_state=2).fit(rows, lengths=lengths,
                                            centroids=c)
    assert bkm.labels_.shape == (sum(lengths),)
    labels = _split(bkm.labels_, lengths)
    for b, xb in enumerate(_split(rows, lengths)):
        _, am_r, sums_r, counts_r = ref.lloyd_step(xb, c[b])
        np.testing.assert_array_equal(np.asarray(labels[b]),
                                      np.asarray(am_r))
        inertia = float(jnp.sum((xb - c[b][am_r]) ** 2))
        scale = float(jnp.sum(xb * xb) + jnp.sum(c[b][am_r] ** 2))
        np.testing.assert_allclose(bkm.inertia_[b], inertia, rtol=1e-5,
                                   atol=1e-6 * scale)
        full = np.asarray(counts_r) > 0
        means = np.asarray(sums_r)[full] / np.asarray(counts_r)[full, None]
        np.testing.assert_allclose(np.asarray(bkm.cluster_centers_[b])[full],
                                   means, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("init", ["random", "kmeans++"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_equal_lengths_bit_identical_to_stacked(backend, init):
    """Packed problems of one length fit exactly as their (B, N, F) stack:
    init, labels, centroids, inertia and iterations, bit for bit."""
    b, n = 3, 200
    x = jnp.stack([make_blobs(n, F, K, seed=20 + i)[0] for i in range(b)])
    kw = dict(max_iter=12, backend=backend, init=init, random_state=3,
              params=TILES)
    stacked = BatchedKMeans(K, **kw).fit(x)
    ragged = BatchedKMeans(K, **kw).fit(x.reshape(b * n, F),
                                        lengths=[n] * b)
    np.testing.assert_array_equal(np.asarray(ragged.cluster_centers_),
                                  np.asarray(stacked.cluster_centers_))
    np.testing.assert_array_equal(np.asarray(ragged.labels_),
                                  np.asarray(stacked.labels_).reshape(-1))
    np.testing.assert_array_equal(ragged.inertia_, stacked.inertia_)
    np.testing.assert_array_equal(ragged.n_iter_, stacked.n_iter_)


@pytest.mark.parametrize("lengths,entry", [
    ((64, 64, 64), "lloyd_step_batched"),
    ((5, 130, 77), "lloyd_step_ragged")], ids=str)
def test_launch_is_named_by_its_lengths(lengths, entry):
    """One kernel under two names: problems of one row count launch as
    ``lloyd_step_batched``, problems of different counts as
    ``lloyd_step_ragged``."""
    rows = _rows(lengths)
    text = str(jax.make_jaxpr(
        lambda p, c: ops.fused_lloyd_batched(p, c, interpret=True))(
            _plan("lloyd_batched", rows, lengths),
            _centroids(rows, lengths)))
    other = ({"lloyd_step_batched", "lloyd_step_ragged"} - {entry}).pop()
    assert f"name={entry}" in text and f"name={other}" not in text


@pytest.mark.parametrize("lengths", LENGTHS[:2], ids=str)
def test_padded_rows_change_nothing(lengths):
    """Values planted in the rows that pad each problem to whole tiles
    (and in their norms) reach no output of the ragged kernel."""
    rows = _rows(lengths, seed=8)
    c = _centroids(rows, lengths, seed=9)
    plan = _plan("lloyd_batched", rows, lengths)
    pad = np.ones(plan.xp.shape[0], bool)
    for off, n in zip(plan.offsets, lengths):
        pad[off:off + n] = False
    assert pad.sum() == plan.rows_padded > 0
    mask = jnp.asarray(pad)
    dirty = dataclasses.replace(
        plan, xp=jnp.where(mask[:, None], 1e3, plan.xp),
        xn=jnp.where(mask, -1e9, plan.xn))
    for a, b in zip(_step("lloyd_batched", plan, c),
                    _step("lloyd_batched", dirty, c)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("backend", BACKENDS)
def test_emptied_cluster_reseeded_from_own_problem(backend):
    """A centroid far from every row empties; it is moved onto the
    farthest row of its own problem, never onto another problem's."""
    lengths = (40, 90, 17)
    rows = _rows(lengths, seed=11)
    c = _centroids(rows, lengths, seed=12).at[1, 0].set(1e4)
    bkm = BatchedKMeans(K, max_iter=1, backend=backend, params=TILES,
                        random_state=0).fit(rows, lengths=lengths,
                                            centroids=c)
    parts = _split(rows, lengths)
    new = np.asarray(bkm.cluster_centers_[1, 0])
    own = np.asarray(parts[1])
    d = np.sum((own[:, None] - np.asarray(c[1])[None, 1:]) ** 2,
               axis=2).min(axis=1)
    np.testing.assert_array_equal(new, own[np.argmax(d)])
    for b in (0, 2):
        assert not np.any(np.all(np.asarray(parts[b]) == new, axis=1))


@pytest.mark.parametrize("init", ["random", "kmeans++"])
def test_init_draws_each_problem_from_its_own_rows(init):
    lengths = (9, 50, 23)
    rows = _rows(lengths, seed=13)
    c = BatchedKMeans(K, init=init, random_state=4).init_centroids(
        rows, lengths=lengths)
    assert c.shape == (len(lengths), K, F)
    for b, xb in enumerate(_split(rows, lengths)):
        hit = np.all(np.asarray(c[b])[:, None, :] == np.asarray(xb)[None],
                     axis=2)
        assert hit.any(axis=1).all()


def test_counters_of_a_launch():
    """The fitted counters: valid rows, the rows padding them to whole
    tiles, and the row tiles, per launch."""
    lengths = (5, 130, 77)
    rows = _rows(lengths)
    bkm = BatchedKMeans(K, max_iter=1, backend="lloyd_batched", params=TILES)
    bkm.fit(rows, lengths=lengths)
    assert bkm.row_tiles_ == 1 + 3 + 2
    assert bkm.rows_valid_ == 212
    assert bkm.rows_padded_ == 64 * 6 - 212
    xla = BatchedKMeans(K, max_iter=1, backend="lloyd_batched_xla")
    xla.fit(rows, lengths=lengths)
    assert (xla.rows_valid_, xla.rows_padded_, xla.row_tiles_) == (212, 0, 0)


@pytest.mark.parametrize("runs", [(4, 4, 4), (1, 5, 12, 3), (7,),
                                  (2, 2, 3, 3, 3, 1)], ids=str)
def test_segment_tree_sum(runs):
    """Each problem's partials, laid out in ``tile_slot`` order, are
    summed in ``_tree_sum``'s own pairs."""
    a = jnp.asarray(np.random.default_rng(0).normal(
        size=(sum(runs), 3, 5)).astype(np.float32))
    slotted = jnp.zeros_like(a).at[ops._tile_slots(list(runs))].set(a)
    got = ops._segment_tree_sum(slotted, runs)
    starts = np.cumsum((0,) + runs[:-1])
    for b, (s, n) in enumerate(zip(starts, runs)):
        np.testing.assert_array_equal(np.asarray(got[b]),
                                      np.asarray(ops._tree_sum(a[s:s + n])))


def test_ragged_tiles_scale_with_k():
    p = ops.ragged_params(KernelParams(1024, 128, 128), 105_545, 256, 128)
    assert p.block_m == 4096
    assert ops.ragged_params(KernelParams(1024, 128, 128), 300, 256,
                             128).block_m == 256
    assert ops.ragged_params(KernelParams(1024, 128, 128), 10**6, 16,
                             128).block_m == 2048


def test_ragged_input_errors():
    rows = _rows((10, 20))
    bkm = BatchedKMeans(K, max_iter=2)
    with pytest.raises(ValueError, match="sum to 31"):
        bkm.fit(rows, lengths=[10, 21])
    with pytest.raises(ValueError, match="at least one row"):
        bkm.fit(rows, lengths=[30, 0])
    with pytest.raises(ValueError, match="packed"):
        bkm.fit(rows.reshape(2, 15, F), lengths=[15, 15])
    with pytest.raises(ValueError, match="1-D integer"):
        bkm.fit(rows, lengths=[[10, 20]])
    with pytest.raises(ValueError, match="lengths="):
        bkm.fit(rows)
    bkm.fit(rows, lengths=[10, 20])
    with pytest.raises(ValueError, match="ragged"):
        bkm.predict(rows)
    with pytest.raises(ValueError, match="ragged"):
        bkm.score(rows)
    with pytest.raises(ValueError, match="without KernelParams"):
        ops.fused_lloyd_batched(ops.plan_data_batched(rows, None, [10, 20]),
                                jnp.zeros((2, K, F)))


def test_distributed_fit_takes_no_lengths():
    from repro.dist.kmeans_dist import DistributedKMeans
    with pytest.raises(TypeError, match="lengths"):
        DistributedKMeans.fit(object(), _rows((10,)), lengths=[10])
