"""Ragged batched fits: B problems of different row counts, packed back to
back as (sum N, F) rows with ``lengths``, through ``BatchedKMeans`` and
the ``lloyd_batched`` / ``lloyd_batched_xla`` backends.

Each problem is judged on its own rows against ``kernels/ref.py``; equal
lengths must equal the stacked fit bit for bit; padded rows must change
nothing. Rows stored in bf16 at f32 arithmetic (``Bf16Rows`` cases) are
judged on their rows upcast to f32. Pallas kernels run interpret=True
(kernel bodies in Python).
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
F_WIDE = 300     # 3 lane groups: pads to 384, in 128-lane steps at TILES


@dataclasses.dataclass(frozen=True)
class Bf16Rows:
    """A case of ragged rows stored in bf16, F_WIDE features, fit at f32
    against centroids that bf16 does not hold (so all three bf16 slices
    of each centroid are nonzero)."""
    lengths: tuple

    def __str__(self):
        return f"bf16-F{F_WIDE}-{self.lengths}"


WIDE = [Bf16Rows((5, 130, 77)), Bf16Rows((64, 128, 1, 200))]


def _rows(lengths, seed=0, f=F):
    return jnp.concatenate([make_blobs(n, f, K, seed=seed + i)[0]
                            for i, n in enumerate(lengths)])


def _case(case, seed):
    """(lengths, rows, centroids) of a case: f32 rows of F features and K
    of their rows (seed + 1), or a Bf16Rows case's bf16 rows with f32
    centroids off the bf16 grid."""
    if not isinstance(case, Bf16Rows):
        rows = _rows(case, seed)
        return case, rows, _centroids(rows, case, seed + 1)
    rows = _rows(case.lengths, seed, F_WIDE).astype(jnp.bfloat16)
    c = _centroids(rows, case.lengths, seed + 1).astype(jnp.float32)
    return case.lengths, rows, c + 0.3 * jax.random.normal(
        jax.random.PRNGKey(seed), c.shape)


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


@pytest.mark.parametrize("lengths", LENGTHS + WIDE, ids=str)
@pytest.mark.parametrize("backend", BACKENDS)
def test_step_matches_reference_per_problem(backend, lengths):
    """One ragged step: each problem's labels, distances, sums and counts
    are those of ``kernels/ref.py`` on that problem's rows alone; slots
    past a problem's rows hold 0. Tolerances are f32's for bf16 rows too:
    every product of the three-pass distance and of the one-pass update
    is exact, and only the order of f32 sums differs."""
    lengths, rows, c = _case(lengths, 0)
    am, md, sums, counts = _step(backend, _plan(backend, rows, lengths), c)
    n_max = max(lengths)
    assert am.shape == md.shape == (len(lengths), n_max)
    for b, (xb, n) in enumerate(zip(_split(rows, lengths), lengths)):
        xb = xb.astype(jnp.float32)
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


@pytest.mark.parametrize("lengths", LENGTHS + WIDE, ids=str)
@pytest.mark.parametrize("backend", BACKENDS)
def test_fit_matches_reference_per_problem(backend, lengths):
    """A ragged fit of one iteration: packed labels, inertia and new
    centroids of every problem from its own rows. Inertia is a sum of f32
    distances, which round on the scale of |x|^2 + |c|^2; centroids are
    means of exact f32 sums, which round only in their order."""
    lengths, rows, c = _case(lengths, 5)
    bkm = BatchedKMeans(K, max_iter=1, backend=backend, params=TILES,
                        random_state=2).fit(rows, lengths=lengths,
                                            centroids=c)
    assert bkm.labels_.shape == (sum(lengths),)
    labels = _split(bkm.labels_, lengths)
    for b, xb in enumerate(_split(rows, lengths)):
        xb = xb.astype(jnp.float32)
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


@pytest.mark.parametrize("rows,compute,f,lanes,passes", [
    ("bfloat16", "float32", F_WIDE, 384, 3),
    ("float32", "float32", 128, 128, 6),
    ("bfloat16", "bfloat16", F_WIDE, 384, 1)])
@pytest.mark.parametrize("backend", BACKENDS)
def test_rows_keep_storage_dtype_and_counters(backend, rows, compute, f,
                                              lanes, passes, monkeypatch):
    """Rows stored in bf16 stay bf16 in the plan under f32 arithmetic (no
    f32 copy is made); the counters give the padded feature width (F on
    the XLA analogue) and the bf16 passes of each distance product."""
    plans = []
    orig = ops.plan_data_batched

    def spy(x, params=None, lengths=None):
        plans.append((x.dtype, orig(x, params, lengths)))
        return plans[-1][1]
    monkeypatch.setattr(ops, "plan_data_batched", spy)
    lengths = (5, 130, 77)
    x = _rows(lengths, 3, f).astype(rows)
    # block_f 512 asks for more than 300 features need: one 384-lane step
    bkm = BatchedKMeans(K, max_iter=1, backend=backend, compute_dtype=compute,
                        params=KernelParams(64, 128, 512)).fit(
        x, lengths=lengths)
    (given, plan), = plans
    assert given == plan.xp.dtype == jnp.dtype(rows)
    assert bkm.cluster_centers_.dtype == jnp.float32
    pallas = backend == "lloyd_batched"
    assert bkm.feature_lanes_ == plan.xp.shape[1] == (lanes if pallas else f)
    assert bkm.distance_passes_ == passes


def test_feature_tiles_pad_to_lane_groups():
    """Padded F is round_up(F, 128) and the feature tile divides it: 300
    features pad to 384 (the halving rule gave 512), 576 to 640 in five
    128-lane steps or one of 640; power-of-two widths are as before."""
    def tile(f, block_f):
        return ops.clamp_params(4096, 256, f, KernelParams(1024, 128,
                                                           block_f)).block_f
    assert tile(300, 512) == 384 and tile(300, 128) == 128
    assert tile(576, 512) == 128 and tile(576, 1024) == 640
    assert [tile(f, 512) for f in (8, 12, 128, 200, 1000)] == [
        128, 128, 128, 256, 512]
    plan = ops.plan_data_batched(_rows((5, 130), 0, F_WIDE),
                                 ops.clamp_params(130, K, F_WIDE,
                                                  ops.DEFAULT_PARAMS),
                                 (5, 130))
    assert plan.xp.shape[1] == 384
    # the VMEM model counts bf16 rows at 2 bytes: their double-buffered
    # tile and their stash, beside an f32 centroid tile
    p = KernelParams(4096, 128, 640)
    f32 = ops.lloyd_batched_vmem_bytes(p, 256, 576, jnp.float32)
    mixed = ops.lloyd_batched_vmem_bytes(p, 256, 576, jnp.float32,
                                         rows_dtype=jnp.bfloat16)
    assert f32 - mixed == 2 * (2 * 4096 * 640 + 4096 * 640)
    # a ragged launch takes the whole padded width in one step where it
    # fits VMEM (128 lanes for the KV-key cell's F, as before)
    q = KernelParams(1024, 128, 128)
    assert ops.ragged_params(q, 202_141, 256, 576, jnp.bfloat16).block_f \
        == 640
    assert ops.ragged_params(q, 105_545, 256, 128).block_f == 128


def test_three_slice_product_is_exact():
    """bf16 rows times f32 centroids in three bf16 passes: the slices sum
    to the centroids exactly, each partial product is exact in f32, and
    the product lies within f32 summation rounding of the float64 one,
    over 2**9 closer than one bf16 pass."""
    from repro.kernels._mosaic import bf16_slices, mxu_dot
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(64, 300)) * 8.0, jnp.bfloat16)
    b = jnp.asarray(rng.normal(size=(32, 300)) * np.exp2(
        rng.integers(-6, 6, size=(32, 300))), jnp.float32)
    a64, b64 = np.asarray(a, np.float64), np.asarray(b, np.float64)
    slices = [np.asarray(s, np.float64) for s in bf16_slices(b)]
    np.testing.assert_array_equal(sum(slices), b64)
    for s in slices:    # 8 x 8 significant bits: exact in f32
        prod = a64[:, None, :] * s[None, :, :]
        np.testing.assert_array_equal(prod.astype(np.float32), prod)
    exact = a64 @ b64.T
    bound = 300 * 2.0 ** -24 * (np.abs(a64) @ np.abs(b64).T)
    err = np.abs(np.asarray(mxu_dot(a, b, (1, 1)), np.float64) - exact)
    assert np.all(err <= bound)
    one_pass = np.asarray(mxu_dot(a, b.astype(jnp.bfloat16), (1, 1)),
                          np.float64)
    assert np.max(err) * 2.0 ** 9 < np.max(np.abs(one_pass - exact))


@pytest.mark.parametrize("backend", BACKENDS)
def test_bf16_control_rounds_centroids_and_misses(backend):
    """``compute_dtype="bfloat16"`` on the same bf16 rows rounds the
    centroids to bf16 for the distance product: its inertia misses the
    f32 tolerance that the f32 arithmetic meets."""
    lengths, rows, c = _case(WIDE[0], 5)
    misses = {}
    for compute in ("float32", "bfloat16"):
        bkm = BatchedKMeans(K, max_iter=1, backend=backend, params=TILES,
                            compute_dtype=compute).fit(
            rows, lengths=lengths, centroids=c)
        worst = 0.0
        for b, xb in enumerate(_split(rows, lengths)):
            xb = xb.astype(jnp.float32)
            _, am_r, _, _ = ref.lloyd_step(xb, c[b])
            inertia = float(jnp.sum((xb - c[b][am_r]) ** 2))
            scale = float(jnp.sum(xb * xb) + jnp.sum(c[b][am_r] ** 2))
            worst = max(worst, abs(bkm.inertia_[b] - inertia)
                        / (1e-5 * inertia + 1e-6 * scale))
        misses[compute] = worst
    assert misses["float32"] < 1.0 < misses["bfloat16"]


def test_wide_random_state():
    """Per-problem keys: bit for bit the int32 ``PRNGKey(random_state +
    b)`` below 2**31 (its wrap past 2**31 and negative seeds included);
    wider seeds work, their bits past 32 in the key's high word."""
    for seed in (0, 7, 2**31 - 3, -5):
        want = jax.vmap(jax.random.PRNGKey)(seed + jnp.arange(6))
        got = BatchedKMeans(K, random_state=seed)._problem_keys(6)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert got.dtype == want.dtype
    wide = BatchedKMeans(K, random_state=2**33 + 7)
    np.testing.assert_array_equal(np.asarray(wide._problem_keys(2)),
                                  [[2, 7], [2, 8]])
    lengths = (40, 90)
    rows = _rows(lengths, seed=14)
    wide.max_iter = 2
    wide.fit(rows, lengths=lengths)
    assert wide.cluster_centers_.shape == (2, K, F)
