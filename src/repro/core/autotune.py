"""Kernel parameter generation + selection (paper §III-B code generation).

The paper generates ~150 CUTLASS kernels per dtype over a pruned parameter
space, keeps those that compile and run, benchmarks 64 problem sizes and
selects a per-shape winner. On TPU the "template instantiation" is a Pallas
closure specialization, but the search/selection pipeline is the same — and
as of the template-family refactor it searches three axes, not one:

  variant x tiles x dtype

  1. ``parameter_space(dtype)`` — candidates under the paper's pruning rules
                               (§III-B-1): powers of two, contraction tile
                               tied to the pipeline depth, MXU-aligned
                               tiles. 2-byte dtypes admit wider tiles (the
                               same VMEM budget holds twice the elements).
  2. ``feasible()``          — does the kernel lower (compile-time check),
                               does the working set fit VMEM (dtype-aware
                               byte sizing), is the sublane alignment legal
                               for the dtype, and — for the ``smallk``
                               variant — does padded K fit one tile.
  3. ``score()``             — selection criterion. Two modes:
                               "model": analytical HBM-traffic/MXU-occupancy
                               model (used when the target TPU is absent —
                               this container), "measure": wall-time of the
                               real kernel (used on device).
  4. ``AutotuneCache``       — per-shape winners, persisted as JSON: the
                               kernel-selection table the runtime consults.
                               Lives in ``repro.api.cache`` as an injectable
                               object (passed per-estimator); this module
                               keeps only the search/selection pipeline.

``select_params`` returns a ``(variant, KernelParams)`` pair. The variant
is implied by the winning tiles (``ops.resolve_variant``: smallk iff K fits
one ``block_k`` tile), so kernel dispatch and selection can never disagree;
the pair makes the chosen template explicit to callers and to the cache.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from typing import Iterable, Optional

import jax
import jax.numpy as jnp

from repro import hw as _hw
from repro.kernels.ops import (PLAN_KINDS, VARIANTS, KernelParams, clamp_params,  # noqa: F401 — VARIANTS re-exported as selection vocabulary
                               feature_block, init_vmem_bytes,
                               int8_vmem_bytes,
                               lloyd_batched_vmem_bytes,
                               lloyd_ft_vmem_bytes, lloyd_vmem_bytes,
                               pruned_vmem_bytes, sublane_align, _round_up)

# TPU v5e constants — hoisted to repro.hw (shared with roofline/hw.py so the
# two models can't drift); the old names stay importable from here.
MXU_FLOPS = _hw.PEAK_FLOPS_BF16   # 2-byte peak; f32 ~ 1/2
HBM_BW = _hw.HBM_BW               # bytes/s
VMEM_BUDGET = _hw.VMEM_BUDGET     # bytes usable per core

# Kernel kinds sharing the tile-parameter space but with distinct VMEM
# footprints and HBM-traffic profiles (winners must not cross kinds).
# "lloyd_ft" is the one-pass FT kernel: one-pass footprint plus the fused
# dual-checksum scratch and the expected-checksum output blocks of the
# protected update epilogue; its model charges the checksum FLOPs/traffic.
# "batched" is the many-problem one-pass kernel: B problems per launch,
# a grid over their row tiles, padded K always a single centroid
# tile (so block_k is not a search axis and winners are additionally keyed
# by the B bucket — a B=4 launch and a B=1024 launch amortize dispatch and
# pipeline ramp-up very differently at the same per-problem shape).
# "pruned" is the bounds-carrying one-pass kernel: surviving tiles pay the
# one-pass cost, skipped tiles pay nothing, so its model takes an assumed
# prune rate and its measure mode runs on *clustered* data (uniform data
# never prunes, which would rank every candidate on full-compute time).
#
# The vocabulary itself lives in ``ops.PLAN_KINDS`` (the dispatch table of
# ``ops.kernel_plan``) so the cache-schema kinds, the contract checker and
# the selection pipeline extend from a single point of change.
KINDS = PLAN_KINDS

# Kinds that run the one-pass (fused-update) kernel family.
_LLOYD_KINDS = ("lloyd", "lloyd_ft", "pruned")


def shard_shape(m: int, k: int, f: int,
                data_shards: int) -> tuple[int, int, int]:
    """The per-shard problem shape a data-sharded fit autotunes for.

    A distributed fit's winner lookups key by the *local*
    ``(rows/shard, K, F)`` problem: tile selection sees the per-device
    GEMM, not the global one, and a winner tuned for the global M would
    pick block_m tiles the shard can't fill. Keeping the division here —
    rather than inline at call sites — makes the contract explicit and
    validated: rows must divide evenly, and a mesh rescale re-keys every
    lookup at the *new* shard shape (``DistributedKMeans`` rebuilds its
    step cache against this function after ``plan_rescale``).
    """
    if data_shards < 1:
        raise ValueError(f"data_shards must be >= 1, got {data_shards}")
    if m % data_shards:
        raise ValueError(
            f"rows m={m} do not divide evenly over {data_shards} data "
            f"shards; pad the input or pick a mesh whose row parallelism "
            f"divides M")
    return (m // data_shards, k, f)


def parameter_space(dtype=jnp.float32) -> list[KernelParams]:
    """Pruned candidate grid (paper rules: powers of 2; Warp.K=Threadblock.K
    maps to a single contraction tile; thread tile fixed by MXU shape).

    The grid is per-dtype, like the paper's per-dtype generator: 2-byte
    dtypes (bf16/fp16) halve every tile's bytes, so the same VMEM budget
    admits one more power of two on the sample and contraction axes.
    """
    block_ms = [64, 128, 256, 512, 1024]
    block_ks = [128, 256, 512]
    block_fs = [128, 256, 512, 1024]
    if jnp.dtype(dtype).itemsize <= 2:
        block_ms = block_ms + [2048]
        block_fs = block_fs + [2048]
    out = []
    for bm, bk, bf in itertools.product(block_ms, block_ks, block_fs):
        out.append(KernelParams(block_m=bm, block_k=bk, block_f=bf))
    return out


def feasible(p: KernelParams, dtype=jnp.float32, *, kind: str = "assign",
             shape: Optional[tuple[int, int, int]] = None,
             variant: str = "generic") -> bool:
    """VMEM fit + alignment. The lowering check happens once in tests
    (tests/test_autotune.py) — analogous to the paper's compile-and-run
    filter; here we apply the cheap structural conditions.

    Dtype-aware: the sublane alignment of ``block_m`` is 16 for 2-byte
    dtypes (vs 8 for f32) and the working-set bytes scale with the input
    itemsize. The ``smallk`` variant additionally needs the problem shape
    to check that padded K fits a single ``block_k`` tile; the one-pass
    Lloyd kernel keeps the whole stashed X row tile and its (K, F)
    partial-sum output block resident, so its VMEM model also depends on
    ``shape=(m, k, f)``.
    """
    if p.block_m % sublane_align(dtype) or p.block_k % 128 or p.block_f % 128:
        return False
    if kind == "batched":
        # one problem's tiles resident at a time; padded K is the single
        # centroid tile by construction, so block_k never enters. The
        # feature tile is the one the launch takes (F = 576: 640 lanes)
        if shape is None:
            return False
        _, k, f = shape
        p = dataclasses.replace(p, block_f=feature_block(f, p.block_f))
        return lloyd_batched_vmem_bytes(p, k, f, dtype) <= VMEM_BUDGET
    if variant == "smallk":
        if kind == "lloyd_ft":
            # FT templates keep the generic grid (checksum scratch is
            # already VMEM-resident; no revisited-output stream to save)
            return False
        if shape is None:
            return False
        _, k, _ = shape
        if _round_up(k, p.block_k) != p.block_k:
            return False
    if kind in _LLOYD_KINDS and shape is not None:
        _, k, f = shape
        vmem = {"lloyd_ft": lloyd_ft_vmem_bytes,
                "pruned": pruned_vmem_bytes}.get(kind, lloyd_vmem_bytes)
        return vmem(p, k, f, dtype) <= VMEM_BUDGET
    if kind == "int8":
        # fixed-dtype template: 1-byte tiles, f32 scale/norm vectors and
        # the int32 accumulator — its own exact byte model
        return int8_vmem_bytes(p) <= VMEM_BUDGET
    if kind == "init":
        # fused k-means++ round: the d² and tile-sum blocks put block_m
        # on a lane-tiled axis, so it needs the 128 alignment; features
        # are fully resident, so feasibility depends on F
        if shape is None or p.block_m % 128:
            return False
        _, _, f = shape
        return init_vmem_bytes(p, f) <= VMEM_BUDGET
    return p.vmem_bytes(dtype) <= VMEM_BUDGET


def iteration_traffic(m: int, k: int, f: int, p: KernelParams, *,
                      pipeline: str = "one_pass",
                      dtype=jnp.float32) -> dict[str, int]:
    """Per-Lloyd-iteration HBM byte traffic, itemized by source.

    ``pipeline`` names the iteration structure (distinct from the kernel
    ``kind`` vocabulary used by selection):

    ``"two_pass"``: the seed pipeline — fused assignment kernel, then
    a separate centroid-update pass that re-reads all of X, plus the
    per-iteration re-pad/re-norm of X the seed estimator performed inside
    every kernel call.

    ``"one_pass"``: the fused ``lloyd_step`` kernel — X enters the
    kernel once per centroid tile and is never read again; the update
    costs only the per-row-tile partial sums/counts round trip of the
    tree-reduction. Padding and norms are amortized by the per-fit
    :class:`~repro.kernels.ops.DataPlan` (zero per-iteration bytes).

    Byte sizing is split by stream: X/C reads move the input dtype
    (f32/bf16/fp16), while distances, partial sums, counts and the final
    centroids are always f32 and the argmin is always i32 — the previous
    model charged the input itemsize for those f32 streams too, skewing
    every non-f32 estimate.
    """
    if pipeline not in ("one_pass", "two_pass"):
        raise ValueError(f"pipeline must be 'one_pass' or 'two_pass', "
                         f"got {pipeline!r}")
    p = clamp_params(m, k, f, p, dtype)
    b = jnp.dtype(dtype).itemsize
    mp = _round_up(m, p.block_m)
    kp = _round_up(k, p.block_k)
    fp = _round_up(f, p.block_f)
    n_ktiles = kp // p.block_k
    n_mtiles = mp // p.block_m
    t = {
        "x_read": mp * fp * n_ktiles * b,         # once per centroid tile
        "c_read": kp * fp * n_mtiles * b,         # once per sample tile
        "assign_out": mp * (4 + 4),               # min-dist f32 + argmin i32
    }
    if pipeline == "two_pass":
        # re-pad write + 2x re-read in the input dtype; row norms are f32
        t["prep"] = (mp * fp + 2 * m * f) * b
        t["update_x_reread"] = m * f * b + m * 4  # second pass over X + labels
        t["update_out"] = (k * f + k) * 4         # sums/counts are f32
    else:
        t["prep"] = 0
        t["update_x_reread"] = 0
        # f32 partial blocks written by the kernel, then read + collapsed by
        # the tree-reduction into the (K, F) sums / (K,) counts
        partials = n_mtiles * (kp * fp + kp) * 4
        t["update_out"] = 2 * partials + (k * f + k) * 4
    t["total"] = sum(t.values())
    return t


def model_score(m: int, k: int, f: int, p: KernelParams,
                dtype=jnp.float32, kind: str = "assign",
                variant: str = "generic", batch: int = 1,
                prune_rate: float = 0.5) -> float:
    """Analytical time estimate (seconds) for one fused-kernel launch.

    HBM traffic: X is re-read once per centroid tile, C once per sample
    tile (the paper's §V-A-6 observation that balanced tiles minimize data
    movement); compute: 2 M K F MACs on the MXU at the dtype's peak rate.
    The kernel is pipelined, so time ~ max(compute, memory) + epilogue.
    The ``lloyd`` kind adds the partial-sum output traffic and the one-hot
    update GEMM of the fused epilogue.

    The variant axis shows up in the min/argmin output stream: the generic
    template initializes the revisited (bm, 1) blocks and re-reads/rewrites
    them on every centroid tile (2 x n_ktiles visits), where the ``smallk``
    template writes each block exactly once — so whenever K fits a single
    centroid tile the small-K variant strictly wins the model, which is
    what routes it through selection.

    The ``batched`` kind is B independent problems through the smallk-style
    one-pass grid: per-problem cost is the smallk ``lloyd`` estimate and
    the launch is its B-fold — dispatch amortization is exactly what the
    model cannot see, which is why batched winners are *measured* on real
    hardware and the B bucket is part of the cache key.

    The ``pruned`` kind discounts the distance GEMM (MACs and the
    per-centroid-tile X re-reads) by ``prune_rate`` — the assumed fraction
    of (row tile, centroid tile) cells the triangle-inequality filter
    skips in steady state; the fused update epilogue, the partial-sum
    round trip and the output streams are unconditional and stay at full
    cost. The default 0.5 is deliberately conservative (late iterations on
    clustered data reach far higher); the real rate is data- and
    alignment-dependent, which is why pruned winners prefer measure mode
    on clustered inputs.

    The ``int8`` kind scores like ``assign`` with 1-byte x/c streams and
    the int8 MXU peak (``hw.PEAK_FLOPS_INT8``): callers pass
    ``dtype=jnp.int8`` and the itemsize/peak lookups do the rest. The f32
    scale vectors and centroid norms are O(M + K) streams — noise next to
    the O(M F) tiles — and are not charged.

    The ``serve`` kind is the ``assign`` score plus the fixed per-launch
    dispatch cost (``hw.DISPATCH_OVERHEAD_S``): an online predict cell is
    one assignment-kernel launch at a bucket shape, and at serving sizes
    the launch cost is a first-order term, not noise.
    """
    if kind == "batched":
        return batch * model_score(m, k, f, p, dtype=dtype, kind="lloyd",
                                   variant="smallk")
    if kind == "init":
        # one fused k-means++ D² round is memory-bound: X streams once
        # against a single centroid row (F MACs per row — VPU work,
        # nowhere near the MXU), while the norm/d² vectors round-trip.
        # Tile size matters only through row padding, which is exactly
        # what this captures; K is not an axis of the round at all.
        bn = max(128, clamp_params(m, k, f, p, dtype).block_m)
        mp = _round_up(m, bn)
        fp = _round_up(f, 128)
        hbm_bytes = (mp * fp + 4 * mp) * 4     # x tile + xn/d2-in/out/ts
        # per-grid-step issue cost breaks the tie between tile sizes that
        # pad M equally — bigger tiles amortize it, like real hardware
        return float(batch * (hbm_bytes / HBM_BW + (mp // bn) * 1e-7))
    if kind == "serve":
        # one AOT predict-cell launch: the assignment kernel at the bucket
        # shape plus the fixed per-launch dispatch cost. The dispatch term
        # is what micro-batching amortizes — summing these scores over a
        # request-size distribution is how the ladder planner trades
        # padding waste against launch count (repro.serve.tuning).
        return _hw.DISPATCH_OVERHEAD_S + model_score(
            m, k, f, p, dtype=dtype, kind="assign", variant=variant)
    p = clamp_params(m, k, f, p, dtype)
    bytes_per = jnp.dtype(dtype).itemsize
    mp = -(-m // p.block_m) * p.block_m
    kp = -(-k // p.block_k) * p.block_k
    fp = -(-f // p.block_f) * p.block_f
    n_ktiles = kp // p.block_k
    x_reads = mp * fp * n_ktiles
    c_reads = kp * fp * (mp // p.block_m)
    hbm_bytes = (x_reads + c_reads) * bytes_per
    macs = mp * kp * fp
    if kind in _LLOYD_KINDS:
        # f32 partial sums/counts blocks out + tree-reduction round trip
        partials = (mp // p.block_m) * (kp * fp + kp) * 4
        hbm_bytes += 2 * partials
        macs += mp * kp * fp          # one-hot scatter GEMM in the epilogue
    if kind == "pruned":
        # skipped cells pay neither the distance MACs nor the per-centroid-
        # tile X re-read; everything else (update epilogue, partials,
        # output streams) is unconditional. Bounds traffic: ub+assign rows
        # in/out, drift-sized centroid snapshot, per-cell tmin/skip words.
        skipped = min(max(prune_rate, 0.0), 1.0)
        hbm_bytes -= skipped * x_reads * bytes_per
        macs -= skipped * mp * kp * fp
        hbm_bytes += 2 * mp * 8 + kp * fp * 4 \
            + 3 * (mp // p.block_m) * (kp // p.block_k) * 4
    if kind == "lloyd_ft":
        # dual-checksum encodings fused into the tile loop: ~2*(bm+bk)*bf
        # MACs per (m, k, f) grid step -> 2*M*K*F*(1/bm + 1/bk) overall
        # (the paper's ~1.2% at (256, 128) tiles), plus the update
        # epilogue's two (bm, fp) encoding products per row tile and the
        # expected-checksum blocks' write + reduce-read round trip
        macs += 2.0 * mp * kp * fp * (1.0 / p.block_m + 1.0 / p.block_k)
        macs += 2 * mp * fp
        hbm_bytes += 2 * (mp // p.block_m) * (2 * fp + 2) * 4
    hbm = hbm_bytes / HBM_BW
    peak = _hw.peak_flops(dtype)
    # MXU efficiency falls off for tiles thinner than the 128x128 systolic
    # array and for padded remainders.
    util = min(p.block_k / 128.0, 1.0) * min(p.block_m / 128.0, 1.0)
    util *= (m / mp) * (k / kp) * (f / fp)
    compute = 2.0 * macs / (peak * max(util, 1e-3))
    # VMEM-resident reduce over the (bm, bk) accumulator — always f32,
    # whatever the input dtype
    epilogue = mp * kp * 4 / (HBM_BW * 16)
    # min/argmin stream: the generic template initializes the revisited
    # (bm, 1) output blocks and re-reads/rewrites them on every centroid
    # tile (2 x n_ktiles visits); smallk writes each block exactly once.
    # This round trip happens at epilogue time, serialized behind the tile
    # pipeline, so it adds outside the max() — which is also what makes the
    # small-K variant strictly outrank the generic one whenever K fits a
    # single centroid tile, even for compute-bound shapes.
    out_visits = 1 if variant == "smallk" else 2 * n_ktiles
    out_stream = out_visits * mp * 8 / HBM_BW
    return float(max(hbm, compute) + epilogue + out_stream)


def measure_score(m: int, k: int, f: int, p: KernelParams, *, iters: int = 3,
                  dtype=jnp.float32, kind: str = "assign",
                  variant: Optional[str] = None, batch: int = 1,
                  interpret: Optional[bool] = None) -> float:
    """Median wall-time of the real kernel on the current backend (seconds).

    ``interpret=None`` resolves to the real compiled kernel whenever a TPU
    backend is present; the Pallas interpreter is only an *explicit*
    fallback for kernel-path smoke timing off-device (it measures the
    interpreter, not the kernel — a number that must never be presented as
    hardware performance).

    Inputs are seeded-random (all-ones invited constant folding), the
    candidate pipeline is compiled exactly once up front (naively repeating
    ``fused_assign`` re-ran its eager padding prologue every call), and
    every timed call is individually ``block_until_ready`` so candidates
    are ranked on real kernel time, not dispatch pipelining. The
    ``batched`` kind times one B-problem launch of the batched kernel —
    the whole point of its measure mode, since dispatch amortization is
    invisible to the analytical model.

    The ``pruned`` kind runs two iterations on *clustered* synthetic data
    (cluster-contiguous rows, centroid order aligned with row order): the
    first call seeds the bounds state (unpruned by construction), the
    timed calls run warmed — the steady state a long fit spends almost all
    its iterations in. Uniform data never prunes, so measuring on it would
    rank every candidate on full-compute time and the pruned kind would
    never beat the plain one-pass winner.

    The ``int8`` kind feeds float data through the full quantize +
    int8-template path (``fused_assign_int8``), so the timed number
    includes the per-call centroid quantization the real iteration pays.

    The ``serve`` kind times the assignment kernel at the bucket shape —
    the same pipeline as ``assign``. The per-launch dispatch constant the
    serve *model* adds is shape-independent, so measured rankings agree
    with modeled ones up to that constant."""
    from repro.kernels.ops import (fused_assign, fused_assign_int8,
                                   fused_lloyd, fused_lloyd_batched,
                                   fused_lloyd_ft, fused_lloyd_pruned,
                                   init_bounds, on_tpu)
    if interpret is None:
        interpret = not on_tpu()
    if kind == "init":
        # time one fused D² round at the candidate's row tile: the round
        # dominates the seeding loop (selection is O(T + bn) glue), and
        # batch enters as the B problems of one launch
        from repro.kernels.kmeanspp_init import (clamp_init_block,
                                                 kmeanspp_round)
        bn = clamp_init_block(m, clamp_params(m, k, f, p, dtype).block_m)
        np_ = _round_up(m, bn)
        fp_ = _round_up(f, 128)
        kx, kc = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(kx, (batch, np_, fp_), jnp.float32)
        xn = jnp.sum(x * x, axis=2)
        c = jax.random.normal(kc, (batch, 1, fp_), jnp.float32)
        d2 = xn + 1.0
        fn_i = jax.jit(functools.partial(kmeanspp_round, block_n=bn,
                                         interpret=interpret))
        jax.block_until_ready(fn_i(x, xn, c, d2))
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn_i(x, xn, c, d2))
            times.append(time.perf_counter() - t0)
        times.sort()
        return times[len(times) // 2]
    kx, kc = jax.random.split(jax.random.PRNGKey(0))
    if kind == "batched":
        x = jax.random.normal(kx, (batch, m, f), dtype)
        c = jax.random.normal(kc, (batch, k, f), dtype)
    elif kind == "pruned":
        x, c = _clustered_data(m, k, f, dtype)
    elif kind == "int8":
        # the template quantizes internally; feed it float data
        x = jax.random.normal(kx, (m, f), jnp.float32)
        c = jax.random.normal(kc, (k, f), jnp.float32)
    else:
        x = jax.random.normal(kx, (m, f), dtype)
        c = jax.random.normal(kc, (k, f), dtype)
    p = clamp_params(m, k, f, p, jnp.int8 if kind == "int8" else dtype)
    if kind == "batched":    # smallk-style grid: no variant/block_k axis
        fn = jax.jit(functools.partial(fused_lloyd_batched, params=p,
                                       interpret=interpret))
    elif kind == "lloyd_ft":   # generic-grid template: no variant axis
        fn = jax.jit(functools.partial(fused_lloyd_ft, params=p,
                                       interpret=interpret))
    elif kind == "int8":
        fn = jax.jit(functools.partial(fused_assign_int8, params=p,
                                       variant=variant, interpret=interpret))
    elif kind == "pruned":
        step_p = jax.jit(functools.partial(fused_lloyd_pruned, params=p,
                                           variant=variant,
                                           interpret=interpret))
        seeded = step_p(x, c, bounds=init_bounds(m, k, f, p, dtype=dtype))
        bounds = seeded[4]   # iteration 1 of 2: the unpruned seeding pass
        fn = functools.partial(step_p, bounds=bounds)
    else:
        step = fused_lloyd if kind == "lloyd" else fused_assign
        fn = jax.jit(functools.partial(step, params=p, variant=variant,
                                       interpret=interpret))
    jax.block_until_ready(fn(x, c))          # compile outside the timing
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x, c))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def _clustered_data(m: int, k: int, f: int, dtype) -> tuple:
    """Seeded well-separated Gaussian blobs for the pruned kind's measure
    mode: cluster-contiguous rows assigned round-robin-free (rows of
    cluster j are the contiguous slice j*m/k..(j+1)*m/k) and centroids in
    cluster order, so row tiles and centroid tiles align — the regime tile
    pruning is built for."""
    kx, kc = jax.random.split(jax.random.PRNGKey(7))
    centers = jax.random.normal(kc, (k, f), jnp.float32) * 8.0
    labels = (jnp.arange(m) * k) // m
    x = centers[labels] + jax.random.normal(kx, (m, f), jnp.float32)
    return x.astype(dtype), centers.astype(dtype)


def select_params(m: int, k: int, f: int, *, mode: str = "model",
                  dtype=jnp.float32, kind: str = "assign",
                  space: Optional[Iterable[KernelParams]] = None,
                  batch: int = 1) -> tuple[str, KernelParams]:
    """Pick the winner for one problem shape and kernel kind.

    Searches variant x tiles for the given dtype and returns the winning
    ``(variant, KernelParams)`` pair. The small-K variant competes whenever
    padded K fits one centroid tile and, by construction of the model,
    outranks the generic template there (no revisited-output machinery).
    The ``batched`` kind searches (block_m, block_f) only — padded K is the
    single centroid tile by construction — and scores one B-problem launch
    (``batch`` enters measure mode directly and the cache key's B bucket).
    """
    from repro.kernels.ops import resolve_variant
    if kind not in KINDS:
        raise ValueError(f"kind must be one of {KINDS}, got {kind!r}")
    # Degenerate shapes: a serving layer legitimately sees zero-row
    # requests (the ops layer early-returns before any launch, but a cache
    # lookup may still ask for a selection at M=0). Score the smallest
    # real shape instead of dividing by a zero-row padded extent.
    m, k, f = max(m, 1), max(k, 1), max(f, 1)
    best, best_s = None, float("inf")
    if kind == "init":
        # the fused k-means++ round kernel has one tile axis: block_m.
        # K never enters the round and F is fully resident, so block_k /
        # block_f are not searched (mirroring how 'batched' drops block_k)
        seen = set()
        for p in (space or parameter_space(dtype)):
            if p.block_m in seen:
                continue
            seen.add(p.block_m)
            if not feasible(p, dtype, kind=kind, shape=(m, k, f)):
                continue
            s = (model_score(m, k, f, p, dtype=dtype, kind=kind,
                             batch=batch)
                 if mode == "model"
                 else measure_score(m, k, f, p, dtype=dtype, kind=kind,
                                    batch=batch))
            if s < best_s:
                best, best_s = ("generic", p), s
        if best is None:
            raise ValueError(
                f"no feasible 'init' kernel parameters for shape "
                f"{(m, k, f)}: every candidate's resident (block_m, F) "
                f"sample tile exceeds VMEM (the round kernel keeps all of "
                f"F resident; reduce F or use the vmapped seeding path)")
        return best
    if kind == "batched":
        seen = set()
        for p in (space or parameter_space(dtype)):
            if (p.block_m, p.block_f) in seen:   # block_k is not an axis
                continue
            seen.add((p.block_m, p.block_f))
            if not feasible(p, dtype, kind=kind, shape=(m, k, f)):
                continue
            s = (model_score(m, k, f, p, dtype=dtype, kind=kind, batch=batch)
                 if mode == "model"
                 else measure_score(m, k, f, p, dtype=dtype, kind=kind,
                                    batch=batch))
            if s < best_s:
                best, best_s = ("batched", p), s
        if best is None:
            raise ValueError(
                f"no feasible 'batched' kernel parameters for per-problem "
                f"shape {(m, k, f)}: every candidate's working set exceeds "
                f"VMEM (the batched kernel keeps one problem's stashed X "
                f"row tile and (K, F) partial block resident; shrink the "
                f"problems or run them through the single-problem path)")
        return best
    for p in (space or parameter_space(dtype)):
        # The variant is a function of (K, tiles) — the dispatch rule — so
        # each tile candidate is scored as the template it would actually
        # run (scoring the other variant would benchmark a kernel the
        # runtime can never launch for these tiles). Dispatch sees the
        # *clamped* tiles, so the variant must be derived from them too:
        # clamping can shrink block_k below the K-fit threshold. FT kinds
        # only ship the generic-grid template.
        variant = ("generic" if kind == "lloyd_ft"
                   else resolve_variant(k, clamp_params(m, k, f, p, dtype)))
        if not feasible(p, dtype, kind=kind, shape=(m, k, f),
                        variant=variant):
            continue
        s = (model_score(m, k, f, p, dtype=dtype, kind=kind,
                         variant=variant)
             if mode == "model"
             else measure_score(m, k, f, p, dtype=dtype, kind=kind,
                                variant=variant))
        if s < best_s:
            best, best_s = (variant, p), s
    if best is None:
        hint = (" (the one-pass kernel keeps the stashed X row tile and "
                "its (K, F) partial-sum block VMEM-resident; use a "
                "two-pass backend for this shape)"
                if kind in _LLOYD_KINDS else "")
        raise ValueError(f"no feasible {kind!r} kernel parameters for "
                         f"shape {(m, k, f)}: every candidate's working "
                         f"set exceeds VMEM{hint}")
    return best

