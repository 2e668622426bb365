"""Pass 1 — kernel contract checker (no TPU, no compile, no real compute).

The Pallas kernels' correctness rests on contracts that nothing used to
enforce: VMEM byte models must match the BlockSpecs the kernels actually
launch with, tiles must respect dtype-aware sublane alignment, 16-bit
inputs must still produce f32 accumulators/outputs, registry capability
flags must match the real callables, and the FT backends' declared
``protected_intervals`` must match the injection-descriptor slots the
kernels implement. Each is checked here statically:

``vmem-model``      declared model vs the jaxpr-implied footprint
                    (:func:`repro.kernels.ops.kernel_plan`), within a
                    small tolerance, and under the ``repro.hw`` budget
``tile-align``      autotune winners respect ``sublane_align(dtype)``
                    and the 128-lane tile rule
``f32-accumulate``  16-bit inputs yield f32 distance/sums/counts and
                    i32 assignment outputs (via ``jax.eval_shape``); the
                    int8 template's traced plan carries an int32
                    accumulator and emits only f32/i32
``flags``           capability flags vs ``inspect.signature`` and the
                    abstract-eval output arity/batch axis
``intervals``       ``protected_intervals``/``kernel_kind`` vs the FT
                    kernels' ``INJ_SLOTS`` and ``autotune.KINDS``
``dist-ft``         the distribution/recovery layer: int8 transport
                    shape/dtype invariants (abstract, ragged tails
                    included), ReducePlan/FaultPolicy enum hygiene, and
                    ``worker_loss="shrink"`` resolving to real
                    ``ft.elastic`` entry points

Every input is injectable (``backends=``, ``vmem_models=``,
``descriptor_slots=``) so the test suite can prove each rule fires on a
deliberately broken fixture without mutating the global registry.
"""
from __future__ import annotations

import inspect
from typing import Any, Callable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp

from repro import hw
from repro.analysis.report import Violation
from repro.core import autotune
from repro.kernels import distance_argmin_ft as _daft
from repro.kernels import lloyd_step_ft as _llft
from repro.kernels import ops

# Representative (m, k, f) grid: a small-K shape (smallk template), a
# multi-tile generic shape, and a large-M bucket. Kept small — each cell
# is a handful of abstract traces.
DEFAULT_SHAPES: tuple[tuple[int, int, int], ...] = (
    (1024, 16, 256), (2048, 256, 512), (65536, 64, 256))
DEFAULT_DTYPES: tuple[str, ...] = ("float32", "bfloat16", "float16")

# Declared-vs-implied tolerance: the models are working-set *estimates*
# (lloyd_ft's model folds the tiny det/checksum blocks into its sums
# term), but a wrong itemsize or a forgotten stash buffer is a >=30%
# miss — far outside this band.
VMEM_RTOL = 0.02
VMEM_ATOL = 64 * 1024

VmemModel = Callable[[ops.KernelParams, int, int, Any], int]


def _default_vmem_models() -> dict[str, VmemModel]:
    """kind -> declared byte model, the registry's documented convention."""
    return {
        "assign": lambda p, k, f, dt: p.vmem_bytes(dt),
        "lloyd": ops.lloyd_vmem_bytes,
        "lloyd_ft": ops.lloyd_ft_vmem_bytes,
        "batched": ops.lloyd_batched_vmem_bytes,
        "pruned": ops.pruned_vmem_bytes,
        "int8": lambda p, k, f, dt: ops.int8_vmem_bytes(p),
        "init": lambda p, k, f, dt: ops.init_vmem_bytes(p, f),
        # a serve predict cell is the assignment kernel at a bucket shape
        "serve": lambda p, k, f, dt: p.vmem_bytes(dt),
    }


def _default_descriptor_slots() -> dict[str, int]:
    """kind -> injection-descriptor slots the FT kernels implement."""
    return {"assign": _daft.INJ_SLOTS, "lloyd_ft": _llft.INJ_SLOTS}


def check_vmem_models(
    shapes: Sequence[tuple[int, int, int]] = DEFAULT_SHAPES,
    dtypes: Sequence[str] = DEFAULT_DTYPES,
    *,
    vmem_models: Optional[Mapping[str, VmemModel]] = None,
    plan_fn: Callable[..., ops.KernelPlan] = ops.kernel_plan,
) -> list[Violation]:
    """``vmem-model`` + ``tile-align``: for every (kind, dtype, shape)
    cell, select the autotune winner, trace the kernel's real plan, and
    verify the declared byte model against the implied footprint and the
    hardware budget; verify the winner's tiles are dtype-legal."""
    models = dict(vmem_models) if vmem_models is not None \
        else _default_vmem_models()
    out: list[Violation] = []
    src = "src/repro/kernels/ops.py"
    for kind in ops.PLAN_KINDS:
        model = models.get(kind)
        if model is None:
            out.append(Violation(
                "contracts", "vmem-model", file=src,
                message=f"kernel kind {kind!r} has no declared VMEM model"))
            continue
        # Per-kind dtype set: the f32 template family is checked at every
        # requested dtype; a fixed-dtype kind (int8 tiles are int8 by
        # construction) falls back to its own dtypes when the requested
        # ones don't apply, so passing ("float32",) still covers it.
        allowed = ops.PLAN_KIND_DTYPES.get(kind, tuple(dtypes))
        kind_dtypes = [d for d in dtypes if d in allowed] or list(allowed)
        for dtype in kind_dtypes:
            dt = jnp.dtype(dtype)
            for (m, k, f) in shapes:
                batch = 8 if kind == "batched" else 1
                _, p = autotune.select_params(m, k, f, mode="model",
                                              dtype=dt, kind=kind,
                                              batch=batch)
                p = ops.clamp_params(m, k, f, p, dtype=dt)
                cell = (f"kind={kind} dtype={dtype} shape={(m, k, f)} "
                        f"tiles=({p.block_m},{p.block_k},{p.block_f})")
                align = ops.sublane_align(dt)
                if (p.block_m % align or p.block_k % 128
                        or p.block_f % 128):
                    out.append(Violation(
                        "contracts", "tile-align", file=src,
                        message=f"winner tiles break alignment (block_m "
                                f"% {align} / 128-lane rule): {cell}"))
                declared = int(model(p, k, f, dt))
                plan = plan_fn(kind, m, k, f, p, dtype=dt, batch=batch)
                implied = plan.vmem_bytes()
                if kind == "int8":
                    # i32-accumulate mirror of the f32-under-16-bit rule:
                    # i8 x i8 tile products overflow anything narrower, so
                    # the traced plan must carry an int32 VMEM accumulator
                    # and emit only f32/i32 outputs.
                    if not any(b.dtype == "int32" for b in plan.scratch):
                        out.append(Violation(
                            "contracts", "f32-accumulate", file=src,
                            message=f"int8 template must accumulate in an "
                                    f"int32 VMEM scratch; traced scratch "
                                    f"dtypes "
                                    f"{[b.dtype for b in plan.scratch]}: "
                                    f"{cell}"))
                    bad = [b.dtype for b in plan.outputs
                           if b.dtype not in ("float32", "int32")]
                    if bad:
                        out.append(Violation(
                            "contracts", "f32-accumulate", file=src,
                            message=f"int8 template must emit f32/i32 "
                                    f"outputs, got {bad}: {cell}"))
                tol = max(VMEM_ATOL, int(VMEM_RTOL * implied))
                if abs(declared - implied) > tol:
                    out.append(Violation(
                        "contracts", "vmem-model", file=src,
                        message=f"declared VMEM model ({declared} B) "
                                f"disagrees with the BlockSpec-implied "
                                f"footprint ({implied} B, tol {tol} B): "
                                f"{cell}"))
                if max(declared, implied) > hw.VMEM_BUDGET:
                    out.append(Violation(
                        "contracts", "vmem-model", file=src,
                        message=f"working set exceeds the "
                                f"hw.VMEM_BUDGET ({hw.VMEM_BUDGET} B): "
                                f"declared={declared} implied={implied} "
                                f"{cell}"))
    return out


def _abstract_outputs(backend: Any, m: int, k: int, f: int,
                      dtype: Any) -> tuple[Any, ...]:
    """Abstractly evaluate the backend's uniform call on (m, k, f)."""
    dt = jnp.dtype(dtype)
    if backend.supports_batch:
        xs = jax.ShapeDtypeStruct((4, m, f), dt)
        cs = jax.ShapeDtypeStruct((4, k, f), dt)
    else:
        xs = jax.ShapeDtypeStruct((m, f), dt)
        cs = jax.ShapeDtypeStruct((k, f), dt)
    params = None
    if backend.takes_params:
        params = ops.clamp_params(m, k, f, ops.DEFAULT_PARAMS, dtype=dt)
    out = jax.eval_shape(lambda x, c: backend(x, c, params=params), xs, cs)
    return tuple(out) if isinstance(out, (tuple, list)) else (out,)


def check_backend_contracts(
    backends: Optional[Mapping[str, Any]] = None,
    *,
    descriptor_slots: Optional[Mapping[str, int]] = None,
    shape: tuple[int, int, int] = (1024, 16, 256),
    dtypes: Sequence[str] = DEFAULT_DTYPES,
) -> list[Violation]:
    """``flags`` + ``f32-accumulate`` + ``intervals``: registry metadata
    vs the real callables, via ``inspect`` and ``jax.eval_shape``."""
    if backends is None:
        from repro.api.registry import list_backends
        backends = list_backends()
    slots = dict(descriptor_slots) if descriptor_slots is not None \
        else _default_descriptor_slots()
    out: list[Violation] = []
    src = "src/repro/api/registry.py"
    m, k, f = shape
    for name in sorted(backends):
        b = backends[name]
        contract = b.contract()
        fn = inspect.unwrap(getattr(b.fn, "__wrapped__", b.fn))
        try:
            sig_params = inspect.signature(fn).parameters
        except (TypeError, ValueError):
            sig_params = {}
        for flag, pname in (("takes_params", "params"),
                            ("takes_injection", "inj"),
                            ("supports_bounds", "bounds")):
            if contract["flags"][flag] != (pname in sig_params):
                out.append(Violation(
                    "contracts", "flags", file=src,
                    message=f"backend {name!r} declares {flag}="
                            f"{contract['flags'][flag]} but its callable "
                            f"{'lacks' if contract['flags'][flag] else 'has'}"
                            f" a {pname!r} parameter"))
                continue
        if contract["kernel_kind"] not in autotune.KINDS:
            out.append(Violation(
                "contracts", "intervals", file=src,
                message=f"backend {name!r} derives kernel_kind="
                        f"{contract['kernel_kind']!r}, not an autotune "
                        f"kind {autotune.KINDS}"))
        if b.takes_injection:
            expect = slots.get(contract["kernel_kind"])
            if expect is None or contract["protected_intervals"] != expect:
                out.append(Violation(
                    "contracts", "intervals", file=src,
                    message=f"backend {name!r} declares "
                            f"{contract['protected_intervals']} protected "
                            f"intervals but its {contract['kernel_kind']!r} "
                            f"kernel implements {expect} injection-"
                            f"descriptor slot(s) (INJ_SLOTS)"))
        for dtype in dtypes:
            try:
                outs = _abstract_outputs(b, m, k, f, dtype)
            except Exception as e:  # pragma: no cover - trace failure
                out.append(Violation(
                    "contracts", "flags", file=src,
                    message=f"backend {name!r} failed abstract evaluation "
                            f"at dtype={dtype}: {e}"))
                continue
            if len(outs) != contract["expected_arity"]:
                out.append(Violation(
                    "contracts", "flags", file=src,
                    message=f"backend {name!r} returns {len(outs)} values "
                            f"but fuses_update={b.fuses_update} implies "
                            f"{contract['expected_arity']}"))
                continue
            am, md, det = outs[0], outs[1], outs[2]
            lead = (4,) if b.supports_batch else ()
            if tuple(am.shape) != lead + (m,):
                out.append(Violation(
                    "contracts", "flags", file=src,
                    message=f"backend {name!r} (supports_batch="
                            f"{b.supports_batch}) returned assignment "
                            f"shape {tuple(am.shape)}, expected "
                            f"{lead + (m,)}"))
            if jnp.dtype(am.dtype) != jnp.int32 \
                    or jnp.dtype(det.dtype) != jnp.int32:
                out.append(Violation(
                    "contracts", "f32-accumulate", file=src,
                    message=f"backend {name!r} must return i32 assignment "
                            f"and detected-count (got {am.dtype}/"
                            f"{det.dtype})"))
            if b.takes_params and jnp.dtype(dtype).itemsize <= 2:
                # outs[3:5] are the fused sums/counts; a bounds-carrying
                # backend's trailing (BoundsState, prune_frac) pair is a
                # pytree + scalar, not an accumulator stream
                bad = [o for o in (md,) + tuple(outs[3:5])
                       if jnp.dtype(o.dtype) != jnp.float32]
                if bad:
                    out.append(Violation(
                        "contracts", "f32-accumulate", file=src,
                        message=f"backend {name!r} at dtype={dtype} "
                                f"returned {[str(o.dtype) for o in bad]} "
                                f"outputs; 16-bit kernel tiles must "
                                f"accumulate and emit f32"))
    return out


def check_dist_ft_contracts(
    *,
    compression_mod: Any = None,
    reduce_mod: Any = None,
    policy_cls: Any = None,
    elastic_mod: Any = None,
) -> list[Violation]:
    """``dist-ft``: the distribution/recovery layer's static contracts.

    The compressed reduce and the elastic restart are correct only if
    three interfaces agree without ever running a fit: the int8 transport
    must preserve shapes/dtypes abstractly (quantize emits int8 payload +
    f32 per-block scales, dequantize round-trips the original shape, ragged
    tails included), the :class:`~repro.dist.reduce.ReducePlan` /
    :class:`~repro.api.FaultPolicy` enums must reject unknown routes, and
    every policy value that *promises* a recovery path must resolve to
    real code (``worker_loss="shrink"`` -> ``ft.elastic`` entry points).
    """
    if compression_mod is None:
        from repro.dist import compression as compression_mod
    if reduce_mod is None:
        from repro.dist import reduce as reduce_mod
    if policy_cls is None:
        from repro.api import FaultPolicy as policy_cls
    if elastic_mod is None:
        from repro.ft import elastic as elastic_mod
    out: list[Violation] = []
    src = "src/repro/dist/compression.py"
    # int8 transport invariants, abstractly, at an aligned and a ragged n
    for n in (256, 100):
        x = jax.ShapeDtypeStruct((16, n), jnp.float32)
        try:
            q, scale = jax.eval_shape(compression_mod.quantize, x)
            deq = jax.eval_shape(
                lambda qq, ss: compression_mod.dequantize(qq, ss, n),
                q, scale)
        except Exception as e:  # pragma: no cover - trace failure
            out.append(Violation(
                "contracts", "dist-ft", file=src,
                message=f"int8 transport failed abstract eval at n={n}: "
                        f"{e}"))
            continue
        if jnp.dtype(q.dtype) != jnp.int8 \
                or jnp.dtype(scale.dtype) != jnp.float32:
            out.append(Violation(
                "contracts", "dist-ft", file=src,
                message=f"quantize must emit int8 payload + f32 scales, "
                        f"got {q.dtype}/{scale.dtype} at n={n}"))
        if q.shape[:-1] != scale.shape[:-1] or scale.shape[-1] != 1:
            out.append(Violation(
                "contracts", "dist-ft", file=src,
                message=f"per-block scales must broadcast over the "
                        f"payload blocks: q={q.shape} scale={scale.shape}"))
        if tuple(deq.shape) != tuple(x.shape) \
                or jnp.dtype(deq.dtype) != jnp.float32:
            out.append(Violation(
                "contracts", "dist-ft", file=src,
                message=f"dequantize must round-trip shape/dtype "
                        f"{x.shape}/f32, got {deq.shape}/{deq.dtype} "
                        f"(ragged tail n={n})"))
    src = "src/repro/dist/reduce.py"
    try:
        reduce_mod.ReducePlan(cross_host="fp4")
        out.append(Violation(
            "contracts", "dist-ft", file=src,
            message="ReducePlan accepted an unknown cross_host transport"))
    except ValueError:
        pass
    if reduce_mod.ReducePlan.compressed(exact=True).cross_host != "exact":
        out.append(Violation(
            "contracts", "dist-ft", file=src,
            message="ReducePlan.compressed(exact=True) must be the exact "
                    "escape hatch"))
    src = "src/repro/api/policy.py"
    from repro.api import policy as _policy_mod
    for value in _policy_mod.WORKER_LOSS:
        try:
            policy_cls(worker_loss=value)
        except ValueError:
            out.append(Violation(
                "contracts", "dist-ft", file=src,
                message=f"FaultPolicy rejects documented worker_loss="
                        f"{value!r}"))
    try:
        policy_cls(worker_loss="migrate")
        out.append(Violation(
            "contracts", "dist-ft", file=src,
            message="FaultPolicy accepted an unknown worker_loss mode"))
    except ValueError:
        pass
    # "shrink" promises the fail-stop rung: the entry points must exist
    for name in ("plan_rescale_rows", "WorkerLossError", "FailureSchedule"):
        if not hasattr(elastic_mod, name):
            out.append(Violation(
                "contracts", "dist-ft", file=src,
                message=f"worker_loss='shrink' routes to ft.elastic."
                        f"{name}, which does not exist"))
    return out


def run(shapes: Sequence[tuple[int, int, int]] = DEFAULT_SHAPES,
        dtypes: Sequence[str] = DEFAULT_DTYPES,
        *,
        backends: Optional[Mapping[str, Any]] = None,
        vmem_models: Optional[Mapping[str, VmemModel]] = None,
        descriptor_slots: Optional[Mapping[str, int]] = None,
        ) -> list[Violation]:
    """Run the whole contract pass; empty list = clean."""
    out = check_vmem_models(shapes, dtypes, vmem_models=vmem_models)
    out += check_backend_contracts(backends, dtypes=dtypes,
                                   descriptor_slots=descriptor_slots)
    out += check_dist_ft_contracts()
    return out
