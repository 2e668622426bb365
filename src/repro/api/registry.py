"""Assignment-backend registry — the paper's kernel-selection surface.

The paper's code-generation pipeline (§III-B) produces a *set* of kernels
and a selection layer that picks one per problem; the stepwise ladder
(§III-A) and the ABFT variants (§IV) are alternative implementations of the
same contract. This module makes that contract explicit: every assignment
implementation is an :class:`AssignmentBackend` with declared capabilities
and one uniform call signature

    backend(x, c, *, params=None, inj=None) -> (assign, min_dist, detected)

so the driver (``repro.api.KMeans``) never branches on backend names.
Capability mismatches (e.g. an injection campaign routed into a backend
without in-kernel injection support) are rejected here, at the boundary,
instead of failing silently inside a kernel.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax


class BackendCapabilityError(TypeError):
    """A backend was asked for a capability it does not declare."""


# Capability flags, in rendering order (also the machine-readable contract
# vocabulary consumed by repro.analysis.contracts).
_FLAG_COLUMNS = ("supports_ft", "takes_params", "takes_injection",
                 "fuses_update", "supports_batch", "supports_bounds",
                 "supports_int8")


@dataclasses.dataclass(frozen=True)
class AssignmentBackend:
    """One cluster-assignment implementation plus its capability flags.

    fn: the raw callable. Its positional signature may be any of
        ``(x, c)``, ``(x, c, params)`` or ``(x, c, params, inj=...)`` —
        the flags say which; ``__call__`` adapts uniformly.
    supports_ft:     detects (and possibly corrects) SDCs, returning a
                     nonzero detected-error count when one fires.
    takes_params:    accepts a :class:`~repro.kernels.ops.KernelParams`
                     tile selection (Pallas-backed kernels). ``x`` may then
                     also be a prebuilt :class:`~repro.kernels.ops.DataPlan`.
    takes_injection: accepts an in-kernel SEU injection descriptor.
    fuses_update:    one-pass Lloyd backend — returns the extended 5-tuple
                     ``(assign, min_dist, detected, sums, counts)`` so the
                     driver skips the separate centroid-update pass over X.
    supports_batch:  many-problem backend — ``x`` is a (B, N, F) stack or
                     a :class:`~repro.kernels.ops.BatchPlan` (whose
                     problems may differ in row count) and ``c`` a
                     (B, K, F) per-problem centroid stack; every output
                     gains the leading problem axis. Single-problem
                     drivers must not route (M, F) data here and batched
                     drivers (``repro.batch``) require the flag.
    supports_bounds: stateful pruned backend — accepts an iteration-carried
                     ``bounds`` state (:class:`~repro.kernels.ops.
                     BoundsState` or the backend's own shape) and returns
                     the extended 7-tuple ``(assign, min_dist, detected,
                     sums, counts, new_bounds, prune_frac)``. ``bounds=None``
                     (or a fresh state from ``bounds_init``) computes every
                     tile and seeds the bounds; anything that moves
                     centroids outside the backend's own update must pass a
                     fresh state.
    supports_int8:   quantized-distance backend — the distance GEMM runs
                     on per-row int8-quantized operands (the int8 kernel
                     template or its f32-carrier XLA analogue); ``x`` may
                     be a prebuilt :class:`~repro.kernels.ops.QuantPlan`.
                     The argmin is bit-exact vs the f32 backends on
                     quantization-safe data and error-bounded on floats;
                     tiles come from the ``int8`` autotune table.
    bounds_init:     for ``supports_bounds`` backends, a callable
                     ``(m, k, f, params=None, *, dtype=...) -> state``
                     building the fresh (all-invalid) bounds state the
                     driver threads into iteration zero.
    """

    name: str
    fn: Callable
    supports_ft: bool = False
    takes_params: bool = False
    takes_injection: bool = False
    fuses_update: bool = False
    supports_batch: bool = False
    supports_bounds: bool = False
    supports_int8: bool = False
    bounds_init: Optional[Callable] = None
    doc: str = ""

    @property
    def kernel_kind(self) -> str:
        """The autotune kernel kind this backend's tiles are selected for
        (``repro.core.autotune.KINDS``): the assignment-only kernel, the
        one-pass (fused-update) kernel, or the one-pass FT kernel — their
        VMEM footprints and traffic profiles differ, so winners must not
        cross. Only meaningful when ``takes_params`` is True, but derived
        from the capability flags either way."""
        if self.supports_int8:
            return "int8"
        if self.supports_batch:
            return "batched"
        if self.supports_bounds:
            return "pruned"
        if self.fuses_update:
            return "lloyd_ft" if self.supports_ft else "lloyd"
        return "assign"

    @property
    def protected_intervals(self) -> int:
        """How many independently verified SEU intervals one step of this
        backend exposes to an injection campaign (§II-A: at most one error
        per detection/correction interval): the distance GEMM and — for
        one-pass FT backends — the update epilogue."""
        if not self.takes_injection:
            return 0
        return 2 if self.fuses_update else 1

    @property
    def expected_arity(self) -> int:
        """Length of the uniform-call return tuple: ``(assign, min_dist,
        detected)``, extended by ``(sums, counts)`` for one-pass backends
        and further by ``(new_bounds, prune_frac)`` for bounds-carrying
        pruned backends. The contract checker verifies this against an
        abstract evaluation of the real callable."""
        if self.supports_bounds:
            return 7
        return 5 if self.fuses_update else 3

    def contract(self) -> dict[str, Any]:
        """Machine-readable contract metadata for this backend — the exact
        surface ``repro.analysis.contracts`` verifies against the kernel
        implementations (flags vs signature, descriptor slots, autotune
        kind)."""
        return {
            "name": self.name,
            "flags": {c: bool(getattr(self, c)) for c in _FLAG_COLUMNS},
            "kernel_kind": self.kernel_kind,
            "protected_intervals": self.protected_intervals,
            "expected_arity": self.expected_arity,
        }

    def __call__(self, x: jax.Array, c: jax.Array, *,
                 params: Any = None,
                 inj: Optional[jax.Array] = None,
                 bounds: Any = None) -> Any:
        if inj is not None and not self.takes_injection:
            raise BackendCapabilityError(
                f"backend {self.name!r} does not take in-kernel injections "
                f"(takes_injection=False); use a fault-tolerant backend or "
                f"drop the injection campaign")
        if params is not None and not self.takes_params:
            raise BackendCapabilityError(
                f"backend {self.name!r} does not take kernel parameters "
                f"(takes_params=False)")
        if bounds is not None and not self.supports_bounds:
            raise BackendCapabilityError(
                f"backend {self.name!r} does not carry pruning bounds "
                f"(supports_bounds=False); use a pruned backend or drop "
                f"the bounds state")
        if self.supports_bounds:
            if self.takes_params:
                return self.fn(x, c, params, bounds=bounds)
            return self.fn(x, c, bounds=bounds)
        if self.takes_injection:
            if self.takes_params:
                return self.fn(x, c, params, inj=inj)
            return self.fn(x, c, inj=inj)
        if self.takes_params:
            return self.fn(x, c, params)
        return self.fn(x, c)


# The registry itself is the one sanctioned module-level mutable: an
# append-only name->backend table, filled with the built-ins on first use.
_REGISTRY: dict[str, AssignmentBackend] = {}  # analysis: allow=module-state


def register_backend(backend: AssignmentBackend) -> AssignmentBackend:
    """Register (or replace) a backend under its name."""
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> AssignmentBackend:
    _ensure_builtin_backends()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown assignment backend {name!r}; "
            f"registered: {sorted(_REGISTRY)}") from None


def list_backends() -> dict[str, AssignmentBackend]:
    """Name -> backend, a snapshot of the registry."""
    _ensure_builtin_backends()
    return dict(_REGISTRY)


@functools.cache
def _ensure_builtin_backends() -> None:
    """Fill the registry with the built-in backends, once, on first use:
    the functions of ``repro.core.assignment`` with their capability
    flags, imported here, at call time, so that ``repro.core`` never
    imports ``repro.api``. A name registered before then keeps the
    caller's backend."""
    from repro.core import assignment as a
    from repro.kernels import ops
    for backend in (
        AssignmentBackend(
            "gemm_fused", a.assign_gemm_fused,
            doc="paper V2/V3 analogue: XLA fuses the GEMM epilogue (cuML "
                "baseline)"),
        AssignmentBackend(
            "fused", a.assign_fused, takes_params=True,
            doc="paper V4/V5: Pallas fused kernel (MXU + in-VMEM argmin)"),
        AssignmentBackend(
            "fused_ft", a.assign_fused_ft, supports_ft=True,
            takes_params=True, takes_injection=True,
            doc="paper §IV: fused kernel + dual-checksum online ABFT "
                "correction"),
        AssignmentBackend(
            "abft_offline", a.assign_abft_offline, supports_ft=True,
            doc="Wu-et-al-style baseline: checksummed GEMM, offline "
                "verification"),
        AssignmentBackend(
            "int8", a.assign_int8, takes_params=True, supports_int8=True,
            doc="int8 distance template: per-row quantized X/C, i8xi8->i32 "
                "MXU tiles, f32 scale-corrected epilogue with exact norm "
                "terms (bit-exact argmin on quantization-safe data)"),
        AssignmentBackend(
            "int8_xla", a.assign_int8_xla, supports_int8=True,
            doc="XLA analogue of the int8 template: same quantization and "
                "epilogue, f32-carrier GEMM over the quantized integers "
                "(non-TPU fast path)"),
        AssignmentBackend(
            "lloyd", a.assign_lloyd, takes_params=True, fuses_update=True,
            doc="one-pass Lloyd Pallas kernel: fused assignment + "
                "in-epilogue centroid accumulation (X read once per "
                "iteration)"),
        AssignmentBackend(
            "lloyd_xla", a.assign_lloyd_xla, fuses_update=True,
            doc="XLA analogue of the one-pass kernel (non-TPU fast path)"),
        AssignmentBackend(
            "lloyd_ft", a.assign_lloyd_ft, supports_ft=True,
            takes_params=True, takes_injection=True, fuses_update=True,
            doc="one-pass FT Lloyd Pallas kernel: fused dual-checksum ABFT "
                "on the distance GEMM + checksum-protected update epilogue "
                "(paper Fig. 6 composed with the fused-update iteration)"),
        AssignmentBackend(
            "lloyd_ft_xla", a.assign_lloyd_ft_xla, supports_ft=True,
            fuses_update=True,
            doc="XLA analogue of the one-pass FT backend (checksummed cross "
                "product + verified one-hot update; non-TPU fast path)"),
        AssignmentBackend(
            "lloyd_batched", a.assign_lloyd_batched, takes_params=True,
            fuses_update=True, supports_batch=True,
            doc="batched one-pass Lloyd Pallas kernel: B independent "
                "problems per launch, a grid over their row tiles and a "
                "tile map naming each tile's problem (smallk epilogue per "
                "tile)"),
        AssignmentBackend(
            "lloyd_batched_xla", a.assign_lloyd_batched_xla,
            fuses_update=True, supports_batch=True,
            doc="XLA analogue of the batched one-pass kernel (batched "
                "contractions over the problem stack; non-TPU fast path)"),
        AssignmentBackend(
            "lloyd_pruned", a.assign_lloyd_pruned, takes_params=True,
            fuses_update=True, supports_bounds=True,
            bounds_init=ops.init_bounds,
            doc="pruned one-pass Lloyd Pallas kernel: Hamerly bounds skip "
                "whole centroid tiles that provably lose (bit-identical to "
                "lloyd; extended 7-tuple with bounds state + prune "
                "fraction)"),
        AssignmentBackend(
            "lloyd_pruned_xla", a.assign_lloyd_pruned_xla,
            fuses_update=True, supports_bounds=True,
            bounds_init=a.init_bounds_xla,
            doc="XLA analogue of the pruned one-pass backend (row-chunk x "
                "16-centroid-group cells under lax.cond; non-TPU fast "
                "path)"),
    ):
        _REGISTRY.setdefault(backend.name, backend)


# ---------------------------------------------------------------------------
# Capability matrix rendering — ``python -m repro.api.registry --markdown``
# generates docs/backends.md; CI re-renders and diffs so the committed file
# cannot go stale (see tests/test_docs.py and the workflow doc-check step).
# ---------------------------------------------------------------------------

_MD_HEADER = """\
# Backend capability matrix

<!-- AUTO-GENERATED by `python -m repro.api.registry --markdown docs/backends.md`.
     Do not edit by hand: CI fails when this file is stale. -->

Every cluster-assignment implementation registers as an
`AssignmentBackend` with declared capabilities and the uniform call
signature `backend(x, c, *, params=None, inj=None)`; drivers select one via
`FaultPolicy.resolve_backend` or `get_backend(name)` and never branch on
backend names. See [architecture.md](architecture.md) for where the
registry sits in the stack and [kernels.md](kernels.md) for the kernels
behind the `takes_params` backends.
"""


def render_markdown() -> str:
    """The registry as a markdown document (capability flags, autotune
    kernel kind, protected injection intervals, one-line doc)."""
    backends = list_backends()
    short = {"supports_ft": "ft", "takes_params": "params",
             "takes_injection": "inject", "fuses_update": "one-pass",
             "supports_batch": "batch", "supports_bounds": "pruned",
             "supports_int8": "int8"}
    lines = [_MD_HEADER]
    lines.append("| backend | " + " | ".join(short[c] for c in _FLAG_COLUMNS)
                 + " | kernel kind | protected intervals | description |")
    lines.append("|---|" + "---|" * (len(_FLAG_COLUMNS) + 3))
    for name in sorted(backends):
        b = backends[name]
        flags = " | ".join("✓" if getattr(b, c) else "·"
                           for c in _FLAG_COLUMNS)
        lines.append(f"| `{name}` | {flags} | `{b.kernel_kind}` | "
                     f"{b.protected_intervals} | {b.doc} |")
    lines.append("")
    lines.append("Flag legend: **ft** = detects/corrects SDCs "
                 "(`supports_ft`); **params** = accepts `KernelParams` "
                 "tiles and `DataPlan`/`BatchPlan` inputs (`takes_params`); "
                 "**inject** = accepts an in-kernel SEU descriptor "
                 "(`takes_injection`); **one-pass** = returns the extended "
                 "`(assign, min_dist, detected, sums, counts)` tuple "
                 "(`fuses_update`); **batch** = operates on (B, N, F) "
                 "problem stacks (`supports_batch`); **pruned** = carries "
                 "triangle-inequality bounds between iterations and "
                 "returns the 7-tuple extended by `(new_bounds, "
                 "prune_frac)` (`supports_bounds`); **int8** = runs the "
                 "distance GEMM on per-row int8-quantized operands and "
                 "accepts `QuantPlan` inputs (`supports_int8`). "
                 "*Kernel kind* is the "
                 "autotune table the backend's tiles come from; *protected "
                 "intervals* counts the independently verified SEU "
                 "intervals one step exposes to an injection campaign.")
    lines.append("")
    return "\n".join(lines)


def main(argv: Optional[list[str]] = None) -> int:
    """CLI: render (or freshness-check) the capability matrix.

    Exit codes are shared with ``python -m repro.analysis`` (see
    ``repro.analysis.report``): 0 = clean, 1 = violations/stale file,
    2 = usage error. ``--format=github`` emits workflow-command
    annotations so CI failures point at the offending file.
    """
    import argparse

    from repro.analysis import report

    ap = argparse.ArgumentParser(
        prog="python -m repro.api.registry",
        description="render the backend capability matrix as markdown")
    ap.add_argument("--markdown", nargs="?", const="-", metavar="PATH",
                    help="write the matrix to PATH (default: stdout)")
    ap.add_argument("--check", metavar="PATH",
                    help=f"exit {report.EXIT_VIOLATIONS} if PATH differs "
                         f"from a fresh render (CI staleness gate)")
    ap.add_argument("--format", choices=report.FORMATS, default="text",
                    help="violation output style (github = workflow "
                         "annotations)")
    args = ap.parse_args(argv)
    if args.check:
        rendered = render_markdown()
        try:
            with open(args.check, encoding="utf-8") as fh:
                committed: Optional[str] = fh.read()
        except FileNotFoundError:
            committed = None
        if committed != rendered:
            stale = report.Violation(
                pass_name="docs", rule="stale-matrix", file=args.check,
                message=(f"{args.check} is stale; regenerate with "
                         f"`python -m repro.api.registry --markdown "
                         f"{args.check}`"))
            return report.emit([stale], fmt=args.format)
        print(f"{args.check} is up to date")
        return report.EXIT_OK
    out = render_markdown()
    if args.markdown in (None, "-"):
        print(out, end="")
    else:
        with open(args.markdown, "w", encoding="utf-8") as fh:
            fh.write(out)
        print(f"wrote {args.markdown}")
    return report.EXIT_OK


if __name__ == "__main__":
    raise SystemExit(main())
