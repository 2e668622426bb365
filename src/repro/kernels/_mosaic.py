"""What every Pallas kernel of the package asks of Mosaic.

Every kernel asks for one scoped-VMEM limit, ``hw.VMEM_BUDGET`` — the
budget the autotuner checks each kernel's VMEM model against
(``repro.core.autotune``), so the tile search and the compiler work to the
same number. Mosaic's default scoped limit is smaller than that budget.

Every floating-point MXU product of the k-means kernels goes through
:func:`mxu_dot`, which keeps f32 operands at f32 precision; the one-hot
products of the centroid updates go through :func:`onehot_dot`, which is
exact in three bf16 passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro import hw


def compiler_params(*dimension_semantics: str) -> pltpu.CompilerParams:
    """TPU compiler parameters for a grid with these dimension semantics."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=hw.VMEM_BUDGET)


def mxu_dot(a: jax.Array, b: jax.Array, contract: tuple[int, int]
            ) -> jax.Array:
    """2-D product contracting axis ``contract[0]`` of ``a`` with axis
    ``contract[1]`` of ``b``, accumulated in f32.

    f32 operands are multiplied at full f32 precision. Mosaic's default
    rounds them to bf16 — one MXU pass, a relative error near 2e-3 on a
    v5e — which breaks the f32 agreement with ``kernels/ref.py`` and makes
    an ABFT checksum disagree with the product it verifies, so every clean
    tile reads as corrupted. bf16 operands multiply exactly at either
    setting, and on the CPU (interpret mode) the precision changes
    nothing."""
    full = jnp.float32 in (a.dtype, b.dtype)
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if full else None,
        preferred_element_type=jnp.float32)


def onehot_dot(onehot: jax.Array, x: jax.Array, contract: tuple[int, int]
               ) -> jax.Array:
    """:func:`mxu_dot` of a 0/1 matrix with ``x``, every term exact and
    the sum accumulated in f32.

    A 2-byte ``x`` takes one pass against the one-hot in its dtype. An f32
    ``x`` is split exactly into three bf16 slices (:func:`bf16_slices`) and
    takes one bf16 MXU pass per slice, summed ``(S_hi + S_mid) + S_lo``.
    ``Precision.HIGHEST`` would take six, three of them against the
    one-hot's bf16 mid and lo parts, which are zero."""
    if x.dtype != jnp.float32:
        return mxu_dot(onehot.astype(x.dtype), x, contract)
    onehot = onehot.astype(jnp.bfloat16)
    hi, mid, lo = (mxu_dot(onehot, s, contract) for s in bf16_slices(x))
    return (hi + mid) + lo


def bf16_slices(x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Split f32 ``x`` into bf16 ``(hi, mid, lo)`` with hi + mid + lo == x.

    Each slice holds the next 8 significant bits of what the slices before
    it left over, and each residual is exact in f32, so the three slices
    carry all 24 bits of a normal f32."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo
