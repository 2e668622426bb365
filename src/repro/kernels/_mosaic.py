"""What every Pallas kernel of the package asks of Mosaic.

Every kernel asks for one scoped-VMEM limit, ``hw.VMEM_BUDGET`` — the
budget the autotuner checks each kernel's VMEM model against
(``repro.core.autotune``), so the tile search and the compiler work to the
same number. Mosaic's default scoped limit is smaller than that budget.

Every floating-point MXU product of the k-means kernels goes through
:func:`mxu_dot`, which keeps f32 operands at f32 precision.
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
