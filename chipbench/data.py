"""Inputs made from ``--seed``: IVF-shaped training data and arrivals.

The data is a Gaussian mixture whose component sizes follow Zipf(s): a few
components hold most rows and the smallest hold a few dozen, as the cells of
a real IVF index do. Rows arrive in random order. The sizes depend only on
the configuration, never on the seed, so every seed does the same work;
the seed draws the centres, the noise and the row order. Everything is made
on the device in one jitted call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the low and high 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def small_seed(seed: int, salt: int = 0) -> int:
    """A seed below 2**31 derived from ``seed``, for APIs that take one."""
    return int(np.random.SeedSequence([int(seed), salt]).generate_state(1)[0]
               & 0x7FFFFFFF)


def zipf_sizes(rows: int, components: int, s: float) -> np.ndarray:
    """Component sizes proportional to 1 / rank**s that sum to ``rows``
    (largest-remainder rounding)."""
    w = 1.0 / np.arange(1, components + 1, dtype=np.float64) ** s
    exact = rows * w / w.sum()
    sizes = np.floor(exact).astype(np.int64)
    short = rows - int(sizes.sum())
    sizes[np.argsort(-(exact - sizes), kind="stable")[:short]] += 1
    return sizes


@functools.partial(jax.jit, static_argnames=("rows", "features", "sizes",
                                             "centre_std", "sigma"))
def _mixture(key, *, rows, features, sizes, centre_std, sigma):
    k_centre, k_order, k_noise = jax.random.split(key, 3)
    n_comp = len(sizes)
    centres = centre_std * jax.random.normal(k_centre, (n_comp, features),
                                             jnp.float32)
    comp = jnp.repeat(jnp.arange(n_comp, dtype=jnp.int32),
                      jnp.asarray(sizes), total_repeat_length=rows)
    comp = jax.random.permutation(k_order, comp)
    noise = jax.random.normal(k_noise, (rows, features), jnp.float32)
    return centres[comp] + sigma * noise, centres


def mixture(key: jax.Array, rows: int, features: int, spec: dict):
    """(X (rows, features) f32, centres (components, features) f32)."""
    sizes = tuple(int(v) for v in zipf_sizes(rows, spec["components"],
                                             spec["zipf_s"]))
    return _mixture(key, rows=rows, features=features, sizes=sizes,
                    centre_std=float(spec["centre_std"]),
                    sigma=float(spec["sigma"]))


@functools.partial(jax.jit, static_argnums=(2,))
def random_rows(key: jax.Array, x: jax.Array, k: int) -> jax.Array:
    """K distinct rows of ``x`` drawn by ``key``: FAISS's random init."""
    return x[jax.random.choice(key, x.shape[0], (k,), replace=False)]


def poisson_schedule(seed: int, mix: dict, seconds: float, offset: int = 0):
    """Open-loop arrivals for ``seconds``: (due times in s, rows per request).

    The multiset of request sizes and inter-arrival gaps is drawn from the
    mix's own fixed seed, so every run seed offers the same work (the gaps
    scaled to fill ``seconds`` exactly); the run seed only permutes them.
    Request sizes are log-uniform integers over [rows_min, rows_max]."""
    n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    base = np.random.default_rng([int(mix["base_seed"]), offset])
    gaps = base.exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    lo, hi = mix["rows_min"], mix["rows_max"]
    sizes = np.floor(np.exp(base.uniform(np.log(lo), np.log(hi + 1), n)))
    sizes = np.clip(sizes, lo, hi).astype(np.int64)
    run = np.random.default_rng([int(seed), offset, 1])
    gaps, sizes = run.permutation(gaps), run.permutation(sizes)
    return np.cumsum(gaps) - gaps, sizes
