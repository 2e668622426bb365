"""Hierarchical vs flat vs compressed centroid reduce on a pod-shaped mesh.

Times one reduced Lloyd step of ``DistributedKMeans`` on an 8-virtual-
device CPU mesh (``mesh2d(8, hosts=2)`` — 2 simulated hosts x 4 rows)
under each :class:`~repro.dist.reduce.ReducePlan`:

  * ``flat``          one psum over every data axis (the PR-1 reduce)
  * ``hierarchical``  exact intra-host psum + exact cross-host hop
  * ``compressed``    exact intra-host psum + int8 error-feedback hop

On virtual CPU devices every "link" is the same memcpy, so the wall-clock
deltas here calibrate the *software* cost of the two-hop structure (extra
collective launches, quantize/dequantize arithmetic), not the cross-pod
bandwidth win the hierarchy exists for — the derived column carries the
ratios so ``check_regression`` can gate the hierarchical rung
(``dist_hier_vs_flat``) against the committed ``BENCH_dist.json``.

Standalone module (like bench_serve): the 8 virtual CPU devices exist only
if ``XLA_FLAGS`` is set before jax initializes, so it is NOT in
``benchmarks.run``'s in-process module list. The module pins no platform
itself; ``make bench-dist`` sets ``JAX_PLATFORMS=cpu`` and the device
count, so on a machine with a chip it never quietly runs on the CPU.

CLI:
  --smoke        tiny shapes (CI wiring)
  --json PATH    write rows + shapes to PATH (CI artifact)
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks.common import row, time_call

M, K, F = 8192, 64, 128
SMOKE_M, SMOKE_K, SMOKE_F = 2048, 16, 64


def _step_seconds(plan, x, c0, mesh, *, k, iters):
    """Seconds per reduced Lloyd step under ``plan`` (jitted, warmed)."""
    from repro.api import KMeans
    from repro.core.fault import no_step_injection
    from repro.dist.kmeans_dist import DistributedKMeans
    est = KMeans(k, max_iter=5, random_state=0)
    d = DistributedKMeans(est, mesh, reduce=plan)
    xs = d.shard_data(x)
    f = x.shape[1]
    step = d._build_step(x.shape[0] // d._rp, f)
    inj = no_step_injection(d._shard_backend().kernel_kind)
    if d._compress:
        res = jax.device_put(
            jnp.zeros((mesh.shape["host"], k, f), jnp.float32),
            NamedSharding(mesh, P("host", None, None)))
    else:
        res = jnp.zeros((1, k, f), jnp.float32)
    c = jnp.asarray(c0)
    return time_call(lambda: step(xs, c, inj, res), iters=iters)


def run(smoke: bool = False) -> list[str]:
    return _collect(smoke=smoke)[0]


def _collect(smoke: bool = False) -> tuple[list[str], dict]:
    from repro.dist.reduce import ReducePlan
    from repro.dist.sharding import mesh2d
    if len(jax.devices()) < 8:
        raise SystemExit("bench_dist needs 8 devices; run `make bench-dist` "
                         "(8 virtual CPU devices)")
    m, k, f = (SMOKE_M, SMOKE_K, SMOKE_F) if smoke else (M, K, F)
    iters = 5 if smoke else 11
    rng = np.random.default_rng(0)
    x = rng.standard_normal((m, f)).astype(np.float32)
    c0 = x[rng.choice(m, size=k, replace=False)].copy()
    mesh = mesh2d(8, hosts=2)

    t_flat = _step_seconds(ReducePlan.flat(), x, c0, mesh, k=k,
                           iters=iters)
    t_hier = _step_seconds(ReducePlan(), x, c0, mesh, k=k, iters=iters)
    t_comp = _step_seconds(ReducePlan.compressed(), x, c0, mesh, k=k,
                           iters=iters)
    shape = f"M={m};K={k};F={f};mesh=2x4"
    out = [
        row("dist_hier_vs_flat", t_hier,
            f"{shape};flat_us={t_flat * 1e6:.1f};"
            f"ratio=x{t_hier / t_flat:.2f}"),
        row("dist_compressed_hop", t_comp,
            f"{shape};hier_us={t_hier * 1e6:.1f};"
            f"ratio=x{t_comp / t_hier:.2f}"),
    ]
    payload = {
        "shapes": {"grid": [m, k, f], "mesh": [2, 4, 1]},
        "smoke": smoke,
        "interpret_rungs": [],      # every plan runs compiled XLA off-TPU
        "rows": [r.split(",", 2) for r in out],
    }
    return out, payload


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="tiny shapes (CI)")
    ap.add_argument("--json", metavar="PATH",
                    help="write rows + shapes to PATH (CI artifact)")
    args = ap.parse_args(argv)
    rows, payload = _collect(smoke=args.smoke)
    print("\n".join(rows))
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=1, sort_keys=True)


if __name__ == "__main__":
    main()
