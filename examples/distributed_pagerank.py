"""Distributed ITA: the 1-D and 2-D edge partitions on a host-device mesh.

Run with several fake devices to see the real shard_map collectives:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/distributed_pagerank.py

``--smoke`` shrinks the graph and the tolerance for CI (the docs job runs
exactly that on the 8-device simulated host mesh).  Besides the
single-vector 1-D/2-D solvers this now also drives the batched-PPR pass
(``ita_batch_distributed`` — batch rows on "data", vertices optionally on
"model"; see docs/SHARDING.md).
"""
import argparse

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

from repro.core import power_method  # noqa: E402
from repro.core.batch import ita_batch, one_hot_personalizations  # noqa: E402
from repro.core.distributed import (  # noqa: E402
    ita_batch_distributed,
    ita_distributed_1d,
    ita_distributed_2d,
)
from repro.graph import paper_dataset  # noqa: E402


def auto_mesh(shape, axes):
    # Auto axes: the solvers rely on sharding propagation, which the
    # Explicit axes that jax.make_mesh defaults to refuse
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: tiny graph, looser xi")
    ap.add_argument("--scale", type=float, default=None)
    args = ap.parse_args(argv)
    scale = args.scale if args.scale is not None else (
        0.004 if args.smoke else 0.02)
    xi = 1e-10 if args.smoke else 1e-12

    n_dev = len(jax.devices())
    print(f"devices: {n_dev}")
    g = paper_dataset("web-Stanford", scale=scale, seed=0)
    print("graph:", g.stats())

    pi_ref = power_method(g, tol=1e-13, max_iter=300).pi

    mesh1 = auto_mesh((n_dev,), ("data",))
    r1 = ita_distributed_1d(g, mesh1, xi=xi)
    print(f"1-D: iters={r1.iterations} "
          f"err={float(jnp.max(jnp.abs(r1.pi - pi_ref))):.2e}")

    if n_dev >= 2:
        rows = max(2, n_dev // 2)
        mesh2 = auto_mesh((rows, n_dev // rows), ("data", "model"))
        r2 = ita_distributed_2d(g, mesh2, xi=xi)
        print(f"2-D ({rows}x{n_dev//rows}): iters={r2.iterations} "
              f"err={float(jnp.max(jnp.abs(r2.pi - pi_ref))):.2e}")

    # batched PPR, the serving shape: batch rows on "data"
    seeds = [1, 5, 11, 17, 23, 29]
    P = one_hot_personalizations(g, seeds)
    ref_b = ita_batch(g, P, xi=xi)
    mesh_b = auto_mesh((n_dev, 1), ("data", "model"))
    rb = ita_batch_distributed(g, P, mesh_b, xi=xi)
    bitwise = bool(jnp.array_equal(ref_b.pi, rb.pi))
    print(f"batched PPR ({n_dev}x1, B={len(seeds)}): iters={rb.iterations} "
          f"bit-identical={bitwise}")
    if n_dev >= 2:
        mesh_bc = auto_mesh((n_dev // 2, 2), ("data", "model"))
        rb2 = ita_batch_distributed(g, P, mesh_bc, xi=xi)
        err = float(jnp.max(jnp.abs(ref_b.pi - rb2.pi)))
        print(f"batched PPR ({n_dev//2}x2, vertex-sharded): "
              f"iters={rb2.iterations} err={err:.2e}")
    if not bitwise:
        raise SystemExit("batch-parallel sharding must be bit-identical")
    print("collective schedule per step: psum_scatter(model) + all_gather(data)"
          " — no all-to-all, no dangling-mass all-reduce (DESIGN.md §2);"
          " the batched pass drops the all_gather entirely (docs/SHARDING.md)")


if __name__ == "__main__":
    main()
