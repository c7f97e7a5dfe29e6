"""Dense-push microbench: time and agreement of the push realisations.

    python benchmarks/bench_push.py [--scale 1.0] [--dtypes float32,float64]

On the paper's web-Google preset at ``--scale`` of full size, for a vector
``[n]`` and a batch ``[16, n]`` of uniform [0, 1) values per vertex, it
times

  * ``gather``      — ``w[src]`` alone,
  * ``segment_sum`` — the gather plus XLA's sorted scatter-add,
  * ``dense.push``  — the dense backend (gather plus segmented scan),

and prints, for every realisation that sums, the largest absolute
difference from a float64 ``np.bincount`` over all rows, beside the
largest |y|.  Times are wall-clock per call after one compiling call,
ended by ``block_until_ready``.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))
jax.config.update("jax_enable_x64", True)

from repro.core.backends import get_step_impl  # noqa: E402
from repro.graph import paper_dataset  # noqa: E402

B = 16


def timeit(f, x, reps: int):
    f = jax.jit(f)
    t0 = time.perf_counter()
    y = jax.block_until_ready(f(x))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        y = f(x)
    jax.block_until_ready(y)
    return y, first, (time.perf_counter() - t0) / reps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--dtypes", default="float32,float64")
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args(argv)

    g = paper_dataset("web-Google", scale=args.scale, seed=0)
    dense = get_step_impl("dense")
    ctx = dense.prepare(g)
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    print(f"web-Google scale={args.scale}: n={g.n} m={g.m}; "
          f"{jax.devices()[0].device_kind}", flush=True)

    def segsum(v):  # edges on the last axis, as the dense push holds them
        y = jax.ops.segment_sum(jnp.moveaxis(v, -1, 0), g.dst,
                                num_segments=g.n, indices_are_sorted=True)
        return jnp.moveaxis(y, 0, -1)

    realisations = {
        "gather": lambda w: w[..., g.src],
        "segment_sum": lambda w: segsum(w[..., g.src]),
        "dense.push": lambda w: (dense.push_batch(g, ctx, w) if w.ndim == 2
                                 else dense.push(g, ctx, w)),
    }
    rng = np.random.default_rng(0)
    for dt in args.dtypes.split(","):
        for shape in ((g.n,), (B, g.n)):
            xd = jnp.asarray(rng.random(shape), dt)
            # float64 sums of the values as the device holds them
            ref = np.stack([np.bincount(dst, weights=row[src], minlength=g.n)
                            for row in np.asarray(xd, np.float64)
                            .reshape(-1, g.n)]).reshape(shape)
            for name, f in realisations.items():
                y, first, per = timeit(f, xd, args.reps)
                line = (f"{name} {shape} {dt}: first call {first:.3f} s, "
                        f"per call {per * 1e3:.3f} ms")
                if name != "gather":
                    err = np.abs(np.asarray(y, np.float64) - ref)
                    row = int(np.unravel_index(np.argmax(err), err.shape)[0])
                    line += (f"; max|y - numpy float64| = {err.max():.3e}"
                             f" (row {row if len(shape) == 2 else '-'},"
                             f" max|y| = {np.abs(ref).max():.3e})")
                print(line, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
