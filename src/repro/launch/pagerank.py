"""The paper's workload as a launchable job, driven through the query plane.

    PYTHONPATH=src python -m repro.launch.pagerank --dataset web-Google \
        --scale 0.05 --method ita --xi 1e-10 --step-impl ell

Single-device by default; ``--partition 1d|2d`` runs the distributed
solvers over whatever devices exist.  ``--batch B`` switches to the serving
shape: B one-hot personalized-PageRank queries solved in one device pass
(a ``PPRQuery`` through ``PageRankEngine.run``; the request-loop driver
around the same path is ``repro.launch.ppr_serve``).  ``--explain`` prints
the planner's decision for the requested query — backend, mesh layout,
execution path and why — and exits without solving (docs/API.md).
"""
from __future__ import annotations

import argparse

import jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="web-Google",
                    help="Table-3 preset name (stat-matched synthetic)")
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--method", default="ita",
                    choices=["ita", "power", "forward_push", "ifp",
                             "monte_carlo"])
    ap.add_argument("--step-impl", default="dense",
                    help="push backend: auto | dense | frontier | "
                         "frontier_priority | ell (core/backends.py registry)")
    ap.add_argument("--batch", type=int, default=0,
                    help="if > 0, solve this many one-hot PPR queries in "
                         "one batched pass instead of one global ranking")
    ap.add_argument("--xi", type=float, default=1e-10)
    ap.add_argument("--c", type=float, default=0.85)
    ap.add_argument("--partition", choices=["none", "1d", "2d"], default="none")
    ap.add_argument("--explain", action="store_true",
                    help="print the ExecutionPlan for the requested query "
                         "(backend, mesh, path, why) and exit")
    ap.add_argument("--symmetrize", action="store_true",
                    help="mirror every edge before solving (makes the "
                         "graph undirected, so --explain shows the "
                         "undirected-schedule planner rule)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    jax.config.update("jax_enable_x64", True)
    from .compile_cache import use_compile_cache
    use_compile_cache()
    from ..core import (
        BatchConfig,
        EnginePlan,
        PageRankEngine,
        PPRQuery,
        RankQuery,
        make_config,
        one_hot_personalizations,
    )
    from ..graph import paper_dataset

    if args.explain and args.partition != "none":
        ap.error("--explain describes engine queries; the --partition "
                 "solvers run outside the engine planner")

    g = paper_dataset(args.dataset, scale=args.scale, seed=args.seed)
    if args.symmetrize:
        import numpy as np

        from ..graph import graph_from_edges
        src, dst = np.asarray(g.src), np.asarray(g.dst)
        g = graph_from_edges(np.concatenate([src, dst]),
                             np.concatenate([dst, src]), g.n)
    print(f"graph: {g.stats()}")

    if args.partition != "none":
        from jax.sharding import AxisType

        from ..core.distributed import ita_distributed_1d, ita_distributed_2d
        n_dev = len(jax.devices())
        if args.partition == "1d":
            mesh = jax.make_mesh((n_dev,), ("data",),
                                 axis_types=(AxisType.Auto,))
            r = ita_distributed_1d(g, mesh, c=args.c, xi=args.xi)
        else:
            rows = max(1, n_dev // 2)
            mesh = jax.make_mesh((rows, n_dev // rows), ("data", "model"),
                                 axis_types=(AxisType.Auto,) * 2)
            r = ita_distributed_2d(g, mesh, c=args.c, xi=args.xi)
        print(f"method={r.method} iterations={r.iterations} ops={r.ops:.3e} "
              f"wall={r.wall_time_s}s converged={r.converged}")
        top = jax.numpy.argsort(-r.pi)[:5]
        print("top-5 vertices:", [(int(i), float(r.pi[i])) for i in top])
        return 0

    engine = PageRankEngine(g, EnginePlan(step_impl=args.step_impl,
                                          c=args.c))
    # the multi-line plan prints separately (--explain)
    print(f"engine: {engine.describe(include_plan=False)}")

    # build the typed query the run (or --explain) is about
    if args.batch > 0:
        import numpy as np
        rng = np.random.default_rng(args.seed)
        seeds = rng.choice(g.n, size=args.batch, replace=False)
        if args.method not in ("ita", "power"):
            ap.error(f"--batch supports methods ita|power, got {args.method!r}")
        P = one_hot_personalizations(g, seeds)
        query = PPRQuery(p_batch=P, cfg=BatchConfig(
            batch_method=args.method, c=args.c, xi=args.xi, tol=args.xi))
    else:
        kwargs = {"c": args.c}
        if args.method in ("ita", "forward_push", "ifp"):
            kwargs["xi"] = args.xi
        elif args.method == "power":
            kwargs["tol"] = args.xi
        query = RankQuery(cfg=make_config(args.method, **kwargs))

    if args.explain:
        print(engine.plan(query).explain())
        return 0

    env = engine.run(query)
    if args.batch > 0:
        rb = env.result
        print(f"batched PPR: {rb.stats()}")
        for b in range(min(args.batch, 4)):
            top = jax.numpy.argsort(-rb.pi[b])[:3]
            print(f"  seed {int(seeds[b])}: top-3 "
                  f"{[(int(i), float(rb.pi[b, i])) for i in top]}")
        return 0

    r = env.result
    print(f"method={r.method} iterations={r.iterations} ops={r.ops:.3e} "
          f"wall={r.wall_time_s}s converged={r.converged} "
          f"(plan: {env.plan.path})")
    top = jax.numpy.argsort(-r.pi)[:5]
    print("top-5 vertices:", [(int(i), float(r.pi[i])) for i in top])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
