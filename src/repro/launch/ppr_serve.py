"""PPR query serving — the production tier in front of the engine.

    PYTHONPATH=src python -m repro.launch.ppr_serve --smoke
    PYTHONPATH=src python -m repro.launch.ppr_serve --dataset web-Google \
        --scale 0.02 --qps 200 --deadline-ms 250 --queue-cap 64 \
        --policy full
    PYTHONPATH=src python -m repro.launch.ppr_serve --smoke --qps 100000 \
        --deadline-ms 50 --queue-cap 8 --expect-shed

Thin CLI over ``repro.serve`` (see docs/SERVING.md): arrivals →
admission (token bucket + cache-aware bypass) → bounded queue →
deadline-aware batcher → ``engine.run(TopKQuery)``.  Without ``--qps``
the stream is the classic closed-loop saturating drain (``--batch``
clients, zero think time — offered load tracks capacity); with ``--qps``
it is an open-loop Poisson arrival process at that offered rate, the
shape that actually exercises shedding and degradation.

``--policy`` picks the protection stack:
  * ``none``     — queue + deadline batcher only (still sheds on full);
  * ``throttle`` — adds the token bucket (``--rate-limit``, default:
                   the calibrated capacity of one engine);
  * ``degrade``  — adds the hysteretic fidelity ladder (looser ξ);
  * ``full``     — both.

The solver itself comes from the engine's serving config — any
registered ``SOLVERS`` entry, including ``"ifp"`` (docs/SOLVERS.md §ifp),
is selectable there; this CLI does not hard-code a method.

``--sim`` replays the identical loop on a virtual clock with modeled
batch cost (calibrated from one real warmup batch) — deterministic
queueing dynamics, no wall-clock dependence; the mode every serving
test and the drift-checked benchmark run in.  ``--expect-shed`` makes
the process exit nonzero unless overload protection actually shed
requests — the CI overload smoke's assertion.
"""
from __future__ import annotations

import argparse
import time

import jax

# re-export: historical home of this helper (PR 5/6 callers import it here)
from ..serve.workload import zipf_seeds  # noqa: F401


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="web-Google",
                    help="Table-3 preset name (stat-matched synthetic)")
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--queries", type=int, default=256,
                    help="total PPR requests in the stream")
    ap.add_argument("--batch", type=int, default=16,
                    help="micro-batch size (one [B, n] device pass each)")
    ap.add_argument("--method", default="ita", choices=["ita", "power"])
    ap.add_argument("--step-impl", default="auto",
                    help="push backend: auto | dense | frontier | ell")
    ap.add_argument("--xi", type=float, default=1e-8,
                    help="serving tolerance (xi for ita, tol for power)")
    ap.add_argument("--c", type=float, default=0.85)
    ap.add_argument("--topk", type=int, default=5)
    ap.add_argument("--zipf", type=float, default=1.1,
                    help="query-skew exponent over in-degree rank; 0=uniform")
    ap.add_argument("--mesh", default=None, metavar="R[,C]",
                    help="serve sharded over an (R, C) device grid: batch "
                         "rows on 'data', vertices on 'model' (C>1 needs "
                         "--step-impl dense)")
    ap.add_argument("--cache", action="store_true",
                    help="attach the result cache (core/cache.py): repeat "
                         "seeds bypass the queue entirely, ita method only")
    ap.add_argument("--cache-capacity", type=int, default=4096,
                    help="max cached seeds before LRU eviction")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: tiny graph, short stream")
    # --- serving-tier knobs (docs/SERVING.md) ---
    ap.add_argument("--qps", type=float, default=None,
                    help="open-loop offered load (Poisson arrivals); "
                         "omit for the closed-loop saturating drain")
    ap.add_argument("--deadline-ms", type=float, default=250.0,
                    help="per-request latency SLO; the batcher dispatches "
                         "partial batches rather than miss the head's")
    ap.add_argument("--queue-cap", type=int, default=None,
                    help="bounded-queue capacity (default 4x batch); "
                         "offers beyond it are shed with a typed Overload")
    ap.add_argument("--policy", default="none",
                    choices=["none", "throttle", "degrade", "full"],
                    help="overload protection stack (see module docstring)")
    ap.add_argument("--rate-limit", type=float, default=None,
                    help="token-bucket sustained qps for --policy "
                         "throttle/full (default: calibrated capacity)")
    ap.add_argument("--sim", action="store_true",
                    help="virtual clock + modeled batch cost: deterministic "
                         "queueing dynamics, no wall-clock sleeps")
    ap.add_argument("--expect-shed", action="store_true",
                    help="exit 1 unless the run shed at least one request "
                         "(the CI overload smoke assertion)")
    args = ap.parse_args(argv)
    if args.smoke:  # shrink whatever the user did not set explicitly
        if args.scale == 0.02:
            args.scale = 0.004
        if args.queries == 256:
            args.queries = 32
        if args.batch == 16:
            args.batch = 8
    if args.queries < 1 or args.batch < 1:
        ap.error("--queries and --batch must be >= 1")
    if args.queue_cap is None:
        args.queue_cap = 4 * args.batch
    if args.queue_cap < 1:
        ap.error("--queue-cap must be >= 1")
    if args.qps is not None and args.qps <= 0:
        ap.error("--qps must be > 0 (omit it for the closed loop)")

    jax.config.update("jax_enable_x64", True)
    from .compile_cache import use_compile_cache
    use_compile_cache()
    import numpy as np

    from ..core import (BatchConfig, CachePolicy, EnginePlan, PageRankEngine,
                        TopKQuery)
    from ..graph import paper_dataset
    from ..serve import (AdmissionPolicy, ClosedLoopWorkload, DegradePolicy,
                         OpenLoopWorkload, PPRService, ServiceConfig,
                         VirtualClock)

    mesh = None
    if args.mesh is not None:
        try:
            mesh = tuple(int(x) for x in args.mesh.split(","))
        except ValueError:
            ap.error(f"--mesh must be R or R,C; got {args.mesh!r}")
        if args.method == "power":
            # only ITA batches run through the sharded pass; serving a
            # power stream "with --mesh" would silently run single-device
            ap.error("--mesh applies to --method ita only (power batches "
                     "run single-device); drop --mesh or use --method ita")
    if args.cache and args.method == "power":
        ap.error("--cache needs --method ita (power rows carry no "
                 "(π̄, h) state to revalidate)")

    g = paper_dataset(args.dataset, scale=args.scale, seed=args.seed)
    print(f"graph: {g.stats()}")

    # 1. prepare — the one-time session cost every query amortizes
    t0 = time.perf_counter()
    cache = CachePolicy(capacity=args.cache_capacity) if args.cache else None
    engine = PageRankEngine(g, EnginePlan(step_impl=args.step_impl,
                                          c=args.c, mesh=mesh, cache=cache))
    t_prepare = time.perf_counter() - t0
    desc = engine.describe(include_plan=False)  # serving plan prints below
    print(f"engine: {desc}  prepare: {t_prepare*1e3:.1f} ms")
    mesh_eff = desc["mesh"]

    cfg = BatchConfig(batch_method=args.method, c=args.c, xi=args.xi,
                      tol=args.xi)
    B = max(1, min(args.batch, args.queries))
    deadline_s = args.deadline_ms / 1e3

    # report the planner's decision for the micro-batch shape we will serve
    probe = np.zeros(B, dtype=np.int64)
    print(engine.plan(TopKQuery(sources=probe, k=args.topk,
                                cfg=cfg)).explain())

    # 2. assemble the tier: admission + queue + batcher + degrade ladder
    throttling = args.policy in ("throttle", "full")
    degrading = args.policy in ("degrade", "full")
    svc_cfg = ServiceConfig(
        batch_size=B, k=args.topk, queue_cap=args.queue_cap,
        admission=AdmissionPolicy(rate_qps=None, burst=float(B),
                                  cache_bypass=args.cache),
        degrade=(DegradePolicy(hi=max(2, (3 * args.queue_cap) // 4),
                               lo=max(1, args.queue_cap // 4))
                 if degrading else None),
        cfg=cfg,
        time_source="model" if args.sim else "wall",
    )
    clock = VirtualClock() if args.sim else None
    service = PPRService(engine, svc_cfg, clock=clock)

    # 3. warmup + calibration — compile the [B, n] pass outside the
    #    measured window and seed the cost model from its wall time
    cal = service.calibrate()
    capacity_qps = B / max(cal["warm_batch_s"], 1e-9)
    print(f"warmup: {cal['warm_batch_s']*1e3:.1f} ms/batch "
          f"({cal['cost_units']:.0f} cost units, "
          f"capacity ≈ {capacity_qps:.0f} q/s)")
    if throttling:
        # the bucket's sustained rate defaults to what one engine can
        # actually serve — known only after calibration, so wire it here
        from ..serve import AdmissionController
        rate = args.rate_limit if args.rate_limit else capacity_qps
        service.admission = AdmissionController(
            AdmissionPolicy(rate_qps=rate, burst=float(B),
                            cache_bypass=args.cache), engine)
        print(f"throttle: token bucket {rate:.0f} q/s, burst {B}")

    # 4. the stream
    if args.qps is None:
        workload = ClosedLoopWorkload(g, clients=B, n_queries=args.queries,
                                      zipf=args.zipf, seed=args.seed,
                                      deadline_s=deadline_s, k=args.topk)
        shape = f"closed-loop x{B} clients"
    else:
        workload = OpenLoopWorkload(g, qps=args.qps, n_queries=args.queries,
                                    zipf=args.zipf, seed=args.seed,
                                    deadline_s=deadline_s, k=args.topk)
        shape = f"open-loop {args.qps:g} q/s offered"

    # 5. serve + report
    report = service.serve(workload)
    s = report.summary()
    lat = s["latency"]
    print(f"served {s['served']}/{s['offered']} queries in {s['batches']} "
          f"micro-batches of {B} ({shape}, method={args.method}, "
          f"step_impl={engine.step_impl}, mesh={mesh_eff}, "
          f"zipf={args.zipf}, policy={args.policy})")
    print(f"latency p50/p99: {lat['p50_ms']:.1f}/{lat['p99_ms']:.1f} ms   "
          f"deadline({args.deadline_ms:.0f} ms) miss: "
          f"{s['deadline_miss_frac']*100:.1f}%   "
          f"throughput: {s['qps']:.1f} q/s")
    print(f"overload: shed={s['shed']} ({s['shed_frac']*100:.1f}%) "
          f"[throttled={s['admission']['throttled']} "
          f"queue_full={s['queue']['rejected']}]   "
          f"degraded={s['degraded_frac']*100:.1f}%   "
          f"max_depth={s['queue']['max_depth']}/{s['queue']['capacity']}   "
          f"dispatch={s['batcher']}")
    if report.degrade_stats is not None:
        print(f"degrade: {report.degrade_stats}")
    if engine.result_cache is not None:
        cs = engine.result_cache.stats()
        print(f"cache: hit_rate={cs['hit_rate']:.2f} hits={cs['hits']} "
              f"misses={cs['misses']} revalidated={cs['revalidated']} "
              f"entries={cs['entries']} evictions={cs['evictions']} "
              f"bypassed_queue={s['admission']['bypassed']} "
              f"(graph_version={engine.graph_version})")
    sample = next((x for x in report.served if x.indices is not None), None)
    if sample is not None:
        pairs = [(int(i), float(v))
                 for i, v in zip(sample.indices, sample.scores)]
        print(f"sample answer — seed {sample.req.seed}: {pairs}")
    if args.expect_shed and s["shed"] == 0:
        print("FAIL: --expect-shed but no requests were shed "
              "(overload protection never engaged)")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
