"""Smoke run of the engine's main paths on a TPU, at full web-Google size.

    python chip_smoke.py             # one chip: global ranking + PPR serving
    python chip_smoke.py --chips 4   # four chips: sharded TopK vs one device

The graph is the paper's Table-3 web-Google preset,
``paper_dataset("web-Google", scale=1.0, seed=0)`` (n = 875,713), made from
its seed on every run.  With no arguments the script

  1. ranks it globally: ``PageRankEngine(g, EnginePlan())`` with the default
     ``step_impl="auto"``, then ``engine.run(RankQuery(ItaConfig(xi=1e-10)))``,
     checked against an independent float64 numpy power iteration;
  2. serves PPR top-k through the ``serve/`` tier on the wall clock (token
     bucket off, bounded queue, deadline batcher, ``engine.run(TopKQuery)``)
     with micro-batches of 16 and a 64-request Zipf-1.1 open-loop stream, and
     checks every answer for the two most asked seed vertices, and their
     whole PPR rows, against the numpy PPR.

``--chips 4`` runs only the mesh phase: one 16-seed ``TopKQuery`` on a
(4, 1) batch-parallel grid, which must equal the single-device engine bit
for bit, and on a (2, 2) vertex-sharded grid, which must agree within 1e-10.

Every phase that fails raises, and the script exits nonzero.  It refuses to
run anywhere but on a TPU.  The times it prints are those of one smoke run,
not a benchmark.  Its last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

DATASET = "web-Google"
C = 0.85          # damping
XI = 1e-10        # ITA threshold, global ranking and serving alike
REF_TOL = 1e-14   # l2 step at which the host reference stops
K = 10            # top-k served and compared
B = 16            # serving micro-batch
N_REQUESTS = 64   # open-loop stream length
ZIPF = 1.1        # seed skew over in-degree rank
MESH_TOL = 1e-10  # (2, 2) vertex-sharded grid vs one device (max abs)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def log(msg: str = "") -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def timing(label: str, seconds: float) -> None:
    log(f"  [smoke timing, not a benchmark] {label}: {seconds:.6f} s")


# ---------------------------------------------------------------------------
# the independent host reference
# ---------------------------------------------------------------------------
def host_pagerank(src, dst, n: int, p, *, c: float = C, tol: float = REF_TOL,
                  max_iter: int = 1000):
    """float64 numpy power iteration with ``reference_pagerank``'s semantics:
    ``pi <- c P pi + c (d . pi) p + (1 - c) p`` from ``pi = p`` until the l2
    step is at most ``tol`` (``d`` marks the dangling vertices).  The push is
    one ``np.bincount`` over the edge list; nothing here touches JAX."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    p = np.asarray(p, np.float64)
    out_deg = np.bincount(src, minlength=n)
    inv_deg = np.where(out_deg > 0, 1.0 / np.maximum(out_deg, 1), 0.0)
    dangling = out_deg == 0
    pi = p.copy()
    for it in range(1, max_iter + 1):
        new = c * np.bincount(dst, weights=(pi * inv_deg)[src], minlength=n)
        new += (c * pi[dangling].sum() + (1.0 - c)) * p
        step = float(np.linalg.norm(new - pi))
        pi = new
        if step <= tol:
            return pi, it
    raise SmokeFailure(f"host reference did not reach an l2 step of {tol:g} "
                       f"in {max_iter} iterations")


def l1_bound(n: int, *, c: float = C, xi: float = XI,
             tol: float = REF_TOL) -> float:
    """Largest L1 distance a correct answer may have from the host reference.

    ITA stops with at most ``xi`` left on each non-dangling vertex, out of a
    total mass of at least ``n``; pushing that rest on would move at most
    ``c / (1 - c)`` times it, and normalizing at most doubles the distance:
    ``2 c xi / (1 - c)``.  The reference stops at an l2 step ``tol``, so it
    is within ``c / (1 - c) sqrt(n) tol`` of the fixed point in L1.
    """
    return 2 * c * xi / (1 - c) + c / (1 - c) * np.sqrt(n) * tol


def compare_topk(idx, scores, ref, bound: float) -> bool:
    """Check served top-k ``(idx, scores)`` against a reference vector.

    The indices must be the reference's top-k, in its order, and each score
    must lie within ``bound`` of the reference value.
    """
    idx = np.asarray(idx)
    scores = np.asarray(scores)
    order = np.argsort(-ref, kind="stable")[: len(idx)]
    identical = bool(np.array_equal(idx, order))
    score_err = float(np.max(np.abs(scores - ref[idx])))
    log(f"    top-{len(idx)} identical: {identical}; max |score - reference| "
        f"= {score_err:.3e} (bound {bound:.3e})")
    return identical and score_err <= bound


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def make_graph(scale: float = 1.0, seed: int = 0):
    from repro.graph import paper_dataset

    t0 = time.perf_counter()
    g = paper_dataset(DATASET, scale=scale, seed=seed)
    log(f"graph: {DATASET} scale={scale} seed={seed}: n={g.n} m={g.m}")
    timing("graph generation (set-up)", time.perf_counter() - t0)
    return g


def run_ranking(g):
    """Global ranking through the engine, checked against the host."""
    import jax

    from repro.core import EnginePlan, ItaConfig, PageRankEngine, RankQuery

    log("== global ranking ==")
    t0 = time.perf_counter()
    engine = PageRankEngine(g, EnginePlan())
    timing("engine prepare", time.perf_counter() - t0)
    query = RankQuery(ItaConfig(xi=XI, c=C))
    log(f"  resolved backend: {engine.step_impl}")
    log(engine.plan(query).explain())
    t0 = time.perf_counter()
    env = engine.run(query)
    jax.block_until_ready(env.values)
    timing("first solve, compile included (set-up)", time.perf_counter() - t0)
    t0 = time.perf_counter()
    env = engine.run(query)
    pi = jax.block_until_ready(env.values)
    timing("warm solve", time.perf_counter() - t0)
    log(f"  rounds: {env.iterations}  converged: {env.converged}")
    check(env.converged, "global ranking did not converge")
    pi = np.asarray(pi, np.float64)
    check(pi.shape == (g.n,) and bool(np.all(np.isfinite(pi))),
          f"global ranking: expected {g.n} finite values, got {pi.shape}")

    t0 = time.perf_counter()
    ref, ref_it = host_pagerank(np.asarray(g.src), np.asarray(g.dst), g.n,
                                np.full(g.n, 1.0 / g.n))
    log(f"  host float64 numpy reference: {ref_it} iterations "
        f"({time.perf_counter() - t0:.1f} s)")
    bound = l1_bound(g.n)
    l1 = float(np.abs(pi - ref).sum())
    log(f"  L1(pi, reference) = {l1:.3e}; bound 2c*xi/(1-c) + "
        f"c/(1-c)*sqrt(n)*tol = {bound:.3e}")
    ok = compare_topk(np.argsort(-pi, kind="stable")[:K],
                      np.sort(pi)[::-1][:K], ref, bound)
    check(ok and l1 <= bound, "global ranking disagrees with the reference")
    log("  global ranking check: PASS")
    return engine


def run_serving(g, engine, *, n_requests: int = N_REQUESTS, seed: int = 0):
    """PPR top-k through the serving tier on the wall clock."""
    from repro.core import BatchConfig, TopKQuery
    from repro.serve import (AdmissionPolicy, OpenLoopWorkload, PPRService,
                             ServiceConfig)

    log("== PPR top-k serving ==")
    cfg = BatchConfig(c=C, xi=XI)
    probe = np.zeros(B, dtype=np.int64)
    log(engine.plan(TopKQuery(sources=probe, k=K, cfg=cfg)).explain())
    service = PPRService(engine, ServiceConfig(
        batch_size=B, k=K, queue_cap=4 * B, cfg=cfg,
        admission=AdmissionPolicy(rate_qps=None, burst=float(B),
                                  cache_bypass=False)))
    t0 = time.perf_counter()
    cal = service.calibrate()
    warm = cal["warm_batch_s"]
    timing("calibration: compile + one warm batch (set-up)",
           time.perf_counter() - t0)
    timing(f"warm micro-batch of {B}", warm)
    # offer half of one engine's calibrated capacity, with a deadline of four
    # warm batches: a healthy service should serve everything in time
    qps = 0.5 * B / warm
    deadline_s = 4 * warm
    workload = OpenLoopWorkload(g, qps=qps, n_queries=n_requests, zipf=ZIPF,
                                seed=seed, deadline_s=deadline_s, k=K)
    report = service.serve(workload)
    s = report.summary()
    lat = s["latency"]
    log(f"  offered {qps:.3f} q/s open loop, deadline {deadline_s * 1e3:.1f} ms,"
        f" zipf={ZIPF}, k={K}, B={B}")
    log(f"  served/offered: {s['served']}/{s['offered']} in {s['batches']} "
        f"micro-batches; shed fraction: {s['shed_frac']:.4f}")
    log(f"  [smoke timing, not a benchmark] latency p50/p99: "
        f"{lat['p50_ms']:.3f}/{lat['p99_ms']:.3f} ms; deadline misses: "
        f"{s['deadline_miss_frac']:.4f}")
    check(s["served"] > 0, "serving answered no request")
    answered = [x for x in report.served if x.indices is not None]
    check(len(answered) == s["served"], "a served request carries no answer")

    # the two seed vertices asked most often; every answer for them is checked
    counts = {}
    for x in answered:
        counts[x.req.seed] = counts.get(x.req.seed, 0) + 1
    top_seeds = sorted(counts, key=lambda v: (-counts[v], v))[:2]
    # their whole PPR rows too, from one more micro-batch of the same shape
    sources = np.asarray(top_seeds + [top_seeds[-1]] * (B - len(top_seeds)))
    env = engine.run(TopKQuery(sources=sources, k=K, cfg=cfg))
    rows = np.asarray(env.result.result.pi, np.float64)
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    bound = l1_bound(g.n)
    ok = True
    for i, v in enumerate(top_seeds):
        p = np.zeros(g.n)
        p[v] = 1.0
        ref, ref_it = host_pagerank(src, dst, g.n, p)
        mine = [x for x in answered if x.req.seed == v]
        l1 = float(np.abs(rows[i] - ref).sum())
        log(f"  seed vertex {v}: {len(mine)} served answers vs host numpy PPR "
            f"({ref_it} iterations); L1(row, reference) = {l1:.3e} "
            f"(bound {bound:.3e})")
        ok = l1 <= bound and ok
        for x in mine:
            check(len(x.indices) == K and np.all(np.isfinite(x.scores)),
                  f"answer for seed {v} is not {K} finite scores")
            ok = compare_topk(x.indices, x.scores, ref, bound) and ok
    check(ok, "a served PPR answer disagrees with the reference")
    log("  serving check: PASS")


def run_mesh(g, *, seed: int = 0):
    """The same 16-seed TopKQuery on one device, (4, 1) and (2, 2) grids."""
    import jax

    from repro.core import BatchConfig, EnginePlan, PageRankEngine, TopKQuery
    from repro.serve import zipf_seeds

    log("== mesh-sharded PPR top-k ==")
    sources = zipf_seeds(g, B, ZIPF, seed)
    query = TopKQuery(sources=sources, k=K, cfg=BatchConfig(c=C, xi=XI))
    results = {}
    for mesh in (None, (4, 1), (2, 2)):
        t0 = time.perf_counter()
        engine = PageRankEngine(g, EnginePlan(mesh=mesh))
        timing(f"prepare mesh={mesh}", time.perf_counter() - t0)
        d = engine.describe(include_plan=False)
        log(f"  mesh={mesh}: backend={d['step_impl']} grid={d['mesh']} "
            f"graph on devices {d['devices']}")
        log(engine.plan(query).explain())
        t0 = time.perf_counter()
        env = engine.run(query)
        jax.block_until_ready(env.values)
        timing(f"TopK mesh={mesh}, compile included",
               time.perf_counter() - t0)
        pi = env.result.result.pi
        log(f"  rounds: {env.iterations}; result on devices "
            f"{sorted(dev.id for dev in pi.devices())}")
        check(env.converged, f"mesh={mesh}: TopK did not converge")
        results[mesh] = (np.asarray(pi), np.asarray(env.result.indices),
                         np.asarray(env.result.scores), env.iterations)
    pi1, idx1, sc1, it1 = results[None]
    pi41, idx41, sc41, it41 = results[(4, 1)]
    same = (np.array_equal(pi41, pi1) and np.array_equal(idx41, idx1)
            and np.array_equal(sc41, sc1) and it41 == it1)
    log(f"  (4, 1) vs one device: bit-identical={same}")
    pi22, idx22, _, _ = results[(2, 2)]
    err = float(np.max(np.abs(pi22 - pi1)))
    log(f"  (2, 2) vs one device: max |diff| = {err:.3e} (limit {MESH_TOL:g}); "
        f"top-{K} indices equal: {bool(np.array_equal(idx22, idx1))}")
    check(same, "(4, 1) grid is not bit-identical to one device")
    check(err <= MESH_TOL, f"(2, 2) grid differs from one device by {err:.3e}")
    log("  mesh check: PASS")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh phase on a four-chip host")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"no TPU: JAX found platform {platform!r}; this smoke run "
              f"does not fall back to it", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPU devices, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"the repro package is not next to chip_smoke.py: {e}",
              file=sys.stderr)
        return 2
    jax.config.update("jax_enable_x64", True)
    log(f"compile cache: {use_compile_cache()}")
    kind = devices[0].device_kind
    log(f"device: platform={platform} device_kind={kind!r} "
        f"count={len(devices)}")
    try:
        g = make_graph()
        if args.chips == 4:
            run_mesh(g)
        else:
            engine = run_ranking(g)
            run_serving(g, engine)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
