"""Latency summary of the serving tier.

:func:`latency_summary` reduces per-request latencies (arrival to
answer, queue wait included: ``Served.latency_s``) to the percentiles
``ServiceReport.summary`` and the ``ppr_serve`` CLI report.
"""

from __future__ import annotations

import numpy as np

__all__ = ["latency_summary"]


def latency_summary(per_query_ms) -> dict:
    """p50/p90/p99/mean/max over per-query latencies (ms)."""
    lat = np.asarray(per_query_ms, dtype=np.float64)
    if lat.size == 0:
        return dict(count=0, p50_ms=0.0, p90_ms=0.0, p99_ms=0.0, mean_ms=0.0, max_ms=0.0)
    return dict(
        count=int(lat.size),
        p50_ms=float(np.percentile(lat, 50)),
        p90_ms=float(np.percentile(lat, 90)),
        p99_ms=float(np.percentile(lat, 99)),
        mean_ms=float(np.mean(lat)),
        max_ms=float(np.max(lat)),
    )
