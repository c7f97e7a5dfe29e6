"""Peaks of the chips the benchmark runs on, and the least bytes of an ITA round.

``PEAKS`` is keyed by ``jax.devices()[0].device_kind``.  A kind missing
from the table is an error, never a default.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" — 197
TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
"""
from __future__ import annotations

__all__ = ["PEAKS", "peaks_for", "round_bytes"]

PEAKS = {
    "TPU v5 lite": dict(hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12,
                        hbm_bytes=16e9),
}

INDEX_BYTES = 4  # int32 vertex ids and offsets


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[str(device_kind)]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def round_bytes(n: int, m: int, rows: int, value_bytes: int) -> int:
    """Least bytes one synchronous ITA round must move through HBM.

    Worked out from the sizes alone, so it is the same work whatever
    implements the push:

      * the edge structure once per round, shared by every row: one source
        index per edge and one offset per vertex (``4 (m + n)``);
      * each vertex's state, read and written once per row: the residual
        ``h`` and the accumulated ``pi_bar`` (``4 v n`` per row);
      * the per-vertex ``1 / out_degree`` once per round (``v n``).

    The pushed value of a source is derived from its ``h``, which is
    already counted, so a push that kept its sources on chip could reach
    this count; one that gathers a value per edge moves more.
    """
    v = int(value_bytes)
    return (INDEX_BYTES * (int(m) + int(n)) + 4 * v * int(n) * int(rows)
            + v * int(n))
