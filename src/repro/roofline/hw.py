"""Per-device hardware specs — the roofline denominators, keyed by device kind.

``SPECS`` is keyed by ``jax.devices()[0].device_kind``:

  * ``"TPU v5 lite"`` (TPU v5e) — published peaks of one chip (Google
    Cloud documentation, "TPU v5e"): 197 TFLOP/s bf16, 16 GB of HBM at
    819 GB/s, 1,600 Gbit/s of interconnect over four ICI links.
  * ``"cpu"`` — a CPU-lowering model for tests: round order-of-magnitude
    numbers that only rank backends priced from CPU lowerings.  It
    predicts no wall time.

A device kind missing from the table is an error, never a default: a new
chip gets its own row with its own source.  The measured-cost layer
(``roofline/planner_costs.py``) prices every sample through
:func:`spec_for_device_kind`.
"""

from __future__ import annotations

import dataclasses

import jax

__all__ = ["TPUChip", "TPUv5e", "CPULowering", "SPECS",
           "device_kind", "spec_for_device_kind"]


@dataclasses.dataclass(frozen=True)
class TPUChip:
    name: str
    peak_bf16_flops: float  # FLOP/s per chip
    hbm_bandwidth: float  # bytes/s per chip
    ici_link_bandwidth: float  # bytes/s per link
    hbm_bytes: float


TPUv5e = TPUChip(
    name="tpu-v5e",
    peak_bf16_flops=197e12,
    hbm_bandwidth=819e9,
    ici_link_bandwidth=50e9,
    hbm_bytes=16e9,
)

CPULowering = TPUChip(
    name="cpu-lowering-model",
    peak_bf16_flops=1e12,
    hbm_bandwidth=100e9,
    ici_link_bandwidth=25e9,
    hbm_bytes=64e9,
)

SPECS = {"TPU v5 lite": TPUv5e, "cpu": CPULowering}


def device_kind() -> str:
    """``device_kind`` of the first device JAX sees ("cpu" on the host)."""
    return jax.devices()[0].device_kind


def spec_for_device_kind(kind: str) -> TPUChip:
    """Spec for a ``device_kind``; raises ``KeyError`` for an unknown kind."""
    try:
        return SPECS[str(kind)]
    except KeyError:
        raise KeyError(f"no hardware spec for device kind {kind!r}; "
                       f"known: {sorted(SPECS)}") from None
