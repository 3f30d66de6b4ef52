"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device).

Target hardware: TPU v5e pods — 256 chips/pod (16x16), 2 pods for the
multi-pod dry-run.  Axis meaning:
  pod   — data-parallel replicas across pods (gradient all-reduce over DCI)
  data  — in-pod data parallel + FSDP weight sharding + SP for long contexts
  model — tensor/expert parallel
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# v5e hardware constants (per chip) used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link
