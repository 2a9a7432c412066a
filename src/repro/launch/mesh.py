"""Production mesh definitions and per-chip peak rates.

Meshes are built by FUNCTIONS, not module-level constants — importing this
module never touches jax device state (the dry-run sets XLA_FLAGS before
any jax import; smoke tests see 1 device).

Target hardware: TPU v5e pods — 256 chips/pod in a 16x16 ICI torus.
  single-pod: (16, 16)      axes ("data", "model")
  multi-pod:  (2, 16, 16)   axes ("pod", "data", "model")  = 512 chips

Per-chip peaks live in one table keyed by ``Device.device_kind`` (what
``jax.devices()[0].device_kind`` reports); the roofline (benchmarks/
roofline.py) reads it. A device kind that is not in the table raises: no
default peak is assumed for a chip nobody looked up.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax

from repro.compat import make_mesh

# Published per-chip peaks. Source: Google Cloud documentation, "TPU v5e"
# (system architecture): 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s chip-to-chip interconnect over the 4 links of a 2D torus.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": dict(
        flops_bf16=197e12,          # FLOP/s
        hbm_bytes=16e9,             # B
        hbm_bw=819e9,               # B/s
        ici_bw=1600e9 / 8 / 4,      # B/s per link
    ),
}

# the chip the production meshes below target
TARGET_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def make_production_mesh(*, multi_pod: bool = False,
                         dp: int = 16, tp: int = 16) -> jax.sharding.Mesh:
    """Default: (16,16) single pod / (2,16,16) multi-pod. dp/tp reshape the
    in-pod grid for mesh-geometry ablations (e.g. 32x8 — §Perf)."""
    shape = (2, dp, tp) if multi_pod else (dp, tp)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1, devices: Optional[Sequence] = None
                   ) -> jax.sharding.Mesh:
    """A ("data", "model") mesh over ``devices`` — by default every device
    this host has (examples/tests); pass ``jax.devices()[:1]`` to hold one
    chip of a multi-chip host."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"), devices=devices)
