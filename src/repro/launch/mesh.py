"""Production meshes.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the pod axis
models the pruned inter-pod link (the paper's 4:1 inter-island OmniPath
pruning has the same shape: cheap intra-island, scarce inter-island).

Defined as FUNCTIONS so importing this module never touches jax device
state; only launch/dryrun.py (which sets XLA_FLAGS first) builds the 512-
device host mesh.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with explicit Auto axis types (sharding follows
    the compiler's propagation, as the step functions expect)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


_mesh = make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: Optional[int] = None):
    """Mesh over whatever host devices exist (smoke tests / examples)."""
    n = data or len(jax.devices())
    return _mesh((n,), ("data",))


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture
# table): 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
# inter-chip interconnect, which the roofline's collective term splits
# over the chip's four ICI links (50 GB/s each).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "ici_bytes_per_s_per_link": 50e9},
}


def device_peaks(device_kind: str) -> dict:
    """Published peaks of one chip; a device not in ``PEAKS`` is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to PEAKS with a source")
    return PEAKS[device_kind]
