"""Production mesh builders.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any jax
initialisation).

    single-pod : (data=16, model=16)           = 256 chips (one v5e pod)
    multi-pod  : (pod=2, data=16, model=16)    = 512 chips

"pod" folds into data parallelism (distributed/sharding.dp_axes); "model"
carries TP/EP/SP and stays inside a pod (ICI); only the gradient
all-reduce crosses pods (DCN), which is also where the int8 gradient
compression (distributed/compression.py) applies.
"""
from __future__ import annotations

import dataclasses

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """The paper-scale (data, model) v5e mesh; ``multi_pod`` prepends a
    2-way "pod" axis (folded into DP by ``distributed.sharding``)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(n: int | None = None, *, devices=None):
    """Whatever this host has — smoke tests, examples and the sharded
    serving tests.  ``n`` takes the first n local devices (data axis) and
    raises when the host has fewer; ``devices`` builds the mesh from an
    explicit device list instead (the engine's fault path re-meshes onto
    the survivors of a host failure).  Either way the mesh is
    (data=n, model=1)."""
    import numpy as np
    from jax.sharding import Mesh
    if devices is not None:
        devs = list(devices)
    else:
        devs = jax.devices() if n is None else jax.devices()[:n]
        if n is not None and len(devs) < n:
            raise ValueError(f"make_host_mesh({n}): this host has only "
                             f"{len(devs)} {jax.default_backend()} device(s)")
    return Mesh(np.asarray(devs).reshape(len(devs), 1), ("data", "model"))


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator kind (rates per second,
    bytes for capacity)."""
    flops_bf16: float
    flops_int8: float
    hbm_bw: float               # bytes/s
    hbm_bytes: int
    ici_bw: float               # bytes/s of chip-to-chip interconnect


# Keyed by ``jax.Device.device_kind``.  TPU v5e: Google Cloud TPU
# documentation, "TPU v5e" system architecture table — 197 TFLOP/s bf16,
# 394 TOP/s int8, 16 GiB HBM2 at 819 GB/s, 1,600 Gbit/s (200 GB/s)
# interchip interconnect per chip.
V5E = "TPU v5 lite"
CHIP_PEAKS = {
    V5E: ChipPeaks(flops_bf16=197e12, flops_int8=394e12, hbm_bw=819e9,
                   hbm_bytes=16 * 1024 ** 3, ici_bw=1600e9 / 8),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; an unknown kind is an error, never a
    default, so no roofline is ever computed against the wrong chip."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}"
                         ) from None
