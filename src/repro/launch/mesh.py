"""Production mesh builders. Defined as FUNCTIONS so importing this module
never touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init).

Production target: TPU v5e, 256 chips/pod (16x16), optionally 2 pods.
  axes: data (batch / federated cohorts / FSDP), model (tensor/expert), pod.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """A mesh whose axes are all ``Auto`` (sharding propagated by XLA), the
    semantics this runtime's specs and shard_maps are written for."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_smoke_mesh(*, multi_pod: bool = False):
    """Tiny mesh for CI on a handful of host devices (2x2 or 2x2x2...)."""
    n = len(jax.devices())
    if multi_pod and n >= 8:
        return _mesh((2, 2, 2), ("pod", "data", "model"))
    if n >= 4:
        return _mesh((2, 2), ("data", "model"))
    return _mesh((1, 1), ("data", "model"))


# TPU v5e hardware constants (per chip) — used by the roofline report and
# obs.profile.peak_table, under the kind a v5e reports as device_kind.
V5E_DEVICE_KIND = "TPU v5 lite"
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link (~per-chip effective)
HBM_BYTES = 16 * 1024 ** 3        # 16 GiB
VMEM_BYTES = 128 * 1024 ** 2
