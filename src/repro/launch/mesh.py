"""Production mesh definitions (TPU v5e).

Single pod: (data=16, model=16) = 256 chips.
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis doubles
as the federated-site axis in tight-mode FL (DESIGN.md §5).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS first).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants (per chip) used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many devices exist (tests on CPU)."""
    return _mesh((data, model), ("data", "model"))


def make_agg_mesh(shards: int):
    """1-D ("data",) mesh for the sharded server aggregation state.

    The FL server's flat fp64 accumulator and FedOpt moments split into
    ``shards`` contiguous ranges over this axis (see
    :func:`repro.sharding.shard_bounds`); each range's fused
    decode+scale+accumulate kernel is pinned to the matching device.  On
    CPU CI the devices are simulated with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    return _mesh((shards,), ("data",))


def _mesh(shape, axes):
    # Auto axes: the shardings in launch/shardings.py are GSPMD hints, and
    # jax.make_mesh's default Explicit axes reject the fsdp embedding
    # gather at trace time
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
