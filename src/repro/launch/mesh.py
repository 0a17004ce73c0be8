"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import to obtain placeholder devices; smoke tests and benches see 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the model code places arrays with
    ``with_sharding_constraint`` and leaves the rest to the partitioner."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for(n_devices: int, *, model_axis: int = None):
    """Elastic helper: best (data, model) mesh for an arbitrary device count."""
    if model_axis is None:
        model_axis = 1
        for cand in (16, 8, 4, 2):
            if n_devices % cand == 0:
                model_axis = cand
                break
    assert n_devices % model_axis == 0, (n_devices, model_axis)
    return make_mesh((n_devices // model_axis, model_axis), ("data", "model"))


# TPU v5e roofline constants (per chip)
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link
