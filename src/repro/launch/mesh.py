"""Production mesh builders.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state.  The production target is TPU v5e:
one pod = 16x16 = 256 chips as ("data", "model"); two pods = (2, 16, 16) as
("pod", "data", "model").  The "pod" axis carries only data parallelism +
FSDP — gradient all-reduces cross the (slow) inter-pod links once per step,
everything else stays intra-pod.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1, devices=None):
    """(data, model) mesh over `devices` — every local device by default;
    pass `jax.devices()[:1]` to pin a run to one chip."""
    devices = jax.devices() if devices is None else list(devices)
    return jax.make_mesh((len(devices) // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)
