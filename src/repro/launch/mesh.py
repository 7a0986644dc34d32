"""Mesh builder over the host's devices.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_host_mesh(model: int = 1, devices=None):
    """(data, model) mesh over `devices` — every local device by default;
    pass `jax.devices()[:1]` to pin a run to one chip."""
    devices = jax.devices() if devices is None else list(devices)
    return jax.make_mesh((len(devices) // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)
