"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis is
the ICI-sparse/DCN dimension — only data-parallel collectives cross it
(hierarchical gradient reduction), never TP/EP traffic.
"""

from __future__ import annotations

import jax


def _mk(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """A (data, model) mesh over the first ``data * model`` devices;
    raises when fewer exist (a silently smaller mesh would run a
    different program than the one asked for)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"mesh ({data} x {model}) needs {data * model} "
                         f"devices, but {n} exist")
    return _mk((data, model), ("data", "model"))
