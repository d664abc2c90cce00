"""The one mesh builder, and the layouts built with it.

Every mesh in the repository comes from :func:`make_mesh`, which states
each axis as ``AxisType.Auto``: GSPMD propagates shardings and
``with_sharding_constraint`` is a hint. That is the semantics the
sharding rules (``train/sharding.py``), the cache placement
(``serve/engine.py``) and the model's activation hooks are written for.
``jax.make_mesh`` on its own builds ``Explicit`` axes, under which those
hints become assertions and gathers demand output shardings.

Production layouts:
  single pod: (16, 16) = 256 chips, axes ("data", "model");
  multi-pod:  (pods, 16, 16) chips, axes ("pod", "data", "model") — the
  "pod" axis crosses the DCN boundary (the paper's non-local region
  boundary); "data"/"model" stay on ICI. ``pods`` need NOT be a power of
  two (DESIGN.md §7).

Functions, not module-level constants: importing this module never touches
jax device state (jax fixes the device count at first backend init).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], *,
              devices=None) -> Mesh:
    """A mesh with every axis ``Auto``.

    ``devices=None`` takes all devices in JAX's topology-aware order;
    otherwise ``devices`` (any array-like of devices, e.g. one pod's slice
    of a larger mesh) is laid out row-major, in the order given."""
    shape, axes = tuple(shape), tuple(axes)
    auto = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=auto)
    arr = np.asarray(devices, dtype=object).reshape(shape)
    return Mesh(arr, axes, axis_types=auto)


def make_dp_mesh(pods: int, data: int, *, devices=None) -> Mesh:
    """A pure-DP ('pod','data') mesh (no model axis); a single pod
    degenerates to the ('data',) mesh."""
    if pods > 1:
        return make_mesh((pods, data), ("pod", "data"), devices=devices)
    return make_mesh((data,), ("data",), devices=devices)


def mesh_from_devices(devices=None) -> Mesh:
    """The DP mesh over the devices present: ('data',) on one or an odd
    number of devices, ('pod','data') = (2, n/2) otherwise (a v5e:2x2 host
    is (2, 2))."""
    devs = list(jax.devices() if devices is None else devices)
    n = len(devs)
    if n > 1 and n % 2 == 0:
        return make_dp_mesh(2, n // 2, devices=devs)
    return make_dp_mesh(1, n, devices=devs)


def parse_mesh(spec: str, devices=None) -> Mesh:
    """``"2x2x2"`` -> a ('pod','data','model') mesh; fewer dims take the
    trailing axis names (``"4x2"`` -> ('data','model'))."""
    shape = tuple(int(x) for x in spec.split("x"))
    axes = ("pod", "data", "model")[-len(shape):]
    devs = None if devices is None else list(devices)[:int(np.prod(shape))]
    return make_mesh(shape, axes, devices=devs)


def make_production_mesh(*, multi_pod: bool = False, pods: int = 2) -> Mesh:
    shape = (pods, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
