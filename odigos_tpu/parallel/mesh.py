"""Device mesh construction.

The reference scales by shared-nothing replica fan-out (DaemonSet node
collectors + HPA'd gateway replicas, SURVEY.md §2.7); our TPU scoring stage
scales inside the accelerator domain instead: a `jax.sharding.Mesh` over the
slice, with XLA collectives riding ICI (BASELINE config #5: data-parallel
across v5e-8). Axes:

    data  — batch (trace) dimension; pure DP scoring/training
    model — tensor parallelism (attention heads / ffn shards)
    seq   — sequence parallelism (ring attention for very long traces)

Multi-host meshes come from jax.distributed + the same axis names over DCN
(data axis outermost so cross-host traffic is gradient/allreduce only).
"""

from __future__ import annotations

import math
import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

DEFAULT_AXES = ("data", "model")


def mesh_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def mesh_key(shape) -> str:
    """Stable label for a mesh shape ("data4xmodel2"): gauge/ladder/stats
    dimensions that are "per mesh" key on this. Accepts a Mesh, a dict,
    or an ((axis, size), ...) tuple; size-1 axes are elided so a pure-DP
    mesh and the same mesh with a vestigial tp axis label identically."""
    if isinstance(shape, Mesh):
        shape = dict(shape.shape)
    items = dict(shape).items() if not isinstance(shape, tuple) \
        else shape
    parts = [f"{a}{int(n)}" for a, n in items if int(n) > 1]
    return "x".join(parts) if parts else "single"


def ensure_host_devices(n_devices: int) -> int:
    """Force an n-device virtual CPU platform, for an EXPLICIT CPU dry
    run of the dp×tp serving path (``__graft_entry__.dryrun_multichip``).
    It takes the process off any accelerator, so nothing may call it because a probe failed or a
    device was not found: a run that was meant for the chip fails
    instead. Must run before the jax backend initializes — XLA_FLAGS is
    only read once; afterwards this degrades to reporting the device
    count that actually exists. Returns the live device count so
    callers can size their mesh to reality."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    jax.config.update("jax_platforms", "cpu")
    return len(jax.devices())


def make_mesh(shape: Optional[dict[str, int]] = None,
              *,
              n_devices: Optional[int] = None,
              axes: Sequence[str] = DEFAULT_AXES,
              devices=None) -> Mesh:
    """Build a mesh.

    make_mesh()                          -> all devices on the data axis
    make_mesh({"data": 4, "model": 2})   -> explicit 4x2
    make_mesh(n_devices=8)               -> 8 devices, all data-parallel
    """
    devices = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    if shape is None:
        shape = {axes[0]: n}
        for a in axes[1:]:
            shape[a] = 1
    total = math.prod(shape.values())
    if total > n:
        raise ValueError(
            f"mesh shape {shape} needs {total} devices, have {n}")
    arr = np.asarray(devices[:total]).reshape(tuple(shape.values()))
    return Mesh(arr, tuple(shape.keys()))
