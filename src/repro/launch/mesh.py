"""Mesh construction — the uncore fabric, as functions (never module
state: importing this must not touch jax device initialization).

Production target (TPU v5e):
  single-pod  (16, 16)    axes (data, model)          = 256 chips
  multi-pod   (2, 16, 16) axes (pod, data, model)     = 512 chips
The ``pod`` axis is the EPAC C2C analogue: slower tier, carries only
data-parallel (all-reduce-friendly) traffic.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: GSPMD propagates shardings and
    the jits need no mesh context, as every caller here expects (the
    default Explicit axes refuse ambiguous contractions and jits
    outside ``with mesh``)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape, axes):
    """Arbitrary mesh over available devices (tests, small runs)."""
    return _auto_mesh(shape, axes)


def make_local_mesh(tp: int = 1):
    """Mesh over whatever devices exist locally: (data = n/tp, model = tp).

    Raises ValueError (not a bare assert) on a tp that is < 1 or does
    not divide the local device count — every ``--tp`` CLI funnels here.
    """
    n = len(jax.devices())
    if tp < 1 or n % tp != 0:
        raise ValueError(
            f"--tp {tp} must be >= 1 and divide the local device count "
            f"({n}); fake devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    return _auto_mesh((n // tp, tp), ("data", "model"))


def dp_axes_of(mesh) -> tuple:
    """All non-model axes, in mesh order (pod first if present)."""
    return tuple(a for a in mesh.axis_names if a != "model")


def replica_cli_mesh(dp: int, tp: int):
    """The mesh a ``--dp R --tp T`` CLI request means: exactly R x T
    devices as a (data=R, model=T) mesh, so each replica owns a (1, T)
    TP subgrid — the topology the README table documents and the bench
    measures. ``--tp T`` alone keeps the PR-3 behavior (shard ONE
    engine over ALL local devices, data = n/T). No parallelism
    requested, or dp replicas on a too-small host (tp == 1), returns
    None: plain single-device engines."""
    n = len(jax.devices())
    if dp > 1:
        if n >= dp * tp:
            return make_mesh((dp, tp), ("data", "model"))
        if tp > 1:
            raise ValueError(
                f"--dp {dp} --tp {tp} needs {dp * tp} devices, have {n}; "
                "fake devices with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N")
        return None                      # host too small: plain replicas
    if tp > 1:
        return make_local_mesh(tp)
    return None


def submeshes(mesh, dp: int, axis: str = "data") -> list:
    """Split ``mesh`` into ``dp`` contiguous submeshes along ``axis``.

    Each submesh keeps ALL axis names (the split axis shrinks to
    size/dp), so the 2-D FSDP x TP sharding rules apply unchanged per
    replica: replica r serves the r-th slice of the data axis with its
    own model-axis TP subgrid — the ReplicaSet analogue of EPAC handing
    each tile its own L2 slice behind the shared hub. Raises ValueError
    when ``dp`` does not divide the axis (every ``--dp`` CLI funnels
    here)."""
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {axis!r} axis: {mesh.axis_names}")
    size = int(mesh.shape[axis])
    if dp < 1 or size % dp != 0:
        raise ValueError(
            f"--dp {dp} must be >= 1 and divide the {axis!r} axis "
            f"({size}); fake devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    ai = list(mesh.axis_names).index(axis)
    per = size // dp
    out = []
    for r in range(dp):
        sl = [slice(None)] * mesh.devices.ndim
        sl[ai] = slice(r * per, (r + 1) * per)
        out.append(Mesh(mesh.devices[tuple(sl)], mesh.axis_names))
    return out


def mesh_summary(mesh) -> dict:
    return {"axes": dict(zip(mesh.axis_names,
                             [int(s) for s in mesh.devices.shape])),
            "n_devices": int(np.prod(mesh.devices.shape))}
