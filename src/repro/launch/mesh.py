"""Production mesh construction.

The paper maps a 3D ``X x Y x Z`` mesh onto a 2D fabric of processing
elements (CS-1: 602 x 595 tiles).  Here the fabric is a TPU pod: a 16 x 16
chip mesh per pod, with a third ``pod`` axis for multi-pod runs.  Axis
meaning is role-dependent:

* stencil solver: ``("data", "model")`` are the fabric (X, Y) axes of the
  paper's Fig. 3; ``pod`` slabs the Z dimension.
* LM stack: ``data`` (x ``pod``) is data-parallel, ``model`` is
  tensor/expert-parallel; decode shapes re-purpose ``model`` for KV-cache
  sequence sharding.

Everything is a function (never module-level state) so importing this module
never touches jax device state.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axis_names, *, devices=None):
    """``jax.make_mesh`` with explicit Auto axis types (the solver's
    shard_maps name every axis; nothing relies on explicit sharding)."""
    return jax.make_mesh(
        shape, axis_names, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """The target mesh: one pod = 16 x 16 = 256 chips; two pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for_devices(n_devices: int | None = None, *, pods: int = 1):
    """Largest near-square 2D (or 3D with pods) mesh over the first
    ``n_devices`` devices (default: all of them).

    On one device this degenerates to a 1x1 mesh and all collectives become
    no-ops (boundary semantics are preserved because ppermute fills
    non-received shards with zeros).
    """
    devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    devices = devices[:n_devices]
    per_pod = n_devices // pods
    x = 1
    for cand in range(int(per_pod ** 0.5), 0, -1):
        if per_pod % cand == 0:
            x = cand
            break
    y = per_pod // x
    if pods > 1:
        return make_mesh((pods, x, y), ("pod", "data", "model"),
                         devices=devices)
    return make_mesh((x, y), ("data", "model"), devices=devices)


def fabric_shape(mesh) -> tuple[int, int, int]:
    """(pods, fabric_x, fabric_y) of a production-style mesh."""
    ax = dict(zip(mesh.axis_names, mesh.devices.shape))
    return ax.get("pod", 1), ax["data"], ax["model"]
