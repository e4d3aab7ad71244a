"""Device-mesh construction + sharding helpers.

The reference scales via Spark executors + shuffle (external dependency;
SURVEY.md §2.10). The TPU-native equivalent: a `jax.sharding.Mesh` over all
devices with named axes, NamedSharding annotations on arrays, and XLA
emitting collectives over ICI from pjit/shard_map. Every algorithm in
models/ trains against a mesh obtained here.

Axis conventions:
- ``DATA_AXIS`` ('d'): batch/entity-row sharding — users in the ALS user
  solve, examples in NB/LR sufficient-stat reductions (psum over 'd').
- ``MODEL_AXIS`` ('m'): reserved for factor/feature sharding when a factor
  matrix exceeds one chip's HBM (ALX-style; 2-D meshes are constructed on
  demand via mesh_from_devices(shape=(dp, mp))).
"""

from __future__ import annotations

import contextlib
import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "d"
MODEL_AXIS = "m"


def local_device_count() -> int:
    return jax.local_device_count()


def device_report(devices=None) -> dict:
    """The devices as JAX reports them — what `pio train`'s completion
    line and the engine server's ``GET /`` print, so a run can prove
    which device did the work. ``devices`` defaults to ``jax.devices()``;
    pass a mesh's ``devices.flat`` to describe that mesh."""
    devices = list(devices if devices is not None else jax.devices())
    return {"platform": devices[0].platform,
            "deviceKind": devices[0].device_kind,
            "deviceCount": len(devices)}


def mesh_from_devices(
    shape: Optional[Sequence[int]] = None,
    axis_names: Sequence[str] = (DATA_AXIS,),
    devices=None,
) -> Mesh:
    """Build a mesh over the given devices (default: all).

    shape=None → 1-D mesh over every device on axis 'd'.
    shape=(dp, mp) with axis_names=('d','m') → 2-D factor-sharded layouts.
    """
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (len(devices),)
    arr = np.array(devices).reshape(tuple(shape))
    return Mesh(arr, tuple(axis_names[: arr.ndim]))


def device_memory_bytes() -> int:
    """Memory of the default device, for the budgets derived from it
    (full-matrix CCO, the sharded-serving threshold). A TPU reports its
    ``bytes_limit``; one that does not is an error, not a guess. The CPU
    backend reports no memory stats and is budgeted as 4 GiB."""
    # a LOCAL device: in a multi-process run jax.devices()[0] belongs to
    # process 0 and only addressable devices answer memory_stats()
    dev = jax.local_devices()[0]
    limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
    if limit > 0:
        return limit
    if dev.platform == "tpu":
        raise RuntimeError(
            f"{dev.device_kind} reports no memory_stats()['bytes_limit']; "
            "refusing to size device budgets from a guess")
    return 4 * 1024 ** 3


_default_mesh: Optional[Mesh] = None


def _mesh_shape_from_env() -> Optional[tuple[int, ...]]:
    """PIO_MESH_SHAPE: "8" → 1-D data mesh over 8 devices; "4x2" →
    2-D (d, m)=(4, 2) ALX mesh. Set directly or via the CLI passthrough
    tier (`pio train -- --mesh=4x2`, SURVEY.md §5.6c)."""
    from ..common import envknobs

    spec = envknobs.env_str("PIO_MESH_SHAPE", "")
    if not spec:
        return None
    try:
        dims = tuple(int(s) for s in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"PIO_MESH_SHAPE={spec!r}: expected D or DxM")
    if len(dims) > 2 or any(d < 1 for d in dims):
        raise ValueError(f"PIO_MESH_SHAPE={spec!r}: expected D or DxM")
    return dims


def default_mesh(refresh: bool = False) -> Mesh:
    """Process-wide default mesh (cached): 1-D over all devices unless
    PIO_MESH_SHAPE overrides the shape."""
    global _default_mesh
    if _default_mesh is None or refresh:
        shape = _mesh_shape_from_env()
        if shape is None:
            _default_mesh = mesh_from_devices()
        else:
            n = int(np.prod(shape))
            devices = jax.devices()
            if n > len(devices):
                raise ValueError(
                    f"PIO_MESH_SHAPE/--mesh requests {shape} = {n} devices "
                    f"but only {len(devices)} are available")
            chosen = devices[:n]
            if jax.process_count() > 1:
                # every process must own a shard or its collectives hang
                # with an opaque sharding error
                procs = {d.process_index for d in chosen}
                if len(procs) != jax.process_count():
                    raise ValueError(
                        f"PIO_MESH_SHAPE/--mesh shape {shape} uses only "
                        f"devices of processes {sorted(procs)} but "
                        f"{jax.process_count()} processes are running — "
                        "the mesh must span every process")
            axes = (DATA_AXIS, MODEL_AXIS)[: len(shape)]
            _default_mesh = mesh_from_devices(
                shape=shape, axis_names=axes, devices=chosen)
    return _default_mesh


def shard_rows(mesh: Mesh, ndim: int = 1, axis: str = DATA_AXIS) -> NamedSharding:
    """Sharding that splits dim 0 over the data axis, replicating the rest."""
    spec = P(axis, *([None] * (ndim - 1)))
    return NamedSharding(mesh, spec)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def device_put_sharded_rows(x, mesh: Mesh, axis: str = DATA_AXIS):
    """Host numpy → row-sharded device array. Row count must divide the
    axis size (callers pad with pad_rows first)."""
    x = np.asarray(x)
    return fast_put(x, shard_rows(mesh, x.ndim, axis))


def fast_put(arr, sharding):
    """``jax.device_put`` with the single-device fast path: a
    NamedSharding over a ONE-device mesh is equivalent
    (`is_equivalent_to`) to plain placement on that device, so the put
    skips the sharded-copy machinery and jit still reuses the buffer
    without a resharding copy. Whether the plain put is faster on this
    machine is unmeasured (ROADMAP D7)."""
    devices = getattr(sharding, "device_set", None)
    if devices is not None and len(devices) == 1:
        return jax.device_put(arr, next(iter(devices)))
    return jax.device_put(arr, sharding)


def pad_rows(x: np.ndarray, multiple: int, fill=0) -> np.ndarray:
    """Pad dim 0 up to a multiple (static shapes for XLA; masked later)."""
    n = x.shape[0]
    target = ((n + multiple - 1) // multiple) * multiple
    if target == n:
        return x
    pad_width = [(0, target - n)] + [(0, 0)] * (x.ndim - 1)
    return np.pad(x, pad_width, constant_values=fill)


@contextlib.contextmanager
def with_mesh(mesh: Mesh):
    with mesh:
        yield mesh
