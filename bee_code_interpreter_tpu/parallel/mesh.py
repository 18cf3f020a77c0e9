"""Mesh construction and sharding helpers.

Multi-host awareness: inside a pod group spawned by the scheduler
(services/kubernetes_code_executor.py), ``initialize_distributed()`` reads the
env the control plane baked into each worker (JAX_COORDINATOR_ADDRESS,
JAX_NUM_PROCESSES, JAX_PROCESS_ID) and brings up ``jax.distributed`` so
``jax.devices()`` spans every host of the slice; the mesh axes then map onto
ICI (within slice) / DCN (across slices) by device order, which is exactly the
layout XLA's collectives want.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import sys

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("dp", "fsdp", "tp", "sp", "ep")


def initialize_distributed() -> bool:
    """Bring up jax.distributed from the pod-group env. Idempotent, no-op on
    single-process sandboxes."""
    num_processes = int(os.environ.get("JAX_NUM_PROCESSES", "1"))
    if num_processes <= 1:
        return False
    # Idempotency must NOT be probed via jax.process_count(): that call
    # initializes the XLA backend, after which jax.distributed.initialize
    # refuses to run at all (caught by tests/test_multihost_distributed.py).
    # is_initialized() checks the coordination client without touching XLA.
    if jax.distributed.is_initialized():
        return True
    jax.distributed.initialize(
        coordinator_address=os.environ["JAX_COORDINATOR_ADDRESS"],
        num_processes=num_processes,
        process_id=int(os.environ.get("JAX_PROCESS_ID", "0")),
    )
    return True


def local_device_count() -> int:
    return len(jax.devices())


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """A named assignment of the device grid: axis name -> size."""

    axes: dict[str, int]

    @property
    def n_devices(self) -> int:
        return math.prod(self.axes.values())

    def names(self) -> tuple[str, ...]:
        return tuple(self.axes.keys())


def make_mesh(axes: dict[str, int], devices=None) -> Mesh:
    """Build a Mesh with the given axis sizes over the (global) device list.

    Axis order follows the dict order; put the most communication-hungry axis
    (tp, then sp) last so it lands on adjacent devices — on TPU, adjacency in
    the device list means ICI neighbours, which is where all-gather/ppermute
    bandwidth lives.
    """
    devices = np.asarray(devices if devices is not None else jax.devices())
    plan = MeshPlan(dict(axes))
    if plan.n_devices > devices.size:
        raise ValueError(
            f"mesh plan {axes} needs {plan.n_devices} devices, have {devices.size}"
        )
    grid = devices[: plan.n_devices].reshape(tuple(axes.values()))
    return Mesh(grid, plan.names())


def auto_mesh(n_devices: int | None = None, *, sp: int = 1) -> Mesh:
    """A sensible default mesh: tp over adjacent chips, dp over the rest.

    ``sp`` > 1 carves a sequence-parallel axis for long-context work.
    """
    total = n_devices or local_device_count()
    if total % sp != 0:
        raise ValueError(f"{total} devices not divisible by sp={sp}")
    rest = total // sp
    # tp gets the largest power of two <= min(rest, 8) that divides rest
    tp = 1
    for candidate in (8, 4, 2):
        if rest % candidate == 0:
            tp = candidate
            break
    dp = rest // tp
    return make_mesh({"dp": dp, "sp": sp, "tp": tp})


def sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Inputs: batch over dp, sequence over sp (if present)."""
    seq_axis = "sp" if "sp" in mesh.axis_names else None
    return NamedSharding(mesh, P("dp", seq_axis))


def batch_axes(mesh: Mesh | None) -> tuple[str, ...] | None:
    """The data-parallel-ish axes an activation batch dim shards over —
    the ONE policy for which mesh axes count as batch (models/transformer
    and models/vision both key off this)."""
    if mesh is None:
        return None
    axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)
    return axes or None


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def mesh_shape_key(mesh: Mesh | None) -> str:
    """Stable string key for a mesh's axis sizes (``"dp=2,tp=4"``) — the
    per-shape bucket the step-telemetry aggregates group under. ``"1"``
    for no mesh (single-device serving)."""
    if mesh is None:
        return "1"
    key = ",".join(
        f"{name}={int(size)}"
        for name, size in zip(mesh.axis_names, mesh.devices.shape)
    )
    return key or "1"


def mesh_descriptor(mesh: Mesh | None) -> dict:
    """JSON-able description of a mesh for telemetry (observability's
    ``GET /v1/accelerator``): axis names/sizes, device counts, this
    process's position in the grid (the coordinates of its first local
    device per axis — dp/tp placement for multi-host step records), and
    the device platform. With no mesh, the single-device degradation:
    axes ``{}``, shape ``"1"``."""
    process_index = int(jax.process_index())
    if mesh is None:
        devices = jax.devices()
        return {
            "axes": {},
            "shape": "1",
            "n_devices": 1,
            "n_local_devices": 1,
            "process_index": process_index,
            "coords": {},
            "platform": devices[0].platform if devices else "unknown",
        }
    local = [d for d in mesh.devices.flat if d.process_index == process_index]
    coords: dict[str, int] = {}
    if local:
        idx = np.argwhere(mesh.devices == local[0])
        if idx.size:
            coords = {
                name: int(i) for name, i in zip(mesh.axis_names, idx[0])
            }
    return {
        "axes": {
            name: int(size)
            for name, size in zip(mesh.axis_names, mesh.devices.shape)
        },
        "shape": mesh_shape_key(mesh),
        "n_devices": int(mesh.devices.size),
        "n_local_devices": len(local),
        "process_index": process_index,
        "coords": coords,
        "platform": local[0].platform if local else "unknown",
    }


def device_memory_rows(devices) -> list[dict]:
    """One memory row per device for telemetry (``observability
    .DeviceMonitor`` samples through the attached batcher's
    ``device_memory``, so the control plane never imports jax):
    ``memory_stats()`` where the backend reports it (TPU), else a
    live-buffer byte estimate (CPU — rows marked ``estimated``, peak and
    limit unknown to this function)."""
    def key_of(device) -> str:
        return f"{device.platform}:{device.id}"

    rows: list[dict] = []
    live_estimate: dict[str, int] | None = None
    for device in devices:
        key = key_of(device)
        stats = device.memory_stats()
        if stats:
            live = int(stats.get("bytes_in_use", 0))
            rows.append(
                {
                    "device": key,
                    "platform": device.platform,
                    "live_bytes": live,
                    "peak_bytes": int(stats.get("peak_bytes_in_use", live)),
                    "limit_bytes": (
                        int(stats["bytes_limit"])
                        if "bytes_limit" in stats
                        else None
                    ),
                    "estimated": False,
                }
            )
            continue
        if live_estimate is None:
            live_estimate = {}
            for arr in jax.live_arrays():
                arr_devices = list(arr.devices())
                # a sharded array's nbytes is the GLOBAL size: spread it
                # evenly over its devices for the per-device view
                per_device = int(arr.nbytes) // max(1, len(arr_devices))
                for arr_device in arr_devices:
                    dk = key_of(arr_device)
                    live_estimate[dk] = live_estimate.get(dk, 0) + per_device
        live = live_estimate.get(key, 0)
        rows.append(
            {
                "device": key,
                "platform": device.platform,
                "live_bytes": live,
                "peak_bytes": live,
                "limit_bytes": None,
                "estimated": True,
            }
        )
    return rows


def require_tpu(script: str):
    """How a one-process chip-facing script starts: a measurement path that
    finds no chip fails, it never falls back to the CPU. Exit 2 unless this
    process's jax backend is a TPU; return ``emit(case, payload)``, which
    prints one JSON line per case stamped with the device (platform, kind,
    count) the process runs on. The caller IS the process that holds the
    chip — no out-of-process probe."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"{script}: no TPU (jax backend is {devices[0].platform!r}); "
            "a device measurement does not run on the host",
            file=sys.stderr,
        )
        sys.exit(2)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }

    def emit(case: str, payload: dict) -> None:
        print(
            json.dumps(
                {"case": case, "script": script, "device": device, **payload}
            ),
            flush=True,
        )

    return emit
