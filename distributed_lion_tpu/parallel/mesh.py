"""Device-mesh construction and sharding helpers.

TPU-native replacement for the reference's implicit ``torchrun`` NCCL process
group (/root/reference/README.md:19, distributed_lion.py:160-164): parallelism
is expressed as a named `jax.sharding.Mesh` and `PartitionSpec`s, and the
collectives ride ICI/DCN wherever the mesh axes land.

Axis conventions used throughout the framework:
- ``data``   — data parallelism (the reference's DDP ranks; the vote axis).
- ``tensor`` — tensor/model parallelism (net-new vs the reference).
- ``seq``    — sequence/context parallelism for ring attention (net-new).
- ``pipe``   — pipeline parallelism over layer stages (net-new).
- ``expert`` — expert parallelism for MoE layers (net-new).
"""

from __future__ import annotations

import os
from typing import Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
TENSOR_AXIS = "tensor"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"
EXPERT_AXIS = "expert"


def make_mesh(
    data: int | None = None,
    tensor: int = 1,
    seq: int = 1,
    pipe: int = 1,
    expert: int = 1,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a (data, tensor, seq, pipe, expert) mesh over the devices.

    ``data=None`` absorbs all remaining devices, mirroring how ``torchrun
    --nproc_per_node N`` sizes the reference's world (README.md:19). On real
    hardware, prefer contiguous ICI neighbors for ``tensor``/``seq`` (the
    high-traffic axes) — `mesh_utils.create_device_mesh` handles that.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    model = tensor * seq * pipe * expert
    if data is None:
        if n % model:
            raise ValueError(
                f"{n} devices not divisible by tensor*seq*pipe*expert={model}"
            )
        data = n // model
    if data * model != n:
        raise ValueError(
            f"mesh {data}x{tensor}x{seq}x{pipe}x{expert} != {n} devices"
        )
    shape = (data, tensor, seq, pipe, expert)
    from jax.experimental import mesh_utils

    # a topology the helper cannot lay out is an error to surface, not a
    # reason to fall back to enumeration order
    dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    return Mesh(dev_array, (DATA_AXIS, TENSOR_AXIS, SEQ_AXIS, PIPE_AXIS, EXPERT_AXIS))


def data_axis_size(mesh: Mesh) -> int:
    return mesh.shape[DATA_AXIS]


def replicated(mesh: Mesh) -> NamedSharding:
    """Sharding for tensors identical on every device (params under pure DP)."""
    return NamedSharding(mesh, P())


def data_sharded(mesh: Mesh, axis: int = 0) -> NamedSharding:
    """Shard a tensor's ``axis`` across the data axis (batches; stacked
    per-worker optimizer state, see optim.distributed_lion)."""
    spec = [None] * (axis + 1)
    spec[axis] = DATA_AXIS
    return NamedSharding(mesh, P(*spec))


def force_cpu_platform() -> bool:
    """Honor ``DLION_PLATFORM=cpu|cpu8`` — the documented, EXPLICIT way to
    run the CLIs and bench scripts on the host CPU (never a fallback: with
    the variable unset nothing here selects a platform). Must run BEFORE
    first device use. ``cpu8`` also requests 8 virtual devices, APPENDING
    to any existing ``XLA_FLAGS`` (a plain setdefault would silently drop
    the device count when other flags are set). Returns whether the
    request was applied."""
    plat = os.environ.get("DLION_PLATFORM")
    if plat not in ("cpu", "cpu8"):
        return False
    if plat == "cpu8":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    jax.config.update("jax_platforms", "cpu")
    return True


def multihost_initialize() -> None:
    """Initialize JAX's distributed runtime when launched multi-host.

    Replaces the reference's ``torchrun`` rendezvous. No-op when the
    coordinator env vars are absent (single-host / test runs).
    """
    if os.environ.get("COORDINATOR_ADDRESS") or os.environ.get("JAX_COORDINATOR_ADDRESS"):
        try:
            jax.distributed.initialize()
        except RuntimeError as e:
            # double-initialize (e.g. a CLI composed into a larger program
            # that already called it) is benign; anything else must be LOUD
            # — swallowing it silently trains N disconnected single-host
            # replicas instead of one job
            # ONLY jax's double-initialize message is benign; matching
            # anything broader (e.g. substring "already") would also match
            # coordination-service failures like "task ... already
            # registered" and silently recreate the disconnected-replica bug
            if "only be called once" in str(e).lower():
                return
            raise RuntimeError(
                "multi-host init failed with coordinator env vars set; "
                "refusing to continue as a silently-disconnected replica "
                "(note: jax.distributed.initialize() must run before "
                "anything initializes the XLA backend)"
            ) from e
