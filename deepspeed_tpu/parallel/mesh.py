"""Device mesh construction — the TPU-native core of all parallelism.

Replaces the reference's process-group machinery (``deepspeed/utils/groups.py``,
``deepspeed/runtime/pipe/topology.py:ProcessTopology``): one
``jax.sharding.Mesh`` with named axes subsumes every "group".  A process group
over ranks sharing all-but-one axis coordinate is simply that axis name; a
collective over the group is a ``psum``/``all_gather`` over the axis.

Axis conventions (outermost → innermost, i.e. slowest → fastest varying on
the ICI torus):

    pipe   — pipeline stages (crosses DCN on multi-slice; lowest volume)
    data   — pure data parallelism (gradient allreduce only)
    fsdp   — ZeRO parameter/optimizer sharding (allgather + reduce-scatter)
    expert — MoE expert parallelism (all-to-all)
    seq    — sequence/context parallelism (all-to-all / ppermute ring)
    tensor — tensor (Megatron-style) parallelism (allreduce every layer;
             highest volume → innermost, rides nearest-neighbor ICI)
"""

import functools
from typing import Dict, Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Canonical axis order in every Mesh this framework builds.
MESH_AXES = ("pipe", "data", "fsdp", "expert", "seq", "tensor")

# Axes a batch is sharded over (every one of these sees distinct samples).
# Expert-parallel ranks are data-parallel ranks for non-expert tensors,
# matching the reference's E+D group arithmetic (``utils/groups.py:108``).
BATCH_AXES = ("data", "fsdp", "expert")


class MeshSpec:
    """Resolved axis sizes for a device mesh.

    ``data=-1`` means "all remaining devices".  Validates that the product
    covers the device count (reference analogue: the implicit
    world = pp*dp*mp factoring in ``PipeModelDataParallelTopology``,
    ``pipe/topology.py:244``).
    """

    def __init__(self, *, pipe: int = 1, data: int = -1, fsdp: int = 1, expert: int = 1,
                 seq: int = 1, tensor: int = 1, device_count: Optional[int] = None):
        if device_count is None:
            device_count = jax.device_count()
        sizes = dict(pipe=pipe, data=data, fsdp=fsdp, expert=expert, seq=seq, tensor=tensor)
        known = 1
        for name, s in sizes.items():
            if s != -1:
                assert s >= 1, f"mesh axis {name} must be >=1 or -1, got {s}"
                known *= s
        if data == -1:
            assert device_count % known == 0, (
                f"device count {device_count} not divisible by fixed axes product {known}")
            sizes["data"] = device_count // known
            known *= sizes["data"]
        assert known == device_count, (
            f"mesh axes product {known} != device count {device_count}: {sizes}")
        self.sizes: Dict[str, int] = sizes
        self.device_count = device_count

    @classmethod
    def from_config(cls, ds_config, device_count: Optional[int] = None) -> "MeshSpec":
        m = ds_config.mesh_config
        tp = max(ds_config.tensor_parallel_config.tp_size, m.tensor, 1)
        pp = max(ds_config.pipeline_config.stages, m.pipe, 1)
        sp = max(ds_config.sequence_parallel_config.sp_size, m.seq, 1)
        fsdp = m.fsdp
        # ZeRO >= 1 shards over the fsdp axis; if the user didn't size it,
        # fold ALL data parallelism into fsdp (the reference partitions over
        # every DP rank: ``stage_1_and_2.py:90``).
        if ds_config.zero_config.stage >= 1 and fsdp == 1:
            if device_count is None:
                device_count = jax.device_count()
            model = tp * pp * sp * max(m.expert, 1)
            assert device_count % model == 0
            fsdp = device_count // model
            data = 1
            # hpZ (ZeRO++): shrink the fsdp axis to the secondary-partition
            # size and put the rest on data, so the (data, fsdp) split IS the
            # (slow, fast) topology the compressed collectives key off.
            hpz = getattr(ds_config.zero_config, "zero_hpz_partition_size", 1)
            if ds_config.zero_config.stage >= 3 and hpz > 1:
                assert fsdp % hpz == 0, (
                    f"zero_hpz_partition_size {hpz} must divide the ZeRO "
                    f"world size {fsdp}")
                if fsdp // hpz > 1:
                    data = fsdp // hpz
                    fsdp = hpz
        else:
            data = m.data
        return cls(pipe=pp, data=data, fsdp=fsdp, expert=max(m.expert, 1), seq=sp,
                   tensor=tp, device_count=device_count)

    def shape(self) -> Sequence[int]:
        return tuple(self.sizes[a] for a in MESH_AXES)

    def build(self, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
        if devices is None:
            devices = jax.devices()
        shape = self.shape()
        n = int(np.prod(shape))
        assert n == len(devices), f"{shape} needs {n} devices, have {len(devices)}"
        if len(devices) > 1 and devices[0].platform == "tpu":
            # topology-aware placement (innermost axes on nearest-neighbour
            # ICI); a shape it cannot place is the caller's error to see
            from jax.experimental import mesh_utils
            dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
        else:
            dev_array = np.asarray(devices).reshape(shape)
        return Mesh(dev_array, MESH_AXES)


# --------------------------------------------------------------------------- #
# Global mesh registry — the analogue of the reference's module-level groups
# (``utils/groups.py`` keeps _WORLD_GROUP/_EXPERT_PARALLEL_GROUP/... globals).
# --------------------------------------------------------------------------- #
_MESH: Optional[Mesh] = None
_MESH_SPEC: Optional[MeshSpec] = None


def set_mesh(mesh: Mesh, spec: Optional[MeshSpec] = None):
    global _MESH, _MESH_SPEC
    _MESH = mesh
    _MESH_SPEC = spec


def get_mesh() -> Mesh:
    assert _MESH is not None, "mesh not initialized; call deepspeed_tpu.initialize() first"
    return _MESH


def has_mesh() -> bool:
    return _MESH is not None


def reset_mesh():
    global _MESH, _MESH_SPEC
    _MESH = None
    _MESH_SPEC = None


def axis_size(axis: str) -> int:
    mesh = get_mesh()
    return int(mesh.shape[axis])


def get_data_parallel_world_size() -> int:
    """DP world size incl. fsdp and expert axes (ZeRO ranks are DP ranks and
    EP ranks are a subset of DP ranks, reference ``utils/groups.py:108,331``)."""
    mesh = get_mesh()
    return int(mesh.shape["data"] * mesh.shape["fsdp"] * mesh.shape["expert"])


def get_model_parallel_world_size() -> int:
    mesh = get_mesh()
    return int(mesh.shape["tensor"])


def get_pipe_parallel_world_size() -> int:
    return axis_size("pipe")


def get_expert_parallel_world_size() -> int:
    return axis_size("expert")


def get_sequence_parallel_world_size() -> int:
    return axis_size("seq")


import contextlib
import threading

_manual = threading.local()


@contextlib.contextmanager
def manual_sharding():
    """Mark code being traced inside a ``shard_map`` body: sharding
    constraints are per-device no-ops there (and would be rejected by jax).
    Trace-time only — wrap the body function's execution."""
    prev = getattr(_manual, "on", False)
    _manual.on = True
    try:
        yield
    finally:
        _manual.on = prev


def in_manual_mode() -> bool:
    return getattr(_manual, "on", False)


def constrain(x, *spec):
    """Activation sharding constraint on the global mesh; no-op when no
    mesh is set (single place for the has_mesh/with_sharding_constraint
    idiom used by models, MoE and sequence parallelism)."""
    if not has_mesh() or in_manual_mode():
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(get_mesh(), PartitionSpec(*spec)))


def batch_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    """Sharding for a [batch, ...] array: batch split over data+fsdp."""
    mesh = mesh or get_mesh()
    return NamedSharding(mesh, PartitionSpec(BATCH_AXES))


def replicated(mesh: Optional[Mesh] = None) -> NamedSharding:
    mesh = mesh or get_mesh()
    return NamedSharding(mesh, PartitionSpec())


@functools.lru_cache(None)
def cpu_mesh(n: int = 8) -> Mesh:
    """A host-platform mesh for tests (reference tests fork N procs over
    loopback NCCL, ``tests/unit/common.py:88``; on TPU we use XLA's virtual
    CPU devices instead)."""
    devices = jax.devices("cpu")[:n]
    return Mesh(np.asarray(devices).reshape(1, len(devices), 1, 1, 1, 1), MESH_AXES)
