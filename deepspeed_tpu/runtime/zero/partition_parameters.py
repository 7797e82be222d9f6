"""ZeRO-3 construction-time parameter sharding.

The reference's ``zero.Init`` (``partition_parameters.py:516``) hijacks
``nn.Module.__init__`` so every parameter is partitioned the moment it is
created, which is what makes "model bigger than one device" possible at all;
``GatheredParameters`` (``:1382``) temporarily reassembles full parameters
for user code that needs them.

TPU-native formulation: parameter *construction* is a pure function, so the
sharded-construction contract becomes "run the init function under jit with
sharded out_shardings" — each device materializes only its own shard and the
full parameter never exists anywhere.  ``Init`` is a context manager kept
for API parity: inside it, ``DeepSpeedEngine`` (and ``materialize`` below)
builds parameters shard-wise even before the engine's ZeRO policy is known.

``GatheredParameters`` yields a fully-replicated host pytree and, when used
with ``modifier_rank=0`` semantics, re-scatters mutations back to the
sharded arrays on exit — the reference's "touch full weights then
repartition" flow.
"""

import contextlib
from typing import Any, Callable, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deepspeed_tpu.runtime.zero.policy import ZeroShardingPolicy

Pytree = Any

# Module-level Init-context state (the analogue of the reference's
# InsertPostInitMethodToModuleSubClasses global patching, scoped here to a
# flag the engine consults instead of monkey-patched constructors).
_INIT_CTX = {"active": False, "mesh": None, "min_size": 2 ** 12}


def init_ctx_active() -> bool:
    return _INIT_CTX["active"]


@contextlib.contextmanager
def Init(mesh: Optional[Mesh] = None, config_dict_or_path=None, enabled: bool = True,
         min_size: int = 2 ** 12, **_compat_kwargs):
    """``with zero.Init(): engine = initialize(...)`` — parameters of models
    constructed inside are materialized shard-wise even if the config stage
    is < 3 (matching the reference, where ``zero.Init`` itself implies
    partitioned construction).  Extra kwargs accepted for reference
    signature compatibility (remote_device, pin_memory, ...) are ignored —
    placement is the sharding's job here."""
    if not enabled:
        yield
        return
    prev = dict(_INIT_CTX)
    _INIT_CTX.update(active=True, mesh=mesh, min_size=min_size)
    try:
        yield
    finally:
        _INIT_CTX.update(prev)


def materialize(init_fn: Callable[..., Pytree], *args,
                mesh: Optional[Mesh] = None,
                policy: Optional[ZeroShardingPolicy] = None,
                logical_specs: Optional[Pytree] = None,
                dtype=None) -> Pytree:
    """Build ``init_fn(*args)``'s pytree with every leaf materialized
    directly into its ZeRO shard (never unsharded anywhere).

    ``jax.eval_shape`` plans the shardings from shapes alone; the actual
    construction runs under jit with those ``out_shardings``, so device i
    only ever computes/holds shard i — the TPU equivalent of the
    reference's construction-time ``partition()`` calls."""
    if policy is None:
        from deepspeed_tpu.parallel import mesh as mesh_lib
        mesh = mesh or _INIT_CTX["mesh"] or mesh_lib.get_mesh()
        policy = ZeroShardingPolicy(mesh, stage=3, min_size=_INIT_CTX["min_size"])

    shapes = jax.eval_shape(init_fn, *args)
    shardings = policy.param_shardings(shapes, logical_specs)

    def build(*a):
        tree = init_fn(*a)
        if dtype is not None:
            tree = jax.tree.map(lambda x: x.astype(dtype), tree)
        return tree

    return jax.jit(build, out_shardings=shardings)(*args)


def zero_gather_dim(spec: PartitionSpec, axes) -> Optional[int]:
    """Which dim of a leaf the ZeRO policy sharded over ``axes`` — the spec
    entry is the tuple itself for multi-axis policies, the bare name for
    single-axis (see ``zero_partition_spec``).  None → leaf is replicated."""
    axes = tuple(axes)
    entry = axes if len(axes) > 1 else axes[0]
    for d, e in enumerate(spec):
        if e == entry:
            return d
    return None


def infer_zero_axes(shardings: Pytree):
    """Recover the ZeRO axes tuple from materialized param shardings (the
    first leaf entry built from data/fsdp axes).  Lets ``GatheredParameters``
    run quantized gathers without the engine handing its policy over."""
    for s in jax.tree.leaves(shardings):
        spec = getattr(s, "spec", None)
        if spec is None:
            continue
        for e in spec:
            if e is None:
                continue
            entry = (e,) if isinstance(e, str) else tuple(e)
            if set(entry) <= {"data", "fsdp"}:
                return entry
    return ("fsdp",)


def gather_partitioned_params(params: Pytree, shardings: Pytree,
                              axes=None, quantized: bool = False,
                              bits: int = 8, block_size: int = 256,
                              mesh: Optional[Mesh] = None) -> Pytree:
    """Device-side gather of stage-3 shards into replicated full parameters
    — the reference's ``_all_gather_params`` (``partition_parameters.py``),
    here one shard_map program.  ``quantized=True`` is qwZ: shards travel as
    blockwise int codes (see ``comm/compression/qwz.py``)."""
    from deepspeed_tpu.comm.compression import qwz
    from deepspeed_tpu.parallel import mesh as mesh_lib
    from jax import lax

    if mesh is None:
        mesh = next(s.mesh for s in jax.tree.leaves(shardings)
                    if hasattr(s, "mesh"))
    if axes is None:
        axes = infer_zero_axes(shardings)
    axes = tuple(axes)
    specs = jax.tree.map(lambda s: s.spec, shardings,
                         is_leaf=lambda s: isinstance(s, NamedSharding))
    plans = jax.tree.map(lambda spec: zero_gather_dim(spec, axes), specs,
                         is_leaf=lambda s: isinstance(s, PartitionSpec))

    def body(tree):
        def gather_leaf(x, dim):
            if dim is None:
                return x
            if quantized:
                return qwz.quantized_all_gather(x, axes, dim=dim, bits=bits,
                                                block_size=block_size,
                                                out_dtype=x.dtype)
            return lax.all_gather(x, axes if len(axes) > 1 else axes[0],
                                  axis=dim, tiled=True)
        return jax.tree.map(gather_leaf, tree, plans)

    out_specs = jax.tree.map(lambda _: PartitionSpec(), specs,
                             is_leaf=lambda s: isinstance(s, PartitionSpec))
    fn = jax.shard_map(body, mesh=mesh, in_specs=(specs,),
                            out_specs=out_specs, check_vma=False)
    return jax.jit(fn)(params)


@contextlib.contextmanager
def GatheredParameters(params: Pytree, modifier_rank: Optional[int] = None,
                       fwd_module=None, enabled: bool = True,
                       quantized: bool = False):
    """Yield a fully-gathered (host) copy of ``params``.

    Mirrors the reference API (``partition_parameters.py:1382``): read-only
    unless ``modifier_rank`` is set, in which case mutations to the yielded
    pytree's leaves are scattered back into the sharded arrays on exit and
    the result replaces the leaves of the *holder* dict under key
    ``"params"`` (JAX arrays are immutable, so in-place module mutation has
    no analogue; callers re-read ``holder["params"]``)."""
    if not enabled:
        yield {"params": params}
        return
    if quantized:
        # qwZ on the reassembly itself: shards cross the wire as int codes,
        # the host copy is the dequantized full tensor (lossy per block
        # bound — callers opting in accept forward-weight tolerance).
        leaves = jax.tree.leaves(params)
        if (leaves and all(isinstance(p, jax.Array)
                           and isinstance(p.sharding, NamedSharding)
                           for p in leaves)
                and any(p.sharding.spec != PartitionSpec() for p in leaves)):
            shardings = jax.tree.map(lambda p: p.sharding, params)
            params = gather_partitioned_params(params, shardings,
                                               quantized=True)
    gathered = jax.device_get(params)
    holder = {"params": jax.tree.map(np.asarray, gathered)}
    yield holder
    if modifier_rank is not None:
        shardings = jax.tree.map(
            lambda p: p.sharding if isinstance(p, jax.Array) else None, params)
        holder["params"] = jax.tree.map(
            lambda new, s: jax.device_put(new, s) if s is not None else new,
            holder["params"], shardings)


def scatter_to(params_host: Pytree, shardings: Pytree) -> Pytree:
    """Place a host pytree according to per-leaf NamedShardings (each device
    receives only its slice)."""
    return jax.tree.map(jax.device_put, params_host, shardings)


def offload_shardings(shardings: Pytree, device: str,
                      shapes: Optional[Pytree] = None) -> Pytree:
    """Re-home shardings to host memory (``offload_param``/``offload_optimizer``
    device=cpu → ``pinned_host`` memory kind; XLA streams shards back to HBM
    at their use sites — the role of the reference's
    ``AsyncPartitionedParameterSwapper`` staging, minus the NVMe tier which
    lives in ``deepspeed_tpu.runtime.swap_tensor``).

    Scalars/counters stay on device (offloading them buys nothing and some
    backends reject host-placed scalars).  A backend that cannot place on
    ``pinned_host`` (the CPU backend) raises: offload that was asked for and
    silently kept on the device would hide a memory cliff until the real
    size.  Support is probed with the same mechanism the engine uses (jit
    out_shardings), not a bare device_put."""
    if device in (None, "none"):
        return shardings
    import jax.numpy as jnp
    mesh = jax.tree.leaves(shardings)[0].mesh
    sample = NamedSharding(mesh, PartitionSpec(), memory_kind="pinned_host")
    try:
        jax.jit(lambda: jnp.zeros((256,), jnp.float32), out_shardings=sample)()
    except jax.errors.JaxRuntimeError as e:
        raise RuntimeError(
            f"offload to '{device}' requested but the "
            f"{mesh.devices.flat[0].platform} backend cannot place on "
            f"pinned_host") from e

    if shapes is None:
        return jax.tree.map(lambda s: s.with_memory_kind("pinned_host"), shardings)

    def maybe(s, shape_leaf):
        shape = getattr(shape_leaf, "shape", ())
        n = int(np.prod(shape)) if shape else 1
        return s if n <= 1 else s.with_memory_kind("pinned_host")

    return jax.tree.map(maybe, shardings, shapes)
