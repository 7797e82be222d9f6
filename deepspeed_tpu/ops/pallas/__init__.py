"""Pallas TPU kernels, and the one place that asks which platform runs them.

THE selection rule: a kernel runs when the platform is ``tpu``
(:func:`use_kernel`), its own shape gate admits the call, and its own
sharding rule holds (for most, :func:`single_device`); otherwise the
reference path beside it runs.  Nothing else selects: no environment
variable, config key or argument.  A caller that needs the other side of
the rule (a parity test on the CPU, ``chip_smoke.py``'s reference step on
the chip) replaces :func:`use_kernel` for the length of a call; it is read
while a program is traced, so each side needs its own jitted function.
"""

import jax


def platform() -> str:
    """Platform of the default backend (``"tpu"``, ``"cpu"``).  A backend
    that fails to start raises here: a chip that cannot be reached is an
    error, never a reason to run somewhere else."""
    return jax.devices()[0].platform


def interpret() -> bool:
    """Whether kernels run through the Pallas interpreter — only because
    the platform *is* ``cpu`` (the test mesh).  Everywhere else they are
    compiled, and a compile error is the caller's to see."""
    return platform() == "cpu"


def use_kernel(name: str) -> bool:
    """Kernel (True) or reference (False) here: kernels run on a TPU and
    nowhere else — on the CPU the interpreter is orders of magnitude slower
    than the reference it would replace.  Necessary, not sufficient: the
    kernel's own shape gate and sharding rule decide after it.  ``name`` is
    the kernel's (``ce``, ``fused_adam``, ``flash_attention``,
    ``decode_attention``, ``paged_attention``, ``paged_gqa_attention``,
    ``paged_mla_attention``, ``paged_sparse_attention``,
    ``sparse_block_scores``, ``grouped_matmul``, ``delta_state_update``,
    ``mamba_state_update``, ``mamba_chunk_scan``, ``masked_chunk_attention``,
    ``masked_latent_attention``, ``index_select``) and is not read here: a
    test's replacement answers for one kernel by it.  ``fused_adam`` is the NVMe offload
    walk's: no compiled step program holds it."""
    del name
    return platform() == "tpu"


def single_device() -> bool:
    """No mesh, or a mesh of one device.  A bare ``pallas_call`` has no
    SPMD partitioning rule, so a kernel that does not bring its own
    ``shard_map`` runs only here, and the reference (which XLA partitions)
    under any larger mesh."""
    from deepspeed_tpu.parallel import mesh as mesh_lib
    return not mesh_lib.has_mesh() or mesh_lib.get_mesh().size == 1
