"""The one kernel-selection rule (``deepspeed_tpu/ops/pallas/__init__.py``):
a kernel runs when ``use_kernel`` says kernels run here, its own shape gate
admits the call and its own sharding rule holds; the reference otherwise.
Each of the four selections a program makes while it is traced is traced on
each side of the rule and the ``pallas_call``s in its jaxpr counted; the
fifth, ``fused_adam``, is made on the host for the NVMe walk; nothing under
``ops/``, ``models/`` or ``moe/`` may read the environment to decide."""

import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt import chunked_cross_entropy
from deepspeed_tpu.models.simple import SimpleModel
from deepspeed_tpu.ops.pallas import decode_attention as da
from deepspeed_tpu.ops.pallas import grouped_matmul as gm
from deepspeed_tpu.parallel import mesh as mesh_lib


def _set_mesh(n, **axes):
    spec = mesh_lib.MeshSpec(device_count=n, **axes)
    mesh = spec.build(jax.devices()[:n])
    mesh_lib.set_mesh(mesh, spec)
    return mesh


def _ce(refused):
    """Refused by the mesh: eight devices, and not inside a shard_map."""
    if refused:
        _set_mesh(8, data=2, fsdp=2, tensor=2)
    x, head = jnp.zeros((2, 32, 32)), jnp.zeros((256, 32))
    labels = jnp.zeros((2, 32), jnp.int32)
    return lambda x, h: chunked_cross_entropy(x, h, labels, 256), (x, head)


def _decode(refused):
    """Refused by the lanes: 3 heads of 16 do not fill a 128-lane tile."""
    H = 3 if refused else 8
    q = jnp.zeros((2, 1, H, 16))
    cache = jnp.zeros((2, 256, H * 16))
    return lambda q, ck, cv: da.decode_attention(q, ck, cv, 100), (q, cache, cache)


def _paged(refused):
    """Refused by the mesh: two devices, sharded over ``seq`` alone (its
    batch and tensor divisors are both 1; what counts is ``mesh.size``)."""
    if refused:
        _set_mesh(2, seq=2)
    q = jnp.zeros((2, 1, 8, 16))
    pages = jnp.zeros((24, 8, 8 * 16))
    tables = jnp.zeros((2, 8), jnp.int32)
    lengths = jnp.full((2,), 20, jnp.int32)
    return da.paged_attention, (q, pages, pages, tables, lengths)


def _grouped(refused):
    """Refused by the rows: 7 is no whole row tile."""
    A = 7 if refused else 256
    sizes = jnp.zeros((8,), jnp.int32).at[0].set(A)
    return gm.grouped_matmul, (jnp.zeros((A, 128)), jnp.zeros((8, 128, 128)), sizes)


SELECTIONS = {"ce": _ce, "decode_attention": _decode,
              "paged_attention": _paged, "grouped_matmul": _grouped}


@pytest.mark.parametrize("state", ["cpu", "kernels", "refused"])
@pytest.mark.parametrize("name", SELECTIONS)
def test_the_rule_selects(kernels, name, state):
    """On the CPU as it stands: the reference.  Where the rule says kernels
    run and the kernel's gate admits the call: the kernel.  The same answer
    at a shape or under a mesh the kernel's own gate refuses: the reference."""
    if state != "cpu":
        kernels(name)
    fn, args = SELECTIONS[name](refused=state == "refused")
    # a new function each time: the rule is read while tracing, and JAX
    # answers a second trace of one function at one shape from its cache
    calls = str(jax.make_jaxpr(lambda *a: fn(*a))(*args)).count("pallas_call")
    assert (calls > 0) == (state == "kernels"), calls


@pytest.mark.usefixtures("offload_on_device")
@pytest.mark.parametrize("state", ["cpu", "kernels", "refused"])
def test_the_rule_selects_fused_adam_for_the_offload_walk(kernels, tmp_path,
                                                          state):
    """``fused_adam`` is selected on the host, a step at a time, for the NVMe
    walk (no compiled step holds it: ``test_fused_optim.py``); refused by the
    engine's mesh (the default one spans all eight devices)."""
    if state != "cpu":
        kernels("fused_adam")
    refused = state == "refused"
    model = SimpleModel(hidden_dim=32, nlayers=2)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(0), batch_size=2),
        config={"train_micro_batch_size_per_gpu": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
                "zero_optimization": {
                    "stage": 3, "offload_optimizer": {
                        "device": "nvme", "nvme_path": str(tmp_path)}}},
        mesh=None if refused else _set_mesh(1))
    assert (engine.mesh.size == 1) == (not refused)
    assert engine._fused_offload_walk_ready() == (state == "kernels")


def test_no_selection_reads_the_environment():
    root = pathlib.Path(deepspeed_tpu.__file__).parent
    reads = re.compile(r"\bos\.(environ|getenv)\b|\bfrom os import\b")
    found = [f"{path.relative_to(root)}:{n}"
             for sub in ("ops", "models", "moe")
             for path in sorted((root / sub).rglob("*.py"))
             for n, line in enumerate(path.read_text().splitlines(), 1)
             if reads.search(line)]
    assert not found, found
