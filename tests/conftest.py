"""Test session setup: force an 8-device virtual CPU mesh.

The reference tests fork N processes over loopback NCCL
(``tests/unit/common.py:DistributedExec:88``).  Here "distributed" tests run
single-process SPMD over 8 virtual CPU devices — XLA's
``--xla_force_host_platform_device_count`` — so CI needs no TPU and no
process forking (SURVEY.md §4 "TPU translation").  Kernels run through the
Pallas interpreter here (``ops.pallas.interpret``); what the chip's compiler
accepts is checked ahead of time in ``tests/unit/ops/test_chip_compile.py``,
and the chip itself by ``chip_smoke.py``.
"""

import os

os.environ.setdefault("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# NOTE: do NOT enable jax's persistent compilation cache here.  On CPU the
# cache stores AOT machine code whose recorded target features
# (+prefer-no-gather etc.) fail to match at reload in a fresh process on
# this very machine — and the failed load SILENTLY yields zero-filled
# outputs (observed: a checkpoint round-trip restoring all-zeros params).
# Entry scripts turn it on through ``utils/compile_cache.py``; tests never.

assert jax.device_count() == 8, f"expected 8 virtual CPU devices, got {jax.devices()}"

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    from deepspeed_tpu.parallel import mesh as mesh_lib
    mesh_lib.reset_mesh()


@pytest.fixture
def kernels(monkeypatch):
    """``kernels("ce", ...)`` replaces ``ops.pallas``'s selection rule for
    the rest of the test: the named kernels run (through the interpreter
    here, where their own gates admit the call), every other selection
    takes its reference; ``kernels()`` is the rule as it stands on the CPU.
    The rule is read while a program is traced, so a test that wants both
    sides at one shape builds a new jitted function (or engine) for each."""
    from deepspeed_tpu.ops import pallas

    def choose(*names):
        monkeypatch.setattr(pallas, "use_kernel", lambda name: name in names)
    return choose


@pytest.fixture
def offload_on_device(monkeypatch):
    """The CPU backend cannot place on ``pinned_host``, and
    ``offload_shardings`` raises there rather than quietly keeping device
    placement.  Tests of what sits ABOVE the memory kind (the layered
    schedule offload implies, the NVMe swappers, the audits) ask for this
    fixture and say so: here the host tier is device memory."""
    from deepspeed_tpu.runtime.zero import partition_parameters as zinit
    monkeypatch.setattr(zinit, "offload_shardings",
                        lambda shardings, device, shapes=None: shardings)
