"""Pallas TPU kernels, and the one place that asks which platform runs them."""

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
