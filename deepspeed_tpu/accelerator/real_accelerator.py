"""Accelerator singleton.

Reference: ``accelerator/real_accelerator.py:37`` (``get_accelerator`` /
``set_accelerator`` with the ``DS_ACCELERATOR`` env override and
auto-detection).
"""

import os
from typing import Optional

from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedAccelerator

_accelerator: Optional[DeepSpeedAccelerator] = None

SUPPORTED = ("tpu", "cpu")


def _detect() -> str:
    name = os.environ.get("DS_ACCELERATOR")
    if name:
        assert name in SUPPORTED, \
            f"DS_ACCELERATOR={name!r} not in {SUPPORTED}"
        return name
    # a backend that fails to start raises here: a chip that cannot be
    # reached is an error to see, not a reason to report "cpu"
    import jax
    if any(d.platform == "tpu" for d in jax.local_devices()):
        return "tpu"
    return "cpu"


def get_accelerator() -> DeepSpeedAccelerator:
    global _accelerator
    if _accelerator is None:
        from deepspeed_tpu.accelerator.tpu_accelerator import (CPU_Accelerator,
                                                               TPU_Accelerator)
        _accelerator = (TPU_Accelerator() if _detect() == "tpu"
                        else CPU_Accelerator())
    return _accelerator


def set_accelerator(accel: DeepSpeedAccelerator):
    global _accelerator
    _accelerator = accel


def is_current_accelerator_supported() -> bool:
    return get_accelerator()._name in SUPPORTED
