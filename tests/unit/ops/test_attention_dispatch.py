"""auto_attention dispatch pins (the round-5 headline bench rides on
flash being selected from S=512 up — a silent crossover regression would
cost ~10 TFLOPs/chip without failing any parity test)."""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops import attention as A


@pytest.mark.parametrize("S,expect_flash", [(256, False), (512, True),
                                            (1024, True)])
def test_auto_crossover(monkeypatch, S, expect_flash):
    calls = []

    def spy_flash(q, k, v, **kw):
        calls.append("flash")
        return A.reference_attention(q, k, v, **kw)

    def spy_ref(q, k, v, **kw):
        calls.append("reference")
        return jnp.zeros_like(q)

    monkeypatch.setattr(A, "flash_attention", spy_flash)
    # note: auto_attention resolves the names at call time from the module
    q = jnp.zeros((1, S, 2, 8), jnp.bfloat16)
    A.auto_attention(q, q, q, causal=True)
    kind = calls[0] if calls else "reference"
    assert (kind == "flash") == expect_flash, (S, calls)


def test_default_flash_blocks_are_tuned():
    """_block_sizes must keep the measured-optimal (512, 512) defaults for
    divisible sequence lengths (v5e, ``tools/flash_kernel_bench.py``, PR 52),
    and take the full-S single block below the cap (fewer online-softmax
    rescales; always a legal Mosaic tile — the divisor hunt that used to
    land on (64, 64) for S=192 is what produced sub-sublane blocks at small
    prime S)."""
    from deepspeed_tpu.ops.pallas.flash_attention import _block_sizes
    assert _block_sizes(512, None, None) == (512, 512)
    assert _block_sizes(1024, None, None) == (512, 512)
    assert _block_sizes(128, None, None) == (128, 128)
    assert _block_sizes(192, None, None) == (192, 192)
