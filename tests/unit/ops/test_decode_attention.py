"""Pallas decode-attention kernel parity (CPU interpreter) and its shape gate.

On CPU the einsum is the default path; the ``kernels`` fixture replaces
``ops.pallas``'s rule so the kernel runs through the interpreter.  Every parity case counts the kernel calls,
so a gate that quietly routed to the reference cannot pass vacuously."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import decode_attention as da
from deepspeed_tpu.ops.pallas.decode_attention import (
    decode_attention, decode_attention_reference, kernel_shape_ok)


@pytest.fixture
def kernel_calls(monkeypatch, kernels):
    """Force the kernel on and count how often dispatch reaches it."""
    kernels("decode_attention")
    calls = []
    real = da._decode_call
    monkeypatch.setattr(da, "_decode_call",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    return calls


def _qkv(B, Sq, T, H, D, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, Sq, H, D), dtype),
            jax.random.normal(ks[1], (B, T, H * D), dtype),
            jax.random.normal(ks[2], (B, T, H * D), dtype))


# (1, 200) crosses a block boundary (nk=2 at bk=128): the online-softmax
# alpha/m/l carry between blocks is live only there
@pytest.mark.parametrize("Sq,pos", [(1, 0), (1, 100), (1, 200), (8, 64),
                                    (8, 180), (16, 0)])
def test_decode_kernel_matches_reference(kernel_calls, Sq, pos):
    q, ck, cv = _qkv(2, Sq, 256, 8, 16, jnp.float32)   # 8 heads in one slice
    out = jax.jit(lambda q, ck, cv: decode_attention(q, ck, cv, pos))(q, ck, cv)
    assert kernel_calls
    ref = decode_attention_reference(q, ck, cv, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("H,kernel", [(12, True), (25, False)])
def test_gpt2_head_shapes(kernel_calls, H, kernel):
    """GPT-2 head shapes, D=64 bf16: 12 heads pair up into 128-lane slices
    and run the kernel; gpt2-xl's 25 do not fill them, and the gate routes
    that shape to the einsum."""
    q, ck, cv = _qkv(2, 1, 256, H, 64, jnp.bfloat16, seed=1)
    out = jax.jit(lambda q, ck, cv: decode_attention(q, ck, cv, 200))(q, ck, cv)
    assert bool(kernel_calls) == kernel
    ref = decode_attention_reference(q.astype(jnp.float32),
                                     ck.astype(jnp.float32),
                                     cv.astype(jnp.float32), 200)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


def test_bias_takes_reference(kernel_calls):
    """ALiBi bias is outside the kernel: forced on, dispatch still takes
    the grouped einsum, which must agree with the kernel at zero bias."""
    q, ck, cv = _qkv(2, 1, 128, 8, 16, jnp.bfloat16, seed=2)
    out = jax.jit(lambda q, ck, cv: decode_attention(q, ck, cv, 77))(q, ck, cv)
    assert len(kernel_calls) == 1
    zero_bias = jnp.zeros((1, 8, 1, 128), jnp.float32)
    ref = jax.jit(lambda q, ck, cv: decode_attention(
        q, ck, cv, 77, bias=zero_bias))(q, ck, cv)
    assert len(kernel_calls) == 1
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("H,Hkv,D,block,dtype,ok", [
    (12, 12, 64, 128, jnp.bfloat16, True),     # gpt2
    (16, 16, 64, 16, jnp.bfloat16, True),      # gpt2-medium, one bf16 page
    (25, 25, 64, 128, jnp.bfloat16, False),    # gpt2-xl: 1600 lanes
    (32, 32, 128, 32, jnp.bfloat16, True),
    (8, 2, 128, 128, jnp.bfloat16, False),     # GQA
    (12, 12, 64, 8, jnp.bfloat16, False),      # page below the bf16 tile
    (12, 12, 64, 8, jnp.float32, True),
    (12, 12, 80, 128, jnp.bfloat16, False),    # head neither fills nor tiles 128
])
def test_kernel_shape_gate(H, Hkv, D, block, dtype, ok):
    """Both sides of the gate; test_chip_compile.py shows the admitted side
    compiles for the chip."""
    assert kernel_shape_ok(H, Hkv, D, block, dtype) is ok
