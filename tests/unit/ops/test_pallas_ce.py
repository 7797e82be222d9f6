"""Fused Pallas cross-entropy vs the chunked XLA reference.

Parity contract (module docstring of ``ops/pallas/cross_entropy.py``):
fp32 forward is BITWISE equal to the reference path — the kernel performs
literally the same op sequence (f32 dot, same -1e9 vocab mask, max,
exp-shift, sum, log, slice-then-mean) — including the multi-vocab-block
online-softmax sweep; gradients agree to a few ulp (the backward
recomputes scores rather than saving them).  Also covers the shape/mesh
support gate and the ``chunked_cross_entropy`` wiring."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt import chunked_cross_entropy
from deepspeed_tpu.ops.pallas import cross_entropy as pce


def make_inputs(N=200, E=64, V=256, dtype=jnp.float32, bias=False, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (N, E), dtype)
    head = jax.random.normal(ks[1], (V, E), dtype) * 0.05
    labels = jax.random.randint(ks[2], (N,), 0, V).astype(jnp.int32)
    head_b = (jax.random.normal(ks[0], (V,), dtype) * 0.1) if bias else None
    return x, head, labels, head_b


def reference_ce(x, head, labels, vocab_size, head_b=None):
    """The XLA path: what ``chunked_cross_entropy`` takes on the CPU."""
    N, E = x.shape
    return chunked_cross_entropy(x.reshape(1, N, E), head,
                                 labels.reshape(1, N), vocab_size,
                                 head_b=head_b)


# --------------------------------------------------------------------------- #
# forward parity (fp32 exact)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("V,vocab_size,bias", [
    (128, 128, False),    # single vocab block, rows padded (N=200 % 128 != 0)
    (384, 384, False),    # 3 vocab blocks: online-softmax rescale sweep
    (256, 250, False),    # masked padded vocab columns (-1e9 sentinel)
    (512, 512, True),     # head bias streamed per vocab block
])
def test_forward_bitwise_fp32(V, vocab_size, bias):
    x, head, labels, head_b = make_inputs(V=V, bias=bias)
    labels = jnp.minimum(labels, vocab_size - 1)
    fused = pce.fused_cross_entropy(x, head, labels, vocab_size,
                                    head_b=head_b)
    ref = reference_ce(x, head, labels, vocab_size, head_b=head_b)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))


def test_backward_parity_fp32():
    x, head, labels, _ = make_inputs(V=384)

    gx_f, gh_f = jax.grad(
        lambda x, h: pce.fused_cross_entropy(x, h, labels, 384),
        argnums=(0, 1))(x, head)
    gx_r, gh_r = jax.grad(
        lambda x, h: reference_ce(x, h, labels, 384), argnums=(0, 1))(x, head)
    np.testing.assert_allclose(gx_f, gx_r, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(gh_f, gh_r, atol=1e-6, rtol=1e-6)


def test_backward_parity_bias_and_mask():
    x, head, labels, head_b = make_inputs(V=256, bias=True)
    labels = jnp.minimum(labels, 249)

    def loss(fn):
        return lambda x, h, b: fn(x, h, labels, 250, head_b=b)

    g_f = jax.grad(loss(pce.fused_cross_entropy), argnums=(0, 1, 2))(
        x, head, head_b)
    g_r = jax.grad(loss(reference_ce), argnums=(0, 1, 2))(x, head, head_b)
    for a, b, name in zip(g_f, g_r, ("dx", "dhead", "dbias")):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6,
                                   err_msg=f"{name} mismatch")


def test_bf16_tolerance():
    """bf16 inputs: the kernel computes in f32 like the reference; the
    dot's bf16 input rounding bounds the difference."""
    x, head, labels, _ = make_inputs(V=256, dtype=jnp.bfloat16)
    fused = pce.fused_cross_entropy(x, head, labels, 256)
    ref = reference_ce(x, head, labels, 256)
    np.testing.assert_allclose(np.float32(fused), np.float32(ref),
                               atol=2e-2, rtol=2e-2)
    g_f = jax.grad(lambda x: pce.fused_cross_entropy(x, head, labels, 256))(x)
    g_r = jax.grad(lambda x: reference_ce(x, head, labels, 256))(x)
    np.testing.assert_allclose(np.float32(g_f), np.float32(g_r),
                               atol=2e-2, rtol=2e-2)


def test_jit_parity():
    """The training path always runs jitted — parity must survive jit."""
    x, head, labels, _ = make_inputs(V=384)
    fused = jax.jit(lambda x, h: pce.fused_cross_entropy(
        x, h, labels, 384))(x, head)
    ref = jax.jit(lambda x, h: reference_ce(x, h, labels, 384))(x, head)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               atol=1e-7, rtol=1e-7)


# --------------------------------------------------------------------------- #
# gates + wiring
# --------------------------------------------------------------------------- #
def test_supported_gate():
    assert pce.ce_supported(64, 64, 256)
    assert not pce.ce_supported(64, 64, 100)    # no 128-multiple block
    assert pce._vocab_block(50304, 768) is not None   # GPT-2 padded vocab


def test_supported_gate_rejects_multi_device_mesh():
    from deepspeed_tpu.parallel import mesh as mesh_lib
    spec = mesh_lib.MeshSpec(device_count=8, data=2, fsdp=2, tensor=2)
    mesh = spec.build(jax.devices()[:8])
    mesh_lib.set_mesh(mesh, spec)
    try:
        assert not pce.ce_supported(64, 64, 256)
    finally:
        mesh_lib.reset_mesh()


def test_chunked_ce_routes_through_kernel(monkeypatch, kernels):
    """chunked_cross_entropy must dispatch to the fused kernel when the
    rule says kernels run, and the result must equal the reference path."""
    x, head, labels, _ = make_inputs(N=64, E=32, V=128)
    x3 = x.reshape(2, 32, 32)
    l2 = labels.reshape(2, 32)

    kernels("ce")
    called = {}
    orig = pce.fused_cross_entropy

    def spy(*a, **kw):
        called["yes"] = True
        return orig(*a, **kw)

    monkeypatch.setattr(pce, "fused_cross_entropy", spy)
    on = chunked_cross_entropy(x3, head, l2, 128)
    assert called.get("yes"), "fused kernel was not dispatched"

    kernels()
    off = chunked_cross_entropy(x3, head, l2, 128)
    np.testing.assert_array_equal(np.asarray(on), np.asarray(off))
