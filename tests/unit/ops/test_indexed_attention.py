"""The attention of a prompt chunk under a selection's mask
(``ops/pallas/indexed_attention.py``): the kernel through the Pallas
interpreter against a dense softmax under the mask, in float32 and bf16, with
a query that chose nothing, keys past the chunk's last position and a
sequence of several key tiles; and the shape gate."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import indexed_attention as ia


def _dense(q, k, v, chosen):
    """float64 numpy: the softmax over the chosen keys alone."""
    C, H, D = q.shape
    T, Hkv = k.shape[0], k.shape[1] // D
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    out = np.zeros((C, H, D))
    for h in range(H):
        kh, vh = (a.reshape(T, Hkv, D)[:, h // (H // Hkv)] for a in (k, v))
        s = np.where(chosen, q[:, h] @ kh.T / np.sqrt(D), -np.inf)
        p = np.exp(s - np.where(chosen.any(-1, keepdims=True), s.max(-1, keepdims=True), 0.0))
        out[:, h] = (p / np.maximum(p.sum(-1, keepdims=True), 1e-300)) @ vh
    return out.reshape(C, H * D)


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("T", [256, 640])
def test_the_kernel_is_a_dense_softmax_under_the_mask(kernels, dtype, tol, T):
    kernels(ia.KERNEL)
    rng = np.random.default_rng(T)
    C, H, Hkv, D = 32, 4, 2, 128
    q, k, v = (jnp.asarray(rng.normal(size=s), dtype)
               for s in ((C, H, D), (T, Hkv * D), (T, Hkv * D)))
    at = T // 2 + np.arange(C)
    chosen = (rng.random((C, T)) < 0.2) & (np.arange(T)[None] <= at[:, None])
    chosen[5] = False                                   # a row that carries nothing
    got = jax.jit(ia.masked_chunk_attention)(q, k, v, jnp.asarray(chosen), jnp.int32(at[-1]))
    want = _dense(q, k, v, chosen)
    assert np.abs(np.asarray(got, np.float64) - want).max() < tol
    assert not np.asarray(got[5], np.float32).any() and np.abs(want).max() > 0.1
    ref = ia.masked_attention_reference(q, k.reshape(T, Hkv, D), v.reshape(T, Hkv, D),
                                        jnp.asarray(chosen))
    assert np.abs(np.asarray(ref, np.float64) - want).max() < tol


def test_the_shape_gate():
    assert ia.kernel_shape_ok(512, 128, 720 * 64, jnp.bfloat16)
    assert ia.key_tile(720 * 64) == 512 and ia.key_tile(128 * 3) == 128 and ia.key_tile(200) == 0
    assert not ia.kernel_shape_ok(512, 64, 46080, jnp.bfloat16)       # half a lane tile a head
    assert not ia.kernel_shape_ok(8, 128, 128, jnp.bfloat16)          # under a sublane tile
    assert not ia.kernel_shape_ok(512, 128, 200, jnp.bfloat16)
