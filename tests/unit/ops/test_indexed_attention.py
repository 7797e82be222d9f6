"""The attention of a prompt chunk under a selection's mask
(``ops/pallas/indexed_attention.py``): the kernel through the Pallas
interpreter against a dense softmax under the mask, in float32 and bf16, with
a query that chose nothing, keys past the chunk's last position and a
sequence of several key tiles; and the shape gate.  The same for a LATENT
cache's masked pass (``masked_latent_attention``) against its jnp reference:
every head's own keys and values made from the latent, the rotated key shared."""

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


# (dtype, tol), keys handed in, the chunk's last position: an extent of two
# whole tiles of 384 keys; the cell's 5,760 (five tiles of 1,152); ``last``
# inside the first tile, on a tile's edge, past it, at the extent's end
LATENT_CASES = {
    "f32-tiles-last_at_end": (jnp.float32, 2e-5, 768, 767),
    "f32-tiles-last_in_first_tile": (jnp.float32, 2e-5, 768, 100),
    "f32-tiles-last_on_an_edge": (jnp.float32, 2e-5, 768, 383),
    "f32-5760-last_in_first_tile": (jnp.float32, 2e-5, 5760, 600),
    "bf16-tiles-last_at_end": (jnp.bfloat16, 3e-2, 768, 767),
    "bf16-tiles-last_past_an_edge": (jnp.bfloat16, 3e-2, 768, 384),
    "bf16-5760-last_on_an_edge": (jnp.bfloat16, 3e-2, 5760, 1151),
    "bf16-5760-last_at_end": (jnp.bfloat16, 3e-2, 5760, 5759),
}


@pytest.mark.parametrize("case", sorted(LATENT_CASES))
def test_the_latent_kernel_is_its_reference(kernels, case):
    """Eight heads (two grid steps of four under one mask tile) of 128 + 64
    lanes a key and 128 a value over a latent of 64 lanes; query 5 chose
    nothing at all (zeros out), query 7 nothing past the first 128 keys (it
    adds nothing in the later tiles), and no query a key past ``last``."""
    dtype, tol, T, last = LATENT_CASES[case]
    rng = np.random.default_rng(T + last)
    C, H, dn, dr, dv, R = 32, 8, 128, 64, 128, 64
    f = lambda *shape: jnp.asarray(rng.normal(0, 1, shape), dtype)
    q, w_uk, w_uv = f(C, H, dn + dr), f(R, H, dn) * R ** -0.5, f(R, H, dv) * R ** -0.5
    c = jnp.pad(f(T, R + dr), ((0, 0), (0, 64)))            # the pages' lanes past the key
    at = np.maximum(last - C + 1 + np.arange(C), 0)
    chosen = (rng.random((C, T)) < 0.2) & (np.arange(T)[None] <= at[:, None])
    chosen[np.arange(C), at] = True                         # a query sees itself
    chosen[5] = False
    chosen[7, 128:] = False
    args = (q, c, jnp.asarray(chosen), jnp.int32(last), w_uk, w_uv)
    assert ia.latent_kernel_shape_ok(C, H, dn, dr, dv, T, dtype)
    want = ia.masked_latent_attention(*args, scale=0.1)     # the rule as it stands here
    kernels(ia.LATENT_KERNEL)
    got = jax.jit(lambda *a: ia.masked_latent_attention(*a, scale=0.1))(*args)
    ref = ia.masked_latent_attention_reference(q, c, jnp.asarray(chosen), w_uk, w_uv, scale=0.1)
    assert got.shape == (C, H, dv) and np.array_equal(np.asarray(want), np.asarray(ref))
    assert np.abs(np.asarray(got, np.float64) - np.asarray(ref, np.float64)).max() < tol
    assert not np.asarray(got[5], np.float32).any() and not np.asarray(ref[5], np.float32).any()
    assert np.abs(np.asarray(ref, np.float32)).max() > 0.1
    assert ia.latent_keys_walked(C, H, dn, dr, dv, T, dtype, last) == min(
        (last // ia.latent_key_tile(T) + 1) * ia.latent_key_tile(T), T)


def test_the_latent_kernel_fetches_no_tile_past_the_last_position(kernels):
    """Keys past the tile that holds ``last`` are never read: NaN there
    changes nothing (the reference, which reads every key, would give NaN)."""
    kernels(ia.LATENT_KERNEL)
    rng = np.random.default_rng(3)
    C, H, dn, dr, dv, R, T, last = 32, 4, 128, 64, 128, 64, 768, 200
    f = lambda *shape: jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
    q, w_uk, w_uv, c = f(C, H, dn + dr), f(R, H, dn), f(R, H, dv), f(T, R + dr + 64)
    chosen = jnp.asarray((rng.random((C, T)) < 0.3) & (np.arange(T)[None] <= last))
    run = jax.jit(lambda c: ia.masked_latent_attention(
        q, c, chosen, jnp.int32(last), w_uk, w_uv, scale=0.1))
    assert ia.latent_key_tile(T) == 384
    got, poisoned = run(c), run(c.at[384:].set(jnp.nan))
    assert np.array_equal(np.asarray(got), np.asarray(poisoned))
    assert np.isfinite(np.asarray(got)).all() and np.abs(np.asarray(got)).max() > 0.1


def test_the_latent_shape_gate(kernels):
    ok = ia.latent_kernel_shape_ok
    assert ok(512, 128, 128, 64, 128, 720 * 64, jnp.bfloat16)
    assert [ia.latent_key_tile(T) for T in (5760, 46080, 640 * 3, 1024, 128 * 3, 200)] == [
        1152, 1152, 640, 512, 384, 0]
    assert not ok(512, 126, 128, 64, 128, 46080, jnp.bfloat16)     # heads not whole groups of 4
    assert not ok(512, 128, 64, 64, 128, 46080, jnp.bfloat16)      # half a lane tile a key
    assert not ok(512, 128, 128, 64, 64, 46080, jnp.bfloat16)      # half a lane tile a value
    assert not ok(512, 128, 128, 192, 128, 46080, jnp.bfloat16)    # a rotated key past a tile
    assert not ok(512, 128, 128, 64, 128, 46080, jnp.float32)      # 512 float32 queries a step
    assert ok(256, 128, 128, 64, 128, 46080, jnp.float32)
    assert not ok(24, 128, 128, 64, 128, 46080, jnp.bfloat16)      # under the mask's sublane tile
    assert not ok(512, 128, 128, 64, 128, 200, jnp.bfloat16)       # no key tile divides
    # a head group that does not divide the heads: the reference answers
    kernels(ia.LATENT_KERNEL)
    rng = np.random.default_rng(0)
    f = lambda *shape: jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
    q, c, w_uk, w_uv = f(32, 6, 192), f(128, 192), f(64, 6, 128), f(64, 6, 128)
    chosen = jnp.asarray(np.tril(np.ones((32, 128), bool), 96))
    got = ia.masked_latent_attention(q, c, chosen, jnp.int32(127), w_uk, w_uv, scale=0.1)
    assert ia.latent_keys_walked(32, 6, 128, 64, 128, 128, jnp.float32, 0) == 128
    assert np.array_equal(np.asarray(got), np.asarray(
        ia.masked_latent_attention_reference(q, c, chosen, w_uk, w_uv, scale=0.1)))
