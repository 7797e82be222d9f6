"""Pallas flash attention vs pure-jnp reference (run through the Pallas
interpreter on the CPU mesh) — the parity pattern of the reference's
``tests/unit/ops/accelerators/test_accelerator_forward.py`` (fused CUDA
kernel vs HF modeling)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import reference_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention


def make_qkv(B=2, S=128, H=4, D=32, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, S, H, D)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_parity(causal):
    q, k, v = make_qkv()
    out = flash_attention(q, k, v, causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_forward_parity_multiblock():
    # S=256 with 128-blocks: exercises the online-softmax accumulation
    q, k, v = make_qkv(B=1, S=256, H=2, D=64, seed=3)
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_backward_parity(causal):
    q, k, v = make_qkv(B=1, S=128, H=2, D=32, seed=1)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("axes", [dict(data=2, fsdp=2, tensor=2),
                                  dict(data=2, seq=2, tensor=2)])
def test_sharded_flash_under_mesh(axes):
    """Pallas path under an active mesh: the shard_map wrapper must shard
    batch over data/fsdp and heads over seq x tensor and still match the
    reference (grads included) — the multichip SPMD path the advisor
    flagged as unvalidated.  The seq=2 case exercises the built-in
    Ulysses re-shard of sequence-sharded inputs."""
    from deepspeed_tpu.parallel import mesh as mesh_lib

    spec = mesh_lib.MeshSpec(device_count=8, **axes)
    mesh = spec.build(jax.devices()[:8])
    mesh_lib.set_mesh(mesh, spec)
    try:
        q, k, v = make_qkv(B=4, S=128, H=4, D=32, seed=4)

        @jax.jit
        def run(q, k, v):
            return flash_attention(q, k, v, causal=True)

        out = run(q, k, v)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

        g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True) ** 2), argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.grad(lambda q, k, v: jnp.sum(
            reference_attention(q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g, g_ref, "qkv"):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                       err_msg=f"d{name} mismatch")
    finally:
        mesh_lib.reset_mesh()


def test_bf16_close():
    q, k, v = make_qkv(B=1, S=128, H=2, D=64, dtype=jnp.bfloat16, seed=2)
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out.astype(np.float32), ref.astype(np.float32),
                               atol=2e-2, rtol=2e-2)


# --------------------------------------------------------------------------- #
# Round 4: grouped-KV (GQA/MQA) + additive logit bias in the kernel
# --------------------------------------------------------------------------- #
def make_gqa(B=2, S=128, H=8, Hkv=2, D=32, dtype=jnp.float32, seed=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("Hkv", [1, 2, 4])
def test_gqa_forward_parity(causal, Hkv):
    q, k, v = make_gqa(Hkv=Hkv)
    out = flash_attention(q, k, v, causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("Hkv", [1, 2])
def test_gqa_backward_parity(Hkv):
    q, k, v = make_gqa(B=1, S=128, H=4, Hkv=Hkv, seed=6)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        assert a.shape == b.shape, (name, a.shape, b.shape)
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch (Hkv={Hkv})")


@pytest.mark.parametrize("causal", [True, False])
def test_bias_forward_parity(causal):
    from deepspeed_tpu.ops.attention import alibi_bias
    q, k, v = make_qkv(B=2, S=128, H=4, D=32, seed=7)
    bias = alibi_bias(4, 128, 128)
    out = flash_attention(q, k, v, causal=causal, bias=bias)
    ref = reference_attention(q, k, v, causal=causal, bias=bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_bias_backward_parity():
    """q/k/v grads must match the reference with a bias present (the bias
    itself is constant — ALiBi — so its zero cotangent is by design)."""
    from deepspeed_tpu.ops.attention import alibi_bias
    q, k, v = make_qkv(B=1, S=128, H=2, D=32, seed=8)
    bias = alibi_bias(2, 128, 128)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True, bias=bias) ** 2)

    g_flash = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch")


def test_gqa_plus_bias_multiblock():
    """GQA and bias together across multiple KV blocks (S=256, 128-blocks),
    forward + backward."""
    from deepspeed_tpu.ops.attention import alibi_bias
    q, k, v = make_gqa(B=1, S=256, H=4, Hkv=2, D=64, seed=9)
    bias = alibi_bias(4, 256, 256)
    out = flash_attention(q, k, v, causal=True, bias=bias)
    ref = reference_attention(q, k, v, causal=True, bias=bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True, bias=bias) ** 2)

    g_flash = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch")


def test_batched_bias():
    """Per-batch bias (Bb = B) exercises the batch-indexed bias BlockSpec."""
    q, k, v = make_qkv(B=2, S=128, H=2, D=32, seed=10)
    bias = jax.random.normal(jax.random.PRNGKey(11), (2, 2, 128, 128),
                             jnp.float32) * 0.1
    out = flash_attention(q, k, v, causal=True, bias=bias)
    ref = reference_attention(q, k, v, causal=True, bias=bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_sharded_gqa_bias_under_mesh():
    """GQA + bias through the shard_map wrapper on a dp2 x tp2 mesh."""
    from deepspeed_tpu.parallel import mesh as mesh_lib
    from deepspeed_tpu.ops.attention import alibi_bias

    spec = mesh_lib.MeshSpec(device_count=8, data=2, fsdp=2, tensor=2)
    mesh = spec.build(jax.devices()[:8])
    mesh_lib.set_mesh(mesh, spec)
    try:
        q, k, v = make_gqa(B=4, S=128, H=8, Hkv=4, D=32, seed=12)
        bias = alibi_bias(8, 128, 128)

        out = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, bias=bias))(q, k, v)
        ref = reference_attention(q, k, v, causal=True, bias=bias)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

        g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, bias=bias) ** 2), argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.grad(lambda q, k, v: jnp.sum(reference_attention(
            q, k, v, causal=True, bias=bias) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g, g_ref, "qkv"):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                       err_msg=f"d{name} mismatch")
    finally:
        mesh_lib.reset_mesh()


def test_alibi_slopes_parity():
    """In-kernel ALiBi (slopes operand, O(H) memory) vs the reference's
    materialized-bias formulation — fwd + bwd."""
    from deepspeed_tpu.ops.attention import alibi_bias, alibi_slopes
    q, k, v = make_qkv(B=2, S=256, H=4, D=32, seed=13)
    slopes = jnp.asarray(alibi_slopes(4))
    bias = alibi_bias(4, 256, 256)
    out = flash_attention(q, k, v, causal=True, alibi=slopes)
    ref = reference_attention(q, k, v, causal=True, bias=bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True, **kw) ** 2)

    g_flash = jax.grad(loss(flash_attention, alibi=slopes), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(reference_attention, bias=bias), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch")


def test_alibi_slopes_gqa():
    from deepspeed_tpu.ops.attention import alibi_bias, alibi_slopes
    q, k, v = make_gqa(B=1, S=128, H=4, Hkv=2, seed=14)
    out = flash_attention(q, k, v, causal=True, alibi=jnp.asarray(alibi_slopes(4)))
    ref = reference_attention(q, k, v, causal=True, bias=alibi_bias(4, 128, 128))
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------------- #
# Shape-survival sweep: every (S, heads) combination must produce a correct
# answer — either through the kernel (blocks fitted to S) or through the
# one-shot-warned reference fallback — never a lowering error.  S=1 is the
# decode-like (1, 1, 128) cliff that used to throw before _block_sizes
# learned to clamp; S=1000 is indivisible by any legal block and must demote.
# --------------------------------------------------------------------------- #
SWEEP_S = [1, 8, 64, 128, 1000]
SWEEP_H = [1, 2, 12]


@pytest.mark.parametrize("H", SWEEP_H)
@pytest.mark.parametrize("S", SWEEP_S)
def test_shape_sweep_forward_parity(S, H):
    q, k, v = make_qkv(B=1, S=S, H=H, D=32, seed=17)
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5,
                               err_msg=f"S={S} H={H}")


@pytest.mark.parametrize("S", [1, 8, 1000])
def test_shape_sweep_backward_parity(S):
    q, k, v = make_qkv(B=1, S=S, H=2, D=32, seed=18)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch (S={S})")


def test_decode_cliff_1_1_128():
    """The (1, 1, 128) repro: batch 1, one query token, D=128 — the exact
    shape the decode path hands the kernel, which the old divisibility
    check rejected and the old block fitter lowered into a Mosaic error."""
    q, k, v = make_qkv(B=1, S=1, H=1, D=128, seed=19)
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    assert out.shape == (1, 1, 1, 128)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_shape_sweep_gqa():
    """GQA across the sweep's odd sizes (kernel path for small S, fallback
    path for the indivisible S) keeps head-group semantics."""
    for S in (1, 8, 1000):
        q, k, v = make_gqa(B=1, S=S, H=4, Hkv=2, D=32, seed=20)
        out = flash_attention(q, k, v, causal=True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5,
                                   err_msg=f"S={S}")


def test_block_fitting_and_fallback_telemetry():
    """_block_sizes must emit Mosaic-legal blocks for every small S (full-S
    blocks below the caps), the indivisible S=1000 must be detected as
    non-lowerable, and the demotion warning must fire exactly once per
    shape (telemetry, not log spam)."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    for S in (1, 3, 8, 13, 64, 128, 255):
        bq, bk = fa._block_sizes(S, None, None)
        assert bq == S and bk == S, (S, bq, bk)
        assert fa._blocks_lowerable(S, bq, bk)
    # large divisible S keeps the tuned caps
    assert fa._block_sizes(1024, None, None) == (256, 512)
    # indivisible: fitted blocks exist but are not sublane-aligned
    bq, bk = fa._block_sizes(1000, None, None)
    assert 1000 % bq == 0 and 1000 % bk == 0
    assert not fa._blocks_lowerable(1000, bq, bk)
    # explicit block_q=/block_k= requests are clamped, never trusted
    assert fa._block_sizes(64, 256, 512) == (64, 64)

    fa._FALLBACK_WARNED.clear()
    q, k, v = make_qkv(B=1, S=1000, H=1, D=32, seed=21)
    flash_attention(q, k, v, causal=True)
    flash_attention(q, k, v, causal=True)
    assert len(fa._FALLBACK_WARNED) == 1   # one shape+reason key, one warn


@pytest.mark.parametrize("rank", [2, 3])
def test_low_rank_bias(rank):
    """The contract says 'broadcastable to [B, H, S, S]' — rank-2/3 biases
    must work on the kernel path (round-4 review finding)."""
    q, k, v = make_qkv(B=2, S=128, H=2, D=32, seed=15)
    shape = (128, 128) if rank == 2 else (2, 128, 128)
    bias = jax.random.normal(jax.random.PRNGKey(16), shape, jnp.float32) * 0.1
    out = flash_attention(q, k, v, causal=True, bias=bias)
    ref = reference_attention(q, k, v, causal=True, bias=bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
