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


# (S, D, block_q, block_k); None takes the default blocks.  The forced blocks
# give ONE call tiles wholly under the diagonal (the loops' unmasked body),
# tiles the diagonal crosses (the masked body) and bq != bk both ways; D = 64
# is the head width whose scale, 2**-3, the kernels fold into q.
SCHEDULES = [(128, 32, None, None), (256, 64, 64, 128), (256, 64, 128, 64),
             (192, 64, 64, 64)]
schedules = pytest.mark.parametrize("S,D,bq,bk", SCHEDULES)


def grads(fn, *args, **kw):
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v, **kw) ** 2),
                    argnums=(0, 1, 2))(*args)


def assert_grads_close(got, want, tol=5e-5, note=""):
    for a, b, name in zip(got, want, "qkv"):
        assert a.shape == b.shape, (name, a.shape, b.shape)
        np.testing.assert_allclose(a, b, atol=tol, rtol=tol,
                                   err_msg=f"d{name} mismatch {note}")


@schedules
@pytest.mark.parametrize("causal", [True, False])
def test_forward_parity(causal, S, D, bq, bk):
    q, k, v = make_qkv(S=S, D=D)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_forward_parity_multiblock():
    # S=256 with 128-blocks: exercises the online-softmax accumulation
    q, k, v = make_qkv(B=1, S=256, H=2, D=64, seed=3)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@schedules
@pytest.mark.parametrize("causal", [True, False])
def test_backward_parity(causal, S, D, bq, bk):
    q, k, v = make_qkv(B=1, S=S, H=2, D=D, seed=1)
    g_flash = grads(flash_attention, q, k, v, causal=causal, block_q=bq,
                    block_k=bk)
    g_ref = grads(reference_attention, q, k, v, causal=causal)
    assert_grads_close(g_flash, g_ref)


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


@schedules
def test_bf16_close(S, D, bq, bk):
    q, k, v = make_qkv(B=1, S=S, H=2, D=D, dtype=jnp.bfloat16, seed=2)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out.astype(np.float32), ref.astype(np.float32),
                               atol=2e-2, rtol=2e-2)


@schedules
def test_bf16_grads_close(S, D, bq, bk):
    """bf16 operands through the backward kernels: within bf16's own
    rounding of the float32 reference's gradients, taken over each
    gradient's scale (an element-wise bound would test the rounding of
    near-zero entries)."""
    q, k, v = make_qkv(B=1, S=S, H=2, D=D, dtype=jnp.bfloat16, seed=2)
    g_flash = grads(flash_attention, q, k, v, causal=True, block_q=bq,
                    block_k=bk)
    g_ref = grads(reference_attention,
                  *(x.astype(jnp.float32) for x in (q, k, v)), causal=True)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        gap = np.abs(np.asarray(a, np.float32) - np.asarray(b)).max()
        assert gap <= 2e-2 * np.abs(np.asarray(b)).max(), (name, gap)


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


@schedules
@pytest.mark.parametrize("Hkv", [1, 2])
def test_gqa_backward_parity(Hkv, S, D, bq, bk):
    q, k, v = make_gqa(B=1, S=S, H=4, Hkv=Hkv, D=D, seed=6)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    np.testing.assert_allclose(out, reference_attention(q, k, v, causal=True),
                               atol=2e-5, rtol=2e-5)
    g_flash = grads(flash_attention, q, k, v, causal=True, block_q=bq,
                    block_k=bk)
    g_ref = grads(reference_attention, q, k, v, causal=True)
    assert_grads_close(g_flash, g_ref, note=f"(Hkv={Hkv})")


@pytest.mark.parametrize("causal", [True, False])
def test_bias_forward_parity(causal):
    from deepspeed_tpu.ops.attention import alibi_bias
    q, k, v = make_qkv(B=2, S=128, H=4, D=32, seed=7)
    bias = alibi_bias(4, 128, 128)
    out = flash_attention(q, k, v, causal=causal, bias=bias)
    ref = reference_attention(q, k, v, causal=causal, bias=bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@schedules
def test_bias_backward_parity(S, D, bq, bk):
    """q/k/v grads must match the reference with a bias present (the bias
    itself is constant — ALiBi — so its zero cotangent is by design); the
    bias keeps the masked body's iotas out of the unmasked tiles but adds
    its own tile to every one."""
    from deepspeed_tpu.ops.attention import alibi_bias
    q, k, v = make_qkv(B=1, S=S, H=2, D=D, seed=8)
    bias = alibi_bias(2, S, S)
    out = flash_attention(q, k, v, causal=True, bias=bias, block_q=bq, block_k=bk)
    ref = reference_attention(q, k, v, causal=True, bias=bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    g_flash = grads(flash_attention, q, k, v, causal=True, bias=bias,
                    block_q=bq, block_k=bk)
    g_ref = grads(reference_attention, q, k, v, causal=True, bias=bias)
    assert_grads_close(g_flash, g_ref)


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


@schedules
def test_alibi_slopes_parity(S, D, bq, bk):
    """In-kernel ALiBi (slopes operand, O(H) memory) vs the reference's
    materialized-bias formulation — fwd + bwd.  ALiBi wants the iotas in
    every tile, the unmasked ones too."""
    from deepspeed_tpu.ops.attention import alibi_bias, alibi_slopes
    q, k, v = make_qkv(B=2, S=S, H=4, D=D, seed=13)
    slopes = jnp.asarray(alibi_slopes(4))
    bias = alibi_bias(4, S, S)
    out = flash_attention(q, k, v, causal=True, alibi=slopes, block_q=bq,
                          block_k=bk)
    ref = reference_attention(q, k, v, causal=True, bias=bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    g_flash = grads(flash_attention, q, k, v, causal=True, alibi=slopes,
                    block_q=bq, block_k=bk)
    g_ref = grads(reference_attention, q, k, v, causal=True, bias=bias)
    assert_grads_close(g_flash, g_ref)


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
    # large divisible S keeps the tuned cap
    assert fa._block_sizes(1024, None, None) == (512, 512)
    assert fa._block_sizes(768, None, None) == (384, 384)
    # indivisible: fitted blocks exist but are not sublane-aligned
    bq, bk = fa._block_sizes(1000, None, None)
    assert 1000 % bq == 0 and 1000 % bk == 0
    assert not fa._blocks_lowerable(1000, bq, bk)
    # explicit block_q=/block_k= requests are clamped, never trusted
    assert fa._block_sizes(64, 256, 512) == (64, 64)
    assert fa._block_sizes(2048, 1024, 96) == (512, 64)
    # a dense bias is blocked along q in its lane dim
    assert fa._blocks_lowerable(1024, 512, 64, dense_bias=True)
    assert not fa._blocks_lowerable(1024, 64, 64, dense_bias=True)

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


def test_scale_folds_only_when_exact():
    """1/sqrt(64) is a power of two and rides on q; 1/sqrt(32) is not and
    stays a multiply on the float32 scores."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    assert fa._scale_folds(1.0 / np.sqrt(64)) and fa._scale_folds(1.0 / np.sqrt(16))
    assert not fa._scale_folds(1.0 / np.sqrt(32))
    assert not fa._scale_folds(1.0 / np.sqrt(128))


def test_ring_hop_statistics_shape():
    """What ring attention calls a hop: (o, lse [B, H, S]) from the forward
    body, gradients from the backward body against an lse and a delta of
    that shape, the unmasked hop exact."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    q, k, v = (x.transpose(0, 2, 1, 3) for x in make_qkv(B=1, S=256, H=2, D=64, seed=22))
    scale = 1.0 / np.sqrt(64)
    o, lse = fa.flash_block_fwd(q, k, v, None, None, causal=False, scale=scale,
                                bq=128, bk=128)
    assert lse.shape == (1, 2, 256) and lse.dtype == jnp.float32
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    np.testing.assert_allclose(lse, jax.nn.logsumexp(s, axis=-1), atol=2e-5, rtol=2e-5)
    do = jnp.ones_like(o)
    delta = jnp.sum(o * do, axis=-1)
    dq, dk, dv = fa.flash_block_bwd(q, k, v, do, lse, delta, causal=False,
                                    scale=scale, bq=128, bk=128)
    ref = jax.grad(lambda q, k, v: jnp.sum(jnp.einsum(
        "bhqk,bhkd->bhqd", jax.nn.softmax(
            jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale, axis=-1), v)),
        argnums=(0, 1, 2))(q, k, v)
    assert_grads_close((dq, dk, dv), ref)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_bench_rehearses(causal, capsys):
    """``tools/flash_kernel_bench.py`` at a toy shape through the
    interpreter: every pair that divides S gives a row with the three
    kernels' times, the pair ``_block_sizes`` takes is marked, and a
    rehearsal names no device metric."""
    import json
    from tools import flash_kernel_bench as bench
    argv = ["--rehearse", "--batch", "1", "--heads", "2", "--seq", "128",
            "--head-dim", "64", "--blocks", "64x64", "128x128", "96x96",
            "--repeats", "1"] + ([] if causal else ["--no-causal"])
    assert bench.main(argv) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["rehearsal"] and len(out["shapes"]) == 1
    rows = out["shapes"][0]["candidates"]
    assert [r["blocks"] for r in rows] == [[64, 64], [128, 128]]   # 96 divides no 128
    assert [r["taken"] for r in rows] == [False, True]
    for r in rows:
        assert set(r["jit_ms"]) == {"fwd", "dq", "dkv"} and not r["refused"]
        assert r["ms"] == {} and r["roofline_pct"] == {}
        assert ("scores_over_triangle" in r) == causal
    assert bench.scores_over_triangle("fwd", 1024, 256, 512) == pytest.approx(
        6 * 256 * 512 / 524800)
    assert bench.scores_over_triangle("dkv", 1024, 256, 256) == pytest.approx(
        10 * 65536 / 524800)
