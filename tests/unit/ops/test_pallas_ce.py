"""Fused Pallas cross-entropy vs the chunked XLA reference.

Parity contract (module docstring of ``ops/pallas/cross_entropy.py``):
fp32 forward is BITWISE equal to the reference path — the kernel performs
literally the same op sequence (f32 dot, same -1e9 vocab mask, max,
exp-shift, sum, log, slice-then-mean) — including the multi-vocab-block
online-softmax sweep; gradients agree to a few ulp (the backward
recomputes scores rather than saving them).  Also covers the block chooser
(``ce_blocks``: the tile a grid step works on, from the call's shapes), the
shape/mesh support gate and the ``chunked_cross_entropy`` wiring."""

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


# vocabs of 128 x 3 x k and rows past one row block, so that the tiles the
# chooser takes at GPT-2's padded vocab are the ones these run: one block of
# 1,152; 3 of 896 (2,688 = 128 x 3 x 7); 131 of 384 under 3 row blocks of
# 1,024 (50,304 = 128 x 3 x 131, the cell's own vocab and mask)
TILED = [
    # N, V, vocab_size, bias, (bn, bv), grid
    (300, 1152, 1100, True, (256, 1152), (2, 1)),
    (2100, 2688, 2688, False, (1024, 896), (3, 3)),
    (2100, 2688, 2600, True, (1024, 896), (3, 3)),
    (2100, 50304, 50257, True, (1024, 384), (3, 131)),
]
TILED_IDS = [f"N{c[0]}-V{c[1]}-mask{c[2]}-bias{int(c[3])}" for c in TILED]


def assert_grads_match(x, head, head_b, labels, vocab_size):
    """dx, dhead (and dbias) of the fused loss against the XLA path's, in
    float32: same shapes and dtypes, equal to summation order."""
    def loss(fn):
        return lambda x, h, b: fn(x, h, labels, vocab_size, head_b=b)

    argnums = (0, 1) if head_b is None else (0, 1, 2)
    g_f = jax.grad(loss(pce.fused_cross_entropy), argnums)(x, head, head_b)
    g_r = jax.grad(loss(reference_ce), argnums)(x, head, head_b)
    for a, b, name in zip(g_f, g_r, ("dx", "dhead", "dbias")):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, atol=1e-7, rtol=1e-5,
                                   err_msg=f"{name} mismatch")
    return g_f


def tiled_inputs(N, V, vocab_size, bias, blocks, grid):
    x, head, labels, head_b = make_inputs(N=N, V=V, bias=bias, seed=N + V)
    assert pce.ce_blocks(N, x.shape[1], V, x.dtype) == blocks
    bn, bv = blocks
    assert (-(-N // bn), V // bv) == grid
    return x, head, jnp.minimum(labels, vocab_size - 1), head_b


@pytest.mark.parametrize("case", TILED, ids=TILED_IDS)
def test_forward_parity_fp32_at_chosen_tiles(case):
    """Several row blocks and vocab blocks that are no power of two: the
    online-softmax sweep differs from one logsumexp by rescale rounding."""
    x, head, labels, head_b = tiled_inputs(*case)
    vocab_size = case[2]
    fused = pce.fused_cross_entropy(x, head, labels, vocab_size, head_b=head_b)
    ref = reference_ce(x, head, labels, vocab_size, head_b=head_b)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               atol=0, rtol=1e-6)


@pytest.mark.parametrize("case", TILED, ids=TILED_IDS)
def test_backward_parity_fp32_at_chosen_tiles(case):
    """ONE kernel feeds both gradients from each score tile: dx sums over
    the vocab blocks in the scratch that spans the rows, dhead and dbias
    over the row blocks of a vocab block, the padded rows of the last row
    block adding nothing."""
    x, head, labels, head_b = tiled_inputs(*case)
    vocab_size = case[2]
    dhead = assert_grads_match(x, head, head_b, labels, vocab_size)[1]
    assert not np.asarray(dhead[vocab_size:]).any()   # masked columns: no gradient


@pytest.mark.parametrize("N,V,vocab_size,bias", [
    (200, 256, 256, False),
    (1500, 1152, 1100, True),     # 2 row blocks of 1,024, masked, biased
])
def test_bf16_tolerance(N, V, vocab_size, bias):
    """bf16 inputs: the kernel's scores, softmax and ``ds`` are f32 like the
    reference's, and each matmul takes bf16 operands like the XLA path's own
    backward (``ds`` rounded once): the loss and BOTH gradients agree with
    the XLA path's to bf16 rounding, and leave in bf16."""
    x, head, labels, head_b = make_inputs(N=N, V=V, dtype=jnp.bfloat16,
                                          bias=bias)
    labels = jnp.minimum(labels, vocab_size - 1)
    fused = pce.fused_cross_entropy(x, head, labels, vocab_size, head_b=head_b)
    ref = reference_ce(x, head, labels, vocab_size, head_b=head_b)
    np.testing.assert_allclose(np.float32(fused), np.float32(ref),
                               atol=2e-2, rtol=2e-2)

    def loss(fn):
        return lambda x, h: fn(x, h, labels, vocab_size, head_b=head_b)

    g_f = jax.grad(loss(pce.fused_cross_entropy), argnums=(0, 1))(x, head)
    g_r = jax.grad(loss(reference_ce), argnums=(0, 1))(x, head)
    for a, b, name in zip(g_f, g_r, ("dx", "dhead")):
        assert a.dtype == b.dtype == jnp.bfloat16
        a, b = np.float32(a), np.float32(b)
        # to a hundredth of the gradient's own scale: a bf16 step is 1/256
        np.testing.assert_allclose(a, b, atol=1e-2 * np.abs(b).max(),
                                   rtol=2e-2, err_msg=f"{name} mismatch")


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
BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("N,E,V,dtype,blocks", [
    (8192, 768, 50304, BF16, (1024, 384)),   # the 124M train cell: 8 x 131 steps
    (4096, 768, 50304, BF16, (1024, 384)),
    (8192, 768, 50304, F32, (1024, 384)),
    (8192, 1600, 50304, BF16, (1024, 384)),  # gpt2-xl on one device
    (8192, 1600, 50304, F32, (512, 384)),
    (8192, 2048, 50304, BF16, (512, 384)),   # OLMoE's width: a shorter row block
    (8192, 768, 65536, BF16, (256, 2048)),   # a power-of-two vocab keeps 2,048
    (8192, 768, 32768, F32, (512, 1024)),    # in float32 2,048 leave no room for 128 rows
    (2100, 64, 2688, F32, (1024, 896)),      # 128 x 3 x 7: 896, not 384
    (300, 64, 1152, F32, (256, 1152)),       # rows pad to 384: 256 is the most
    (200, 64, 128, F32, (256, 128)),         # one block each way
    (64, 64, 256, F32, (128, 256)),
    (8192, 4096, 50304, BF16, (256, 384)),   # a width that leaves room for little
    (8192, 8192, 50304, F32, None),          # and one for nothing
    (200, 64, 100, F32, None),               # no lane-multiple block
])
def test_blocks_from_the_shape(N, E, V, dtype, blocks):
    """THE rule, as a table: the widest lane-multiple block that divides the
    vocab, then the tallest row block not above the padded rows, under the
    VMEM budget.  Nothing but the call's shapes and dtype goes in."""
    assert pce.ce_blocks(N, E, V, dtype) == blocks
    if blocks is None:
        return
    bn, bv = blocks
    assert V % bv == 0 and bv % 128 == 0 and bv <= 2048
    assert bn in (1024, 512, 256, 128) and bn <= -(-N // 128) * 128
    itemsize = np.dtype(dtype).itemsize
    assert pce.ce_step_bytes(bn, bv, E, itemsize) <= pce._VMEM_BUDGET_BYTES
    assert pce._VMEM_BUDGET_BYTES < pce._VMEM_LIMIT_BYTES
    # no wider vocab block and no taller row block would have fitted
    wider = [b for b in range(bv + 128, min(V, 2048) + 1, 128) if V % b == 0]
    assert all(pce.ce_step_bytes(128, b, E, itemsize) > pce._VMEM_BUDGET_BYTES
               for b in wider)
    if bn < 1024 and 2 * bn <= -(-N // 128) * 128:
        assert (pce.ce_step_bytes(2 * bn, bv, E, itemsize)
                > pce._VMEM_BUDGET_BYTES)


@pytest.mark.parametrize("N,E,bn,sweeps,blocks", [
    (8192, 768, 1024, 1, 8),     # the train cell: all rows' dx in 24 MiB
    (16384, 768, 1024, 2, 8),
    (8192, 1600, 1024, 2, 4),
    (8192, 2048, 512, 2, 8),
    (7168, 1600, 1024, 2, 4),    # 7 blocks in sweeps of at most 5: padded to 8
    (200, 64, 256, 1, 1),
])
def test_row_sweeps_from_the_shape(N, E, bn, sweeps, blocks):
    """The backward holds a sweep's float32 dx in VMEM: as few sweeps as
    the accumulator's budget allows, of equal length, covering the rows."""
    assert pce.ce_row_sweeps(N, E, bn) == (sweeps, blocks)
    assert N <= sweeps * blocks * bn < N + sweeps * bn    # padding: under a block a sweep
    assert blocks * bn * E * 4 <= pce._DX_ACC_BYTES
    if sweeps > 1:   # one sweep fewer would not have fitted
        longer = -(-(-(-N // bn)) // (sweeps - 1))      # row blocks a sweep then
        assert longer * bn * E * 4 > pce._DX_ACC_BYTES


@pytest.mark.parametrize("acc_blocks,sweeps", [(2, 2), (1, 3)])
def test_backward_parity_over_several_sweeps(monkeypatch, acc_blocks, sweeps):
    """Rows past what the dx accumulator holds make further sweeps, each
    with a partial dhead and dbias that are summed outside; 3 row blocks in
    sweeps of 2 pad the rows to 4 blocks, whose extra block adds nothing."""
    N, V, vocab_size = 2100, 2688, 2600
    x, head, labels, head_b = tiled_inputs(N, V, vocab_size, True,
                                           (1024, 896), (3, 3))
    monkeypatch.setattr(pce, "_DX_ACC_BYTES", acc_blocks * 1024 * 64 * 4)
    assert pce.ce_row_sweeps(N, 64, 1024)[0] == sweeps
    fused = pce.fused_cross_entropy(x, head, labels, vocab_size, head_b=head_b)
    ref = reference_ce(x, head, labels, vocab_size, head_b=head_b)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref), rtol=1e-6)
    assert_grads_match(x, head, head_b, labels, vocab_size)


def test_supported_gate():
    assert pce.ce_supported(64, 64, 256)
    assert not pce.ce_supported(64, 64, 100)    # no 128-multiple block
    assert pce.ce_supported(8192, 768, 50304)   # GPT-2 padded vocab
    assert not pce.ce_supported(8192, 16384, 50304)   # no block fits VMEM


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
