"""Paged (block-table) decode attention: reference parity, Pallas-interpret
parity, masking of stale arena contents, and the kernel selection policy."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import decode_attention as da
from deepspeed_tpu.ops.pallas.decode_attention import (
    _kernel_wanted, decode_attention_reference, paged_attention,
    paged_attention_reference)


@pytest.fixture
def kernel_calls(monkeypatch):
    """Force the kernel on and count how often dispatch reaches it."""
    monkeypatch.setenv("DST_PALLAS_PAGED", "1")
    calls = []
    real = da._paged_call
    monkeypatch.setattr(da, "_paged_call",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    return calls


def make_paged(B=2, Sq=1, H=8, D=16, Hkv=None, NB=24, BS=8, MB=8, seed=0,
               length=20, dtype=np.float32):
    """Random arena (pages ``[NB, BS, Hkv*D]``, heads folded into lanes) +
    per-row tables mapping logical block j to a distinct physical block."""
    Hkv = Hkv or H
    rng = np.random.default_rng(seed)
    k_pages = rng.standard_normal((NB, BS, Hkv * D)).astype(np.float32)
    v_pages = rng.standard_normal((NB, BS, Hkv * D)).astype(np.float32)
    tables = np.zeros((B, MB), np.int32)
    free = list(range(1, NB))
    rng.shuffle(free)
    for b in range(B):
        for j in range(MB):
            tables[b, j] = free.pop()
    q = rng.standard_normal((B, Sq, H, D)).astype(np.float32)
    lengths = np.full((B,), length, np.int32)
    return (jnp.asarray(q, dtype), jnp.asarray(k_pages, dtype),
            jnp.asarray(v_pages, dtype), jnp.asarray(tables),
            jnp.asarray(lengths))


def test_reference_matches_dense_cache():
    """Gathering pages through the table and running dense full-cache
    attention must equal the paged reference exactly."""
    q, kp, vp, tables, lengths = make_paged(Sq=1, length=20)
    B, Sq, H, D = q.shape
    T = tables.shape[1] * kp.shape[1]
    ck = kp[tables].reshape(B, T, H * D)
    cv = vp[tables].reshape(B, T, H * D)
    ref = decode_attention_reference(q, ck, cv, jnp.asarray(20, jnp.int32))
    out = paged_attention_reference(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)


def test_reference_gqa_matches_expanded_heads():
    q, kp, vp, tables, lengths = make_paged(H=8, Hkv=2, length=13)
    B, Sq, H, D = q.shape
    T = tables.shape[1] * kp.shape[1]
    # expand 2 kv heads to 8 query heads and use the dense MHA reference
    ck = jnp.repeat(kp[tables].reshape(B, T, 2, D), 4, axis=2).reshape(B, T, H * D)
    cv = jnp.repeat(vp[tables].reshape(B, T, 2, D), 4, axis=2).reshape(B, T, H * D)
    ref = decode_attention_reference(q, ck, cv, jnp.asarray(13, jnp.int32))
    out = paged_attention_reference(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)


def test_stale_arena_contents_masked():
    """Positions past ``lengths`` (trash-padded table slots, stale block
    tails from a previous owner) must not change the output."""
    q, kp, vp, tables, lengths = make_paged(length=11)
    out = paged_attention_reference(q, kp, vp, tables, lengths)
    BS = kp.shape[1]
    # clobber everything past logical position lengths+Sq-1 = 11
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    for b in range(tables.shape[0]):
        for j in range(tables.shape[1]):
            for o in range(BS):
                if j * BS + o > 11:
                    kp2[tables[b, j], o] = 1e3
                    vp2[tables[b, j], o] = -1e3
    kp2[0] = 7e3                                    # trash block is never read
    out2 = paged_attention_reference(q, jnp.asarray(kp2), jnp.asarray(vp2),
                                     tables, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("Sq,length", [(1, 20), (1, 0), (4, 9)])
def test_pallas_kernel_parity(kernel_calls, Sq, length):
    """Forced-on Pallas paged kernel (interpret mode on CPU) vs the jnp
    reference, decode and chunked-prefill shapes, per-row lengths."""
    q, kp, vp, tables, lengths = make_paged(Sq=Sq, length=length, seed=3)
    lengths = jnp.asarray([length, max(0, length - 5)], jnp.int32)
    ref = paged_attention_reference(q, kp, vp, tables, lengths)
    out = paged_attention(q, kp, vp, tables, lengths)
    assert kernel_calls
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("H,kernel", [(12, True), (25, False)])
def test_gpt2_head_shapes(kernel_calls, H, kernel):
    """GPT-2 head shapes, D=64 bf16, 16-row pages, a prefill chunk that
    crosses a page: 12 heads run the kernel; gpt2-xl's 25 (1600 lanes, not
    a multiple of 128) are routed to the gather reference by the gate."""
    q, kp, vp, tables, lengths = make_paged(
        Sq=4, H=H, D=64, BS=16, MB=4, length=29, seed=7, dtype=jnp.bfloat16)
    out = paged_attention(q, kp, vp, tables, lengths)
    assert bool(kernel_calls) == kernel
    ref = paged_attention_reference(
        q.astype(jnp.float32), kp.astype(jnp.float32),
        vp.astype(jnp.float32), tables, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("Sq,length", [(4, 62), (8, 57)])
def test_pallas_kernel_padded_chunk_overhang(kernel_calls, Sq, length):
    """A padded prefill chunk can push ``length + Sq`` past the table
    capacity ``MB*BS`` (prefill_chunk not dividing the tail): the kernel's
    static MB-bound loop must keep every ``tbl_ref`` read inside the row —
    the old data-dependent trip count ran ``ceil((length+Sq)/BS) > MB``
    iterations and gathered a garbage physical block id — and still match
    the reference exactly."""
    q, kp, vp, tables, lengths = make_paged(Sq=Sq, length=length, seed=5)
    MB, BS = tables.shape[1], kp.shape[1]
    assert length + Sq > MB * BS          # the overhang this test is about
    ref = paged_attention_reference(q, kp, vp, tables, lengths)
    out = paged_attention(q, kp, vp, tables, lengths)
    assert kernel_calls
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_dispatch_takes_reference_on_bias_and_gqa(kernel_calls):
    """Shapes outside the kernel (ALiBi bias, grouped heads) must route to
    the reference even when the kernel is forced on."""
    q, kp, vp, tables, lengths = make_paged(H=8, Hkv=2, length=10)
    out = paged_attention(q, kp, vp, tables, lengths)     # GQA -> reference
    ref = paged_attention_reference(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref))
    q2, kp2, vp2, tables2, lengths2 = make_paged(length=10)
    T = tables2.shape[1] * kp2.shape[1]
    bias = jnp.zeros((2, 8, 1, T), jnp.float32)
    out2 = paged_attention(q2, kp2, vp2, tables2, lengths2, bias=bias)
    ref2 = paged_attention_reference(q2, kp2, vp2, tables2, lengths2,
                                     bias=bias)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2))
    assert not kernel_calls


def test_env_policy_default_on_with_opt_out(monkeypatch):
    """Unset, the kernels are wanted on TPU only (on CPU only the
    interpreter exists); ``=0`` opts out, ``=1`` forces them on."""
    for var in ("DST_PALLAS_DECODE", "DST_PALLAS_PAGED"):
        monkeypatch.delenv(var, raising=False)
        assert _kernel_wanted(var) == (jax.default_backend() == "tpu")
        monkeypatch.setenv(var, "0")
        assert _kernel_wanted(var) is False
        monkeypatch.setenv(var, "1")
        assert _kernel_wanted(var) is True
