"""Paged (block-table) decode attention: reference parity, Pallas-interpret
parity, masking of stale arena contents, and the kernel selection policy."""

import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import decode_attention as da
from deepspeed_tpu.ops.pallas.decode_attention import (
    decode_attention_reference, paged_attention, paged_attention_reference,
    paged_tile_pages)


@pytest.fixture
def kernel_calls(monkeypatch, kernels):
    """Force the kernel on and count how often dispatch reaches it."""
    kernels("paged_attention")
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


# the tiled kernel's borders: 16-row pages, a table of 20 (two whole tiles of
# G = 8 pages and a short one of 4), two D=64 heads sharing one lane slice
T_BS, T_MB, T_H, T_D = 16, 20, 2, 64
T_G = paged_tile_pages(T_BS, T_MB, 1, T_H * T_D, np.float32)
TILE = T_G * T_BS
# resident lengths on and round the page and tile borders, and the rows by
# which the queries' end passes the table's last row: "full" fills the table
# exactly, "over" is a padded chunk that spills 5 rows past it
BORDERS = {"0": 0, "15": 15, "16": 16, "17": 17, "tile-1": TILE - 1,
           "tile": TILE, "tile+1": TILE + 1}
PAST_TABLE = {"full": 0, "over": 5}


@pytest.mark.parametrize("Sq", [1, 64])
@pytest.mark.parametrize("border", [*BORDERS, *PAST_TABLE])
def test_tiled_kernel_parity_at_page_and_tile_borders(kernel_calls, border,
                                                      Sq):
    """The tiled kernel (interpreter) against the gather reference: a row
    at the border under test beside an idle slot (length 0, every table
    entry the trash block), a short row (one page at Sq=1) and a full row,
    with EVERY arena block that no table lists filled with NaN — a page
    slot of a tile that was not fetched must not reach ``p @ v`` as
    whatever the buffer held."""
    assert T_G == 8 and T_MB % T_G != 0
    full = T_MB * T_BS - Sq
    length = (BORDERS[border] if border in BORDERS
              else full + PAST_TABLE[border])
    lengths = np.asarray([length, 0, 5, full], np.int32)
    q, kp, vp, tables, _ = make_paged(B=4, Sq=Sq, H=T_H, D=T_D, NB=96,
                                      BS=T_BS, MB=T_MB, seed=11)
    tables = np.asarray(tables).copy()
    live = np.minimum(-(-(lengths + Sq) // T_BS), T_MB)
    for b in range(4):
        tables[b, live[b]:] = 0               # the engine pads with trash
    tables[1] = 0                             # idle slot
    unlisted = np.setdiff1d(np.arange(kp.shape[0]), tables.ravel())
    assert unlisted.size > 10 and 0 not in unlisted
    kp = kp.at[unlisted].set(np.nan)
    vp = vp.at[unlisted].set(np.nan)
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
    ref = paged_attention_reference(q, kp, vp, tables, lengths)
    out = paged_attention(q, kp, vp, tables, lengths)
    assert kernel_calls
    assert np.isfinite(np.asarray(ref)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_tile_pages_follow_shapes_only():
    """``G`` is a function of the static shapes: about 128 rows a tile,
    never more pages than the table has, fewer where four tile buffers
    would not fit the VMEM budget; the same for the prefill chunk and for
    decode; and a table width that ``G`` does not divide is fine (the
    parity test above runs 20 pages as 8 + 8 + 4)."""
    bf16 = jnp.bfloat16
    assert paged_tile_pages(16, 64, 1, 768, bf16) == 8       # gpt2 decode
    assert paged_tile_pages(16, 64, 64, 768, bf16) == 8      # gpt2 chunk
    assert paged_tile_pages(32, 32, 1, 768, bf16) == 4
    assert paged_tile_pages(16, 64, 1, 1280, bf16) == 8      # gpt2-large
    assert paged_tile_pages(128, 8, 1, 768, bf16) == 1       # a page is a tile
    assert paged_tile_pages(256, 8, 1, 768, bf16) == 1
    assert paged_tile_pages(16, 3, 1, 768, bf16) == 3        # short table
    assert paged_tile_pages(8, 20, 1, 128, np.float32) == 16
    # the VMEM budget: 4 buffers x G*BS rows x lanes x itemsize
    for lanes in (768, 8192, 32768):
        G = paged_tile_pages(16, 64, 1, lanes, np.float32)
        assert G == 1 or 4 * G * 16 * lanes * 4 <= da._TILE_VMEM_BYTES
    assert paged_tile_pages(16, 64, 1, 32768, np.float32) < 8


def test_dispatch_reports_its_tile(kernel_calls):
    """What the dispatch reports (and the serving engine's stats carry):
    ``G`` where the kernel runs, 0 where the einsum does."""
    tile = lambda H, Hkv, bias=False: da.softmax_plan(
        H, Hkv, 64, 16, 64, 0, jnp.bfloat16, bias, name="paged_attention").tile_pages
    assert tile(12, 12) == 8
    assert tile(12, 12, bias=True) == 0
    assert tile(25, 25) == 0   # xl
    assert tile(8, 2) == 0     # GQA


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
