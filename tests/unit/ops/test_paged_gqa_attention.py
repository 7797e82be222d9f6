"""The paged kernel over grouped K/V heads with an optional window
(``paged_gqa_attention``), through the Pallas interpreter, and the gather
reference's window, both against attention written out over each row's
LOGICAL keys: ``g`` in {1, 7}, with and without a window, ragged lengths, an
idle row, tables that are rings where there is a window; and at the ``Sq`` a
prompt chunk's rows hold (``paged_chunk_queries``: 32 at SmallThinker's
heads), where a row's queries span pages, tiles and the window's edge.  And
the layer's rule (``paged_layer_attention``): multi-head attention whose
heads are whole lane tiles (``H == Hkv``, D = 128: OLMoE's) takes the same
kernel at a group of ONE, with and without a prompt chunk; D = 64 keeps the
sliced layer and ``paged_attention``."""

import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import decode_attention as da

D, BS = 128, 8


def make(g, Hkv, window, lengths, Sq, MB, layers=2, seed=0, D=D):
    """An arena of ``layers`` layers in which layer 1 holds each row's live
    blocks (layer 0 holds noise: a kernel that reads the wrong layer fails),
    the tables, and the expected output from the logical keys."""
    rng = np.random.default_rng(seed)
    B, H, lanes = len(lengths), g * Hkv, Hkv * D
    pages = 1 + sum(-(-(n + Sq) // BS) for n in lengths)
    k_arena = rng.standard_normal((layers, pages, BS, lanes)).astype(np.float32)
    v_arena = rng.standard_normal((layers, pages, BS, lanes)).astype(np.float32)
    q = rng.standard_normal((B, Sq, H, D)).astype(np.float32)
    tables = np.zeros((B, MB), np.int32)
    want = np.zeros((B, Sq, H, D), np.float32)
    free = list(range(1, pages))
    rng.shuffle(free)
    for b, n in enumerate(lengths):
        if n == 0:                      # an idle row: all trash, any output
            continue
        T = n + Sq
        k = rng.standard_normal((T, Hkv, D)).astype(np.float32)
        v = rng.standard_normal((T, Hkv, D)).astype(np.float32)
        first = 0 if window is None else max(0, n - window + 1) // BS
        for blk in range(first, -(-T // BS)):
            phys = free.pop()
            tables[b, blk % MB if window else blk] = phys
            rows = slice(blk * BS, min((blk + 1) * BS, T))
            k_arena[1, phys, :rows.stop - rows.start] = k[rows].reshape(-1, lanes)
            v_arena[1, phys, :rows.stop - rows.start] = v[rows].reshape(-1, lanes)
        for s in range(Sq):
            t = n + s
            lo = 0 if window is None else max(0, t - window + 1)
            for h in range(H):
                sc = k[lo:t + 1, h // g] @ q[b, s, h] / np.sqrt(D)
                p = np.exp(sc - sc.max())
                want[b, s, h] = (p / p.sum()) @ v[lo:t + 1, h // g]
    return (jnp.asarray(q), jnp.asarray(k_arena), jnp.asarray(v_arena),
            jnp.asarray(tables), jnp.asarray(lengths, jnp.int32), want)


CASES = [  # g, window, Sq, lengths (0: an idle row)
    (1, None, 1, (150, 0, 7, 129)),
    (7, None, 1, (150, 0, 7, 129)),
    (1, 40, 1, (150, 0, 7, 39, 40, 41)),
    (7, 40, 1, (150, 0, 7, 39, 40, 41)),
    (7, 40, 3, (150, 38, 7)),
    (7, None, 3, (150, 7)),
    # a prompt chunk's row, 32 queries.  Window 100 in a ring of 18 pages of
    # 8, tiles of 16 pages: from 300 the row reads pages 25..41 (the ring
    # wraps twice), the last query's window starts inside the first tile,
    # and every key of the second tile is masked for the first 28 queries;
    # from 90 the first queries see every key and the last have lost some
    (7, 100, 32, (300, 90, 7)),
    (7, None, 32, (150, 7, 100)),
    (1, 100, 32, (300,)),
]


@pytest.mark.parametrize("g,window,Sq,lengths", CASES)
def test_kernel_and_reference_equal_attention_over_the_logical_keys(
        kernels, g, window, Sq, lengths):
    MB = 24 if window is None else -(-(window + Sq - 1) // BS) + 1
    q, ka, va, tables, lens, want = make(g, 2, window, lengths, Sq, MB)
    live = np.asarray(lengths) > 0
    ref = da.paged_attention_reference(q, ka[1], va[1], tables, lens,
                                       window=window)
    np.testing.assert_allclose(np.asarray(ref)[live], want[live], atol=2e-5)
    kernels("paged_gqa_attention")
    assert da.softmax_plan(2 * g, 2, D, BS, MB, 0, jnp.float32,
                           name="paged_gqa_attention").tile_pages > 0
    if Sq == 32:        # what the rule gives SmallThinker's heads and chunk
        assert da.softmax_plan(28, 4, D, 16, MB, 224, jnp.bfloat16,
                               window=window).chunk_queries == Sq
    out = da.paged_gqa_attention(q, ka, va, jnp.asarray(1), tables, lens,
                                 window=window)
    assert np.isfinite(np.asarray(out)).all()       # the idle row too
    np.testing.assert_allclose(np.asarray(out)[live], want[live], atol=2e-5)


def layer_step(Hkv, lengths, chunk, start, live, MB=24, seed=0):
    """The rows of a serve step's attention at ``H == Hkv``: a decode row a
    length of ``lengths`` (0: an idle slot, the trash block at position 0),
    then ``chunk`` rows that are consecutive queries of ONE sequence from
    position ``start`` (the same table a row; K and V of their own positions
    already in the pages, as the step scatters them before it attends), or,
    not ``live``, a chunk that carries nothing: all trash at position 0."""
    q, ka, va, tables, lens, _ = make(
        1, Hkv, None, tuple(lengths) + (start + max(chunk, 1) - 1,), 1, MB,
        seed=seed)
    rng = np.random.default_rng(seed + 1)
    qc = jnp.asarray(rng.standard_normal((chunk, 1, Hkv, D)), jnp.float32)
    row = tables[-1:] if live else jnp.zeros((1, MB), jnp.int32)
    at = start + jnp.arange(chunk, dtype=jnp.int32) if live else jnp.zeros(
        (chunk,), jnp.int32)
    return (jnp.concatenate([q[:-1], qc]), ka, va,
            jnp.concatenate([tables[:-1], jnp.repeat(row, chunk, axis=0)]),
            jnp.concatenate([lens[:-1], at]))


@pytest.mark.parametrize("Hkv,lengths,chunk,start,live", [
    (4, (150, 0, 7, 129), 0, 0, False),         # decode rows alone, one idle
    (4, (150, 0, 7, 129), 8, 37, True),         # and a chunk that spans pages
    (4, (150, 0, 7), 8, 0, False),              # the chunk's rows carry nothing
    (16, (33, 0, 130), 16, 120, True),          # OLMoE's 16 heads, a chunk across tiles
    (16, (0, 0), 16, 0, True),                  # every slot idle, a prompt's first chunk
], ids=["decode", "chunk", "idle-chunk", "olmoe-heads", "first-chunk"])
def test_multi_head_attention_of_whole_lane_tiles_is_a_group_of_one(
        kernels, monkeypatch, Hkv, lengths, chunk, start, live):
    """``paged_layer_attention`` at ``H == Hkv``, D = 128, no window and no
    bias: the arena WHOLE through ``paged_gqa_attention`` (a K/V head's one
    query the rows of its product), the chunk packed ``Sq`` queries a row,
    equal to the gather reference over the sliced layer a query a row."""
    q, ka, va, tables, lens = layer_step(Hkv, lengths, chunk, start, live)
    want = da.paged_attention_reference(q, ka[1], va[1], tables, lens)
    kernels("paged_gqa_attention")
    called, call = [], da._paged_gqa_call
    monkeypatch.setattr(da, "_paged_gqa_call", lambda q, *a: (
        called.append(q.shape[:2]) or call(q, *a)))
    out = da.paged_layer_attention(q, ka, va, jnp.asarray(1), tables, lens,
                                   chunk=chunk)
    plan = da.softmax_plan(Hkv, Hkv, D, BS, tables.shape[1], chunk, jnp.float32)
    Sq = chunk and plan.chunk_queries
    assert called == [(len(lengths), 1)] + ([(chunk // Sq, Sq)] if chunk else [])
    assert Sq == chunk              # the whole chunk one row at these sizes
    assert plan.tile_pages > 0
    carries = np.asarray(lens) > 0
    carries[len(lengths):] = live
    assert np.isfinite(np.asarray(out)).all()       # idle rows too
    np.testing.assert_allclose(np.asarray(out)[carries],
                               np.asarray(want)[carries], atol=2e-5)


@pytest.mark.parametrize("g,window,head_dim,alibi,kernel", [
    (1, None, 64, False, "mha"),        # GPT-2's heads: two share a lane tile
    (1, None, 128, False, "gqa"),       # OLMoE's: a head a lane tile
    (7, None, 128, False, "gqa"),
    (1, 40, 128, False, "gqa"),
    (1, None, 128, True, None),         # ALiBi: neither kernel, the gather
], ids=["mha-d64", "mha-d128", "grouped", "window", "mha-d128-alibi"])
def test_the_layer_rule(kernels, monkeypatch, g, window, head_dim, alibi, kernel):
    """``paged_layer_attention``: grouped K/V heads, a window, or heads that
    are whole lane tiles take the successor kernel and the arena whole;
    multi-head attention at D = 64 over every key keeps the layer slice and
    ``paged_attention`` (ROADMAP S1); a bias keeps the gather reference."""
    kernels("paged_gqa_attention", "paged_attention")
    seen = []
    monkeypatch.setattr(da, "_paged_gqa_call",
                        lambda *a: seen.append("gqa") or a[0])
    monkeypatch.setattr(da, "_paged_call",
                        lambda *a: seen.append("mha") or a[0])
    q, ka, va, tables, lens, _ = make(g, 2, window, (9,), 1, 8, D=head_dim)
    bias = jnp.zeros((1, 2 * g, 1, 8 * BS)) if alibi else None
    da.paged_layer_attention(q, ka, va, jnp.asarray(1), tables, lens,
                             bias=bias, window=window)
    assert seen == ([kernel] if kernel else [])
    plan = da.softmax_plan(2 * g, 2, head_dim, BS, 8, 0, jnp.float32, alibi, window)
    assert plan.kernel == {"mha": "paged_attention", "gqa": "paged_gqa_attention",
                           None: None}[kernel]
    assert bool(plan.tile_pages) == bool(kernel)


def test_a_window_layer_with_a_bias_is_refused():
    """No paged path masks a window under an additive bias (ALiBi): the rule
    refuses the pair and does not drop the window."""
    q, ka, va, tables, lens, _ = make(1, 2, 40, (9,), 1, 8)
    bias = jnp.zeros((q.shape[0], q.shape[2], 1, tables.shape[1] * ka.shape[2]))
    with pytest.raises(AssertionError, match="window layer with an additive bias"):
        da.paged_layer_attention(q, ka, va, jnp.asarray(1), tables, lens,
                                 bias=bias, window=40)
