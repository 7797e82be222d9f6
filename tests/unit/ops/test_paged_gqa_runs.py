"""``paged_gqa_attention`` with the flags of ``paged_tile_runs``: a tile of
consecutive live pages is one copy an operand, any other tile a copy a page,
and the flags (and the larger tile they bring) change NOTHING in what comes
out: equal to the call without them to the bit.  The tables are those of
``test_paged_mla_runs.py``: the same 32 pages of 16 keys a tile.  A window
group's ring is read the same way, its tiles cut on the runs' boundaries."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import decode_attention as da
from tests.unit.ops.test_paged_mla_runs import (
    BS, G, MB, NB, runs_of, tables_and_lengths)

D, Hkv = 128, 2                                   # 256 lanes a page


def flags_of(tables, g, dtype=jnp.float32):
    """The flags of ``tables`` as a step works them out for a full group."""
    plan = da.softmax_plan(g * Hkv, Hkv, D, BS, tables.shape[1], 0, dtype)
    return plan.run_pages, plan.tile_runs(jnp.asarray(tables), NB)


def arenas(rng, untouched=(), layers=2):
    k, v = (rng.standard_normal((layers, NB, BS, Hkv * D)).astype(np.float32)
            for _ in range(2))
    clean = k.copy(), v.copy()
    for a, c in zip((k, v), clean):
        a[:, untouched] = np.nan          # unwritten garbage past the lengths
        c[:, untouched] = 0.0
    return (jnp.asarray(k), jnp.asarray(v)), clean


WANT_FLAGS = {"runs": [[1, 1, 1]] * 3,
              "mixed": [[1, 0, 1], [0, 1, 1], [0, 0, 0], [0, 0, 0]],
              "broken": [[0, 1, 1], [1, 0, 1], [0, 1, 1]],
              "earmarked_tail": [[1, 0, 0], [1, 1, 1]]}


@pytest.mark.parametrize("g", [1, 4], ids=["group_of_one", "grouped"])
@pytest.mark.parametrize("Sq", [1, 16])
@pytest.mark.parametrize("case", list(WANT_FLAGS))
def test_with_flags_equals_without_to_the_bit(kernels, case, Sq, g):
    """Runs aligned, unaligned and at the arena's last pages; runs beside
    scattered tiles and an idle row; runs broken by a swap, a foreign page, a
    falling order; a run whose live pages end inside it and a table that
    lists pages the row has not reached (NaN there: nothing may fetch them)."""
    kernels("paged_gqa_attention")
    rng = np.random.default_rng(5)
    tables, lengths, untouched = tables_and_lengths(case, Sq, rng)
    (ka, va), (kc, vc) = arenas(rng, untouched)
    q = jnp.asarray(rng.standard_normal((len(tables), Sq, g * Hkv, D)), jnp.float32)
    pages, flags = flags_of(tables, g)
    assert pages == G and da.paged_tile_pages(BS, MB, Sq, Hkv * D, jnp.float32) == 8
    assert np.asarray(flags).tolist() == WANT_FLAGS[case]
    call = lambda runs: jax.jit(lambda *a: da.paged_gqa_attention(
        *a, tile_runs=runs))(q, ka, va, jnp.int32(1), jnp.asarray(tables),
                             jnp.asarray(lengths))
    got, plain = call(flags), call(None)
    want = da.paged_attention_reference(q, jnp.asarray(kc[1]), jnp.asarray(vc[1]),
                                        jnp.asarray(tables), jnp.asarray(lengths))
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - plain).max()) == 0.0
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("g", [1, 4], ids=["group_of_one", "grouped"])
@pytest.mark.parametrize("runs", [True, False])
def test_the_layers_call_cuts_the_flags_as_it_cuts_the_tables(kernels, runs, g, dtype):
    """What the step calls: rows of one query, the last ``chunk`` a prompt
    chunk of one sequence attended ``Sq`` queries a row; the flags, worked out
    once for all rows, go with the tables through ``paged_layer_attention``
    and give the call without them to the bit (bf16 too: the sums are in the
    same order, 128 keys an update)."""
    kernels("paged_gqa_attention")
    rng = np.random.default_rng(3)
    chunk, slots = 32, 3
    pages = da.softmax_plan(g * Hkv, Hkv, D, BS, MB, 0, dtype).run_pages
    assert pages == G
    table = (runs_of(G, 4 * G, 2 * G) if runs
             else rng.permutation(np.arange(1, NB))[:MB])
    decode = np.stack([runs_of(5 * G, 6 * G, 3 * G),
                       rng.permutation(np.arange(1, NB))[:MB], np.zeros(MB, int)])
    tables = jnp.asarray(np.concatenate([decode, np.tile(table, (chunk, 1))]),
                         jnp.int32)
    start = G * BS + 11                      # the chunk starts past a whole tile
    lengths = jnp.asarray([2 * G * BS + 3, 77, 0] + list(range(start, start + chunk)),
                          jnp.int32)
    ka, va = (jnp.asarray(rng.standard_normal((2, NB, BS, Hkv * D)), dtype)
              for _ in range(2))
    q = jnp.asarray(rng.standard_normal((slots + chunk, 1, g * Hkv, D)), dtype)
    flags = da.paged_tile_runs(tables, NB, pages)
    assert flags.shape == (slots + chunk, MB // G)
    assert flags[slots:].tolist() == [[int(runs)] * 3] * chunk
    call = lambda runs: jax.jit(lambda *a: da.paged_layer_attention(
        *a, chunk=chunk, tile_runs=runs))(q, ka, va, jnp.int32(0), tables, lengths)
    got, plain = call(flags), call(None)
    assert float(jnp.abs(got.astype(jnp.float32)
                         - plain.astype(jnp.float32)).max()) == 0.0
    want = da.paged_attention_reference(q, ka[0], va[0], tables, lengths)
    assert float(jnp.abs((got - want).astype(jnp.float32)).max()) < (
        2e-5 if dtype == jnp.float32 else 0.05)


@pytest.mark.parametrize("call", ["window", "sparse", "full"])
def test_a_call_without_flags_is_the_program_that_copies_page_by_page(kernels, call):
    """The pages a query chose and any caller that hands no flags, over a
    window group's ring too, trace the kernel they always traced: the tables
    its only SMEM blocks, the arena with its pages a dimension, a K and a V
    copy a page in each of the three places a tile is started or waited for;
    with flags a run's one copy stands beside each of them, in a ring as in a
    table that only grows."""
    kernels("paged_gqa_attention", "paged_sparse_attention")
    g, rows = 4, 3
    q = jnp.zeros((rows, 1, g * Hkv, D), jnp.float32)
    arena = jnp.zeros((2, NB, BS, Hkv * D), jnp.float32)
    tables, lengths = jnp.zeros((rows, MB), jnp.int32), jnp.zeros((rows,), jnp.int32)
    if call == "sparse":
        q, arena = q[:, :, :g], arena[..., :D]
        fn = lambda *a: da.paged_sparse_attention(*a)
    else:
        window = 40 if call == "window" else None
        fn = lambda *a: da.paged_layer_attention(*a, window=window)
    text = str(jax.make_jaxpr(fn)(q, arena, arena, jnp.int32(0), tables, lengths))
    assert text.count("dma_start") == 4 and text.count("dma_wait") == 2
    assert f"f32[2,{NB},{BS}," in text and f"f32[2,{NB * BS}," not in text
    if call != "sparse":
        flags = da.paged_tile_runs(tables, NB, G)
        text = str(jax.make_jaxpr(lambda *a: da.paged_layer_attention(
            *a, window=window, tile_runs=flags))(
                q, arena, arena, jnp.int32(0), tables, lengths))
        assert text.count("dma_start") == 8 and text.count("dma_wait") == 4
        assert f"f32[2,{NB * BS}," in text


def test_a_window_group_asks_for_runs_and_chosen_pages_and_the_reference_do_not(kernels):
    plan = lambda H=4 * Hkv, Hkv=Hkv, D=D, MB=MB, **rule: da.softmax_plan(
        H, Hkv, D, BS, MB, 0, jnp.bfloat16, **rule)
    run_pages = lambda *a, **rule: plan(*a, **rule).run_pages
    assert run_pages() == run_pages(window=64) == 0         # the CPU's rule
    kernels("paged_gqa_attention", "paged_sparse_attention")
    assert run_pages() == G
    assert run_pages(window=64) == G                        # the same tile
    assert run_pages(bias=True) == 0
    assert run_pages(12, 12, 64, 64) == 0                   # D = 64
    assert da.chosen_plan(Hkv, 4, D, BS, MB, jnp.bfloat16).run_pages == 0
    assert da.paged_tile_runs(jnp.zeros((2, MB), jnp.int32), NB, 0) is None
    # flags of a ring that is a whole number of runs wide, none of any other
    ring = lambda MB: plan(MB=MB, window=64).tile_runs(jnp.zeros((2, MB), jnp.int32), NB)
    assert ring(MB).shape == (2, MB // G) and ring(MB + 8) is None
    assert plan(MB=MB + 8).tile_runs(jnp.zeros((2, MB + 8), jnp.int32), NB).shape == (2, 4)


@pytest.mark.parametrize("BS_,lanes,MB_,attend,copy", [
    (64, 256, 256, 4, 8),        # ZAYA1-8B: 256 keys an update, 512 a copy
    (16, 2048, 256, 8, 8),       # OLMoE: 128 keys are 1 MiB of K and V already
    (16, 512, 1024, 8, 32),      # SmallThinker: 128 and 512
    (16, 512, 12, 8, 8),         # a table under two tiles keeps the attend's
], ids=["zaya", "olmoe", "smallthinker", "narrow"])
def test_the_copys_tile_is_whole_attend_steps_inside_the_budget(
        BS_, lanes, MB_, attend, copy):
    assert da.paged_tile_pages(BS_, MB_, 1, lanes, jnp.bfloat16) == attend
    G_ = da.paged_run_tile_pages(BS_, MB_, lanes, jnp.bfloat16)
    assert G_ == copy and G_ % attend == 0
    assert G_ == attend or G_ * BS_ <= da._RUN_TILE_ROWS
    assert G_ == attend or 2 * G_ * BS_ * lanes * 2 <= da._RUN_TILE_BYTES
    assert 4 * G_ * BS_ * lanes * 2 <= da._TILE_VMEM_BYTES


@pytest.mark.parametrize("Sq", [1, 16])
def test_what_comes_out_does_not_depend_on_how_many_keys_a_copy_brings(
        kernels, monkeypatch, Sq):
    """Tiles of 512, 256 and 128 keys under flags, and no flags at all: the
    attend keeps its 128 keys an update, so all four are equal to the bit."""
    kernels("paged_gqa_attention")
    rng = np.random.default_rng(11)
    tables, lengths, _ = tables_and_lengths("mixed", Sq, rng)
    ka, va = (jnp.asarray(rng.standard_normal((2, NB, BS, Hkv * D)), jnp.bfloat16)
              for _ in range(2))
    q = jnp.asarray(rng.standard_normal((len(tables), Sq, 4 * Hkv, D)), jnp.bfloat16)
    call = lambda runs: jax.jit(lambda *a: da.paged_gqa_attention(
        *a, tile_runs=runs))(q, ka, va, jnp.int32(1), jnp.asarray(tables),
                             jnp.asarray(lengths)).astype(jnp.float32)
    plain = call(None)
    for rows in (512, 256, 128):
        monkeypatch.setattr(da, "_RUN_TILE_ROWS", rows)
        pages, flags = flags_of(tables, 4, jnp.bfloat16)
        assert pages == rows // BS and flags.shape[1] == -(-MB // pages)
        assert float(jnp.abs(call(flags) - plain).max()) == 0.0


# --------------------------------------------------------------------------- #
# A window group's RING with runs.  Window 1000 keys under a ring of 96 pages,
# three runs of 32: what ``serving/kv_cache.py`` lays down (logical block ``b``
# in column ``b % 96``, a run kept until its last key is out of the window).
# --------------------------------------------------------------------------- #
WINDOW = 1000
NAN_PAGES = [1, 2, 3]         # what a column the row does not hold points at


def ring_row(length, Sq, bases, spoil=None):
    """A row's ring: the logical tiles from the run of the window's first
    page to the row's newest page lie at physical ``bases`` in turn, the
    other columns point at pages nothing may fetch.  ``spoil(pages)`` changes
    the ring's pages in place, logical block by logical block.
    -> (table [MB], the window's first page, the first page held)."""
    p0 = max(length - (WINDOW - 1), 0) // BS
    a0, newest = p0 - p0 % G, (length + Sq - 1) // BS
    assert newest + 1 - a0 <= MB
    pages = {}
    for base, T in zip(bases, range(a0 // G, newest // G + 1)):
        for j in range(G):
            if T * G + j <= newest:
                pages[T * G + j] = base + j
    if spoil:
        spoil(pages)
    table = np.asarray(NAN_PAGES * (MB // 3), np.int32)
    for b, page in pages.items():
        table[b % MB] = page
    return table, p0, a0


def swap(first):
    def spoil(pages):
        pages[first + 7], pages[first + 8] = pages[first + 8], pages[first + 7]
    return spoil


# (name, keys before the row, bases, spoil, the flags of ring tiles 0, 1, 2)
RING_ROWS = [
    # shorter than the window: from page 0, ONE whole tile at Sq = 1
    ("short", 500, [64, 96], None, [1, 0, 0]),
    # the window starts mid-run (page 37 of the run 32..63, which ends at
    # the arena's last page; the next run is not aligned); the newest
    # pages have wrapped into ring tile 0, a short tile
    ("mid_run", 100 * BS + 3, [NB - G, 37, 128], None, [0, 1, 1]),
    # page 40: on an attend step's boundary and inside a run
    ("attend_step", 1640, [160, 32, 224], None, [0, 1, 1]),
    # the ring has wrapped three times: logical tiles 7, 8 and 9
    ("wrapped", 300 * BS + 9, [192, 64, 128], None, [0, 1, 1]),
    # broken: two pages of the window's run swapped, the next run whole
    ("broken", 90 * BS + 3, [128, 224, 32], swap(0), [0, 1, 0]),
    ("idle", 0, [], None, [0, 0, 0]),
]


def ring_case(Sq):
    rows = [ring_row(length, Sq, bases, spoil)
            for _, length, bases, spoil, _ in RING_ROWS]
    rows[-1] = (np.zeros(MB, np.int32), 0, 0)            # an idle slot: all trash
    tables = np.stack([table for table, _, _ in rows])
    lengths = np.asarray([length for _, length, *_ in RING_ROWS], np.int32)
    return tables, lengths, [p0 for _, p0, _ in rows], [a0 for _, _, a0 in rows]


@pytest.mark.parametrize("g", [1, 4], ids=["group_of_one", "grouped"])
@pytest.mark.parametrize("Sq", [1, 16])
def test_a_window_ring_with_flags_equals_without_to_the_bit(kernels, Sq, g):
    """A row shorter than the window, one whose window starts mid-run, a ring
    that has wrapped, a broken run and an idle slot, decode rows
    (``Sq`` 1) and a packed chunk's row (16 queries): with the flags a tile is
    one copy, with the flags cleared the same tiles come page by page, and
    the two are equal to the bit.  The call WITHOUT flags walks from the
    window's first page and not from its run's, so its attend steps hold other
    keys: equal to the bit where the two walks cut alike (the first page on an
    attend step's boundary), to rounding elsewhere.  Columns the row does
    not hold point at NaN pages: nothing may fetch them."""
    kernels("paged_gqa_attention")
    rng = np.random.default_rng(17)
    tables, lengths, first, held = ring_case(Sq)
    (ka, va), (kc, vc) = arenas(rng, NAN_PAGES)
    q = jnp.asarray(rng.standard_normal((len(tables), Sq, g * Hkv, D)), jnp.float32)
    plan = da.softmax_plan(g * Hkv, Hkv, D, BS, MB, 0, jnp.float32, window=WINDOW)
    assert plan.run_pages == G and plan.tile_pages == 8
    flags = plan.tile_runs(jnp.asarray(tables), NB)
    assert np.asarray(flags).tolist() == [row[-1] for row in RING_ROWS]
    call = lambda runs: jax.jit(lambda *a: da.paged_gqa_attention(
        *a, window=WINDOW, tile_runs=runs))(
            q, ka, va, jnp.int32(1), jnp.asarray(tables), jnp.asarray(lengths))
    got, cleared, plain = call(flags), call(jnp.zeros_like(flags)), call(None)
    want = da.paged_attention_reference(
        q, jnp.asarray(kc[1]), jnp.asarray(vc[1]), jnp.asarray(tables),
        jnp.asarray(lengths), window=WINDOW)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - cleared).max()) == 0.0
    for row, (p0, a0) in enumerate(zip(first, held)):
        gap = float(jnp.abs(got[row] - plain[row]).max())
        assert gap == 0.0 if (p0 - a0) % plan.tile_pages == 0 else gap < 2e-5, row
    assert {(p0 - a0) % plan.tile_pages == 0 for p0, a0 in zip(first, held)} == {
        True, False}
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_the_layers_call_cuts_a_rings_flags_as_it_cuts_the_tables(kernels, dtype):
    """What the step calls of a window layer: decode rows and a prompt chunk
    of one sequence, ``Sq`` queries a row, that starts mid-run past the
    window; the ring's flags go with the tables through
    ``paged_layer_attention`` and change nothing to the bit."""
    kernels("paged_gqa_attention")
    rng = np.random.default_rng(23)
    chunk, g = 32, 4
    start = 110 * BS + 5                                 # the window's page: 47
    ring, p0, a0 = ring_row(start, chunk, [64, 128, 192])
    assert (p0, a0) == (47, 32)
    decode = [ring_row(100 * BS + 3, 1, [NB - G, 37, 128])[0],
              ring_row(200, 1, [96])[0], np.zeros(MB, np.int32)]
    tables = jnp.asarray(np.stack(decode + [ring] * chunk), jnp.int32)
    lengths = jnp.asarray([100 * BS + 3, 200, 0] + list(range(start, start + chunk)),
                          jnp.int32)
    ka, va = (jnp.asarray(rng.standard_normal((2, NB, BS, Hkv * D)), dtype)
              for _ in range(2))
    q = jnp.asarray(rng.standard_normal((3 + chunk, 1, g * Hkv, D)), dtype)
    plan = da.softmax_plan(g * Hkv, Hkv, D, BS, MB, chunk, dtype, window=WINDOW)
    flags = plan.tile_runs(tables, NB)
    assert flags[3:].tolist() == [[0, 1, 1]] * chunk and flags[0].tolist() == [0, 1, 1]
    call = lambda runs: jax.jit(lambda *a: da.paged_layer_attention(
        *a, window=WINDOW, chunk=chunk, tile_runs=runs))(
            q, ka, va, jnp.int32(0), tables, lengths).astype(jnp.float32)
    got, cleared, plain = call(flags), call(jnp.zeros_like(flags)), call(None)
    assert float(jnp.abs(got - cleared).max()) == 0.0
    want = da.paged_attention_reference(q, ka[0], va[0], tables, lengths,
                                        window=WINDOW).astype(jnp.float32)
    tol = 2e-5 if dtype == jnp.float32 else 0.05
    assert float(jnp.abs(got - plain).max()) < tol
    assert float(jnp.abs(got - want).max()) < tol
