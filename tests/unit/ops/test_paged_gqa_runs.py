"""``paged_gqa_attention`` with the flags of ``paged_tile_runs``: a tile of
consecutive live pages is one copy an operand, any other tile a copy a page,
and the flags (and the larger tile they bring) change NOTHING in what comes
out: equal to the call without them to the bit.  The tables are those of
``test_paged_mla_runs.py``: the same 32 pages of 16 keys a tile."""

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
    """A window group's ring, the pages a query chose and any caller that
    hands no flags trace the kernel they always traced: the tables its only
    SMEM blocks, the arena with its pages a dimension, a K and a V copy a
    page in each of the three places a tile is started or waited for; with
    flags a run's one copy stands beside each of them."""
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
    if call == "full":
        flags = da.paged_tile_runs(tables, NB, G)
        text = str(jax.make_jaxpr(lambda *a: da.paged_layer_attention(
            *a, tile_runs=flags))(q, arena, arena, jnp.int32(0), tables, lengths))
        assert text.count("dma_start") == 8 and text.count("dma_wait") == 4
        assert f"f32[2,{NB * BS}," in text


def test_no_window_group_and_no_reference_path_asks_for_runs(kernels):
    run_pages = lambda H=4 * Hkv, Hkv=Hkv, D=D, MB=MB, **rule: da.softmax_plan(
        H, Hkv, D, BS, MB, 0, jnp.bfloat16, **rule).run_pages
    assert run_pages() == 0                                 # the CPU's rule
    kernels("paged_gqa_attention")
    assert run_pages() == G
    assert run_pages(window=64) == 0
    assert run_pages(bias=True) == 0
    assert run_pages(12, 12, 64, 64) == 0                   # D = 64
    assert da.paged_tile_runs(jnp.zeros((2, MB), jnp.int32), NB, 0) is None


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
