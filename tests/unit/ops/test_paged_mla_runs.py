"""``paged_mla_attention`` through the interpreter on tables that hold RUNS: a
tile of consecutive pages is one copy, any other tile a copy a page, and which
it is may change nothing in what comes out."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import decode_attention as da

H, W, R, BS, MB, G = 5, 256, 128, 16, 96, 32     # tiles of 512 keys: 32 pages
NB = 8 * G + 5
SCALE = 0.17


def plan_of(MB, dtype):
    return da.latent_plan(W, R, H, BS, MB, 0, dtype, SCALE)


def mla_tile_runs(tables, arena):
    """The flags of ``tables`` at the pages a tile of the latent kernel holds
    for them (None where it takes the reference), as the step works them out."""
    return plan_of(tables.shape[1], arena.dtype).tile_runs(tables, arena.shape[1])


def runs_of(*firsts):
    """A table of whole runs starting at these pages."""
    return np.concatenate([np.arange(f, f + G) for f in firsts])


def tables_and_lengths(case, Sq, rng):
    """(tables [B, MB], lengths [B], pages no row may read) of a case."""
    scattered = lambda: rng.permutation(np.arange(1, NB))[:MB]
    untouched = []
    if case == "runs":
        # aligned, unaligned and ending at the arena's last page
        rows = [runs_of(G, 3 * G, 5 * G), runs_of(2 * G + 3, 6 * G + 3, 4 * G + 3),
                runs_of(NB - G, G, 2 * G)]
        lengths = [3 * G * BS - Sq, 2 * G * BS + 5, G * BS + BS - 1]
    elif case == "mixed":
        rows = [np.concatenate([runs_of(2 * G), scattered()[:G], runs_of(5 * G)]),
                np.concatenate([scattered()[:G], runs_of(G, 3 * G)]),
                scattered(), np.zeros(MB, np.int64)]
        lengths = [3 * G * BS - Sq, 2 * G * BS + 9, 2 * G * BS + 1, 0]
    elif case == "broken":
        # a run with two pages swapped, one with a page from elsewhere, one
        # whose pages lie in falling order
        swapped = runs_of(G, 2 * G, 3 * G)
        swapped[[7, 8]] = swapped[[8, 7]]
        foreign = runs_of(4 * G, 5 * G, 6 * G)
        foreign[G + 11] = 3
        falling = np.concatenate([np.arange(8 * G - 1, 7 * G - 1, -1),
                                  runs_of(G, 2 * G)])
        rows, lengths = [swapped, foreign, falling], [
            3 * G * BS - Sq, 2 * G * BS + 3, 3 * G * BS - Sq]
    elif case == "earmarked_tail":
        # a run handed out as far as the row has grown: the table lists the
        # row's pages, the rest of the run lies behind them in the arena,
        # and a table may list pages the row has not reached
        held = 5
        partly = np.concatenate([runs_of(2 * G), np.arange(4 * G, 4 * G + held),
                                 np.zeros(MB - G - held, np.int64)])
        ahead = runs_of(5 * G, 6 * G, 7 * G)
        rows = [partly, ahead]
        lengths = [(G + held) * BS - Sq, G * BS + 3 * BS + 2]
        reached = -(-(lengths[1] + Sq) // BS)
        untouched = list(range(4 * G + held, 5 * G)) + list(ahead[reached:])
    rows = [np.pad(r, (0, MB - len(r))) for r in rows]
    return np.stack(rows).astype(np.int32), np.asarray(lengths, np.int32), untouched


@pytest.mark.parametrize("Sq", [1, 16])
@pytest.mark.parametrize("case", ["runs", "mixed", "broken", "earmarked_tail"])
def test_the_kernel_on_runs_equals_the_gather_reference(kernels, case, Sq):
    kernels("paged_mla_attention")
    rng = np.random.default_rng(7)
    tables, lengths, untouched = tables_and_lengths(case, Sq, rng)
    B = len(tables)
    arena = rng.standard_normal((2, NB, BS, W)).astype(np.float32)
    clean = arena.copy()
    arena[:, untouched] = np.nan          # unwritten garbage past the lengths
    clean[:, untouched] = 0.0
    q = jnp.asarray(rng.standard_normal((B, Sq, H, W)), jnp.float32)
    assert plan_of(MB, jnp.float32).run_pages == G
    flags = np.asarray(mla_tile_runs(jnp.asarray(tables), jnp.asarray(arena)))
    want_flags = {"runs": [[1, 1, 1]] * 3,
                  "mixed": [[1, 0, 1], [0, 1, 1], [0, 0, 0], [0, 0, 0]],
                  "broken": [[0, 1, 1], [1, 0, 1], [0, 1, 1]],
                  "earmarked_tail": [[1, 0, 0], [1, 1, 1]]}[case]
    assert flags.tolist() == want_flags
    got = jax.jit(lambda *a: da.paged_mla_attention(
        *a, scale=SCALE, value_lanes=R))(q, jnp.asarray(arena), jnp.int32(1),
                                         jnp.asarray(tables), jnp.asarray(lengths))
    want = da.paged_mla_attention_reference(
        q, jnp.asarray(clean[1]), jnp.asarray(tables), jnp.asarray(lengths),
        scale=SCALE, value_lanes=R)
    assert got.shape == (B, Sq, H, R)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("runs", [True, False])
def test_a_packed_chunk_beside_decode_rows_takes_the_rows_flags(kernels, runs):
    """What the step calls: rows of one query, the last ``chunk`` a prompt
    chunk of one sequence attended ``Sq`` queries a row; the flags are cut
    as the tables are, and handed in (worked out once a step) they give what
    the call works out itself."""
    kernels("paged_mla_attention")
    rng = np.random.default_rng(3)
    chunk, slots = 32, 3
    table = (runs_of(G, 4 * G, 2 * G) if runs
             else rng.permutation(np.arange(1, NB))[:MB])
    decode = np.stack([runs_of(5 * G, 6 * G, 3 * G),
                       rng.permutation(np.arange(1, NB))[:MB], np.zeros(MB, int)])
    tables = jnp.asarray(np.concatenate([decode, np.tile(table, (chunk, 1))]),
                         jnp.int32)
    start = G * BS + 11                      # the chunk starts past a whole tile
    lengths = jnp.asarray([2 * G * BS + 3, 77, 0] + list(range(start, start + chunk)),
                          jnp.int32)
    arena = jnp.asarray(rng.standard_normal((2, NB, BS, W)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((slots + chunk, 1, H, W)), jnp.float32)
    call = lambda **kw: jax.jit(lambda *a: da.paged_mla_attention(
        *a, scale=SCALE, value_lanes=R, chunk=chunk, **kw))(
            q, arena, jnp.int32(0), tables, lengths)
    flags = mla_tile_runs(tables, arena)
    assert flags.shape == (slots + chunk, MB // G)
    assert flags[slots:].tolist() == [[int(runs)] * 3] * chunk
    got, handed = call(), call(tile_runs=flags)
    want = da.paged_mla_attention_reference(q, arena[0], tables, lengths,
                                            scale=SCALE, value_lanes=R)
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert float(jnp.abs(handed - got).max()) == 0.0


def test_a_table_that_is_no_whole_number_of_tiles_has_no_run_in_its_last(kernels):
    kernels("paged_mla_attention")
    arena = jnp.zeros((1, NB, BS, W), jnp.float32)
    tables = jnp.asarray([np.arange(G, G + 40)], jnp.int32)        # 32 + 8
    assert mla_tile_runs(tables, arena).tolist() == [[1, 0]]
    # consecutive pages of which the last would lie past the arena are none
    ends = jnp.asarray([np.arange(NB - G, NB), np.arange(NB - G + 1, NB + 1)])
    assert mla_tile_runs(ends, arena).tolist() == [[1], [0]]
    kernels()
    assert mla_tile_runs(tables, arena) is None         # the reference


@pytest.mark.parametrize("Sq", [1, 16])
def test_what_comes_out_does_not_depend_on_how_many_keys_a_copy_brings(
        kernels, monkeypatch, Sq):
    """A tile is the unit of the copy, not of the softmax: tiles of 512 keys
    (32 pages, as served) and of 256 (16 pages, the attend's own step) give
    the same numbers to the bit, runs or no runs."""
    kernels("paged_mla_attention")
    rng = np.random.default_rng(11)
    tables, lengths, _ = tables_and_lengths("mixed", Sq, rng)
    arena = jnp.asarray(rng.standard_normal((2, NB, BS, W)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((len(tables), Sq, H, W)), jnp.bfloat16)
    got = {}
    for rows in (512, 256):
        monkeypatch.setattr(da, "_MLA_TILE_ROWS", rows)
        assert plan_of(MB, jnp.bfloat16).tile_pages == rows // BS
        got[rows] = jax.jit(lambda *a: da.paged_mla_attention(
            *a, scale=SCALE, value_lanes=R))(
                q, arena, jnp.int32(1), jnp.asarray(tables), jnp.asarray(lengths))
    assert float(jnp.abs(got[512].astype(jnp.float32)
                         - got[256].astype(jnp.float32)).max()) == 0.0
