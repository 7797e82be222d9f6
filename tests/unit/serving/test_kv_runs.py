"""PagedKVAllocator with ``run_blocks > 1``: a full-attention table grows in
runs of a kernel tile, what is not handed out yet is earmarked and free, and
nothing is refused that the single-block allocator would grant.  Pure host
logic, no jax."""

import numpy as np
import pytest

from deepspeed_tpu.serving.kv_cache import PagedKVAllocator

G = 16


def tiles(alloc, seq, group=0):
    blocks = alloc.owned_blocks(seq, group)
    return [blocks[i:i + alloc.run_blocks]
            for i in range(0, len(blocks), alloc.run_blocks)]


def is_aligned_run(tile, g):
    return tile[0] % g == 0 and tile == list(range(tile[0], tile[0] + g))


def test_default_is_the_single_block_allocator():
    a = PagedKVAllocator(64, 4, 16)
    assert a.run_blocks == 1 and not a._free_runs and not a._earmarks
    assert a.allocate("s", 12) and a.owned_blocks("s") == [1, 2, 3]
    assert (a.tiles_held, a.tiles_run) == (0, 0)
    a.check_consistent()


@pytest.mark.parametrize("g", [4, 16])
def test_grown_a_token_at_a_time_among_128_others_every_full_tile_is_a_run(g):
    bs, rows, tokens = 4, 129, 3 * g * 4 + 7
    a = PagedKVAllocator(rows * (tokens // bs + g) + g, bs, 8 * g, run_blocks=g)
    for n in range(1, tokens + 1):
        for s in range(rows):
            assert a.allocate(s, n)
        if n % 37 == 0:
            a.check_consistent()
    a.check_consistent()
    for s in range(rows):
        *full, last = tiles(a, s)
        assert len(full) == 3 and all(is_aligned_run(t, g) for t in full)
        assert last == list(range(last[0], last[0] + len(last)))
    assert a.tiles_held == 4 * rows and a.tiles_run == 3 * rows
    # what is not handed out yet is free all the same
    assert a.blocks_in_use == rows * a.blocks_for_tokens(tokens)
    assert a.free_blocks == a.num_blocks - 1 - a.blocks_in_use


def test_a_prompt_takes_whole_runs_at_once_and_earmarks_the_rest_of_the_last():
    a = PagedKVAllocator(8 * G, 1, 4 * G, run_blocks=G)
    assert a.allocate("p", 2 * G + 3)
    assert tiles(a, "p") == [list(range(G, 2 * G)), list(range(2 * G, 3 * G)),
                             [3 * G, 3 * G + 1, 3 * G + 2]]
    assert a._earmarks[3 * G][1:] == [3 * G + 3, 4 * G]
    assert a.free_blocks == 8 * G - 1 - (2 * G + 3)
    a.check_consistent()


def test_earmarks_are_taken_before_a_growth_is_refused_and_a_refusal_changes_nothing():
    # 15 loose blocks (1..15) and 3 runs; three sequences hold a block of a
    # run each, so 45 blocks are earmarked
    a = PagedKVAllocator(4 * G, 1, 4 * G, run_blocks=G)
    for s in "abc":
        assert a.allocate(s, 1)
    assert a._earmarked == 3 * (G - 1) and a.free_blocks == 4 * G - 1 - 3
    # a fourth takes the loose blocks, then earmarked ones, youngest first,
    # from the end of the run
    assert a.can_allocate("d", 20) and a.allocate("d", 20)
    got = a.owned_blocks("d")
    assert sorted(got[:15]) == list(range(1, 16))
    assert got[15:] == [4 * G - 1, 4 * G - 2, 4 * G - 3, 4 * G - 4, 4 * G - 5]
    a.check_consistent()
    # all or nothing: one more than what is left, earmarks and all
    left = a.free_blocks
    before = (a.owned_blocks("d"), dict(a._free), {k: list(v[1:]) for k, v in a._earmarks.items()})
    assert not a.can_allocate("d", 20 + left + 1)
    assert not a.allocate("d", 20 + left + 1)
    assert not a.allocate("e", left + 1) and "e" not in a._owned
    assert before == (a.owned_blocks("d"), dict(a._free),
                      {k: list(v[1:]) for k, v in a._earmarks.items()})
    assert a.allocate("e", left) and a.free_blocks == 0 and not a._earmarks
    a.check_consistent()
    # the robbed owner goes on with what it is handed: here nothing is left
    assert not a.allocate("c", 2)
    a.free("e")
    assert a.allocate("c", 2)
    a.check_consistent()


def test_a_robbed_owner_keeps_its_blocks_in_order_and_finishes_with_loose_ones():
    a = PagedKVAllocator(2 * G, 1, 2 * G, run_blocks=G)
    assert a.allocate("o", 3)                       # G, G+1, G+2; 13 earmarked
    assert a.allocate("t", 15 + 10)                 # 15 loose, 10 off the run's end
    assert a._earmarks[G][1:] == [G + 3, 2 * G - 10]
    assert a.allocate("o", 6)
    assert a.owned_blocks("o") == list(range(G, G + 6)) and not a._earmarks
    assert not a.allocate("o", 7)
    a.free("t")
    assert a.allocate("o", G)                       # finished with loose blocks
    assert a.tiles_run == 0 and a.tiles_held == 1
    a.check_consistent()


def test_freed_loose_blocks_re_form_a_run():
    a = PagedKVAllocator(3 * G, 1, 3 * G, run_blocks=G)
    assert a.allocate("x", 15) and a.allocate("y", G + 15)
    assert a.owned_blocks("x") == list(range(G, G + 15))
    # y holds [2G, 3G) whole, then the 15 loose blocks: no run was left
    assert tiles(a, "y")[0] == list(range(2 * G, 3 * G))
    assert sorted(tiles(a, "y")[1]) == list(range(1, 16))
    assert a._free_runs == [] and a._earmarked == 1 and a.free_blocks == 1
    a.free("x")                                     # 15 blocks and the earmark
    assert a._free_runs == [G] and a._earmarked == 0 and not a._free
    a.free("y")
    assert sorted(a._free_runs) == [G, 2 * G]
    assert sorted(a._free) == list(range(1, 16))    # 1..15 never make a run
    a.check_consistent()
    assert a.allocate("z", 2 * G)
    assert all(is_aligned_run(t, G) for t in tiles(a, "z"))
    a.check_consistent()


def test_adopt_ref_unref_and_evict_deal_in_single_blocks():
    a = PagedKVAllocator(4 * G, 1, 4 * G, run_blocks=G)
    assert a.allocate("first", G + 2)
    shared = a.owned_blocks("first")[:G]
    for b in shared:
        a.ref(b)                                     # the prefix cache's pin
    a.adopt("second", shared)
    assert a.tiles_run == 2 and a.tiles_held == 3
    assert a.allocate("second", G + 5)              # private growth past the prefix
    assert a.owned_blocks("second")[:G] == shared
    assert a.owned_blocks("second")[G:] == list(range(3 * G, 3 * G + 5))
    assert a._earmarks[3 * G][1:] == [3 * G + 5, 4 * G]
    a.check_consistent()
    assert a.evict("first") == G + 2 and a.eviction_count == 1
    a.check_consistent()
    assert all(b in a._refs for b in shared)
    a.free("second")
    assert all(a._refs[b] == 1 for b in shared)     # the pin alone
    assert all(a.unref(b) for b in shared)
    assert sorted(a._free_runs) == [G, 2 * G, 3 * G] and not a._refs
    assert (a.tiles_held, a.tiles_run) == (0, 0)
    a.check_consistent()


def test_a_window_ring_beside_a_group_in_runs_gives_back_and_takes_single_blocks():
    bs, window, g = 4, 16, 4
    a = PagedKVAllocator(2 * 64, bs, 32, windows=(None, window), chunk=8,
                         run_blocks=g)
    a.bind("s", 0)
    ring = a.widths[1]
    for n in range(1, 100):
        assert a.allocate("s", n, resident=n - 1)
        a.check_consistent()
        assert len(a.owned_blocks("s", 1)) <= ring
    assert a.given_back_ever > 0
    full = tiles(a, "s", 0)
    assert all(is_aligned_run(t, g) for t in full[:-1])
    cleared, edits = a.drain_edits()
    tables = [np.zeros(w, np.int32) for w in a.widths]
    for grp, slot, col, b in edits:
        assert slot == 0
        tables[grp][col] = b
    for grp in range(2):
        np.testing.assert_array_equal(tables[grp], a.block_table("s", grp))


def script(seed, steps=400, seqs=12, max_tokens=150):
    rng = np.random.default_rng(seed)
    for _ in range(steps):
        s = int(rng.integers(seqs))
        kind = rng.choice(["grow", "grow", "grow", "prompt", "free", "evict", "ask"])
        yield kind, s, int(rng.integers(1, max_tokens))


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("g", [4, 16])
def test_a_random_script_counts_as_run_blocks_1_does_and_stays_consistent(g, seed):
    """The same calls on both allocators: the same answers and the same
    counts of what is free and in use, whatever the blocks' ids."""
    bs = 2
    one = PagedKVAllocator(20 * g + 3, bs, 80, run_blocks=1)
    runs = PagedKVAllocator(20 * g + 3, bs, 80, run_blocks=g)
    size = {}
    for slot in range(12):
        one.bind(slot, slot), runs.bind(slot, slot)
    for kind, s, n in script(seed):
        if kind in ("grow", "prompt"):
            want = size.get(s, 0) + (1 if kind == "grow" else n)
            want = min(want, 80 * bs)
            assert one.can_allocate(s, want) == runs.can_allocate(s, want)
            ok = one.allocate(s, want)
            assert runs.allocate(s, want) == ok
            if ok:
                size[s] = want
        elif kind == "ask":
            assert one.can_allocate(s, n) == runs.can_allocate(s, n)
        else:
            drop = one.free if kind == "free" else one.evict
            drop_runs = runs.free if kind == "free" else runs.evict
            assert drop(s) == drop_runs(s)
            size.pop(s, None)
            if s in range(12) and s not in one._slot:
                one.bind(s, s), runs.bind(s, s)
        runs.check_consistent()
        assert (one.free_blocks, one.blocks_in_use, one.eviction_count) == (
            runs.free_blocks, runs.blocks_in_use, runs.eviction_count)
        assert one.pages_full == runs.pages_full
        for q in size:
            assert len(one.owned_blocks(q)) == len(runs.owned_blocks(q))
    # the edits add up to the tables, as they do under single blocks
    tables = np.zeros((12, 80), np.int32)
    cleared, edits = runs.drain_edits()
    for grp, slot, col, b in edits:
        tables[slot, col] = b
    for q in range(12):
        np.testing.assert_array_equal(tables[q], runs.block_table(q))
    one.check_consistent()


def test_the_runs_that_can_be_are_after_every_sequence_was_freed():
    a = PagedKVAllocator(10 * G + 5, 1, 10 * G, run_blocks=G)
    rng = np.random.default_rng(0)
    for s in range(30):
        a.allocate(s, int(rng.integers(1, 40)))
    for s in rng.permutation(30):
        a.free(int(s))
    assert sorted(a._free_runs) == list(range(G, 10 * G, G))
    assert sorted(a._free) == list(range(1, G)) + list(range(10 * G, 10 * G + 5))
    assert a.free_blocks == 10 * G + 4 and a._earmarked == 0
    a.check_consistent()
