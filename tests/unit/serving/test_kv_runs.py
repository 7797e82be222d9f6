"""PagedKVAllocator with ``run_blocks > 1``: a table grows in runs of a kernel
tile, a window group's ring as a full group's table, and a ring gives a run
back whole; what is not handed out yet is earmarked and free, and nothing is
refused that the single-block allocator would grant of the same free pages.
Pure host logic, no jax."""

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


def ring_allocator(g, pages=2 * 64, bs=4, window=16, chunk=8, max_blocks=32,
                   groups=1):
    return PagedKVAllocator(pages, bs, max_blocks,
                            windows=(None,) + (window,) * groups, chunk=chunk,
                            run_blocks=g)


@pytest.mark.parametrize("bs,window,chunk,g,single,want", [
    (16, 4096, 512, 16, 289, 304),       # Trinity: 19 runs of 16 pages
    (16, 4096, 224, 32, 271, 320),       # SmallThinker: 10 runs of 32
    (4, 16, 8, 4, 7, 12),
    (4, 16, 8, 1, 7, 7),                 # run_blocks 1: the ring as it was
])
def test_a_ring_is_whole_runs_wide_and_holds_the_run_of_the_windows_first_page(
        bs, window, chunk, g, single, want):
    a = PagedKVAllocator(4096, bs, 4096, windows=(None, window), chunk=chunk,
                         run_blocks=g)
    assert PagedKVAllocator(4096, bs, 4096, windows=(None, window),
                            chunk=chunk).widths[1] == single
    assert a.widths == (4096, want) and want % g == 0
    # whatever ``start``: the run that holds the window's first block up to
    # the chunk's last block is inside the ring
    for start in range(0, 3 * want * bs, 7):
        first = a.first_live_block(1, start)
        assert first % g == 0
        assert first <= max(0, start - window + 1) // bs < first + g
        assert (start + chunk - 1) // bs - first < want


def test_a_ring_cut_to_a_table_that_is_no_whole_runs_deals_in_single_blocks():
    a = PagedKVAllocator(128, 4, 10, windows=(None, 64), chunk=8, run_blocks=4)
    assert a.widths == (10, 10) and a._in_runs == (True, False)
    for n in range(1, 40):
        assert a.allocate("s", n, resident=n - 1)
    assert is_aligned_run(tiles(a, "s", 0)[0], 4)
    assert a.tiles_held == len(tiles(a, "s", 0))         # the full group's alone
    a.check_consistent()


@pytest.mark.parametrize("g", [4, 8])
def test_every_whole_tile_of_a_ring_is_a_run_while_free_runs_last(g):
    """Grown a token at a time with everything before the token resident,
    twice round the ring: the ring starts on a run, every whole tile is an
    aligned run, the run of the window's first block is still held, and the
    edits add up to the table with logical tile ``t`` in ring tile ``t %
    (width / g)``."""
    bs, window = 4, 16
    a = ring_allocator(g, pages=40 * g, max_blocks=64, groups=2)
    a.bind("s", 0)
    ring = a.widths[1]
    assert ring % g == 0 and a._in_runs == (True, True, True)
    tables = [np.zeros(w, np.int32) for w in a.widths]
    for n in range(1, 2 * ring * bs + 9):
        assert a.allocate("s", n, resident=n - 1)
        a.check_consistent()
        for grp in (1, 2):
            run = a._owned["s"][grp]
            assert run.first == a.first_live_block(grp, n - 1) and run.first % g == 0
            assert run.first <= max(0, n - window) // bs and run.end * bs >= n
            assert len(run.blocks) <= ring
            *whole, last = tiles(a, "s", grp)
            assert all(is_aligned_run(t, g) for t in whole)
            assert last == list(range(last[0], last[0] + len(last)))
        _, edits = a.drain_edits()
        for grp, slot, col, b in edits:
            assert slot == 0
            tables[grp][col] = b
        for grp in range(3):
            np.testing.assert_array_equal(tables[grp], a.block_table("s", grp))
        run = a._owned["s"][1]
        for t in range(run.first // g, run.end // g):    # the whole tiles
            at = t % (ring // g) * g
            assert tables[1][at:at + g].tolist() == run.blocks[
                t * g - run.first:(t + 1) * g - run.first]
    assert a.given_back_ever > 0 and a.given_back_ever % g == 0
    held = sum(len(tiles(a, "s", grp)) for grp in range(3))
    assert a.tiles_held == held and a.tiles_run >= held - 3
    assert a.pages_window == sum(len(a.owned_blocks("s", grp)) for grp in (1, 2))


def test_a_run_given_back_whole_is_a_free_run_again():
    g, bs = 4, 4
    a = ring_allocator(g, pages=8 * g)
    assert sorted(a._free_runs) == list(range(g, 8 * g, g))
    for n in range(1, 16 + 3 * g * bs + 1):
        assert a.allocate("s", n, resident=n - 1)
        # a block leaves with its whole run or not at all: what is free is
        # loose blocks that never made a run, whole runs and earmarks
        assert not a._loose_in or set(a._loose_in) == {0}
    assert a.given_back_ever == 3 * g
    ring_runs = {t[0] for t in tiles(a, "s", 1)}
    full_runs = {t[0] for t in tiles(a, "s", 0)}
    assert set(a._free_runs) == set(range(g, 8 * g, g)) - ring_runs - full_runs
    assert len(a._free_runs) == 7 - len(ring_runs) - len(full_runs)
    a.check_consistent()
    a.free("s")
    assert sorted(a._free_runs) == list(range(g, 8 * g, g)) and not a._earmarks
    assert (a.tiles_held, a.tiles_run, a.pages_window, a.given_back_total) == (0, 0, 0, 0)
    a.check_consistent()


def test_a_prompt_longer_than_the_ring_takes_the_ring_and_asks_again_a_chunk():
    g, bs, chunk = 4, 4, 8
    a = ring_allocator(g, pages=64 * g, max_blocks=64, chunk=chunk)
    ring = a.widths[1]
    prompt = 5 * ring * bs + 3
    assert a.allocate("p", prompt)                       # admission: nothing resident
    assert len(a.owned_blocks("p", 1)) == ring
    assert all(is_aligned_run(t, g) for t in tiles(a, "p", 1))
    for start in range(0, prompt, chunk):
        assert a.allocate("p", prompt, resident=start)
        lo, hi = max(0, start - 16 + 1) // bs, (min(start + chunk, prompt) - 1) // bs
        run = a._owned["p"][1]
        assert run.first <= lo and hi < run.end      # what the chunk reads and writes
        a.write_map("p", start, min(chunk, prompt - start), 1)
        a.check_consistent()
    assert all(is_aligned_run(t, g) for t in tiles(a, "p", 1)[:-1])


def ring_script(seed, steps=500, seqs=10):
    rng = np.random.default_rng(seed)
    for _ in range(steps):
        yield (str(rng.choice(["grow", "grow", "grow", "chunk", "admit", "free",
                               "evict"])),
               int(rng.integers(seqs)), int(rng.integers(1, 120)))


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("g", [4, 8])
def test_a_random_script_over_rings_stays_consistent_and_refuses_for_want_of_pages_alone(
        g, seed):
    """Random admit / grow / give back / free over a full group and two rings
    in a tight arena: consistent after every call, a growth is refused only
    when it is more than the free pages, earmarked ones and all (nothing
    that ``run_blocks = 1`` would grant of the same count), a refusal changes
    nothing it owns, and a sequence holds what ``pages_for_tokens`` says."""
    bs, max_blocks = 2, 64
    a = PagedKVAllocator(14 * g + 3, bs, max_blocks, windows=(None, 12, 12),
                         chunk=6, run_blocks=g)
    assert a._in_runs == (True, True, True)
    size = {}                                            # seq -> (tokens, resident)
    held = lambda s: sum(len(a.owned_blocks(s, grp)) for grp in range(3))
    refused = 0
    for kind, s, n in ring_script(seed):
        if kind in ("free", "evict"):
            (a.free if kind == "free" else a.evict)(s)
            size.pop(s, None)
        else:
            tokens, resident = size.get(s, (0, 0))
            if kind == "admit" and s not in size:
                tokens, resident = n, 0
            elif kind == "chunk":
                resident = min(tokens, resident + 6)
                tokens = max(tokens, min(resident + 6, max_blocks * bs))
            else:
                resident, tokens = tokens, min(tokens + 1, max_blocks * bs)
            want = a.pages_for_tokens(tokens, resident)
            room = held(s) + a.free_pages
            before = [a.owned_blocks(s, grp)[-1:] for grp in range(3)]
            ok = a.allocate(s, tokens, resident)
            assert ok == (want <= room)
            if ok:
                size[s] = (tokens, resident)
                assert held(s) == want
            else:
                refused += 1
                assert before == [a.owned_blocks(s, grp)[-1:] for grp in range(3)]
                if s not in size:
                    assert s not in a._owned
        a.check_consistent()
        assert a.pages_full + a.pages_window == sum(held(q) for q in a._owned)
    assert refused and a.given_back_ever


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
