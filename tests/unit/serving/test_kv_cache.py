"""PagedKVAllocator unit tests — alloc/free/evict invariants and
block-table/write-map correctness.  Pure host logic, no jax."""

import numpy as np
import pytest

from deepspeed_tpu.serving.kv_cache import ArenaExhausted, PagedKVAllocator


def make(num_blocks=8, block_size=4, max_blocks=6):
    return PagedKVAllocator(num_blocks, block_size, max_blocks)


def test_initial_state():
    a = make()
    assert a.free_blocks == 7          # block 0 reserved as trash
    assert a.blocks_in_use == 0
    assert a.capacity_tokens() == 6 * 4
    a.check_consistent()


def test_allocate_grow_and_table_prefix_stable():
    a = make()
    assert a.allocate("s", 10)         # ceil(10/4) = 3 blocks
    assert a.blocks_in_use == 3
    t1 = a.block_table("s")
    assert t1.dtype == np.int32 and t1.shape == (6,)
    assert (t1[:3] > 0).all() and (t1[3:] == 0).all()
    assert a.allocate("s", 13)         # grows to 4 blocks
    t2 = a.block_table("s")
    # growth appends: already-written blocks keep their physical identity
    assert (t2[:3] == t1[:3]).all() and t2[3] > 0
    # shrink request is a no-op
    assert a.allocate("s", 2)
    assert a.blocks_in_use == 4
    a.check_consistent()


def test_allocate_failure_leaves_state_unchanged():
    a = make(num_blocks=4)             # 3 usable blocks
    assert a.allocate("a", 8)          # 2 blocks
    assert not a.allocate("b", 8)      # needs 2, only 1 free
    assert "b" not in a._owned and a.free_blocks == 1
    # partial-grow failure keeps existing ownership intact
    assert a.allocate("b", 4)
    assert not a.allocate("b", 12)
    assert len(a._owned["b"]) == 1
    a.check_consistent()


def test_free_and_evict():
    a = make()
    a.allocate("a", 9)
    n = a.free("a")
    assert n == 3 and a.free_blocks == 7 and a.eviction_count == 0
    assert a.free("a") == 0            # idempotent
    a.allocate("b", 5)
    assert a.evict("b") == 2 and a.eviction_count == 1
    assert a.evict("b") == 0 and a.eviction_count == 1
    a.check_consistent()


def test_blocks_reused_after_free():
    a = make(num_blocks=4)
    a.allocate("a", 12)                # all 3 usable blocks
    assert not a.can_allocate("b", 4)
    a.free("a")
    assert a.can_allocate("b", 12) and a.allocate("b", 12)
    a.check_consistent()


def test_max_blocks_per_seq_raises():
    a = make(num_blocks=32, max_blocks=2)
    with pytest.raises(ArenaExhausted):
        a.allocate("s", 12)            # 3 blocks > max 2


def test_write_map_positions():
    a = make(block_size=4)
    a.allocate("s", 12)
    tbl = a.block_table("s")
    blocks, offs = a.write_map("s", 5, 4)
    # logical positions 5..8 -> (block 1, off 1..3) then (block 2, off 0)
    assert list(offs) == [1, 2, 3, 0]
    assert list(blocks) == [tbl[1], tbl[1], tbl[1], tbl[2]]
    # a short last chunk maps its own tokens and nothing past them (the
    # rows behind it stay on the trash block: the engine never asks)
    blocks, offs = a.write_map("s", 8, 2)
    assert (blocks == tbl[2]).all() and list(offs) == [0, 1]


def test_write_past_allocation_asserts():
    a = make(block_size=4)
    a.allocate("s", 4)
    with pytest.raises(AssertionError):
        a.write_map("s", 4, 1)


def test_consistency_detects_double_ownership():
    a = make()
    a.allocate("a", 4)
    a._owned["b"] = list(a._owned["a"])   # corrupt: same block, two owners
    with pytest.raises(AssertionError):
        a.check_consistent()


# ---- refcounted sharing (prefix cache substrate) ------------------------- #
def test_ref_unref_shared_block_lifecycle():
    a = make()
    a.allocate("a", 4)
    b = a.owned_blocks("a")[0]
    a.ref(b)                              # cache pin
    assert a.free("a") == 1               # owner gone, pin keeps it live
    assert a.free_blocks == 6             # block NOT freed yet
    a.check_consistent()
    assert a.unref(b)                     # last reference frees it
    assert a.free_blocks == 7
    a.check_consistent()
    with pytest.raises(AssertionError):
        a.unref(b)                        # dead block
    with pytest.raises(AssertionError):
        a.ref(b)


def test_adopt_shares_blocks_copy_free():
    a = make()
    a.allocate("a", 8)
    shared = a.owned_blocks("a")
    a.adopt("b", shared)
    assert a.owned_blocks("b") == shared
    assert a.blocks_in_use == 2           # no new physical blocks
    a.check_consistent()
    # adopter grows privately past the shared prefix
    assert a.allocate("b", 12)
    assert a.owned_blocks("b")[:2] == shared
    assert a.owned_blocks("b")[2] not in shared
    a.check_consistent()
    # either side freeing leaves the other's view intact
    a.free("a")
    assert a.owned_blocks("b")[:2] == shared
    a.check_consistent()
    a.free("b")
    assert a.free_blocks == 7
    a.check_consistent()
    # adopt must precede private growth
    a.allocate("c", 4)
    with pytest.raises(AssertionError):
        a.adopt("c", [a.owned_blocks("c")[0]])


def test_failed_growth_contract_under_sharing():
    a = make(num_blocks=4)                # 3 usable
    a.allocate("a", 8)                    # 2 blocks
    a.adopt("b", a.owned_blocks("a"))
    before = a.owned_blocks("b")
    assert not a.allocate("b", 16)        # needs 2 more, only 1 free
    assert a.owned_blocks("b") == before  # untouched on failure
    a.check_consistent()


def test_allocator_fuzz_random_interleavings():
    """Random allocate/free/evict/adopt/ref/unref interleavings (the spill
    path is free+re-allocate, so it is covered by construction), with
    check_consistent after every operation."""
    rng = np.random.default_rng(12345)
    a = PagedKVAllocator(num_blocks=16, block_size=4, max_blocks_per_seq=8)
    seqs = [f"s{i}" for i in range(6)]
    pinned = []                           # blocks holding an extra ref
    for _ in range(2000):
        op = rng.integers(0, 5)
        s = seqs[rng.integers(0, len(seqs))]
        if op == 0:                       # allocate / grow
            want = int(rng.integers(1, 33))
            try:
                a.allocate(s, want)
            except ArenaExhausted:
                pass
        elif op == 1:
            a.free(s)
        elif op == 2:
            a.evict(s)
        elif op == 3:                     # cache-style pin of a live block
            owned = a.owned_blocks(s)
            if owned and len(pinned) < 8:
                b = owned[int(rng.integers(0, len(owned)))]
                a.ref(b)
                pinned.append(b)
        elif op == 4:                     # drop a pin
            if pinned:
                a.unref(pinned.pop(int(rng.integers(0, len(pinned)))))
        if rng.integers(0, 4) == 0:       # adopt: shared prefix attach
            src = seqs[rng.integers(0, len(seqs))]
            dst = f"adopted{rng.integers(0, 3)}"
            if a.owned_blocks(src) and not a.owned_blocks(dst):
                a.adopt(dst, a.owned_blocks(src)[:2])
            elif a.owned_blocks(dst):
                a.free(dst)
        a.check_consistent()
    # teardown drains everything back to a full free list
    for s in list(a._owned):
        a.free(s)
    for b in pinned:
        a.unref(b)
    a.check_consistent()
    assert a.free_blocks == 15


# ---- layer groups: a window group gives its pages back ------------------------- #
def _grouped(num_pages=64, chunk=8):
    """Blocks of 4 tokens; a full group and two groups with a window of 16;
    tables 32 wide, the window groups' a ring of (16 + 8 - 1) / 4 -> 6, + 1."""
    return PagedKVAllocator(num_pages, 4, 32, windows=(None, 16, 16), chunk=chunk)


def test_window_group_frees_exactly_the_pages_out_of_the_window():
    a = _grouped()
    assert a.widths == (32, 7, 7) and a.n_groups == 3
    # admission of a 40-token prompt: all of it under the full group, a ring
    # under each window group
    assert a.allocate("s", 40)
    assert [len(a.owned_blocks("s", g)) for g in range(3)] == [10, 7, 7]
    a.check_consistent()
    held = set(a.owned_blocks("s", 1))
    for resident in range(0, 60):
        # the chunk or decode step at ``resident`` writes one token
        assert a.allocate("s", max(40, resident + 1), resident)
        a.check_consistent()
        first = max(0, resident - 16 + 1) // 4       # block of the oldest visible key
        end = min(-(-max(40, resident + 1) // 4), first + 7)
        for g in (1, 2):
            table = a.block_table("s", g)
            run = a.owned_blocks("s", g)
            assert len(run) == end - first
            # the ring: logical block b in column b % 7, the rest trash
            assert [table[b % 7] for b in range(first, end)] == run
            assert (table != 0).sum() == len(run)
        assert len(a.owned_blocks("s", 0)) == -(-max(40, resident + 1) // 4)
        held |= set(a.owned_blocks("s", 1))
    assert a.given_back_total == 2 * first and a.pages_window == 2 * (end - first)
    # what a window group gave back went to the one pool
    assert a.free_blocks == (63 - a.pages_full - a.pages_window) // 3
    blocks, offsets = a.write_map("s", 59, 1, group=2)
    assert blocks[0] == a.owned_blocks("s", 2)[-1] and offsets[0] == 3
    with pytest.raises(AssertionError, match="outside allocation"):
        a.write_map("s", 8, 1, group=1)              # block 2 was given back
    assert a.free("s") == 15 + 2 * (end - first)
    a.check_consistent()
    assert a.pages_full == a.pages_window == a.given_back_total == 0
    assert a.given_back_ever == 2 * first


def test_grouped_growth_is_all_or_nothing_and_counts_every_group():
    a = _grouped(num_pages=20)                        # 19 usable pages
    assert a.pages_for_tokens(40) == 10 + 7 + 7
    assert a.pages_for_tokens(40, resident=40) == 10 + 2 * 4
    assert not a.can_allocate("s", 40) and not a.allocate("s", 40)
    a.check_consistent()
    assert a.blocks_in_use == 0 and "s" not in a._owned
    assert a.allocate("s", 24)                        # 6 + 6 + 6
    assert a.blocks_in_use == 6 and a.free_blocks == 0
    before = [a.owned_blocks("s", g) for g in range(3)]
    assert not a.allocate("s", 32, resident=24)       # needs 2 + 2 + 2, 1 free
    assert [a.owned_blocks("s", g)[-4:] for g in range(3)] == [b[-4:] for b in before]
    a.check_consistent()
    with pytest.raises(AssertionError, match="one group"):
        a.adopt("t", [1])


# ---- the slots' tables as edits (the engine's device tables) -------------------- #
def _apply(tables, cleared, edits):
    """What the step program does with a drain: rows to trash, then entries."""
    for slot in cleared:
        for t in tables:
            t[slot] = 0
    for g, slot, col, block in edits:
        tables[g][slot, col] = block


@pytest.mark.parametrize("bound_first", [False, True],
                         ids=["allocate_then_bind", "bind_then_allocate"])
def test_edits_add_up_to_the_block_tables(bound_first):
    """A sequence's tables, kept only by applying what ``drain_edits`` says,
    equal ``block_table`` after every step of a prompt and sixty decode steps
    past a window; an unbound sequence says nothing; a ring column given back
    and taken again in one drain is said once."""
    a = _grouped()
    tables = [np.zeros((4, w), np.int32) for w in a.widths]
    if bound_first:
        a.bind("s", 2)
    assert a.allocate("s", 40)
    assert a.allocate("other", 12)                    # never has a slot
    if not bound_first:
        assert a.drain_edits() == ([], [])
        a.bind("s", 2)
    cleared, edits = a.drain_edits()
    assert cleared == [] and len(edits) == 10 + 7 + 7
    _apply(tables, cleared, edits)
    for resident in range(40, 100):
        assert a.allocate("s", resident + 1, resident)
        cleared, edits = a.drain_edits()
        # the first step behind the prompt turns the rings over (each column
        # once: given back and taken again); after it a block a group at most
        assert cleared == [] and len(edits) <= (3 if resident > 40 else 1 + 7 + 7)
        assert len({e[:3] for e in edits}) == len(edits)
        _apply(tables, cleared, edits)
        for g in range(3):
            np.testing.assert_array_equal(tables[g][2], a.block_table("s", g))
            assert not tables[g][[0, 1, 3]].any()
    np.testing.assert_array_equal(np.stack([t[2] for t in a.slot_tables(4)][1:]),
                                  np.stack([t[2] for t in tables][1:]))
    # freed: the row goes back whole, and nothing is left to say of it
    a.allocate("s", 101, 100)
    a.free("s")
    assert a.drain_edits() == ([2], [])
    a.bind("other", 2)                                # the slot is free again
    cleared, edits = a.drain_edits()
    _apply(tables, [2], [])
    _apply(tables, cleared, edits)
    for g in range(3):
        np.testing.assert_array_equal(tables[g][2], a.block_table("other", g))
    with pytest.raises(AssertionError, match="taken"):
        a.bind("third", 2)
    a.check_consistent()
