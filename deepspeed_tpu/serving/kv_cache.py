"""Paged KV-cache allocator — fixed-size blocks in a preallocated arena.

The serving-side analogue of ZeRO-Infinity's memory virtualization (arxiv
2104.07857): a sequence's LOGICAL KV memory is decoupled from PHYSICAL HBM
placement, so arena capacity — not batch shape — is the binding constraint.
The device arena is ``[n_layer, num_blocks, block_size, lanes]`` for each array
of the model's cache spec (``cfg.cache_lanes``: K and V with the heads folded
into the lane dimension, the layout the paged kernel can DMA; ONE array under
latent attention); this module owns the host-side bookkeeping:

* a free list of physical block ids (block 0 is reserved as the TRASH
  block: padded/inactive tokens scatter their K/V there, so the compiled
  step needs no write predication); where the kernel that reads the arena
  fetches a tile of consecutive pages with one copy, the free blocks are
  also kept as whole RUNS of a tile, and a table grows a run at a time, a
  window group's ring as a full group's (``run_blocks``, below);
* a per-sequence block table in logical order, padded to
  ``max_blocks_per_seq`` with trash for the traced ``[B, MB]`` input;
* **per-block refcounts**: a block may be shared by several sequences (the
  prefix cache attaches a cached system-prompt block to every request that
  matches it) plus the cache itself; a block returns to the free list only
  when its last reference drops.  Divergence is copy-on-write by
  construction: only *full*, immutable prompt blocks are ever shared, so
  every KV write lands in a private (refcount-1, single-owner) block;
* eviction: a preempted sequence returns every block to the free list and
  is later *recomputed* (re-prefilled over prompt + generated-so-far) — or,
  with tiering enabled, its block contents are spilled to host/NVMe first
  and *restored* on re-admission (``serving/kv_tiering.py``).

The serving engine keeps every slot's tables ON THE DEVICE and changes them
by edits (``serving/engine.py``): a sequence that has a slot (:meth:`bind`)
leaves a record of every table entry that changes, which the engine takes
once a step (:meth:`drain_edits`).  :meth:`block_table` and
:meth:`write_map` build whole tables as before; they are what the engine
reloads from, and the oracle the tests hold the edited tables to.

All methods are O(blocks touched); nothing here ever touches jax.
"""

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


class ArenaExhausted(Exception):
    """No free blocks and the caller chose not to (or could not) evict."""


def window_table_blocks(window: int, chunk: int, block_size: int,
                        run_blocks: int = 1) -> int:
    """Width of a window group's block table: the blocks that hold the keys
    ``start - window + 1 .. start + chunk - 1`` a prompt chunk at ``start``
    reads and writes, wherever ``start`` falls in a block.  Where the tables
    grow in runs of ``run_blocks`` the ring is a whole number of runs wide
    and holds, beside those, the ``run_blocks - 1`` blocks below the
    window's first that share its run: a run is kept until its LAST key is
    out of the window, so the tile that holds the window's first page is
    still a run when the kernel reads it."""
    blocks = -(-(window + chunk - 1) // block_size) + 1
    return -(-(blocks + run_blocks - 1) // run_blocks) * run_blocks


def table_widths(windows, max_blocks_per_seq: int, chunk: int,
                 block_size: int, run_blocks: int = 1) -> Tuple[int, ...]:
    """Columns of each group's block table: a full group's grows to
    ``max_blocks_per_seq``, a window group's is its ring.  THE widths: the
    allocator's, hence the engine's device tables' and the ones the step
    builds its plans from."""
    return tuple(
        max_blocks_per_seq if w is None else min(
            max_blocks_per_seq,
            window_table_blocks(w, chunk, block_size, run_blocks))
        for w in windows)


class _Run:
    """One sequence's blocks in ONE group, in logical order: ``blocks[i]`` is
    logical block ``first + i``.  A full group's ``first`` stays 0; a ring's
    that grows in runs stays a multiple of the run."""
    __slots__ = ("first", "blocks", "given_back", "grow", "streak", "tiles_run")

    def __init__(self):
        self.first, self.blocks, self.given_back, self.grow = 0, [], 0, 0
        # of a group that grows in runs: how many of the last blocks are
        # physically consecutive, and how many whole tiles are
        self.streak = self.tiles_run = 0

    @property
    def end(self) -> int:
        return self.first + len(self.blocks)


class PagedKVAllocator:
    """Host-side free-list allocator over ``num_blocks`` physical blocks.

    Block 0 is the trash block and is never handed out; usable capacity is
    ``num_blocks - 1`` blocks = ``(num_blocks - 1) * block_size`` tokens.

    ``windows`` names the model's layer GROUPS (``models/gpt.py``: layers by
    position in the period of the layer pattern), an entry a group: None for
    full attention, else the keys a query sees.  A sequence holds one block
    table a group, all from the ONE pool of equal blocks (a block then holds
    one group's layers: a PAGE; ``num_blocks`` counts pages).  A full
    group's table only grows.  A window group gives a block back once its
    last key is out of every later query's window (:meth:`allocate` is told
    how many tokens are ``resident``), and its table is a RING as wide as
    the window and one prompt chunk of ``chunk`` tokens need
    (:func:`window_table_blocks`): logical block ``b`` sits in column
    ``b % width``.  The default, one full group, is the allocator every
    homogeneous model has.

    ``run_blocks`` (``G``; the engine passes the pages of a tile of the
    kernel that reads the groups, where that kernel fetches ``G``
    consecutive pages with one copy; 1, the default, is the allocator as it
    was, block id for block id).  A RUN is ``G`` physically consecutive
    blocks aligned to ``G``.  A table grows in runs: when a sequence first
    needs logical block ``k*G`` of a group it takes a whole free run for
    logical blocks ``k*G .. k*G+G-1`` and is handed the run's blocks in
    order as it grows, so every full tile of its table is one run.  What it
    has not been handed yet is EARMARKED, NOT OWNED: those blocks have no
    reference and count as free in :attr:`free_blocks`,
    :attr:`blocks_in_use` and :meth:`can_allocate`, exactly as if they lay
    on the free list.  A growth that the loose free blocks and the whole
    free runs (broken up, if need be) cannot cover takes earmarked blocks
    from the end of their runs, the youngest earmark first, before
    :meth:`allocate` returns False: nothing is refused for the runs' sake
    that the same count of free pages would grant under ``run_blocks = 1``.
    (The sequence robbed goes on with loose blocks for the rest of that
    tile, which is then no run.)  A freed block joins the loose ones, and
    when all ``G`` of a run are loose the run is whole again.
    A window group's ring grows the same way: it is a whole number of runs
    wide (:func:`window_table_blocks`), so logical tile ``b // G`` is ring
    tile ``(b // G) % (width // G)``, ``G`` adjacent columns, and it GIVES
    BACK A RUN AT A TIME, the blocks of a tile when the tile's last key is
    out of every later query's window (:meth:`first_live_block`): the tile
    that holds the window's first page is still a run when the kernel reads
    it, at up to ``G - 1`` more pages a group held.  (A ring whose width is
    cut to ``max_blocks_per_seq`` and is no whole number of runs deals in
    single blocks, as every group does under ``run_blocks = 1``.)
    :meth:`adopt`, :meth:`ref` and :meth:`unref` deal in single blocks as
    before.  ``tiles_held`` and ``tiles_run`` count, over every group of
    the live sequences that grows in runs, the tiles their tables hold (the
    last one may be short) and those that are whole runs in order, aligned
    or not: what the kernel fetches with one copy.
    """

    TRASH = 0

    def __init__(self, num_blocks: int, block_size: int,
                 max_blocks_per_seq: int,
                 windows: Sequence[Optional[int]] = (None,), chunk: int = 1,
                 run_blocks: int = 1):
        assert num_blocks >= 2, "arena needs >= 1 usable block + trash"
        assert block_size >= 1 and max_blocks_per_seq >= 1
        assert run_blocks >= 1
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.max_blocks_per_seq = int(max_blocks_per_seq)
        self.windows = tuple(windows)
        self.n_groups = len(self.windows)
        G = self.run_blocks = int(run_blocks)
        self.widths = table_widths(self.windows, self.max_blocks_per_seq,
                                   chunk, self.block_size, G)
        # the groups whose tables grow in runs: every one but a ring that is
        # no whole number of runs wide
        self._in_runs = tuple(G > 1 and (w is None or width % G == 0)
                              for w, width in zip(self.windows, self.widths))
        # LIFO free list: recently-freed blocks are reused first (their
        # pages are hot, and stale contents are fully overwritten before
        # any masked-in position can read them).  A dict in insertion order:
        # ``popitem`` is the list's ``pop``, and a run that re-forms takes
        # its blocks out of the middle
        whole = range(G, self.num_blocks - G + 1, G) if G > 1 else ()
        self._free: Dict[int, None] = dict.fromkeys(
            b for b in range(self.num_blocks - 1, 0, -1)
            if not (whole and whole[0] <= b < whole[-1] + G))
        # the whole free runs, by their first block (LIFO, lowest first);
        # how many blocks of each run are loose in ``_free``; the open
        # earmarks, oldest first: a run's first block -> [the ``_Run`` it
        # is for, lo, hi], blocks ``lo .. hi-1`` not handed out yet
        self._free_runs: List[int] = list(reversed(whole))
        self._loose_in: Dict[int, int] = {}
        for b in (self._free if G > 1 else ()):
            self._loose_in[b - b % G] = self._loose_in.get(b - b % G, 0) + 1
        self._earmarks: Dict[int, list] = {}
        self._earmarked = 0
        self.tiles_held = self.tiles_run = 0
        self._owned: Dict[object, List[_Run]] = {}   # seq id -> a run a group
        # block id -> total references (sequence owners + prefix-cache pins);
        # a block is live iff it has an entry here, free iff it is in _free
        self._refs: Dict[int, int] = {}
        self.eviction_count = 0
        # pages the live sequences hold in full and in window groups, those
        # their window groups have given back (what tables that only grow
        # would still hold), and all that were ever given back
        self.pages_full = self.pages_window = 0
        self.given_back_total = self.given_back_ever = 0
        # what changed in the slots' tables since the engine last asked:
        # seq id -> its slot, slot -> {(group, column): block}, and the slots
        # whose whole row went back to trash BEFORE those entries
        self._slot: Dict[object, int] = {}
        self._edits: Dict[int, Dict[Tuple[int, int], int]] = {}
        self._cleared: set = set()

    # -- capacity queries -------------------------------------------------- #
    @property
    def free_pages(self) -> int:
        """Pages nobody holds a reference on: loose, in whole free runs, and
        earmarked."""
        return (len(self._free) + self.run_blocks * len(self._free_runs)
                + self._earmarked)

    @property
    def free_blocks(self) -> int:
        """Free capacity in blocks of ALL layers (a page of each group)."""
        return self.free_pages // self.n_groups

    @property
    def blocks_in_use(self) -> int:
        """Pages in use in the caller's unit, blocks of ALL layers (rounded
        up): a share of ``num_blocks / n_groups``."""
        return -(-((self.num_blocks - 1) - self.free_pages) // self.n_groups)

    def blocks_for_tokens(self, n_tokens: int) -> int:
        return -(-max(0, int(n_tokens)) // self.block_size)

    def first_live_block(self, group: int, resident: int) -> int:
        """The oldest logical block of ``group`` a sequence keeps with
        ``resident`` tokens behind it: the one that holds the oldest key a
        query at position ``resident`` or later can still see, or, where the
        ring grows in runs, the first of that block's run."""
        w = self.windows[group]
        if w is None:
            return 0
        first = max(0, resident - w + 1) // self.block_size
        return first - first % self.run_blocks if self._in_runs[group] else first

    def pages_for_tokens(self, n_tokens: int, resident: int = 0) -> int:
        """Pages a sequence holds, over all groups, with ``resident`` tokens
        behind it and blocks up to ``n_tokens``."""
        need = self.blocks_for_tokens(n_tokens)
        return sum(min(need - self.first_live_block(g, resident), self.widths[g])
                   for g in range(self.n_groups))

    def capacity_tokens(self) -> int:
        """Largest single-sequence footprint this arena can ever hold."""
        return min((self.num_blocks - 1) // self.n_groups,
                   self.max_blocks_per_seq) * self.block_size

    def can_allocate(self, seq_id, n_tokens: int) -> bool:
        held = sum(len(r.blocks) for r in self._owned.get(seq_id, ()))
        return self.pages_for_tokens(n_tokens) - held <= self.free_pages

    # -- lifecycle --------------------------------------------------------- #
    def allocate(self, seq_id, n_tokens: int, resident: int = 0) -> bool:
        """Grow ``seq_id``'s block lists to cover ``n_tokens`` logical
        tokens, ``resident`` of which are behind every query still to come:
        a window group first gives back the blocks no such query can see,
        and grows no further than its ring (``widths``) holds — a long
        prompt's later chunks ask again as they come.  Returns False when
        the free list cannot cover the growth — the scheduler then evicts a
        victim and retries.
        Raises when a single sequence exceeds ``max_blocks_per_seq``.

        Partial-growth contract: a failed growth is all-or-nothing.  The
        free-list check happens before any block is popped, so on False a
        nonempty owner's lists are as they were after the giving back (the
        scheduler may already have written KV into those blocks; mutating
        them here would orphan live device state), and an owner that was
        empty is removed rather than left as a zero-block entry.  Every free
        page can be taken, an earmarked one too (the class docstring), so the
        check is the same count under any ``run_blocks``, and no earmark is
        touched by a growth that fails."""
        bs = self.block_size
        need = -(-max(0, int(n_tokens)) // bs)
        if need > self.max_blocks_per_seq:
            raise ArenaExhausted(
                f"sequence needs {need} blocks > max_blocks_per_seq "
                f"{self.max_blocks_per_seq}")
        runs = self._owned.get(seq_id)
        new = runs is None
        if new:
            runs = [_Run() for _ in range(self.n_groups)]
        slot = self._slot.get(seq_id)
        total = 0
        for g, (run, window, width) in enumerate(
                zip(runs, self.windows, self.widths)):
            if window is not None:
                self._give_back(run, self.first_live_block(g, resident), g, slot)
            run.grow = min(need, run.first + width) - run.first - len(run.blocks)
            if run.grow > 0:
                total += run.grow
        if total == 0:                  # every decode step inside a block
            return True
        if total > self.free_pages:
            return False
        if new:
            self._owned[seq_id] = runs
        any_runs = self.run_blocks > 1
        for g, (run, window) in enumerate(zip(runs, self.windows)):
            end = run.end
            if self._in_runs[g]:
                self._grow_in_runs(run, run.grow)
            else:
                for _ in range(run.grow):
                    b = self._take_loose() if any_runs else self._free.popitem()[0]
                    self._refs[b] = 1
                    run.blocks.append(b)
            if run.grow > 0 and slot is not None:
                self._record(g, slot, end, run.blocks[end - run.first:])
            if run.grow > 0 and window is None:
                self.pages_full += run.grow
            elif run.grow > 0:
                self.pages_window += run.grow
        return True

    def _give_back(self, run: _Run, first_live: int, group: int,
                   slot: Optional[int]) -> None:
        """A window group's blocks below ``first_live`` return to the pool
        (whole tiles where the ring grows in runs: ``first_live`` is a run's
        first block, and ``run.first`` stays one)."""
        n = min(max(0, first_live - run.first), len(run.blocks))
        if self._in_runs[group]:
            G = self.run_blocks
            n -= n % G
            whole = sum(self._is_run(run.blocks[i:i + G]) for i in range(0, n, G))
            run.tiles_run -= whole
            self.tiles_run -= whole
            self.tiles_held -= n // G
        for b in run.blocks[:n]:
            self.unref(b)
        if n and slot is not None:
            self._record(group, slot, run.first, [self.TRASH] * n)
        del run.blocks[:n]
        run.first += n
        run.given_back += n
        self.pages_window -= n
        self.given_back_total += n
        self.given_back_ever += n

    # -- runs (``run_blocks > 1`` only) ------------------------------------- #
    def _take_loose(self) -> int:
        """A free block for whoever has no run to take it from: a loose one;
        else a whole free run is broken up; else the youngest earmark loses
        its last block."""
        G = self.run_blocks
        if not self._free:
            if not self._free_runs:
                base, mark = next(reversed(self._earmarks.items()))
                mark[2] -= 1
                self._earmarked -= 1
                if mark[1] == mark[2]:
                    del self._earmarks[base]
                return mark[2]
            base = self._free_runs.pop()
            self._free.update(dict.fromkeys(range(base + G - 1, base - 1, -1)))
            self._loose_in[base] = G
        b, _ = self._free.popitem()
        self._loose_in[b - b % G] -= 1
        return b

    def _is_run(self, tile: List[int]) -> bool:
        """``tile``, ``run_blocks`` blocks of a table, lies together in order."""
        return tile == list(range(tile[0], tile[0] + self.run_blocks))

    def _grow_in_runs(self, run: _Run, n: int) -> None:
        """``n`` more blocks for ``run`` from LOGICAL block ``run.end`` on (a
        ring's first block is not block 0), each tile of its table a whole
        free run while there is one."""
        G, blocks = self.run_blocks, run.blocks
        for i in range(run.end, run.end + n):
            j = i % G
            # an open earmark of its own continues the table: the run whose
            # first ``j`` blocks are the table's last ``j``
            mark = self._earmarks.get(blocks[-1] - j + 1) if j else None
            if mark is not None and mark[0] is run:
                b = mark[1]
                mark[1] += 1
                self._earmarked -= 1
                if mark[1] == mark[2]:
                    del self._earmarks[b - j]
            elif j == 0 and self._free_runs:
                b = self._free_runs.pop()
                self._earmarks[b] = [run, b + 1, b + G]
                self._earmarked += G - 1
            else:
                b = self._take_loose()
            self._refs[b] = 1
            blocks.append(b)
            self._count_tile(run, i)

    def _count_tile(self, run: _Run, i: int) -> None:
        """Logical block ``i`` has joined ``run``."""
        blocks, k = run.blocks, i - run.first
        run.streak = run.streak + 1 if k and blocks[k] == blocks[k - 1] + 1 else 1
        j = i % self.run_blocks
        if j == 0:
            self.tiles_held += 1
        elif j == self.run_blocks - 1 and run.streak >= self.run_blocks:
            run.tiles_run += 1
            self.tiles_run += 1

    def _release(self, block: int) -> None:
        """``block`` has lost its last reference: it is loose, and with it
        its run may be whole again."""
        self._free[block] = None
        G = self.run_blocks
        if G > 1:
            base = block - block % G
            n = self._loose_in[base] = self._loose_in.get(base, 0) + 1
            if n == G:
                for b in range(base, base + G):
                    del self._free[b]
                del self._loose_in[base]
                self._free_runs.append(base)

    def _drop_earmark(self, run: _Run) -> None:
        """What was earmarked for ``run`` (it is being freed) is loose."""
        G = self.run_blocks
        j = run.end % G
        base = run.blocks[-1] - j + 1 if j else None
        mark = self._earmarks.get(base)
        if mark is not None and mark[0] is run:
            del self._earmarks[base]
            self._earmarked -= mark[2] - mark[1]
            for b in range(mark[2] - 1, mark[1] - 1, -1):
                self._release(b)

    def free(self, seq_id) -> int:
        """Drop ``seq_id``'s reference on every owned block; blocks whose
        last reference this was return to the free list.  Idempotent on
        unknown ids (a finished-then-evicted race is not an error)."""
        n = 0
        slot = self._slot.pop(seq_id, None)
        if slot is not None:
            # the row goes to trash whole, whatever was still to be said of it
            self._edits.pop(slot, None)
            self._cleared.add(slot)
        for g, run in enumerate(self._owned.pop(seq_id, ())):
            if self._in_runs[g] and run.blocks:
                self._drop_earmark(run)
                self.tiles_held -= -(-len(run.blocks) // self.run_blocks)
                self.tiles_run -= run.tiles_run
            # unref in reverse logical order so unshared blocks re-enter the
            # LIFO free list in the same order the pre-refcount free() used
            for b in reversed(run.blocks):
                self.unref(b)
            n += len(run.blocks)
            if self.windows[g] is None:
                self.pages_full -= len(run.blocks)
            else:
                self.pages_window -= len(run.blocks)
                self.given_back_total -= run.given_back
        return n

    def evict(self, seq_id) -> int:
        """Preemption-path free: same reclamation, counted separately so
        telemetry can distinguish completion from eviction."""
        n = self.free(seq_id)
        if n:
            self.eviction_count += 1
        return n

    # -- sharing (prefix cache) -------------------------------------------- #
    def ref(self, block: int) -> None:
        """Add a reference to a live block (prefix-cache pin or attach)."""
        assert block in self._refs, f"ref on non-live block {block}"
        self._refs[block] += 1

    def unref(self, block: int) -> bool:
        """Drop one reference; returns True when the block was actually
        freed (last reference gone → back on the free list)."""
        refs = self._refs.get(block)
        assert refs is not None and refs > 0, f"unref on dead block {block}"
        if refs > 1:
            self._refs[block] = refs - 1
            return False
        del self._refs[block]
        self._release(block)
        return True

    def adopt(self, seq_id, blocks: List[int]) -> None:
        """Attach already-live (cached-prefix) blocks as ``seq_id``'s
        logical prefix, copy-free: each gains a reference.  Must precede
        any private growth — the shared blocks are the sequence's first
        logical blocks, and they are full by construction, so every later
        write lands past them in private blocks (structural COW).  One
        group only: a shared block holds every layer's keys."""
        assert self.n_groups == 1, "a prefix is shared under one group only"
        assert seq_id not in self._owned, (
            f"adopt must precede private growth for {seq_id}")
        for b in blocks:
            self.ref(b)
        run = _Run()
        run.blocks = list(blocks)
        if self.run_blocks > 1:
            for i in range(len(blocks)):
                self._count_tile(run, i)
        self._owned[seq_id] = [run]
        self.pages_full += len(blocks)

    def owned_blocks(self, seq_id, group: int = 0) -> List[int]:
        """Copy of ``seq_id``'s physical block list in ``group``, logical
        order (from the group's oldest live block)."""
        runs = self._owned.get(seq_id)
        return list(runs[group].blocks) if runs else []

    # -- the slots' tables as edits (the engine's device tables) ------------ #
    def bind(self, seq_id, slot: int) -> None:
        """``seq_id`` decodes in ``slot`` until it is freed: row ``slot`` of
        the engine's tables is its :meth:`block_table` from now on.  What it
        holds already (admission allocates a prompt's blocks, and adopts a
        cached prefix, before a slot is chosen) is recorded here."""
        assert slot not in self._slot.values(), f"slot {slot} is taken"
        self._slot[seq_id] = slot
        for g, run in enumerate(self._owned.get(seq_id, ())):
            self._record(g, slot, run.first, run.blocks)

    def _record(self, group: int, slot: int, first: int, blocks) -> None:
        """Logical blocks ``first ..`` of ``slot``'s run in ``group`` are now
        ``blocks`` (column ``logical % width``, as :meth:`block_table`)."""
        row, width = self._edits.setdefault(slot, {}), self.widths[group]
        for logical, b in enumerate(blocks, first):
            row[(group, logical % width)] = b

    def drain_edits(self):
        """What changed in the slots' tables since the last call, in the
        order it applies: the slots whose row is all trash again, then
        ``(group, slot, column, block)`` entries, each entry once (the last
        word on it)."""
        cleared = list(self._cleared)
        edits = [(g, slot, col, b) for slot, row in self._edits.items()
                 for (g, col), b in row.items()]
        self._cleared, self._edits = set(), {}
        return cleared, edits

    def slot_tables(self, num_slots: int) -> List[np.ndarray]:
        """Every slot's tables whole, ``[num_slots, widths[g]]`` int32 a
        group: what all edits so far add up to (a slot nobody holds is all
        trash).  The engine sends these when a step's edits outgrow its one
        upload; pending edits are in them, so it drains and drops those."""
        tables = [np.full((num_slots, w), self.TRASH, np.int32)
                  for w in self.widths]
        for seq_id, slot in self._slot.items():
            for g, table in enumerate(tables):
                table[slot] = self.block_table(seq_id, g)
        return tables

    # -- table / write-map construction (the reload, and the tests' oracle) - #
    def block_table(self, seq_id, group: int = 0) -> np.ndarray:
        """[widths[group]] int32 physical ids, trash-padded; a window
        group's is the ring (logical block ``b`` in column ``b % width``)."""
        width = self.widths[group]
        table = np.full((width,), self.TRASH, np.int32)
        runs = self._owned.get(seq_id)
        if runs:
            run = runs[group]
            if self.windows[group] is None:
                table[:len(run.blocks)] = run.blocks
            elif run.blocks:
                table[np.arange(run.first, run.end) % width] = run.blocks
        return table

    def write_map(self, seq_id, start: int, n_tokens: int, group: int = 0):
        """Physical (block, offset) for tokens at logical positions
        ``start .. start + n_tokens - 1``.
        → ([n_tokens] int32 blocks, [n_tokens] int32 offsets)."""
        runs = self._owned.get(seq_id)
        run = runs[group] if runs else _Run()
        pos = start + np.arange(int(n_tokens))
        logical = pos // self.block_size
        assert not n_tokens or (run.first <= logical[0]
                                and logical[-1] < max(run.end, 1)), (
            f"write outside allocation: positions {pos[0]}..{pos[-1]} need "
            f"blocks {logical[0]}..{logical[-1]}, own {run.first}..{run.end - 1}")
        phys = np.asarray([run.blocks[b - run.first] if b < run.end
                           else self.TRASH for b in logical], np.int32)
        return phys, (pos % self.block_size).astype(np.int32)

    # -- invariants (tests) ------------------------------------------------ #
    def check_consistent(self):
        """Every physical block is exactly one of: trash, free, or live
        with refcount >= 1 — and a live block's references account for
        every sequence holding it (sharing beyond the owner count is the
        prefix cache's pin); a run fits its group's table; the page counts
        are the runs'.  Free is: loose, in a whole free run, or earmarked
        (an earmark is the rest of the aligned run whose first blocks end its
        owner's table), and the tile counts are the tables', a ring's as a
        table's that only grows.  Raises
        AssertionError on violation."""
        owners: Dict[int, int] = {}
        full = window = given_back = 0
        G = self.run_blocks
        held = in_order = 0
        for seq_id, runs in self._owned.items():
            in_seq = set()
            for g, run in enumerate(runs):
                assert len(run.blocks) <= self.widths[g], (
                    f"{seq_id}: {len(run.blocks)} blocks in group {g}'s "
                    f"table of {self.widths[g]}")
                assert run.first == 0 or self.windows[g] is not None
                if self._in_runs[g]:
                    assert run.first % G == 0, (
                        f"{seq_id}: group {g}'s ring starts inside a run")
                    tiles = [run.blocks[i:i + G]
                             for i in range(0, len(run.blocks), G)]
                    n = sum(map(self._is_run, tiles))
                    assert n == run.tiles_run, f"{seq_id}: runs miscounted"
                    held, in_order = held + len(tiles), in_order + n
                if self.windows[g] is None:
                    full += len(run.blocks)
                else:
                    window += len(run.blocks)
                    given_back += run.given_back
                for b in run.blocks:
                    assert 0 < b < self.num_blocks, f"bad block id {b}"
                    assert b not in in_seq, f"block {b} twice in {seq_id}"
                    in_seq.add(b)
                    owners[b] = owners.get(b, 0) + 1
        assert (full, window, given_back) == (
            self.pages_full, self.pages_window, self.given_back_total), (
            "page counts drifted from the runs")
        for b, refs in self._refs.items():
            assert 0 < b < self.num_blocks, f"bad live block id {b}"
            assert refs >= 1, f"live block {b} with refcount {refs}"
        for b, n in owners.items():
            assert n <= self._refs.get(b, 0), (
                f"block {b}: {n} owners > {self._refs.get(b, 0)} refs")
        assert (held, in_order) == (self.tiles_held, self.tiles_run), (
            "tile counts drifted from the tables")
        free = set(self._free)
        for base in self._free_runs:
            assert base % G == 0 and 0 < base <= self.num_blocks - G, (
                f"no run starts at {base}")
            free.update(range(base, base + G))
        for base, (run, lo, hi) in self._earmarks.items():
            assert base % G == 0 and base < lo < hi <= base + G, (
                f"earmark {base}: {lo}..{hi}")
            assert run.blocks[-(lo - base):] == list(range(base, lo)) and (
                run.end % G == lo - base), (
                f"earmark {base} does not continue its owner's table")
            assert any(run is r for runs in self._owned.values() for r in runs)
            free.update(range(lo, hi))
        assert self._earmarked == sum(
            hi - lo for _, lo, hi in self._earmarks.values())
        assert len(free) == self.free_pages, "a block is free twice"
        for base in range(0, self.num_blocks, G) if G > 1 else ():
            n = sum(b in self._free for b in range(base, base + G))
            assert n == self._loose_in.get(base, 0) and n < G, (
                f"run {base}: {n} loose, counted {self._loose_in.get(base, 0)}")
        assert not (free & self._refs.keys()), (
            f"blocks both free and live: {sorted(free & self._refs.keys())}")
        assert self.TRASH not in free and self.TRASH not in self._refs, (
            "trash block handed out")
        covered = {self.TRASH} | free | self._refs.keys()
        assert len(covered) == self.num_blocks, (
            f"leaked blocks: {self.num_blocks - len(covered)}")


def init_arena(cfg, num_blocks: int, block_size: int, dtype=None):
    """Device arena for ``models/gpt.py:gpt_paged_step``, an array for each
    entry of the model's cache spec (``cfg.cache_lanes``: lanes a token a
    layer): ``[n_layer / P, num_blocks * P, block_size, lanes]`` for a layer
    pattern of ``P`` kinds (``num_blocks`` counts blocks of ALL layers; a
    page holds a block of the ``n_layer / P`` layers of one group).  ``P =
    1``: ``[n_layer, num_blocks, ...]``.  A hybrid stack pages its sparse
    layers alone, a K/V head a page: ``[sparse layers, num_blocks * Hkv,
    block_size, head_dim]`` (``cfg.arena_layout`` says which; what such a
    stack caches beside K and V is ``models/hybrid.py:init_aux``'s).  Always
    a PAIR, as the step takes it: K and V, or a latent cache's one array and
    None."""
    import jax.numpy as jnp
    dtype = dtype or cfg.dtype
    layers, pages, lanes = cfg.arena_layout
    arrays = [jnp.zeros((layers, num_blocks * pages, block_size, n), dtype)
              for n in lanes]
    return tuple(arrays + [None] * (2 - len(arrays)))


def arena_bytes(cfg, num_blocks: int, block_size: int, dtype_bytes: int = 2) -> int:
    """Bytes :func:`init_arena` holds: every array of the cache spec."""
    layers, pages, lanes = cfg.arena_layout
    return layers * pages * num_blocks * block_size * sum(lanes) * dtype_bytes
