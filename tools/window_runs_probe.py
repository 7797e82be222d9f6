"""On-device timing of one step's WINDOW attention alone: ``paged_gqa_attention``
over the rings of a model's window groups, a copy a page against a copy a run.

Run standalone on a TPU host (``chiprun --chips 1 -- python
tools/window_runs_probe.py``); any other platform is an error (exit 1;
``--rehearse`` runs the control flow on the CPU at a tiny size through the
interpreter).  For each of the two serve cells with window groups, at the
cell's shapes (Trinity-Large-Preview: 48 query heads on 8 K/V heads of 128, 32
slots, a chunk of 512; SmallThinker-21B-A3B: 28 on 4, 32 slots, a chunk of
224; a window of 4,096 keys, pages of 16, THREE window groups of two layers in
an arena of the cell's pages), rows of 1,500 to 37,000 keys (16,000 under
SmallThinker's tables) whose rings the allocator itself laid down, a prompt
chunk at a time with the rows taking turns, it times the six calls of a decode step (and of a step that carries a
prompt chunk) four ways:

* ``pages``: the parent's program, the ring of single blocks and no flags;
* ``runs``: the ring in runs (``run_blocks`` the plan's ``run_pages``), a run
  given back whole, the flags of the tables: a tile is one copy an operand;
* ``first_tile_pages``: the other form of giving back, blocks given back
  singly: the entries below the window's first page are trash, so the first
  tile of every row is no run and comes page by page;
* ``cleared``: the ring in runs with every flag cleared, each tile of a run's
  size page by page (what is left of the gain without the single copy).

``runs`` and ``cleared`` must be equal to the bit; ``runs`` against the
parent's walk over ``runs``' own tables (no flags: from the window's first
page, a copy a page) is reported: the two cut a row's attend steps at other
keys and agree to rounding.  One JSON line at the end.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (query heads, K/V heads, slots, chunk, pages of one layer group in the arena,
# a full table's columns, the rows' longest context)
CELLS = {"trinity-large-preview": (48, 8, 32, 512, 49152, 2400, 37000),
         "smallthinker-21b-a3b": (28, 4, 32, 224, 57344, 1024, 16000)}
D, BS, WINDOW, GROUPS, LAYERS = 128, 16, 4096, 3, 2


def laid_down(lengths, chunk, pages, max_blocks, run_blocks, window, bs):
    """The rings of ``len(lengths)`` sequences as the allocator lays them
    down: admitted together, prefilled a chunk at a time in turns, so their
    runs interleave in the arena.  -> (tables a window group ``[rows,
    width]``, the allocator)."""
    import numpy as np
    from deepspeed_tpu.serving.kv_cache import PagedKVAllocator
    alloc = PagedKVAllocator(pages, bs, max_blocks, windows=(window,) * GROUPS,
                             chunk=chunk, run_blocks=run_blocks)
    for s, n in enumerate(lengths):
        assert alloc.allocate(s, n + 1)
    for start in range(0, max(lengths) + 1, chunk):
        for s, n in enumerate(lengths):
            if start <= n:
                assert alloc.allocate(s, n + 1, resident=min(start, n))
    for s, n in enumerate(lengths):
        assert alloc.allocate(s, n + 1, resident=n)
    alloc.check_consistent()
    return [np.stack([alloc.block_table(s, g) for s in range(len(lengths))])
            for g in range(GROUPS)], alloc


def timed(fn, *args, repeats):
    import jax
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / repeats, out


def bench(name, H, Hkv, slots, chunk, pages, lengths, window, max_blocks, bs,
          repeats):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.ops.pallas import decode_attention as da
    from deepspeed_tpu.serving.kv_cache import window_table_blocks

    dtype, lanes = jnp.bfloat16, Hkv * D
    # as ``init_serving``: the tile off the plan at the ring of single blocks,
    # the plan at the ring of that tile
    plan_at = lambda G: da.softmax_plan(
        H, Hkv, D, bs, window_table_blocks(window, chunk, bs, G), chunk, dtype,
        window=window)
    G = plan_at(1).run_pages
    assert G > 1, "the plan asks for no runs here"
    plan = plan_at(G)
    single, _ = laid_down(lengths, chunk, pages, max_blocks, 1, window, bs)
    in_runs, alloc = laid_down(lengths, chunk, pages, max_blocks, G, window, bs)
    assert plan.run_pages == G and in_runs[0].shape[1] % G == 0

    def given_back_singly(oldest):
        """The other form of giving back: what lies below the window's first
        page (of the oldest query a table serves, ``oldest[row]``) has gone
        back to the pool, its entries are trash."""
        tables = [t.copy() for t in in_runs]
        for t in tables:
            for s, n in enumerate(oldest):
                p0 = max(n - (window - 1), 0) // bs
                for b in range(p0 - p0 % G, p0):
                    t[s, b % t.shape[1]] = 0
        return tables

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    # an eighth of the arena drawn, eight times over: the whole arena in
    # memory at the cell's size, and no float32 copy of it on the way
    ka, va = (jnp.tile(jax.random.normal(k, (LAYERS, pages // 8, bs, lanes), dtype),
                       (1, 8, 1, 1)) for k in keys[:2])
    lens = jnp.asarray(lengths, jnp.int32)

    def step(ka, va, tables, flags, q, lens, chunk):
        """The window layers' attention of one step: every group's every
        layer, the outputs summed so that none is dropped."""
        out = 0.0
        for g in range(GROUPS):
            for layer in range(LAYERS):
                out = out + da.paged_layer_attention(
                    q, ka, va, jnp.int32(layer), tables[g], lens, window=window,
                    chunk=chunk, tile_runs=None if flags is None else flags[g])
        return out

    def ways(rows_of, oldest):
        """name -> (tables, flags) of the four ways and of ``pages_in_runs``
        (``runs``' own tables and no flags: the parent's walk over the same
        keys, what ``runs`` is held to), ``rows_of`` cutting a table to the
        program's rows."""
        tables = {"pages": single, "runs": in_runs,
                  "first_tile_pages": given_back_singly(oldest),
                  "cleared": in_runs, "pages_in_runs": in_runs}
        out = {}
        for way, groups in tables.items():
            groups = [jnp.asarray(rows_of(t), jnp.int32) for t in groups]
            flags = None
            if not way.startswith("pages"):
                flags = [plan.tile_runs(t, pages) for t in groups]
                if way == "cleared":
                    flags = [jnp.zeros_like(f) for f in flags]
            out[way] = (groups, flags)
        return out

    result = {"cell": name, "run_pages": G, "ring": {
        "single": int(single[0].shape[1]), "in_runs": int(in_runs[0].shape[1])},
              "tile_runs_pct": 100.0 * alloc.tiles_run / alloc.tiles_held,
              "pages_window": {"single": int(sum((t != 0).sum() for t in single)),
                               "in_runs": int(alloc.pages_window)}}
    # bytes the window layers need a decode step: each row's visible keys
    # (at most the window), K and V, every window layer
    visible = sum(min(n + 1, window) for n in lengths)
    need = visible * 2 * lanes * 2 * GROUPS * LAYERS
    for program, n_chunk in (("decode", 0), ("decode_and_chunk", chunk)):
        if n_chunk:
            # the chunk: the longest row's next ``chunk`` tokens would want
            # pages it has not got; take a row's LAST chunk instead
            row = int(np.argmax(lengths))
            start = lengths[row] + 1 - n_chunk
            rows_of = lambda t: np.concatenate([t, np.tile(t[row], (n_chunk, 1))])
            program_lens = jnp.concatenate(
                [lens, start + jnp.arange(n_chunk, dtype=jnp.int32)])
            oldest = [start if s == row else n for s, n in enumerate(lengths)]
        else:
            rows_of, program_lens, oldest = (lambda t: t), lens, lengths
        q = jax.random.normal(keys[2], (slots + n_chunk, 1, H, D), dtype)
        outs, times = {}, {}
        for way, (tables, flags) in ways(rows_of, oldest).items():
            fn = jax.jit(lambda ka, va, tables, flags, q, lens, n=n_chunk: step(
                ka, va, tables, flags, q, lens, n))
            times[way], outs[way] = timed(fn, ka, va, tables, flags, q,
                                          program_lens, repeats=repeats)
        f32 = lambda a: np.asarray(a.astype(jnp.float32))
        gap = lambda a, b: float(np.abs(f32(outs[a]) - f32(outs[b])).max())
        result[program] = {
            "ms": {way: 1e3 * t for way, t in times.items()},
            "runs_over_pages": times["runs"] / times["pages"],
            "first_tile_pages_over_pages": times["first_tile_pages"] / times["pages"],
            "runs_equal_cleared_to_the_bit": gap("runs", "cleared") == 0.0,
            "runs_equal_first_tile_pages_to_the_bit": gap("runs", "first_tile_pages") == 0.0,
            "gap_runs_pages": gap("runs", "pages_in_runs"),
            "scale": float(np.abs(f32(outs["runs"])).max())}
        if not n_chunk:
            result[program]["needed_GB_per_s"] = {
                way: need / t / 1e9 for way, t in times.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cells", nargs="+", default=list(CELLS), choices=list(CELLS))
    ap.add_argument("--repeats", type=int, default=30)
    ap.add_argument("--rehearse", action="store_true",
                    help="the control flow on the CPU, tiny, through the interpreter")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    platform = jax.devices()[0].platform
    if platform != ("cpu" if args.rehearse else "tpu"):
        print(f"FAIL: needs {'the CPU to rehearse' if args.rehearse else 'a TPU'}, "
              f"found {platform}")
        return 1
    out = {"device": jax.devices()[0].device_kind, "rehearsal": args.rehearse,
           "cells": []}
    rng = np.random.default_rng(0)
    ok = True
    for name in args.cells:
        H, Hkv, slots, chunk, pages, max_blocks, longest = CELLS[name]
        if args.rehearse:
            from deepspeed_tpu.ops import pallas
            from deepspeed_tpu.ops.pallas import decode_attention as da
            pallas.use_kernel = lambda kernel: True
            da._TILE_ROWS, da._TILE_PAGES, da._RUN_TILE_ROWS = 32, 2, 64
            lengths = [int(n) for n in rng.integers(20, 800, 3)]
            result = bench(name, 4, 2, 3, 32, 1024, lengths, 128, 64, BS, 1)
        else:
            lengths = [int(n) for n in rng.integers(1500, longest, slots)]
            result = bench(name, H, Hkv, slots, chunk, pages, lengths, WINDOW,
                           max_blocks, BS, args.repeats)
        for program in ("decode", "decode_and_chunk"):
            r = result[program]
            ok = ok and r["runs_equal_cleared_to_the_bit"]
            print(f"{name} {program}: " + ", ".join(
                f"{way} {ms:.3f} ms" for way, ms in r["ms"].items())
                + f"; runs/pages {r['runs_over_pages']:.3f}, equal to the bit "
                f"{r['runs_equal_cleared_to_the_bit']}, gap to pages "
                f"{r['gap_runs_pages']:.2e} of {r['scale']:.2f}")
        out["cells"].append(result)
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
