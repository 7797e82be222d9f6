"""On-device timing of one indexed layer's SELECTION alone: ``jax.lax.top_k``
(a sort, what a decode row took for its positions until PR 63) and the
bisection in plain ``jax.numpy`` (``models/hybrid.py:chosen_tokens`` where the
kernel's gate refuses: every pass its own fusions over the scores in HBM)
against the kernel (``ops/pallas/index_select.py``: the whole bisection with
the rows' scores in VMEM) and, for a decode row, the positions by rank behind
it (``chosen_positions``).

Run standalone on a TPU host (``chiprun --chips 1 -- python
tools/index_select_probe.py``); any other platform is an error (exit 1;
``--rehearse`` runs the control flow on the CPU at a small size through the
interpreter).  At the shapes of ``keye-vl-2.0-30b-a3b.serve-long-indexed`` (8
decode rows, a prompt chunk of 512 in tiles of 128 queries) and of
``deepseek-v3.2-exp.serve-long-latent-indexed`` (12 decode rows, tiles of 32
queries, the chunk's extents of 5,760, 23,040 and 46,080 keys), 2,048 of
46,080 keys a row, and at MiniCPM-SALA's block selection (64 of 768 blocks,
32 decode rows and a chunk's 1,024: rows the kernel's gate refuses, timed
through the kernel all the same, which is what set the gate).  A timed program makes sixteen
selections one after another (or as many as half a GB of scores hold), so
what is read is the device's time and not the host's 0.2 ms a call.  Every
form's set is compared to the sort's: one that differs makes the exit code 1.
One JSON line at the end.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CHUNK, TABLE, TOPK, BS = 512, 46_080, 2_048, 64
# (what, rows that select together, rows in all, keys, k, positions wanted)
CALLS = [
    ("keye decode rows", 8, 8, TABLE, TOPK, True),
    ("deepseek decode rows", 12, 12, TABLE, TOPK, True),
    ("keye chunk", 128, CHUNK, TABLE, TOPK, False),
    ("deepseek chunk", 32, CHUNK, TABLE, TOPK, False),
    ("deepseek chunk, half the table", 32, CHUNK, TABLE // 2, TOPK, False),
    ("deepseek chunk, an eighth", 32, CHUNK, TABLE // 8, TOPK, False),
    ("sala decode rows", 32, 32, 768, 64, False),
    ("sala chunk", 1024, 1024, 768, 64, False),
]
REHEARSAL = [
    ("decode rows", 5, 5, 2176, 48, True),
    ("chunk", 16, 32, 2176, 48, False),
    ("blocks", 32, 32, 128, 16, False),
]


# selections one timed program makes one after another: a call from the host
# costs 0.2 ms, more than a decode row's selection itself
STACK = 16


def timed(fn, stack, repeats):
    """``fn`` over each of ``stack [STACK, n, T]`` in ONE program: -> (ms a
    selection, the first one's output)."""
    import jax
    run = jax.jit(lambda stack: jax.lax.map(fn, stack))
    out = jax.block_until_ready(run(stack))
    t = time.perf_counter()
    for _ in range(repeats):
        out = run(stack)
    jax.block_until_ready(out)
    return (1e3 * (time.perf_counter() - t) / repeats / stack.shape[0],
            jax.tree.map(lambda a: a[0], out))


def scores_of(rng, n, T, ties):
    """Seeded scores as the indexer leaves them: -inf past each row's own
    position, the rows of a chunk at consecutive positions; with ``ties`` a
    third are whole numbers, which puts equal scores AT the k-th place."""
    import numpy as np
    s = rng.normal(size=(n, T)).astype(np.float32) * 4
    if ties:
        s[:, ::3] = np.round(s[:, ::3]) + 0.0     # no -0: the sort calls it +0's equal
    last = (rng.integers(T // 2, T, n) if T < 4 * n
            else rng.integers(T // 2, T - n) + np.arange(n))
    s[np.arange(T)[None] > last[:, None]] = -np.inf
    return s


def bench(what, tile, n, T, k, positions, repeats, rng, ties):
    """One call's selection in each form -> a dict of times and of whether
    the forms chose the sort's set."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.ops import pallas
    from deepspeed_tpu.ops.pallas.index_select import index_select

    stack = 1 if repeats == 1 else max(1, min(STACK, (1 << 29) // (n * T * 4)))
    s = jnp.asarray(np.stack([scores_of(rng, n, T, ties) for _ in range(stack)]))
    tiles = lambda a: a.reshape(n // tile, tile, T)
    G = BS if T % BS == 0 else 1

    def sort(s):
        top, at = jax.lax.top_k(s, k)
        return at, top > -jnp.inf

    def by_tiles(choose):
        return lambda s: jax.lax.map(choose, tiles(s)).reshape(n, T)

    # the kernel itself, whatever its gate says of the shape: what the gate
    # is set by; the positions as the model takes them, through the gate
    forms = {"sort": (sort, False),
             "bisection": (by_tiles(lambda t: hybrid.chosen_tokens(t, k, G)), False),
             "kernel": (by_tiles(lambda t: index_select(t, k)[0] != 0), True)}
    if positions:
        for name, kernel in (("bisection", False), ("kernel", True)):
            forms[name + "_positions"] = (lambda s: hybrid.chosen_positions(s, k), kernel)
    rule = pallas.use_kernel
    admitted = hybrid.selects_in_vmem(tile, T, k)
    ms, out = {}, {}
    for name, (fn, kernel) in forms.items():
        # the rule is read while a form is traced, in its first call, and a
        # trace is kept by the function traced: a new one a form
        pallas.use_kernel = lambda name, kernel=kernel: kernel and rule(name)
        ms[name], out[name] = timed(lambda s, fn=fn: fn(s), s, repeats)
    pallas.use_kernel = rule
    at, real = (np.asarray(a) for a in out.pop("sort"))
    want = np.zeros((n, T), bool)
    want[np.arange(n)[:, None].repeat(k, 1)[real], at[real]] = True
    equal = {}
    for name, got in out.items():
        if name.endswith("_positions"):
            at, real = (np.asarray(a) for a in got)
            chose = np.zeros((n, T), bool)
            chose[np.arange(n)[:, None].repeat(k, 1)[real], at[real]] = True
            rising = bool(((np.diff(at, axis=1) > 0) | ~real[:, 1:]).all())
            equal[name] = rising and bool((chose == want).all()) and bool(
                (real.sum(1) == want.sum(1)).all())
        else:
            equal[name] = bool((np.asarray(got) == want).all())
    return {"what": what, "rows": n, "tile": tile, "keys": T, "k": k, "ties": ties,
            "stack": stack, "gate_admits": admitted, "ms": ms, "sets_equal": equal}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the control flow on the CPU, small, through the interpreter")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    platform = jax.devices()[0].platform
    if platform != ("cpu" if args.rehearse else "tpu"):
        print(f"FAIL: needs {'the CPU to rehearse' if args.rehearse else 'a TPU'}, "
              f"found {platform}")
        return 1
    if args.rehearse:
        from deepspeed_tpu.ops import pallas
        pallas.use_kernel = lambda kernel: True
    calls, repeats = (REHEARSAL, 1) if args.rehearse else (CALLS, args.repeats)
    rng = np.random.default_rng(args.seed)
    out = {"device": jax.devices()[0].device_kind, "rehearsal": args.rehearse, "calls": []}
    ok = True
    for i, call in enumerate(calls):
        r = bench(*call, repeats, rng, ties=i % 2 == 1)
        ok = ok and all(r["sets_equal"].values())
        print(f"{r['what']}: {r['rows']} rows in tiles of {r['tile']}, {r['k']} of "
              f"{r['keys']} (the gate {'admits' if r['gate_admits'] else 'REFUSES'}): " + ", ".join(f"{n} {ms:.3f} ms" for n, ms in r["ms"].items())
              + "; the sort's set: " + ", ".join(
                  f"{n} {'yes' if e else 'NO'}" for n, e in r["sets_equal"].items()))
        out["calls"].append(r)
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
