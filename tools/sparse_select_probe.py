"""On-device timing of one sparse layer's SELECTION alone: the plain
``jax.numpy`` form (``models/hybrid.py:_select``: the heads' scores in HBM, a
``top_k`` and a sort) against the kernel and the bisection
(``_select_on_chip``: ``ops/pallas/sparse_select.py`` and ``chosen_tokens``).

Run standalone on a TPU host (``chiprun --chips 1 -- python
tools/sparse_select_probe.py``); any other platform is an error (exit 1;
``--rehearse`` runs the control flow on the CPU at a small size through the
interpreter).  At the shapes of ``minicpm-sala-9b.serve-long-mixed``
(MiniCPM-SALA: 16 query heads of 128 lanes on each of 2 K/V heads, tables of
768 pages of 64 keys and so 3,072 compressed keys, the top 64 blocks) it
times the two programs of a step's sparse layer: a prompt chunk's 512 queries
under ONE table, at three depths of the prompt, and the 16 decode rows under
their own tables; of the second form also its two halves, the block scores
and the choice.  The chosen blocks are compared to the entry; a row whose
blocks differ must differ by a near tie (the two forms sum a softmax in
another order on the chip: the blocks swapped score within ``1e-5`` of each
other in the reference's own scores), or the exit code is 1.  One JSON line
at the end.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the cell's chunk and slots; its contexts are 33,000 to 41,000 tokens
CHUNK, SLOTS, TABLE, BS = 512, 16, 768, 64
STARTS = (8192, 24576, 40448)
NEAR = 1e-5


def timed(fn, *args, repeats):
    import jax
    out = jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / repeats, out


def bench(cfg, n, shared, positions, MB, repeats, seed):
    """One call's selection both ways -> a dict of times and of what the two
    forms chose."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.ops.pallas.sparse_select import sparse_block_scores

    sp, Hkv, D = cfg.sparse, cfg.kv_heads, cfg.head_dim
    g, r = cfg.n_head // Hkv, hybrid.keys_a_page(cfg)
    assert hybrid.selects_on_chip(cfg, n, MB, shared), "the kernel's gate refuses the shape"
    kq, kk = jax.random.split(jax.random.PRNGKey(seed))
    # a normed query of 128 lanes against the mean of 32 normed keys
    q = jax.random.normal(kq, (n, Hkv, g, D), jnp.bfloat16)
    kc = (jax.random.normal(kk, (1 if shared else n, MB, r * Hkv * D), jnp.float32)
          / np.sqrt(sp.kernel) * 4).astype(jnp.bfloat16)
    at = jnp.asarray(positions, jnp.int32)
    rows = lambda kc: kc.reshape(kc.shape[0], -1, Hkv, D)
    forms = {
        "reference": lambda q, kc, at: hybrid._select(cfg, q, rows(kc), at, BS),
        "kernel": lambda q, kc, at: hybrid._select_on_chip(cfg, q, kc, at, BS),
        "kernel_scores": lambda q, kc, at: sparse_block_scores(
            q, kc, at, stride=sp.stride, block=BS, init_blocks=sp.init_blocks,
            window=sp.window),
        "reference_scores": lambda q, kc, at: hybrid._block_scores(cfg, q, rows(kc), at, BS),
    }
    ms, out = {}, {}
    for name, fn in forms.items():
        ms[name], out[name] = timed(jax.jit(fn), q, kc, at, repeats=repeats)
    (b0, a0), (b1, a1) = out["reference"], out["kernel"]
    b0, b1 = np.asarray(b0), np.asarray(b1)
    score = np.asarray(out["reference_scores"])
    unequal = np.argwhere((b0 != b1).any(axis=-1))
    worst = 0.0
    for i, h in unequal:
        swapped = np.setxor1d(b0[i, h], b1[i, h])
        worst = max(worst, float(np.ptp(score[i, h, swapped])
                                 / max(np.abs(score[i, h, swapped]).max(), 1e-30)))
    finite = np.isfinite(score)
    got = np.asarray(out["kernel_scores"])
    gap = np.abs(np.where(finite, got, 0.0) - np.where(finite, score, 0.0)).max()
    return {"queries": n, "shared": shared, "first_position": int(positions[0]),
            "ms": ms, "kernel_over_reference": ms["kernel"] / ms["reference"],
            "rows": int(b0.shape[0] * b0.shape[1]), "rows_unequal": int(len(unequal)),
            "worst_swap_gap": worst, "at_equal": bool((np.asarray(a0) == np.asarray(a1)).all()),
            "inf_equal": bool((got[~finite] == score[~finite]).all()),
            "scores_gap": float(gap), "scores_scale": float(np.abs(score[finite]).max())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--repeats", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the control flow on the CPU, small, through the interpreter")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.models import gpt
    platform = jax.devices()[0].platform
    if platform != ("cpu" if args.rehearse else "tpu"):
        print(f"FAIL: needs {'the CPU to rehearse' if args.rehearse else 'a TPU'}, "
              f"found {platform}")
        return 1
    cfg = gpt.minicpm_sala_config(mixer_types=["minicpm4"], first_layer=9,
                                  dtype=jnp.bfloat16)
    rng = np.random.default_rng(args.seed)
    if args.rehearse:
        from deepspeed_tpu.ops import pallas
        pallas.use_kernel = lambda kernel: True
        calls = [(32, True, 8200 + np.arange(32), 256),
                 (3, False, np.asarray([100, 8191, 16000]), 256)]
        repeats = 1
    else:
        calls = [(CHUNK, True, s + np.arange(CHUNK), TABLE) for s in STARTS]
        calls.append((SLOTS, False, rng.integers(33000, 41000, SLOTS), TABLE))
        repeats = args.repeats
    out = {"device": jax.devices()[0].device_kind, "rehearsal": args.rehearse, "calls": []}
    ok = True
    for n, shared, positions, MB in calls:
        r = bench(cfg, n, shared, positions, MB, repeats, args.seed)
        ok = ok and r["at_equal"] and r["inf_equal"] and r["worst_swap_gap"] < NEAR
        print(f"{n} queries, {'one table' if shared else 'a table a row'}, from "
              f"{r['first_position']}: " + ", ".join(
                  f"{name} {ms:.3f} ms" for name, ms in r["ms"].items())
              + f"; {r['rows_unequal']} of {r['rows']} rows chose other blocks "
              f"(worst swap {r['worst_swap_gap']:.1e}), scores within "
              f"{r['scores_gap']:.1e} of {r['scores_scale']:.2f}")
        out["calls"].append(r)
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
