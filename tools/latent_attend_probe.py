"""On-device timing of ONE layer's attention over the rows of the latent cache
a prompt chunk's queries chose, alone, at the shapes of
``deepseek-v3.2-exp.serve-long-latent-indexed`` (DeepSeek-V3.2-Exp: 512
queries of 128 heads absorbed into 640 lanes, a sequence of 35,000 cached
vectors under a table of 720 pages of 64, 2,048 chosen a query), in the forms
ISSUE 61 asks to be measured against each other:

* ``gather``: the chosen rows gathered out of the pages, a tile of queries at
  a time (512 x 2,048 rows of 1,280 B, 1.34 GB a layer), and attended densely
  (``ops/pallas/indexed_attention.py:chosen_latent_attention``, what a decode
  row runs);
* ``kernel``: what ``models/gpt.py:gpt_paged_step`` ships for the chunk, a
  dense pass over the sequence's latent, read once, under the selection's
  mask, in the PLAIN form (``masked_latent_attention``: every head's keys and
  values made from the latent by XLA, a group of heads at a time, and attended
  by the Pallas kernel, all 512 queries a grid step; 3.5 T operations a layer
  at the whole table), at the whole table and at each shorter extent of
  ``gpt.CHUNK_EXTENTS`` with the bisection over the same keys, the chunk at the
  extent's END (no tile of keys skipped) and in its MIDDLE (``skip``: the
  tiles past the chunk's last position neither fetched nor computed);
* ``masked``: the same pass through XLA alone, which shipped before the kernel
  and is its reference (``masked_latent_attention_reference``: 128 queries a
  tile, a tile's float32 scores out to memory and back), at the same extents;
  and ``masked_absorbed``, the same pass in the decode rows' form (the 128
  heads' scores over the latent itself, 7.0 T);

and beside them the two forms of the chunk's SELECTION at this indexer's 64
heads (``jax.lax.top_k``: a sort, what gave a gather its positions until PR 63
(``tools/index_select_probe.py`` times what does since); ``chosen_tokens``:
the bisection's mask, which the masked pass takes), a tile of queries at a
time, and the 12 decode rows' gather and attend.  The forms of the attend are
compared, the kernel with its reference at every extent with and without the
skip: the largest difference must be rounding's (exit 1 otherwise).

Run standalone on a TPU host (``chiprun --chips 1 -- python
tools/latent_attend_probe.py``); any other platform is an error (exit 1;
``--rehearse`` runs the control flow on the CPU at a small size).  One JSON
line at the end.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CHUNK, SLOTS, TABLE, BS = 512, 12, 720, 64
H, W, R, K, CONTEXT = 128, 640, 512, 2048, 35_000
DN, DR, DV = 128, 64, 128           # a head's lanes: no position, rotated, value
INDEX_HEADS = 64
GATHER_TILE = 64        # queries whose chosen rows the gather form copies together


def timed(fn, *args, repeats):
    import jax
    out = jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / repeats, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=61)
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.models import gpt, hybrid
    from deepspeed_tpu.ops import pallas
    from deepspeed_tpu.ops.pallas.indexed_attention import (
        LATENT_KERNEL, chosen_latent_attention, masked_latent_attention,
        masked_latent_attention_reference)

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print(f"FAIL: needs a TPU, found {jax.devices()[0].platform}")
        return 1
    if args.rehearse:           # the kernel through the interpreter, where its gate admits
        pallas.use_kernel = lambda name: name == LATENT_KERNEL
    chunk, slots, table, heads, k, context, dtype, repeats = (
        (32, 2, 8, 4, 48, 400, jnp.float32, 1) if args.rehearse else
        (CHUNK, SLOTS, TABLE, H, K, CONTEXT, jnp.bfloat16, args.repeats))
    T, scale = table * BS, (DN + DR) ** -0.5
    blocks = 1 + (slots + 1) * table
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    pages = jnp.pad(jax.random.normal(keys[0], (blocks, BS, R + DR), dtype),
                    ((0, 0), (0, 0), (0, W - R - DR)))
    plain = jax.random.normal(keys[1], (chunk, heads, DN + DR), dtype)
    w_uk = jax.random.normal(keys[4], (R, heads, DN), dtype) * R ** -0.5
    w_uv = jax.random.normal(keys[5], (R, heads, DV), dtype) * R ** -0.5
    # a head's query in the cached vector's lanes, as the decode rows take it
    q = jnp.pad(jnp.concatenate([jnp.einsum("chd,rhd->chr", plain[..., :DN], w_uk),
                                 plain[..., DN:]], axis=-1).astype(dtype),
                ((0, 0), (0, 0), (0, W - R - DR)))
    tb = (1 + jnp.arange(table, dtype=jnp.int32))[None]
    positions = context - chunk + jnp.arange(chunk)
    scores = jnp.where(jnp.arange(T)[None] <= positions[:, None],
                       jax.random.normal(keys[2], (chunk, T), jnp.float32), -jnp.inf)
    tile = hybrid.indexed_chunk_tile(chunk, INDEX_HEADS)
    tiles = lambda a, n: a.reshape(chunk // n, n, *a.shape[1:])
    out = {"device": jax.devices()[0].device_kind, "chunk": chunk, "heads": heads,
           "context": context, "topk": k, "select_tile": tile,
           "gather_tile": min(GATHER_TILE, chunk)}

    # ---- the selection, a tile of queries at a time ------------------------------- #
    def sort(t):
        top, at = jax.lax.top_k(t, k)
        return at, top > -jnp.inf

    by_sort = jax.jit(lambda s: jax.tree.map(lambda a: a.reshape(chunk, -1),
                                             jax.lax.map(sort, tiles(s, tile))))
    by_bisection = jax.jit(lambda s: jax.lax.map(
        lambda t: hybrid.chosen_tokens(t, k, BS), tiles(s, tile)).reshape(chunk, T))
    out["select_sort_ms"], (at, real) = timed(by_sort, scores, repeats=repeats)
    out["select_bisection_ms"], chosen = timed(by_bisection, scores, repeats=repeats)
    same = np.zeros((chunk, T), bool)
    np.put_along_axis(same, np.asarray(at), np.asarray(real), axis=1)
    out["selections_agree"] = bool((same == np.asarray(chosen)).all())

    # ---- the attend: gathered rows against the masked pass ----------------------- #
    n = min(GATHER_TILE, chunk)

    def rows(pages, a):
        """The cached vectors at the positions ``a [n, K]`` under the table."""
        return pages[jnp.take_along_axis(
            jnp.broadcast_to(tb, (a.shape[0], table)), a // BS, axis=1), a % BS]

    def gather(pages, q, at, real):
        o = jax.lax.map(lambda a: chosen_latent_attention(
            a[0], rows(pages, a[1]), a[2], scale=scale, value_lanes=R),
            (tiles(q, n), tiles(at, n), tiles(real, n))).reshape(chunk, heads, R)
        return jnp.einsum("chr,rhd->chd", o, w_uv).astype(o.dtype)

    def gather_alone(pages, at):
        return jax.lax.map(lambda a: rows(pages, a).astype(jnp.float32).sum(1), tiles(at, n))

    under = lambda pages, chosen: pages[tb[0, :chosen.shape[1] // BS]].reshape(-1, W)

    def kernel(pages, plain, chosen, last):
        """What ships: the plain form over the first ``chosen.shape[1]`` keys,
        the chunk's last position ``last``."""
        return masked_latent_attention(plain, under(pages, chosen), chosen, last,
                                       w_uk, w_uv, scale=scale)

    def masked(pages, plain, chosen):
        """The kernel's reference: the same pass through XLA alone."""
        return masked_latent_attention_reference(plain, under(pages, chosen), chosen,
                                                 w_uk, w_uv, scale=scale)

    def masked_absorbed(pages, q, chosen, m=4 if not args.rehearse else 8):
        """The same pass in the decode rows' form: the 128 heads' queries in
        the cached vector's lanes over the latent itself, ``m`` queries a tile."""
        c = pages[tb[0]].reshape(T, W)

        def one(a):
            qt, keep = a
            s = jnp.einsum("nhw,tw->nht", qt, c, preferred_element_type=jnp.float32) * scale
            p = jax.nn.softmax(jnp.where(keep[:, None], s, -1e30), axis=-1)
            return jnp.einsum("nht,tv->nhv", p.astype(c.dtype), c[:, :R],
                              preferred_element_type=jnp.float32).astype(qt.dtype)
        o = jax.lax.map(one, (tiles(q, m), tiles(chosen, m))).reshape(chunk, heads, R)
        return jnp.einsum("chr,rhd->chd", o, w_uv).astype(o.dtype)

    out["attend_gather_ms"], got = timed(jax.jit(gather), pages, q, at, real, repeats=repeats)
    out["gather_alone_ms"], _ = timed(jax.jit(gather_alone), pages, at, repeats=repeats)
    out["attend_masked_ms"], want = timed(jax.jit(masked), pages, plain, chosen,
                                          repeats=repeats)
    out["attend_masked_absorbed_ms"], other = timed(jax.jit(masked_absorbed), pages, q, chosen,
                                                   repeats=repeats)
    differs = lambda a, b: float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
    gap = max(differs(got, want), differs(other, want))
    out["gathered_gb"] = chunk * k * W * pages.dtype.itemsize / 1e9
    out["masked_tera_ops"] = 2 * T * (chunk * heads * (DN + DR + DV) + R * heads * (DN + DV)) / 1e12
    out["masked_absorbed_tera_ops"] = 2 * chunk * heads * T * (W + R) / 1e12

    # ---- the kernel against its reference, a chunk at each extent's end and middle -- #
    # (the extents of ``gpt.CHUNK_EXTENTS``: a chunk early in its prompt)
    out["extents"] = {}
    per = -(-table // gpt.CHUNK_EXTENTS) * BS
    select = jax.jit(lambda s: jax.lax.map(
        lambda t: hybrid.chosen_tokens(t, min(k, s.shape[1]), BS), tiles(s, tile)
    ).reshape(chunk, -1))
    for i in range(1, gpt.CHUNK_EXTENTS + 1):
        E = min(per * i, T)
        if E < k or str(E) in out["extents"]:
            continue
        row = out["extents"][str(E)] = {}
        for name, last in (("", E - 1), ("_skip", E - per // 2 - 1)):
            s_e = jnp.where(jnp.arange(E)[None] <= (last - chunk + 1 + jnp.arange(chunk))[:, None],
                            scores[:, :E], -jnp.inf)
            # the selection and the reference walk every key wherever the chunk
            # lies: timed at the extent's end alone
            once = 1 if name else repeats
            t_sel, chosen_e = timed(select, s_e, repeats=once)
            t_ref, want_e = timed(jax.jit(masked), pages, plain, chosen_e, repeats=once)
            if not name:
                row.update(select_bisection_ms=t_sel, attend_masked_ms=t_ref)
            row["attend_kernel_ms" + name], got_e = timed(
                jax.jit(kernel), pages, plain, chosen_e, jnp.int32(last), repeats=repeats)
            row["difference" + name] = differs(got_e, want_e)
            gap = max(gap, row["difference" + name])
    out["attend_kernel_ms"] = out["extents"][str(T)]["attend_kernel_ms"]
    out["largest_difference"] = gap

    # ---- the decode rows ------------------------------------------------------------ #
    dq, dat = q[:slots], at[:slots]
    decode = jax.jit(lambda pages, q, at, real: jnp.einsum(
        "nhr,rhd->nhd", chosen_latent_attention(q, rows(pages, at), real, scale=scale,
                                                value_lanes=R), w_uv))
    out["decode_rows_ms"], _ = timed(decode, pages, dq, dat, real[:slots], repeats=repeats)
    out["ok"] = bool(out["selections_agree"] and gap < (1e-4 if args.rehearse else 0.05))
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
