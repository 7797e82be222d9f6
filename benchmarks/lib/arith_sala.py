"""Operations and bytes of a MiniCPM-SALA serve step, from the rows' lengths
alone: the benchmark's own arithmetic for the sparse layers' attention (the
kernel ``paged_gqa_attention`` walking the pages a query chose), for the
linear layers' states, and for ``step_mfu_pct``'s weights, beside
``arith.py`` and ``arith_step.py``.  Nothing here looks at an op's name, so
the count is the same work whatever implements it.

A row is one query at position ``t`` (its own key is written before it
attends).  What the ALGORITHM needs of it:

* a SPARSE layer, a K/V head: with ``t + 1 <= dense_len`` keys the pages
  that hold the keys ``0 .. t``; beyond, ``topk`` pages (the selection's
  block is a page).  Of K and of V, each ``block x head_dim``; the ``g``
  query heads of a K/V head share the read.  And for the selection the
  compressed keys that end at or before ``t``, once: ``(t + 1) // stride -
  1`` of ``head_dim`` (none where every key is attended anyway).  The rows of
  a prompt CHUNK are one sequence's: each chooses its own pages, but no form
  of the attention must read a page more than once for all of them, nor more
  than the sequence's pages up to the chunk's end (ONE masked pass over the
  chunk's context reads exactly those), so the chunk's bytes are the smaller
  of its rows' chosen pages summed and those; its rows share one read of the
  compressed keys too, the last row's.  Operations: each row's own pages;
* a LINEAR layer: the state ``[heads, head_dim, head_dim]`` float32 read and
  written once a decode row, and once a prompt CHUNK (its tokens share the
  read and the write); ``4 x head_dim^2`` operations a head a token (the
  update ``k^T v`` and the read ``q S``).

A row that carries no request needs nothing.
"""

import numpy as np

SPARSE = {"kernel": 32, "stride": 16, "block": 64, "topk": 64,
          "init_blocks": 1, "window": 2048, "dense_len": 8192}


def sala_weights(kw):
    """``lib/arith_step.py``'s family function for ``model.kwargs`` of a
    MiniCPM-SALA configuration: a sparse layer's q, gate and o of E x H D and
    k, v of E x Hkv D; a linear layer's q, k, v, gate and o of E x H D and its
    output norm; both a SwiGLU MLP of 3 E I, two RMSNorms and a norm a head
    on q and on k; the final norm and an untied head.  No bank."""
    E, H, Hkv, D = kw["n_embd"], kw["n_head"], kw["n_kv_head"], kw["head_dim"]
    I, V, A = kw["intermediate_size"], kw["vocab_size"], kw["n_head"] * kw["head_dim"]
    shared = 3 * E * I + 2 * E + 2 * D
    per = {"minicpm4": 3 * E * A + 2 * E * Hkv * D + shared,
           "lightning-attn": 5 * E * A + A + shared}
    rows = -(-V // 128) * 128               # the head's rows as the program pads them
    return {"dense": sum(per[m] for m in kw["mixer_types"]) + E + rows * E,
            "gathered": rows * E, "bank": None}


def pages_attended(positions, sp=SPARSE):
    """Pages a query at each of ``positions`` attends in one sparse layer and
    K/V head."""
    t = np.asarray(positions, np.int64)
    return np.where(t + 1 <= sp["dense_len"], t // sp["block"] + 1, sp["topk"])


def keys_attended(positions, sp=SPARSE):
    """Keys of those pages at or before the query: what it attends."""
    t = np.asarray(positions, np.int64)
    return np.where(t + 1 <= sp["dense_len"], t + 1,
                    (sp["topk"] - 1) * sp["block"] + t % sp["block"] + 1)


def compressed_keys_read(positions, sp=SPARSE):
    """Compressed keys a query at each of ``positions`` scores in one sparse
    layer and K/V head: those that end at or before it, where it selects."""
    t = np.asarray(positions, np.int64)
    return np.where(t + 1 <= sp["dense_len"], 0, np.maximum((t + 1) // sp["stride"] - 1, 0))


def sparse_rows(decode, chunks, layers, heads, kv_heads, head_dim,
                sp=SPARSE, itemsize=2):
    """(operations, bytes) of the attention over the chosen pages, all
    ``layers`` sparse layers: the decode rows at the positions ``decode``,
    the prompt chunks ``(first, tokens)`` each one sequence's queries (their
    pages once a chunk, no more than the sequence's up to the chunk's end);
    and the bytes of compressed keys the selection reads beside it."""
    decode = np.asarray(decode, np.int64)
    attended = read = int(pages_attended(decode, sp).sum())
    compressed = int(compressed_keys_read(decode, sp).sum())
    rows = len(decode)
    for first, n in chunks:
        own = int(pages_attended(first + np.arange(n), sp).sum())
        attended += own
        read += min(own, (first + n - 1) // sp["block"] + 1)
        compressed += int(compressed_keys_read([first + n - 1], sp)[0])
        rows += n
    nbytes = (2 * read * sp["block"] * kv_heads * head_dim
              + 2 * rows * heads * head_dim) * itemsize
    flops = 2 * 2 * attended * sp["block"] * heads * head_dim
    return (layers * flops, layers * nbytes,
            layers * compressed * kv_heads * head_dim * itemsize)


def linear_rows(tokens, state_moves, layers, heads, head_dim):
    """(operations, bytes) of ``layers`` linear layers over ``tokens`` live
    tokens whose states were read and written ``state_moves`` times (once a
    decode row, once a prompt chunk)."""
    return (layers * 4 * tokens * heads * head_dim * head_dim,
            layers * 2 * state_moves * heads * head_dim * head_dim * 4)
