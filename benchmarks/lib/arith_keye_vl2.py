"""Operations and bytes of a Keye-VL-2.0 serve step, from shapes and the
rows' lengths alone: the benchmark's own arithmetic for ``step_mfu_pct``'s
weights, for the lightning indexer's scores and for the attention over the
tokens a query chose, beside ``arith.py`` and ``arith_step.py``.  Nothing
here looks at an op's name, so the count is the same work whatever implements
it (a gather and a dense product, a kernel that copies a key at a time, a
dense attention under the selection's mask).

A row is one query at position ``t`` (its own key is written before it
attends).  What the ALGORITHM needs of it, an indexed layer:

* the SCORES: the index keys ``0 .. t``, ``lanes`` of the cache's type each,
  read once, and ``2 heads lanes`` operations a key.  The rows of a prompt
  chunk are one sequence's: they share one read of its index keys;
* the ATTEND: the ``min(t + 1, topk)`` chosen tokens, each its K row and its V
  row of ``kv_heads head_dim`` lanes (a token's K/V heads lie side by side:
  once a K/V head), and ``4 heads head_dim`` operations a key.  The rows of a
  prompt chunk choose each their own set, but no form of the attend must read
  more than the sequence's K and V once (a dense attention under the
  selection's mask does that): the chunk's bytes are the smaller of the two.

A row that carries no request needs nothing.
"""

import numpy as np

INDEXER = {"heads": 16, "head_dim": 64, "topk": 2048}


def indexer_of(kw):
    """The indexer's sizes of ``model.kwargs`` (its ``indexer`` a list in
    :data:`INDEXER`'s order), the defaults where it gives none."""
    return dict(INDEXER, **dict(zip(INDEXER, kw.get("indexer", ()))))


def keye_vl2_weights(kw):
    """``lib/arith_step.py``'s family function for ``model.kwargs`` of a
    Keye-VL-2.0 configuration: a layer's fused q/k/v of ``E x (H + 2 Hkv) D``
    and o of ``H D x E``, a norm a head on q and on k (``2 D``), the indexer
    (``E x (heads + 1) lanes`` for its queries and its key, ``E x heads`` for
    the heads' weights, a LayerNorm of ``2 lanes``), two RMSNorms, a router of
    ``E x experts``; the final norm and an untied head; the embedding's rows
    are gathered.  The bank: ``num_experts`` SwiGLU experts of ``3 E I`` a layer,
    ``top_k`` a token."""
    E, L, V = kw["n_embd"], kw["n_layer"], kw["vocab_size"]
    H, Hkv, D = kw["n_head"], kw["n_kv_head"], kw["head_dim"]
    ix = indexer_of(kw)
    mixer = E * (H + 2 * Hkv) * D + H * D * E + 2 * D
    indexer = E * ((ix["heads"] + 1) * ix["head_dim"] + ix["heads"]) + 2 * ix["head_dim"]
    rows = -(-V // 128) * 128               # the head's rows as the program pads them
    return {"dense": L * (mixer + indexer + 2 * E + E * kw["num_experts"]) + E + rows * E,
            "gathered": rows * E,
            "bank": {"layers": L, "experts": kw["num_experts"], "held": kw["num_experts"],
                     "top_k": kw["top_k"], "hidden": E, "width": kw["intermediate_size"]}}


def keys_attended(positions, ix=INDEXER):
    """Keys a query at each of ``positions`` attends in one indexed layer."""
    return np.minimum(np.asarray(positions, np.int64) + 1, ix["topk"])


def score_rows(decode_positions, chunks, layers, ix=INDEXER, itemsize=2):
    """(operations, bytes) of the indexer's scores, all ``layers`` layers:
    a decode row at each of ``decode_positions`` reads its own sequence's
    index keys; each of ``chunks`` (first position, tokens) reads its
    sequence's once for all its rows."""
    t = np.asarray(decode_positions, np.int64)
    keys_scored = int((t + 1).sum())
    keys_read = keys_scored
    for first, n in chunks:
        keys_scored += int((first + np.arange(n) + 1).sum())
        keys_read += first + n
    return (layers * 2 * ix["heads"] * ix["head_dim"] * keys_scored,
            layers * keys_read * ix["head_dim"] * itemsize)


def attend_rows(decode_positions, chunks, layers, heads, kv_heads, head_dim,
                ix=INDEXER, itemsize=2):
    """(operations, bytes) of the attention over the chosen tokens, all
    ``layers`` layers: K and V of every chosen token once a K/V head, a
    prompt chunk's no more than its sequence's K and V once."""
    row = 2 * kv_heads * head_dim * itemsize            # a token's K and V
    attended = int(keys_attended(decode_positions, ix).sum())
    nbytes = attended * row
    for first, n in chunks:
        own = int(keys_attended(first + np.arange(n), ix).sum())
        attended += own
        nbytes += min(own, first + n) * row
    return layers * 4 * heads * head_dim * attended, layers * nbytes
