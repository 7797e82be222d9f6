"""Operations and bytes of a DeepSeek-V3.2-Exp serve step, from shapes and the
rows' lengths alone: the benchmark's own arithmetic for ``step_mfu_pct``'s
weights, for the lightning indexer's scores and for the attention over the
rows of the LATENT cache a query chose, beside ``arith_keye_vl2.py`` (the same
indexer over K and V heads).  Nothing here looks at an op's name, so the
count is the same work whatever implements it (a gather and a dense product,
a kernel that copies a chosen row, a dense attention under the selection's
mask).

A row is one query at position ``t`` (its own latent and index key are written
before it attends).  What the ALGORITHM needs of it, a layer:

* the SCORES: the index keys ``0 .. t``, ``lanes`` of the cache's type each,
  read once, and ``2 heads lanes`` operations a key; the rows of a prompt
  chunk are one sequence's and share one read of its index keys
  (``arith_keye_vl2.score_rows`` at this indexer's sizes);
* the ATTEND: the ``min(t + 1, topk)`` chosen tokens, each ONE cached vector
  of ``latent + rope`` numbers (1,152 B in bf16) that serves all ``heads``
  heads as key and, its first ``latent`` numbers, as value: read once a row,
  ``2 heads ((latent + rope) + latent)`` operations a key (278,528).  The
  rows of a prompt chunk choose each their own set, but no form of the attend
  must read more than the sequence's latent once (a dense attention under the
  selection's mask does that): the chunk's bytes are the smaller of the two.
  The arena's padding of the vector to whole lane tiles (576 numbers in 640
  lanes) is the layout's and is not counted.

A row that carries no request needs nothing.
"""

import numpy as np

from benchmarks.lib import arith_keye_vl2, arith_moe

INDEXER = {"heads": 64, "head_dim": 128, "topk": 2048}


def indexer_of(kw):
    """The indexer's sizes of ``model.kwargs`` (its ``indexer`` a list in
    :data:`INDEXER`'s order), the defaults where it gives none."""
    return dict(INDEXER, **dict(zip(INDEXER, kw.get("indexer", ()))))


def attention_params(kw):
    """A layer outside its feed-forward: the query down to ``q_lora_rank``
    (normed) and up to ``n_head x head_dim``; keys and values down to
    ``kv_lora_rank + qk_rope_dim`` (the latent normed) and the latent up to
    ``n_head x (head_dim - qk_rope_dim + v_head_dim)``; the output projection;
    the indexer (its queries from the query's latent, ``[W_KI | W_w]`` from
    the hidden size, a LayerNorm of ``2 lanes``); two RMSNorms."""
    E, H = kw["n_embd"], kw["n_head"]
    q, kv, rope = kw["q_lora_rank"], kw["kv_lora_rank"], kw["qk_rope_dim"]
    nope, v = kw["head_dim"] - rope, kw["v_head_dim"]
    ix = indexer_of(kw)
    mixer = (E * q + q + q * H * (nope + rope) + E * (kv + rope) + kv
             + kv * H * (nope + v) + H * v * E)
    indexer = (q * ix["heads"] * ix["head_dim"] + E * (ix["head_dim"] + ix["heads"])
               + 2 * ix["head_dim"])
    return mixer + indexer + 2 * E


def deepseek_v32_weights(kw):
    """``lib/arith_step.py``'s family function for ``model.kwargs`` of a
    DeepSeek-V3.2-Exp configuration: ``dense_layers`` layers with a dense
    SwiGLU ``intermediate_size`` wide, every later one a router as wide as
    the experts it chooses among with a bias each, ``shared_experts`` experts
    every row goes through, and of the routed experts the ``experts_held``
    here; the final norm and an untied head; the embedding's rows gathered."""
    E, L, V = kw["n_embd"], kw["n_layer"], kw["vocab_size"]
    lead, N = kw["dense_layers"], kw["num_experts"]
    _, held = kw.get("experts_held") or (0, N)
    expert = arith_moe.expert_params(E, kw["moe_intermediate_size"])
    rows = -(-V // 128) * 128               # the head's rows as the program pads them
    dense = (L * attention_params(kw)
             + lead * arith_moe.expert_params(E, kw["intermediate_size"])
             + (L - lead) * (E * N + N + kw.get("shared_experts", 1) * expert)
             + E + rows * E)
    return {"dense": dense, "gathered": rows * E,
            "bank": {"layers": L - lead, "experts": N, "held": held,
                     "top_k": kw["top_k"], "hidden": E,
                     "width": kw["moe_intermediate_size"]}}


def keys_attended(positions, ix=INDEXER):
    """Keys a query at each of ``positions`` attends in one layer."""
    return arith_keye_vl2.keys_attended(positions, ix)


def score_rows(decode_positions, chunks, layers, ix=INDEXER, itemsize=2):
    """(operations, bytes) of the indexer's scores, all ``layers`` layers."""
    return arith_keye_vl2.score_rows(decode_positions, chunks, layers, ix, itemsize)


def attend_rows(decode_positions, chunks, layers, heads, latent, rope,
                ix=INDEXER, itemsize=2):
    """(operations, bytes) of the attention over the chosen rows of the
    latent cache, all ``layers`` layers: a decode row at each of
    ``decode_positions`` reads its chosen tokens' vectors once; each of
    ``chunks`` (first position, tokens) no more than its sequence's once."""
    row = (latent + rope) * itemsize
    attended = int(keys_attended(decode_positions, ix).sum())
    nbytes = attended * row
    for first, n in chunks:
        own = int(keys_attended(first + np.arange(n), ix).sum())
        attended += own
        nbytes += min(own, first + n) * row
    return (layers * 2 * heads * ((latent + rope) + latent) * attended,
            layers * nbytes)
