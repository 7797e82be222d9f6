"""Operations and bytes of a Xing4.0 serve step, from shapes and the rows'
lengths alone: the benchmark's own arithmetic for ``step_mfu_pct``'s weights,
for latent attention over EVERY cached key under prompt-heavy traffic, and
for the residual streams' mixes, beside ``arith_mla.py`` (the same cached
vector, a row a query) and ``arith_deepseek_v32.py`` (the same layer under an
indexer).  Nothing here looks at an op's name, so the count is the same work
whatever implements it (XLA's fusions today; a kernel that fuses the mix is
held to the same bytes).

LATENT ATTENTION.  A row is one query at position ``t``; each of its
``heads`` scores the ONE cached vector of every token ``0 .. t`` over its
``latent + rope`` numbers and sums its first ``latent`` as values: ``2 heads
((latent + rope) + latent)`` operations a (query, key) pair (69,632 at 32
heads of 512 + 64).  A decode row reads its keys once; the rows of a prompt
chunk are ONE sequence's and no form of the attention must read that
sequence's vectors more than once for all of them (``arith_mla.py`` takes
the reads and the pairs apart for that).  The arena's
padding of the vector to whole lane tiles (576 numbers in 640 lanes) is the
layout's and is not counted.

THE MIX.  A sublayer reads a token's ``n`` streams once and writes them once
(``2 n hidden``), writes what the sublayer reads (``u``, ``hidden``) and
reads what it gave (``F(u)``, ``hidden``); its ``phi [n hidden, 2n + n n]``
is read once a step whatever the rows.  Two sublayers a layer.  The maps
themselves (``2n + n n`` numbers a token) are registers' or VMEM's.
"""

import numpy as np

from benchmarks.lib import arith_mla, arith_moe


def mix_params(kw):
    """Both sublayers' ``phi``, ``b`` and ``alpha`` of one layer."""
    n = kw["hyper"][0]
    maps = 2 * n + n * n
    return 2 * (n * kw["n_embd"] * maps + maps + 3)


def attention_params(kw):
    """A layer outside its feed-forward: the query down to ``q_lora_rank``
    (normed) and up to ``n_head x head_dim``; keys and values down to
    ``kv_lora_rank + qk_rope_dim`` (the latent normed) and the latent up to
    ``n_head x (head_dim - qk_rope_dim + v_head_dim)``; the output projection;
    two RMSNorms; the two stream mixes."""
    E, H = kw["n_embd"], kw["n_head"]
    q, kv, rope = kw["q_lora_rank"], kw["kv_lora_rank"], kw["qk_rope_dim"]
    nope, v = kw["head_dim"] - rope, kw["v_head_dim"]
    return (E * q + q + q * H * (nope + rope) + E * (kv + rope) + kv
            + kv * H * (nope + v) + H * v * E + 2 * E + mix_params(kw))


def xing4_weights(kw):
    """``lib/arith_step.py``'s family function for ``model.kwargs`` of a
    Xing4.0 configuration: ``dense_layers`` layers with a dense SwiGLU
    ``intermediate_size`` wide, every later one a router of ``num_experts``
    with a bias each, ``shared_experts`` experts every row goes through and
    the WHOLE bank; the final norm and an untied head; the embedding's rows
    gathered."""
    E, L, V = kw["n_embd"], kw["n_layer"], kw["vocab_size"]
    lead, N = kw["dense_layers"], kw["num_experts"]
    expert = arith_moe.expert_params(E, kw["moe_intermediate_size"])
    dense = (L * attention_params(kw)
             + lead * arith_moe.expert_params(E, kw["intermediate_size"])
             + (L - lead) * (E * N + N + kw.get("shared_experts", 1) * expert)
             + E + V * E)
    return {"dense": dense, "gathered": V * E,
            "bank": {"layers": L - lead, "experts": N, "held": N,
                     "top_k": kw["top_k"], "hidden": E,
                     "width": kw["moe_intermediate_size"]}}


def latent_keys(decode_positions, chunks, layers):
    """(cached vectors read, (query, key) pairs) over all ``layers`` layers,
    to the key: a decode row at each of ``decode_positions`` reads the keys
    ``0 .. t`` once, each of ``chunks`` (first position, tokens) its
    sequence's vectors ONCE for all its queries' pairs."""
    decode_positions = np.asarray(decode_positions, np.int64)
    pairs = read = int((decode_positions + 1).sum())
    for first, n in chunks:
        pairs += int((first + np.arange(n) + 1).sum())
        read += first + n
    return layers * read, layers * pairs


def latent_rows(decode_positions, chunks, layers, heads, latent, rope, itemsize=2):
    """(operations, bytes) of latent attention over every cached key, all
    ``layers`` layers: :func:`latent_keys`' reads and pairs at
    ``arith_mla.latent_attention``'s cost of each; a row's query read and its
    output written, ``latent + rope`` and ``latent`` a head."""
    read, pairs = latent_keys(decode_positions, chunks, layers)
    rows = layers * (len(decode_positions) + sum(n for _, n in chunks))
    return arith_mla.latent_attention(read, pairs, rows, heads, latent, rope, itemsize)


def mix_bytes(rows, steps, layers, streams, hidden, itemsize=2):
    """Bytes the ``2 x layers`` mixes of ``steps`` steps over ``rows`` live
    rows in all must move: the streams read once and written once, ``u``
    written and ``F(u)`` read, a row a sublayer; ``phi`` once a sublayer a
    step."""
    maps = 2 * streams + streams * streams
    a_row = (2 * streams + 2) * hidden
    return 2 * layers * (rows * a_row + steps * streams * hidden * maps) * itemsize
