"""What one serve step of a Trinity (``afmoe``) configuration MUST read and
compute, from shapes alone: this family's function for ``step_mfu_pct``
(``lib/arith_step.py`` says what the three parts are and how
``arith_step.step_work`` spends them).

The stack is ``dense_layers`` layers with a dense SwiGLU and ``n_layer -
dense_layers`` layers with a bank, so two things differ from the families of
``arith_step.py``: the dense part counts the lead's MLP a leading layer and
the router, its bias and the shared expert an EXPERT layer, and the bank's
``layers`` are the expert layers alone.  ``experts`` is the width the router
chooses among (256) and ``held`` the experts here (16): ``step_work`` gives a
bank that holds a share that share of the expected reach and of the
assignments.

And the cache's reads of this cell (:func:`attention`), where
``arith_window.stack`` counts every row a single query: a prompt chunk is ``n``
consecutive queries of ONE sequence, and what the algorithm needs of a layer
is each page that holds a key one of them sees ONCE for all of them, not once
a query (at a chunk of 512 deep in a prompt of 30,000 the row-a-token count
asks for 512 times the pages; the kernel packs the chunk's queries and reads
a key once a packed row).  A row that carries no request needs nothing: 512
of a decode-only step's 544 rows.  Both make the count smaller, so the
kernel's share of its roofline and ``step_mfu_pct.gen`` err low, never high.
"""

import numpy as np

from benchmarks.lib import arith_moe, arith_window


def attention_params(kw):
    """A layer's attention: q, k, v and the output gate from the hidden
    size, the output projection, ONE gain of ``head_dim`` each for q and k,
    and the four norms of the block."""
    E, D = kw["n_embd"], kw["head_dim"]
    H, Hkv = kw["n_head"], kw["n_kv_head"]
    return E * (H + 2 * Hkv) * D + 2 * E * H * D + 2 * D + 4 * E


def trinity_weights(kw):
    """``model.kwargs`` of the configuration file -> ``{"dense", "gathered",
    "bank"}`` (``arith_step.py``)."""
    E, L, V = kw["n_embd"], kw["n_layer"], kw["vocab_size"]
    lead = kw["dense_layers"]
    first, held = kw["experts_held"] or (0, kw["num_experts"])
    expert = arith_moe.expert_params(E, kw["moe_intermediate_size"])
    per_expert_layer = (E * kw["num_experts"] + kw["num_experts"]
                        + kw["shared_experts"] * expert)
    dense = (L * attention_params(kw)
             + lead * arith_moe.expert_params(E, kw["intermediate_size"])
             + (L - lead) * per_expert_layer + E + V * E)
    return {"dense": dense, "gathered": V * E,
            "bank": {"layers": L - lead, "experts": kw["num_experts"], "held": held,
                     "top_k": kw["top_k"], "hidden": E,
                     "width": kw["moe_intermediate_size"]}}


def chunk_rows(first, n, block, lanes, heads, head_dim, window=None, itemsize=2):
    """(operations, bytes) of one layer's attention over a prompt chunk: the
    queries at ``first .. first + n - 1`` of one sequence.  Bytes: the pages
    from the first one the FIRST query sees to the one that holds the last
    query's key, K and V, once; the queries read and the outputs written.
    Operations: each query's products over the pages IT sees, as
    ``arith_window.rows`` counts a row's."""
    pages = arith_window.pages_seen(first + np.arange(n), block, window)
    oldest = 0 if window is None else max(first - window + 1, 0) // block
    span = (first + n - 1) // block + 1 - oldest
    nbytes = (2 * span * block * lanes + 2 * n * heads * head_dim) * itemsize
    return 2 * 2 * int(pages.sum()) * block * heads * head_dim, nbytes


def attention(decode, chunks, layers_by_window, block, lanes, heads, head_dim,
              itemsize=2):
    """(operations, bytes) over the stack: ``decode`` the positions of the
    single-query rows, ``chunks`` the prompt chunks run as ``(first, n)``,
    ``layers_by_window`` a window (None: full) -> the layers of that kind."""
    flops = nbytes = 0
    for window, layers in layers_by_window.items():
        f, b = arith_window.rows(decode, block, lanes, heads, head_dim, window, itemsize)
        for first, n in chunks:
            cf, cb = chunk_rows(first, n, block, lanes, heads, head_dim, window, itemsize)
            f, b = f + cf, b + cb
        flops, nbytes = flops + layers * f, nbytes + layers * b
    return flops, nbytes
