"""Operations and bytes of paged attention over grouped K/V heads, with an
optional window: the benchmark's own arithmetic for the kernel
``paged_gqa_attention``, beside ``arith.py``, and THE count of what attention
reads for every resident kind (``kinds/serve_backlog_resident.py:
attention_counters``).  It is the algorithm's LEAST, so no packing of a
chunk's queries into the kernel's rows can read over 100% of it.

A DECODE row is one query at position ``t`` (``t`` tokens of its sequence are
resident before it; its own key is written before it attends).  What the
algorithm needs of one layer: the pages that hold a key the query can see,
of K and of V, each ``block x lanes`` (``lanes`` = K/V heads x head size:
the ``g`` query heads of a group share one read); the query read and the
output written, ``heads x head_dim`` each.  A full layer sees the keys
``0 .. t``, a window layer ``t - window + 1 .. t``: the pages from the one
that holds its oldest visible key to the one that holds ``t``.

A prompt CHUNK is ``n`` consecutive queries of ONE sequence: each page that
holds a key one of them sees is needed ONCE for all of them (bytes), and each
query's products run over the pages IT sees (operations).  At a chunk of 512
deep in a prompt of 30,000 a row a token would ask for 512 times the pages;
the kernel packs ``Sq`` of the chunk's queries a row and reads a key once a
packed row, ``pages x ceil(n / Sq)`` at the most.

A row that carries no request needs NOTHING: 512 of a decode-only step's 544
rows.
"""

import numpy as np


def pages_seen(positions, block, window=None):
    """Pages of one layer that hold a key the query at each of ``positions``
    can see (an array in, an array out)."""
    t = np.asarray(positions, np.int64)
    first = 0 if window is None else np.maximum(t - window + 1, 0) // block
    return t // block + 1 - first


def rows(positions, block, lanes, heads, head_dim, window=None, itemsize=2):
    """(operations, bytes) of one layer's call over single-query rows at
    ``positions``, summed over the rows."""
    pages = pages_seen(positions, block, window)
    keys = int(pages.sum()) * block
    nbytes = 2 * keys * lanes * itemsize + 2 * len(pages) * heads * head_dim * itemsize
    flops = 2 * 2 * keys * heads * head_dim
    return flops, nbytes


def chunk_rows(first, n, block, lanes, heads, head_dim, window=None, itemsize=2):
    """(operations, bytes) of one layer's attention over a prompt chunk: the
    queries at ``first .. first + n - 1`` of one sequence.  Bytes: the pages
    from the first one the FIRST query sees to the one that holds the last
    query's key, K and V, once; the queries read and the outputs written.
    Operations: each query's products over the pages IT sees, as :func:`rows`
    counts a row's."""
    return cost(*chunk_keys(first, n, block, window), n, lanes, heads, head_dim, itemsize)


def chunk_keys(first, n, block, window=None):
    """(keys read, (query, key) products) of one layer over a prompt chunk,
    whole pages: the chunk's span of pages once, each query's own pages."""
    pages = pages_seen(first + np.arange(n), block, window)
    oldest = 0 if window is None else max(first - window + 1, 0) // block
    span = (first + n - 1) // block + 1 - oldest
    return span * block, int(pages.sum()) * block


def keys(decode, chunks, layers_by_window, block):
    """(keys read, (query, key) products) over the stack, whole pages:
    ``decode`` the positions of the single-query rows (a row reads what it
    multiplies), ``chunks`` the prompt chunks run as ``(first, n)``,
    ``layers_by_window`` a window (None: full) -> the layers of that kind.
    What a cache that is not K and V heads costs a key is its reader's
    (``lib/arith_mla.py``)."""
    read = products = 0
    for window, layers in layers_by_window.items():
        r = p = int(pages_seen(decode, block, window).sum()) * block
        for first, n in chunks:
            cr, cp = chunk_keys(first, n, block, window)
            r, p = r + cr, p + cp
        read, products = read + layers * r, products + layers * p
    return read, products


def cost(read, products, rows, lanes, heads, head_dim, itemsize=2):
    """(operations, bytes) of ``read`` keys read and ``products`` (query,
    key) pairs multiplied at K and V of ``lanes`` each, the queries of
    ``rows`` live rows read and their outputs written (all three summed over
    layers)."""
    return (2 * 2 * products * heads * head_dim,
            (2 * read * lanes + 2 * rows * heads * head_dim) * itemsize)


def attention(decode, chunks, layers_by_window, block, lanes, heads, head_dim,
              itemsize=2):
    """(operations, bytes) over the stack: :func:`keys`' reads and products
    at :func:`cost`, every live row a layer."""
    rows = (len(decode) + sum(n for _, n in chunks)) * sum(layers_by_window.values())
    return cost(*keys(decode, chunks, layers_by_window, block), rows, lanes, heads,
                head_dim, itemsize)


def full_rows(decode, chunks, layers, block, kw, itemsize=2):
    """:func:`attention` of ``layers`` full layers at the sizes of a
    configuration's ``model.kwargs`` (``n_kv_head``, ``head_dim``,
    ``n_head``): what the families whose other layers keep a state share."""
    return attention(decode, chunks, {None: layers}, block,
                     kw["n_kv_head"] * kw["head_dim"], kw["n_head"],
                     kw["head_dim"], itemsize)
