"""Operations and bytes of paged attention whose rows are single queries,
over grouped K/V heads, with an optional window: the benchmark's own
arithmetic for the kernel ``paged_gqa_attention``, beside ``arith.py``.

A row is one query at position ``t`` (``t`` tokens of its sequence are
resident before it; its own key is written before it attends).  What the
ALGORITHM needs of one layer: the pages that hold a key the query can see,
of K and of V, each ``block x lanes`` (``lanes`` = K/V heads x head size:
the ``g`` query heads of a group share one read); the query read and the
output written, ``heads x head_dim`` each.  A full layer sees the keys
``0 .. t``, a window layer ``t - window + 1 .. t``: the pages from the one
that holds its oldest visible key to the one that holds ``t``.  A row that
carries no request reads its one trash page.
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


def stack(positions, idle_rows, layers_by_window, block, lanes, heads, head_dim,
          itemsize=2):
    """(operations, bytes) over a stack: ``layers_by_window`` maps a window
    (None: full) to the number of layers of that kind; every layer runs the
    live rows at ``positions`` and ``idle_rows`` rows of one page each."""
    flops = nbytes = 0
    n_layers = sum(layers_by_window.values())
    for window, n in layers_by_window.items():
        f, b = rows(positions, block, lanes, heads, head_dim, window, itemsize)
        flops, nbytes = flops + n * f, nbytes + n * b
    f, b = rows(np.zeros(int(idle_rows), np.int64), block, lanes, heads, head_dim,
                None, itemsize)
    return flops + n_layers * f, nbytes + n_layers * b
