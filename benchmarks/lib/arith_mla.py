"""Operations and bytes of paged LATENT attention (the kernel
``paged_mla_attention``): the benchmark's own arithmetic, beside ``arith.py``
and ``arith_window.py``.

A row is one query; each of its ``heads`` reads, of every key it can see, the
ONE cached vector of that token: ``latent + rope`` numbers (the normed latent
and the rotated key all heads share).  What the ALGORITHM needs of one
layer: each such vector once a row (``cache bytes x keys``; the heads share
the read); a score over all its lanes and a value sum over its first
``latent``, ``2 x heads x ((latent + rope) + latent)`` operations a key; the
query read, ``latent + rope`` a head, and the output written, ``latent`` a
head.  The arena's padding of the vector to whole lane tiles (320 numbers in
384 lanes) is the layout's, not the algorithm's, and is not counted: a
kernel that reads it reads more than this.

The count of key reads is the traffic kind's own
(``kinds/serve_backlog_resident.py:attention_counters``: every row at the
pages it can see, summed over layers), which it leaves as
``paged_gqa_flops = 4 x heads x head_dim x keys``.
"""


def keys_read(paged_gqa_flops, heads, head_dim):
    """The kind's count of (row, key) pairs over all layers, whole pages."""
    return int(paged_gqa_flops) // (4 * heads * head_dim)


def latent_attention(keys, rows, heads, latent, rope, itemsize=2):
    """(operations, bytes) of ``rows`` single-query rows that see ``keys``
    keys between them (both summed over layers)."""
    flops = 2 * heads * ((latent + rope) + latent) * keys
    nbytes = (keys * (latent + rope) + rows * heads * ((latent + rope) + latent)) * itemsize
    return flops, nbytes
