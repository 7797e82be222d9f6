"""Operations and bytes of paged LATENT attention (the kernel
``paged_mla_attention``): the benchmark's own arithmetic, beside ``arith.py``
and ``arith_window.py``.

Each of a query's ``heads`` reads, of every key it can see, the ONE cached
vector of that token: ``latent + rope`` numbers (the normed latent and the
rotated key all heads share).  What the ALGORITHM needs of one layer: each
such vector ONCE a decode row and once a prompt CHUNK, whose queries are one
sequence's and share the read (``cache bytes x key reads``; the heads share
it too); a score over all its lanes and a value sum over its first
``latent``, ``2 x heads x ((latent + rope) + latent)`` operations a (query,
key) PAIR; the query read, ``latent + rope`` a head, and the output written,
``latent`` a head, a live row.  The arena's padding of the vector to whole
lane tiles (320 numbers in 384 lanes) is the layout's, not the algorithm's,
and is not counted: a kernel that reads it reads more than this.

Both counts are the traffic kind's own, left as ``attention_keys_read`` and
``attention_key_products`` (``kinds/serve_backlog_resident.py:
attention_counters`` over ``arith_window.keys``, whole pages;
``kinds/serve_backlog_resident_hyper.py`` over ``arith_xing4.latent_keys``,
to the key), summed over layers.
"""


def latent_attention(reads, pairs, rows, heads, latent, rope, itemsize=2):
    """(operations, bytes) of ``rows`` live rows that read ``reads`` cached
    vectors (a decode row's keys, a chunk's once) and multiply ``pairs``
    (query, key) pairs between them (all three summed over layers)."""
    flops = 2 * heads * ((latent + rope) + latent) * pairs
    nbytes = (reads * (latent + rope) + rows * heads * ((latent + rope) + latent)) * itemsize
    return flops, nbytes
