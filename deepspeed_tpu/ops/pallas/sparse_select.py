"""Block scores of a sparse layer's selection (the ``sparse`` mixer of
``models/hybrid.py``: a query scores its sequence's compressed keys and
attends the 64 pages whose scores are highest).

A query's score of a block is the softmax of its ``g`` query heads over the
compressed keys that end at or before it, summed over the heads and pooled
(a max) over the keys that overlap the block.  Formed in XLA, the heads'
scores ``[n, Hkv, g, F]`` float32 are written to HBM and read three to four
times (512 x 2 x 16 x 3,072 x 4 B = 201 MB a layer at MiniCPM-SALA's
widths) for 3 MB of block scores.  Here a grid step holds one K/V head's
compressed keys and a tile of queries, forms the tile's scores on the MXU
into VMEM, and writes the ``[tile, blocks]`` block scores alone.

The compressed keys come as the pages hold them, ``[tables, blocks, r * Hkv
* D]`` (entry ``f = r b + j`` of a head in the lanes of ``j * Hkv + head``),
so plane ``j`` of a head is one ``[blocks, D]`` operand with the BLOCKS on
the score's lanes: the pooling over a block's ``r`` keys is an elementwise
max over the planes and the first key of the next block is plane 0 rolled
by a lane.  Queries of one table (a prompt chunk) share the operand, which
is fetched once a head; rows under their own tables (decode rows) are a
tile of one query each.

What has been shown: the selection it feeds against ``hybrid._select``
through the Pallas interpreter (``tests/unit/ops/test_sparse_select.py``) and
an ahead-of-time compile for v5e at the served shape
(``tests/unit/ops/test_chip_compile.py``).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import pallas as _pallas

KERNEL = "sparse_block_scores"
NEG_INF = -1e30
# queries of one table a grid step scores: a head's slab of the scores is two
# float32 sublane tiles, the ``g`` heads' ``[g * 16, F]`` 3.1 MB at 16 x 3,072
_QUERY_TILE = 16


def query_tile(n: int, shared: bool) -> int:
    """Queries a grid step scores: a tile of a shared table's, or one row."""
    return min(n, _QUERY_TILE) if shared else 1


def kernel_shape_ok(n: int, g: int, D: int, blocks: int, shared: bool) -> bool:
    """What :func:`_kernel` takes: heads of whole 128-lane tiles, blocks in
    whole lane tiles, a shared table's queries in whole tiles of whole
    sublane tiles, a tile's ``g`` heads whole sublane tiles of the queries'
    type (16 rows hold bf16's and float32's)."""
    tq = query_tile(n, shared)
    return (D % 128 == 0 and blocks % 128 == 0 and n % tq == 0
            and (tq == 1 or tq % 8 == 0) and (g * tq) % 16 == 0)


def _kernel(at_ref, q_ref, *refs, g, r, scale, init_blocks):
    """``at_ref [tq, 3]``: a query's last compressed key ``(t + 1) // stride
    - 1``, its own block and the first block of its window; ``q_ref [g * tq,
    D]``, a head's queries together; ``refs``: the ``r`` planes ``[blocks,
    D]``, the output ``[tq, blocks]`` and the scores' scratch ``[r, g * tq,
    blocks]``."""
    planes, o_ref, s_scr = refs[:r], refs[r], refs[r + 1]
    tq, MB = o_ref.shape
    q = q_ref[...]
    for j in range(r):
        s_scr[j] = jax.lax.dot_general(q, planes[j][...], (((1,), (1,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    at = at_ref[...]
    last_key, own, window = at[:, 0:1], at[:, 1:2], at[:, 2:3]
    b = jax.lax.broadcasted_iota(jnp.int32, (tq, MB), 1)
    # entry f = r b + j is key f - 1: those that end at or before the query
    keep = [(r * b + j >= 1) & (r * b + j <= last_key) for j in range(r)]
    some = last_key >= 1
    a = [jnp.zeros((tq, MB), jnp.float32) for _ in range(r)]
    for i in range(g):
        s = [jnp.where(keep[j], s_scr[j, i * tq:(i + 1) * tq, :] * scale, NEG_INF)
             for j in range(r)]
        m = functools.reduce(jnp.maximum, [jnp.max(x, axis=-1, keepdims=True) for x in s])
        e = [jnp.exp(x - m) for x in s]
        total = functools.reduce(jnp.add, [jnp.sum(x, axis=-1, keepdims=True) for x in e])
        # a query before its first compressed key scores nothing
        share = jnp.where(some, 1.0 / total, 0.0)
        a = [a[j] + e[j] * share for j in range(r)]
    # block b is overlapped by the keys f = r b .. r b + r
    nxt = jnp.where(b < MB - 1, pltpu.roll(a[0], MB - 1, 1), 0.0)
    score = functools.reduce(jnp.maximum, a + [nxt])
    score = jnp.where((b < init_blocks) | (b >= window), jnp.inf, score)
    o_ref[...] = jnp.where(b <= own, score, -jnp.inf)


def sparse_block_scores(q, kc, positions, *, stride, block, init_blocks, window):
    """``q [n, Hkv, g, D]``; ``kc [n | 1, blocks, r * Hkv * D]``: the pages
    of compressed keys under the queries' tables in logical order (one table
    where all queries share it); ``positions [n]``.  -> ``[n, Hkv, blocks]``
    float32: each query's score of each block of its K/V head, ``+inf`` the
    blocks it is made to attend (the first ``init_blocks``, its window's),
    ``-inf`` those past it.  The caller has asked :func:`kernel_shape_ok`."""
    n, Hkv, g, D = q.shape
    tables, MB, lanes = kc.shape
    shared, r = tables == 1, lanes // (Hkv * D)
    tq = query_tile(n, shared)
    nt = n // tq
    t = positions.astype(jnp.int32)
    at = jnp.stack([(t + 1) // stride - 1, t // block,
                    jnp.maximum(t - window + 1, 0) // block], axis=-1).reshape(nt, tq, 3)
    # a K/V head's rows head by head, so the sum over the heads adds slabs
    qg = q.reshape(nt, tq, Hkv, g, D).transpose(0, 2, 3, 1, 4).reshape(nt, Hkv, g * tq, D)
    plane = lambda j: pl.BlockSpec(
        (None, MB, D), lambda h, i: (0 if shared else i, 0, j * Hkv + h))
    out = pl.pallas_call(
        functools.partial(_kernel, g=g, r=r, scale=1.0 / math.sqrt(D),
                          init_blocks=init_blocks),
        grid=(Hkv, nt),
        in_specs=[pl.BlockSpec((None, tq, 3), lambda h, i: (i, 0, 0)),
                  pl.BlockSpec((None, None, g * tq, D), lambda h, i: (i, h, 0, 0))]
        + [plane(j) for j in range(r)],
        out_specs=pl.BlockSpec((None, tq, MB), lambda h, i: (i, 0, h)),
        out_shape=jax.ShapeDtypeStruct((nt, tq, Hkv * MB), jnp.float32),
        scratch_shapes=[pltpu.VMEM((r, g * tq, MB), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_pallas.interpret(),
        name=KERNEL,
    )(at, qg, *([kc] * r))
    return out.reshape(n, Hkv, MB)
