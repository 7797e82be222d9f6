"""Attention of a prompt chunk under a selection's MASK (the ``indexed``
mixer of ``models/hybrid.py``: every query of the chunk attends the tokens
its indexer chose, 2,048 of tens of thousands).

Each of the chunk's 512 queries chose its own set, so gathering the chosen
keys would read ``512 x 2,048`` keys a layer where the sequence holds 35,000;
the published kernels run prefill as dense attention under the selection's
mask instead, and so does this one: the sequence's K and V (gathered once,
page by page, into ``[T, Hkv * D]``) are streamed a tile of keys at a time
through an online softmax, a K/V head and a tile of queries a grid step, the
``g`` query heads of the K/V head one product each, and a key a query did not
choose is masked out of its softmax.  Tiles of keys past the chunk's last
position are neither fetched again nor computed (``tiles``, a scalar
prefetch, clamps the block index and gates the body).

What has been shown: parity against :func:`masked_attention_reference`
through the Pallas interpreter (``tests/unit/ops/test_indexed_attention.py``)
and an ahead-of-time compile for v5e at the published shape
(``tests/unit/ops/test_chip_compile.py``).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import pallas as _pallas

KERNEL = "masked_chunk_attention"
NEG_INF = -1e30
# queries a grid step attends, in bytes of a query's lane: 256 of bf16, 128 of
# float32 (the ``g`` heads' scores, probabilities and accumulators of a step
# then fit the 16 MiB of VMEM a kernel may use)
_QUERY_TILE_BYTES = 512
_KEY_TILES = (512, 256, 128)


def masked_attention_reference(q, k, v, chosen):
    """``q [C, H, D]``, ``k`` and ``v`` ``[T, Hkv, D]``, ``chosen [C, T]``
    (whether query ``c`` attends key ``t``) -> ``[C, H * D]``: the softmax
    over the chosen keys alone, in float32; a query that chose none gives
    zeros."""
    C, H, D = q.shape
    Hkv = k.shape[1]
    s = jnp.einsum("chgd,thd->chgt", q.reshape(C, Hkv, H // Hkv, D), k,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    keep = chosen[:, None, None, :]
    a = jnp.where(keep, jax.nn.softmax(jnp.where(keep, s, NEG_INF), axis=-1), 0.0)
    return jnp.einsum("chgt,thd->chgd", a.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype).reshape(C, H * D)


def key_tile(T: int) -> int:
    """Keys a grid step attends: the largest of 512, 256, 128 that divides
    ``T`` (0: none does, and the reference runs)."""
    return next((t for t in _KEY_TILES if T % t == 0), 0)


def query_tile(C: int, dtype) -> int:
    """Queries a grid step attends: the whole chunk, or a tile of it."""
    return min(C, _QUERY_TILE_BYTES // np.dtype(dtype).itemsize)


def kernel_shape_ok(C: int, D: int, T: int, dtype) -> bool:
    """What :func:`_kernel` takes: heads of whole 128-lane tiles, a chunk of
    whole query tiles (or one under a tile, of whole sublane tiles), keys in
    whole tiles."""
    sublane = 8 * 4 // np.dtype(dtype).itemsize
    tq = query_tile(C, dtype)
    return D % 128 == 0 and C % tq == 0 and tq % sublane == 0 and key_tile(T) > 0


def _kernel(tiles_ref, q_ref, k_ref, v_ref, c_ref, o_ref, m_scr, l_scr, acc_scr,
            *, g, scale):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j < tiles_ref[0])
    def _():
        k, v = k_ref[...], v_ref[...]
        keep = c_ref[...].astype(jnp.float32) > 0.0                # [tq, tk]
        for i in range(g):
            s = jax.lax.dot_general(q_ref[i], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep, s, NEG_INF)
            m_new = jnp.maximum(m_scr[i], jnp.max(s, axis=-1, keepdims=True))
            # a query that chose nothing of the tile adds exactly nothing
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_scr[i] - m_new)
            l_scr[i] = l_scr[i] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[i] = acc_scr[i] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[i] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        for i in range(g):
            o_ref[i] = (acc_scr[i] / jnp.maximum(l_scr[i], 1e-30)).astype(o_ref.dtype)


def _call(q, k, v, chosen, tiles):
    C, H, D = q.shape
    T, lanes = k.shape
    Hkv = lanes // D
    g, tq, tk = H // Hkv, query_tile(C, q.dtype), key_tile(T)
    qg = q.reshape(C, Hkv, g, D).transpose(1, 2, 0, 3)              # [Hkv, g, C, D]
    live = lambda j, tiles: jnp.minimum(j, tiles[0] - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Hkv, C // tq, T // tk),
        in_specs=[
            pl.BlockSpec((None, g, tq, D), lambda h, i, j, tiles: (h, 0, i, 0)),
            pl.BlockSpec((tk, D), lambda h, i, j, tiles: (live(j, tiles), h)),
            pl.BlockSpec((tk, D), lambda h, i, j, tiles: (live(j, tiles), h)),
            pl.BlockSpec((tq, tk), lambda h, i, j, tiles: (i, live(j, tiles))),
        ],
        out_specs=pl.BlockSpec((None, g, tq, D), lambda h, i, j, tiles: (h, 0, i, 0)),
        scratch_shapes=[pltpu.VMEM((g, tq, 1), jnp.float32),
                        pltpu.VMEM((g, tq, 1), jnp.float32),
                        pltpu.VMEM((g, tq, D), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, g=g, scale=1.0 / math.sqrt(D)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, g, C, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_pallas.interpret(),
        name=KERNEL,
    )(jnp.asarray(tiles, jnp.int32).reshape(1), qg, k, v, chosen)
    return out.transpose(2, 0, 1, 3).reshape(C, H * D)


def masked_chunk_attention(q, k, v, chosen, last):
    """Attention of a prompt chunk's queries ``q [C, H, D]`` over the keys
    ``k`` and values ``v`` ``[T, Hkv * D]`` of their sequence (a token's K/V
    heads side by side), each query the keys ``chosen [C, T]`` (bool) says
    and no others; ``last``: the chunk's last position (no query chose a key
    past it).  -> ``[C, H * D]``.  The kernel on a TPU where its shape gate
    admits the call, the reference elsewhere."""
    C, H, D = q.shape
    T = k.shape[0]
    if (_pallas.use_kernel(KERNEL) and _pallas.single_device()
            and kernel_shape_ok(C, D, T, q.dtype)):
        return _call(q, k, v, chosen.astype(jnp.bfloat16), last // key_tile(T) + 1)
    heads = lambda a: a.reshape(T, -1, D)
    return masked_attention_reference(q, heads(k), heads(v), chosen)


# scores a step of :func:`masked_latent_attention` holds at once, in float32,
# and the queries it takes a head: 128 queries of 4 heads over 46,080 keys are
# 94 MB.  Measured on v5e on the same pass in the absorbed form (PERF.md
# section 6, PR 61): a step of twice the scores took 1.4 to 1.5 times as long
# at every extent, one of half the scores the same
_MASKED_STEP_SCORES = 4 * 128 * 46_080
_MASKED_STEP_QUERIES = 128


def chosen_latent_attention(q, rows, real, *, scale, value_lanes):
    """Latent attention (MLA in its absorbed form) of each query over the
    rows of the latent cache IT chose: ``q [n, H, W]`` (a head's query moved
    into the cached vector's lanes), ``rows [n, K, W]`` the chosen tokens'
    cached vectors, all ``W`` lanes the key and the first ``value_lanes`` the
    value for every head, ``real [n, K]`` which of them are tokens (a query
    with fewer than ``K`` keys before it chose fewer) -> ``[n, H,
    value_lanes]``; the softmax in float32, the logits times ``scale``.  Plain
    jnp: the rows are gathered by the caller (``models/gpt.py:gpt_paged_step``,
    a row of 1,280 B a chosen token: a decode row's), and a kernel that copies
    a chosen row under scalar-prefetched positions would take this function's
    place and its name as the reference."""
    s = jnp.einsum("nhw,nkw->nhk", q, rows,
                   preferred_element_type=jnp.float32) * scale
    a = jax.nn.softmax(jnp.where(real[:, None], s, NEG_INF), axis=-1)
    return jnp.einsum("nhk,nkv->nhv", a.astype(rows.dtype), rows[..., :value_lanes],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def masked_latent_attention(q, c, chosen, w_uk, w_uv, *, scale):
    """Latent attention in its PLAIN form of a prompt chunk's queries ``q [C,
    H, D]`` (a head's lanes ``[no position | rotated]``, as
    ``models/gpt.py:_latent_project`` makes them) over the cached vectors ``c
    [T, lanes]`` of their ONE sequence (``[latent | the one rotated key |
    padding]``), read once, under the selection's mask ``chosen [C, T]`` ->
    ``[C, H, v lanes]``: every head's own keys and values are made from the
    latent (``w_uk [R, H, D - rotated]``, ``w_uv [R, H, v lanes]``), a group of
    heads at a time, and a tile of queries attends them densely (the caller
    hands in no more of the sequence than the chunk can see); the softmax in
    float32, the logits times ``scale``.  Plain jnp.  512 queries share what
    the up-projection of a key costs, so a key and a head take 2 x (192 + 128)
    operations and not the absorbed form's 2 x (576 + 512), which is the right
    form for a decode row's ONE query (:func:`chosen_latent_attention`)."""
    C, H, D = q.shape
    T, R, dn = c.shape[0], w_uk.shape[0], w_uk.shape[2]
    latent, k_rope = c[:, :R], c[:, R:R + D - dn]
    n = math.gcd(C, _MASKED_STEP_QUERIES)
    G = H
    while n * G * T > _MASKED_STEP_SCORES and G % 2 == 0:
        G //= 2
    by_group = lambda a, axis: jnp.moveaxis(
        a.reshape(*a.shape[:axis], H // G, G, *a.shape[axis + 1:]), axis, 0)
    tiles = lambda a: a.reshape(C // n, n, *a.shape[1:])

    def group(a):
        wk, wv, qg = a                                        # [R, G, .], [C, G, D]
        k = jnp.concatenate([jnp.einsum("tr,rgd->gtd", latent, wk),
                             jnp.broadcast_to(k_rope, (G, *k_rope.shape))], axis=-1)
        v = jnp.einsum("tr,rgd->gtd", latent, wv)

        def tile(b):
            qt, keep = b
            s = jnp.einsum("ngd,gtd->gnt", qt, k, preferred_element_type=jnp.float32) * scale
            p = jax.nn.softmax(jnp.where(keep[None], s, NEG_INF), axis=-1)
            return jnp.einsum("gnt,gtd->ngd", p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32).astype(q.dtype)

        return jax.lax.map(tile, (tiles(qg), tiles(chosen))).reshape(C, G, -1)

    o = jax.lax.map(group, (by_group(w_uk, 1), by_group(w_uv, 1), by_group(q, 1)))
    return jnp.moveaxis(o, 0, 1).reshape(C, H, -1)
