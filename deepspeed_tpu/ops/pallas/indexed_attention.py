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

Over a LATENT cache (DeepSeek-V3.2-Exp: one vector a token, from which every
head's own keys and values are made) the same pass is a sibling kernel,
:func:`masked_latent_attention`: XLA makes a group of heads' keys and values
from the latent, and the kernel holds ALL the chunk's queries of a few heads
in VMEM while those heads' keys and values stream past once; the one rotated
key a token is shared by all heads and its product is added to the score in
the kernel; the mask comes in as int8, once for the heads of a grid step; and
the key tiles past the chunk's last position are skipped the same way.  A
decode row's ONE query attends the rows it chose, gathered, in the absorbed
form (:func:`chosen_latent_attention`, plain jnp).

What has been shown: parity of each kernel against its jnp reference
(:func:`masked_attention_reference`, :func:`masked_latent_attention_reference`)
through the Pallas interpreter (``tests/unit/ops/test_indexed_attention.py``),
an ahead-of-time compile for v5e at the published shapes
(``tests/unit/ops/test_chip_compile.py``), and on the chip
``tools/latent_attend_probe.py`` (PERF.md section 5).
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


# scores a step of :func:`masked_latent_attention_reference` holds at once, in float32,
# and the queries it takes a head: 128 queries of 4 heads over 46,080 keys are
# 94 MB.  Measured on v5e on the same pass in the absorbed form (PERF.md
# section 6, PR 61): a step of twice the scores took 1.4 to 1.5 times as long
# at every extent, one of half the scores the same
_MASKED_STEP_SCORES = 4 * 128 * 46_080
_MASKED_STEP_QUERIES = 128


def masked_latent_attention_reference(q, c, chosen, w_uk, w_uv, *, scale):
    """What :func:`masked_latent_attention` computes, in plain jnp (the
    arguments there): every head's own keys and values made from the latent,
    a group of heads at a time, and a tile of 128 queries attends them densely
    under the mask; a tile's float32 scores go out to memory and come back
    (PERF.md section 6, PR 62: 47% of the peak at 46,080 keys, bound by those
    bytes).  Runs wherever the kernel's gate does not admit the call."""
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
            p = jnp.where(keep[None], p, 0.0)             # a query that chose none: zeros
            return jnp.einsum("gnt,gtd->ngd", p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32).astype(q.dtype)

        return jax.lax.map(tile, (tiles(qg), tiles(chosen))).reshape(C, G, -1)

    o = jax.lax.map(group, (by_group(w_uk, 1), by_group(w_uv, 1), by_group(q, 1)))
    return jnp.moveaxis(o, 0, 1).reshape(C, H, -1)


LATENT_KERNEL = "masked_latent_attention"
# heads a grid step of :func:`_latent_kernel` walks under ONE tile of the mask
# (the mask's bytes are the keys' and values' over 64 x this), and heads whose
# keys and values XLA makes from the latent at once (what is held of them
# beside the cache: 2 x 16 x 46,080 x 256 B = 377 MB at the whole table).
# Measured on v5e at 46,080 keys (PERF.md section 6, PR 62): 8 heads a step
# 0.2 ms of 23.5 faster and twice as long to compile, 32 heads a group 0.4 ms
# faster at twice the bytes held
_LATENT_HEADS = 4
_LATENT_GROUP = 16
# keys a grid step attends: 1,152 and 640 divide an extent of 5,760 (a table
# of 720 pages of 64 in eighths), which 512 and 256 do not.  At 46,080 keys
# 640 read 23.9 ms, 1,152 23.5, 1,920 22.9 (and 4.6 s to compile, not 2.6)
_LATENT_KEY_TILES = (1152, 640, 512, 384, 256, 128)
# queries a grid step attends, ALL of the chunk, in bytes of a query's lane:
# 512 of bf16 (a head's K and V tile is read once a chunk)
_LATENT_QUERY_BYTES = 1024
# 512 queries of 4 heads over 1,152 keys count 23 MiB: the float32 scores, the
# mask as they take it and the probabilities are 2.4 MB each
_LATENT_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# under this no key was chosen yet: a running maximum is held above it where
# it is subtracted, so a key that was not chosen weighs exp(-9e29) = 0, exactly
_ABSENT = 0.1 * NEG_INF


def latent_key_tile(T: int) -> int:
    """Keys a grid step of :func:`_latent_kernel` attends: the largest of
    :data:`_LATENT_KEY_TILES` that divides ``T`` (0: none does)."""
    return next((t for t in _LATENT_KEY_TILES if T % t == 0), 0)


def latent_kernel_shape_ok(C: int, H: int, dn: int, dr: int, dv: int, T: int, dtype) -> bool:
    """What :func:`_latent_kernel` takes: a head's key without position and
    its value in whole 128-lane tiles, a rotated key of one tile or less,
    heads in whole groups of :data:`_LATENT_HEADS`, the WHOLE chunk a grid
    step (whole sublane tiles of the int8 mask, and no more queries than fit
    beside a group's keys and values), keys in whole tiles."""
    return (dn % 128 == 0 and dv % 128 == 0 and 0 < dr <= 128
            and H % _LATENT_HEADS == 0 and C % 32 == 0
            and C * np.dtype(dtype).itemsize <= _LATENT_QUERY_BYTES
            and latent_key_tile(T) > 0)


def _latent_kernel_runs(*shape) -> bool:
    """Whether :func:`masked_latent_attention` takes the kernel at the shape
    :func:`latent_kernel_shape_ok` is asked about."""
    return (_pallas.use_kernel(LATENT_KERNEL) and _pallas.single_device()
            and latent_kernel_shape_ok(*shape))


def latent_keys_walked(C: int, H: int, dn: int, dr: int, dv: int, T: int, dtype,
                       last: int) -> int:
    """Keys of the ``T`` it is handed that :func:`masked_latent_attention`
    walks for a chunk whose last position is ``last`` (the arguments of
    :func:`latent_kernel_shape_ok`): the kernel's whole tiles up to it, and
    every key where the reference runs."""
    if not _latent_kernel_runs(C, H, dn, dr, dv, T, dtype):
        return T
    tk = latent_key_tile(T)
    return min(last // tk + 1, T // tk) * tk


def _latent_kernel(at_ref, qn_ref, qr_ref, k_ref, v_ref, kr_ref, c_ref, _, o_ref,
                   m_scr, l_scr, acc_scr, *, g, dn, dv, scale):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j < at_ref[0])
    def _():
        nt = (((1,), (1,)), ((), ()))
        # the mask once for the g heads: what a query did not choose lies at
        # NEG_INF (a logit is nothing beside it), what it chose where it was
        absent = jnp.where(c_ref[...].astype(jnp.float32) > 0.0, 0.0, NEG_INF)   # [C, tk]
        kr, tk = kr_ref[...], kr_ref.shape[0]
        for i in range(g):
            k, v = k_ref[:, i * dn:(i + 1) * dn], v_ref[:, i * dv:(i + 1) * dv]
            s = (jax.lax.dot_general(qn_ref[:, i * dn:(i + 1) * dn], k, nt,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qr_ref[:, i * 128:(i + 1) * 128], kr, nt,
                                       preferred_element_type=jnp.float32)) * scale + absent
            # the running maximum and sum are held in all 128 lanes alike
            m_old = m_scr[i]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            # a query that chose nothing of the tile adds exactly nothing
            p = jnp.exp(s - pltpu.repeat(jnp.maximum(m_new, _ABSENT), tk // 128, axis=1))
            alpha = jnp.exp(m_old - m_new)
            l_scr[i] = l_scr[i] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[i] = acc_scr[i] * pltpu.repeat(alpha, dv // 128, axis=1) + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[i] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        for i in range(g):
            o_ref[:, i * dv:(i + 1) * dv] = (acc_scr[i] / pltpu.repeat(
                jnp.maximum(l_scr[i], 1e-30), dv // 128, axis=1)).astype(o_ref.dtype)


def _latent_call(qn, qr, k, v, kr, chosen, o, tiles, group, *, dn, dv, scale):
    """``qn [C, H * dn]`` and ``qr [C, H * 128]`` the heads' queries side by
    side (without position; rotated, padded to a lane tile), ``k [T, G * dn]``
    and ``v [T, G * dv]`` the keys and values of the heads of group ``group``
    (``G`` heads), ``kr [T, 128]`` the ONE rotated key a token, ``chosen [C,
    T]`` int8 -> ``o [C, H * dv]`` with the group's heads written in place;
    only the first ``tiles`` tiles of keys are fetched and attended."""
    C, T = chosen.shape
    G, g, tk = k.shape[1] // dn, _LATENT_HEADS, latent_key_tile(T)
    live = lambda j, at: jnp.minimum(j, at[0] - 1)
    heads = lambda h, at: at[1] * (G // g) + h
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G // g, T // tk),
        in_specs=[
            pl.BlockSpec((C, g * dn), lambda h, j, at: (0, heads(h, at))),
            pl.BlockSpec((C, g * 128), lambda h, j, at: (0, heads(h, at))),
            pl.BlockSpec((tk, g * dn), lambda h, j, at: (live(j, at), h)),
            pl.BlockSpec((tk, g * dv), lambda h, j, at: (live(j, at), h)),
            pl.BlockSpec((tk, 128), lambda h, j, at: (live(j, at), 0)),
            pl.BlockSpec((C, tk), lambda h, j, at: (0, live(j, at))),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((C, g * dv), lambda h, j, at: (0, heads(h, at))),
        scratch_shapes=[pltpu.VMEM((g, C, 128), jnp.float32),
                        pltpu.VMEM((g, C, 128), jnp.float32),
                        pltpu.VMEM((g, C, dv), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_latent_kernel, g=g, dn=dn, dv=dv, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_LATENT_VMEM_LIMIT_BYTES),
        interpret=_pallas.interpret(),
        name=LATENT_KERNEL,
    )(jnp.stack([tiles, group]).astype(jnp.int32), qn, qr, k, v, kr, chosen, o)


def masked_latent_attention(q, c, chosen, last, w_uk, w_uv, *, scale):
    """Latent attention in its PLAIN form of a prompt chunk's queries ``q [C,
    H, D]`` (a head's lanes ``[no position | rotated]``, as
    ``models/gpt.py:_latent_project`` makes them) over the cached vectors ``c
    [T, lanes]`` of their ONE sequence (``[latent | the one rotated key |
    padding]``), read once, under the selection's mask ``chosen [C, T]``;
    ``last``: the chunk's last position (no query chose a key past it) ->
    ``[C, H, v lanes]``; the softmax in float32, the logits times ``scale``, a
    query that chose none gives zeros.  Every head's own keys and values are
    made from the latent (``w_uk [R, H, D - rotated]``, ``w_uv [R, H, v
    lanes]``) by XLA, :data:`_LATENT_GROUP` heads at a time: 512 queries share
    what the up-projection of a key costs, so a key and a head take 2 x (192 +
    128) operations and not the absorbed form's 2 x (576 + 512), which is the
    right form for a decode row's ONE query (:func:`chosen_latent_attention`).
    The kernel (:func:`_latent_kernel`) then holds ALL the chunk's queries of
    :data:`_LATENT_HEADS` heads in VMEM while those heads' keys and values
    stream past once, a tile of keys a grid step through an online softmax:
    the rotated key, one a token for all heads, comes in beside them and its
    product is added to the score; the mask, one set a query for all heads,
    comes in as int8 once for the heads of a step; no score goes to memory;
    tiles of keys past ``last`` are neither fetched nor computed (the caller
    hands in no more of the sequence than the least extent that holds the
    chunk; the kernel takes the remainder of that rounding).  On a TPU where
    the shape gate admits the call; :func:`masked_latent_attention_reference`
    elsewhere."""
    C, H, D = q.shape
    T, R, dn, dv = c.shape[0], w_uk.shape[0], w_uk.shape[2], w_uv.shape[2]
    dr = D - dn
    if not _latent_kernel_runs(C, H, dn, dr, dv, T, q.dtype):
        return masked_latent_attention_reference(q, c, chosen, w_uk, w_uv, scale=scale)
    G, tk = math.gcd(H, _LATENT_GROUP), latent_key_tile(T)
    latent = c[:, :R]
    kr = jnp.pad(c[:, R:R + dr], ((0, 0), (0, 128 - dr)))
    qn = q[..., :dn].reshape(C, H * dn)
    qr = jnp.pad(q[..., dn:], ((0, 0), (0, 0), (0, 128 - dr))).reshape(C, H * 128)
    keep = chosen.astype(jnp.int8)
    tiles = jnp.minimum(last // tk + 1, T // tk)
    of_group = lambda w, i: jax.lax.dynamic_slice_in_dim(w, i * G, G, axis=1).reshape(R, -1)

    def group(i, o):
        return _latent_call(qn, qr, latent @ of_group(w_uk, i), latent @ of_group(w_uv, i),
                            kr, keep, o, tiles, i, dn=dn, dv=dv, scale=scale)

    o = jax.lax.fori_loop(0, H // G, group, jnp.zeros((C, H * dv), q.dtype))
    return o.reshape(C, H, dv)
