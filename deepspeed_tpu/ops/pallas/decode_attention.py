"""Pallas TPU decode attention with KV cache (inference fast path).

The reference's decode hot loop is the fused ``softmax_context`` CUDA kernel
(``csrc/transformer/inference/csrc/pt_binding.cpp:1717-1781``) reading a
KV-cache workspace (``inference_context.h``).  The plain-jnp path attends
over all ``max_len`` cache positions every step; these kernels read ONLY
the ``pos + S_q`` valid positions:

* ``pos`` arrives via scalar prefetch; the dense-cache kernel's loop runs a
  STATIC trip count (``T/bk``, known at compile time) and predicates each
  iteration's whole copy+compute block on ``j < ceil((pos+S_q)/bk)`` —
  invalid cache blocks are neither DMA'd nor computed.  ``start()``/
  ``wait()`` are paired inside the same predicated branch so the DMA
  semaphores stay balanced on every control path.
* K/V stay in HBM (``MemorySpace.ANY``); each valid block is staged into a
  VMEM scratch buffer with an explicit ``make_async_copy`` keyed by the
  dynamic block index.
* The paged kernel stages a TILE of ``G`` pages (:func:`paged_tile_pages`,
  about 128 rows) per online-softmax update, all its live pages in flight
  together, and fetches the next tile into a second buffer while this one
  is attended; a tile's copies are started and waited for by one loop.
* Online softmax in fp32 registers, exactly like the training flash kernel.

Layouts: q ``[B, S_q, H, D]`` (S_q = 1 for decode, small for chunked
prefill); the cache keeps the heads FOLDED INTO THE LANE DIMENSION —
``[B, T, H*D]``, pages ``[NB, BS, H*D]``.  Mosaic requires the trailing two
dimensions of a DMA slice to be aligned to the (sublane, 128) tile, so a
``[bk, H, D]`` slice of a ``[B, T, H, D]`` cache is refused at every GPT-2
head shape (H=12/25 against the sublane tile, D=64 against the 128 lanes);
``[bk, H*D]`` is aligned whenever ``H*D % 128 == 0``, and the folded cache
carries no lane padding at D=64.  Inside the kernel a head is a static
128-aligned lane slice; two D=64 heads share one slice and are separated by
zeroing the other head's query lanes (the MXU contracts 128 wide anyway).

What has been shown: parity against the jnp reference through the Pallas
interpreter (``tests/unit/ops/test_decode_attention.py``,
``test_paged_attention.py``), ahead-of-time compilation for v5e at the
shapes :func:`kernel_shape_ok` admits (``tests/unit/ops/test_chip_compile.py``),
and a run on the chip through ``chip_smoke.py`` (CHANGES.md PR 21).
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops import pallas as _pallas

NEG_INF = -1e30
_LANES = 128


def kernel_shape_ok(H: int, Hkv: int, D: int, block: int, dtype) -> bool:
    """THE shape gate of both kernels — what the v5e compiler accepts
    (``tests/unit/ops/test_chip_compile.py`` pins both sides of it):

    * MHA only (the folded cache is indexed by query head);
    * a head is a whole number of 128-lane tiles, or several heads fill one
      exactly (D=64 needs an even H: gpt2 12 / medium 16 / large 20 pass,
      gpt2-xl's 25 heads do not, and take the einsum path);
    * the DMA'd block (``bk`` cache rows / one page) is a multiple of the
      cache dtype's sublane tile (8 fp32, 16 bf16, 32 int8).
    """
    sublane = 8 * 4 // np.dtype(dtype).itemsize
    lanes_ok = D % _LANES == 0 or (_LANES % D == 0 and (H * D) % _LANES == 0)
    return Hkv == H and lanes_ok and block % sublane == 0


def _lane_slices(H, D):
    """(slice width W, heads per slice, slice count): a head is a whole
    number of 128-lane tiles, or ``128 // D`` heads share one."""
    W = max(D, _LANES)
    return W, W // D, H * D // W


def _attend_block(qm, k_buf, v_buf, valid, carry, *, scale, H, D):
    """One online-softmax update of every head against the staged
    ``[bk, H*D]`` K/V block.  ``qm[h]`` is head ``h``'s query in its lane
    slice (other heads' lanes zeroed), ``valid`` the ``[Sq, bk]`` causal
    mask; carry = (m[h], l[h] per head ``[Sq, 1]``; acc[g] per lane slice
    ``[Sq, W]``)."""
    W, hpg, n_slices = _lane_slices(H, D)
    m, l, acc = (list(c) for c in carry)
    Sq = valid.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (Sq, W), 1)
    for g in range(n_slices):
        k = k_buf[:, g * W:(g + 1) * W]           # [bk, W], aligned slice
        v = v_buf[:, g * W:(g + 1) * W]
        alpha_g = pv_g = None
        for i in range(hpg):
            h = g * hpg + i
            # bf16 MXU operands, fp32 accumulation; contract the W lanes
            s = jax.lax.dot_general(qm[h], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG_INF)      # [Sq, bk]
            m_new = jnp.maximum(m[h], jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m[h] - m_new)
            l[h] = l[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            m[h] = m_new
            pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            if i == 0:
                alpha_g, pv_g = jnp.broadcast_to(alpha, (Sq, W)), pv
            else:                                 # head i owns lanes [iD, (i+1)D)
                own = lane >= i * D
                alpha_g = jnp.where(own, alpha, alpha_g)
                pv_g = jnp.where(own, pv, pv_g)
        acc[g] = acc[g] * alpha_g + pv_g
    return tuple(m), tuple(l), tuple(acc)


def _split_heads(q, H, D):
    """``[Sq, H*D]`` → per-head ``[Sq, W]`` lane slices with the slice's
    other heads zeroed (loop-invariant, built once per grid step)."""
    W, hpg, _ = _lane_slices(H, D)
    lane = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], W), 1)
    out = []
    for h in range(H):
        g, i = divmod(h, hpg)
        qs = q[:, g * W:(g + 1) * W]
        if hpg > 1:
            qs = jnp.where((lane >= i * D) & (lane < (i + 1) * D), qs,
                           jnp.zeros_like(qs))
        out.append(qs)
    return out


def _init_carry(Sq, H, D):
    W, _, n_slices = _lane_slices(H, D)
    return (tuple(jnp.full((Sq, 1), NEG_INF, jnp.float32) for _ in range(H)),
            tuple(jnp.zeros((Sq, 1), jnp.float32) for _ in range(H)),
            tuple(jnp.zeros((Sq, W), jnp.float32) for _ in range(n_slices)))


def _write_out(o_ref, carry, H, D):
    W, hpg, n_slices = _lane_slices(H, D)
    _, l, acc = carry
    Sq = acc[0].shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (Sq, W), 1)
    for g in range(n_slices):
        l_g = jnp.broadcast_to(l[g * hpg], (Sq, W))
        for i in range(1, hpg):
            l_g = jnp.where(lane >= i * D, l[g * hpg + i], l_g)
        out = acc[g] / jnp.maximum(l_g, 1e-30)
        o_ref[0, :, g * W:(g + 1) * W] = out.astype(o_ref.dtype)


def _decode_kernel(pos_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
                   sem_k, sem_v, *, scale, bk, Sq, H, D, nk_max):
    """Grid (B,): ONE ``[bk, H*D]`` DMA per cache block serves every head.

    The loop bound is STATIC (``nk_max = T // bk``); every iteration
    predicates its copy+compute block on ``j < nk`` via ``lax.cond`` — dead
    blocks cost no HBM traffic and no MXU work, and both DMAs start AND
    wait inside the same branch, so semaphores stay balanced whichever way
    the predicate resolves."""
    b = pl.program_id(0)
    pos = pos_ref[0]
    qm = _split_heads(q_ref[0], H, D)
    nk = (pos + Sq + bk - 1) // bk                # live (DMA'd) block count

    def live(j, carry):
        cp_k = pltpu.make_async_copy(k_hbm.at[b, pl.ds(j * bk, bk), :],
                                     k_buf, sem_k)
        cp_v = pltpu.make_async_copy(v_hbm.at[b, pl.ds(j * bk, bk), :],
                                     v_buf, sem_v)
        cp_k.start()
        cp_v.start()
        cp_k.wait()
        cp_v.wait()
        rows = jax.lax.broadcasted_iota(jnp.int32, (Sq, bk), 0)
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (Sq, bk), 1)
        return _attend_block(qm, k_buf, v_buf, cols <= pos + rows, carry,
                             scale=scale, H=H, D=D)

    def body(j, carry):
        return jax.lax.cond(j < nk, lambda c: live(j, c), lambda c: c, carry)

    carry = jax.lax.fori_loop(0, nk_max, body, _init_carry(Sq, H, D))
    _write_out(o_ref, carry, H, D)


def _decode_call(q, ck, cv, pos, *, bk):
    """q [B,Sq,H,D], cache [B,T,H*D], pos scalar → out [B,Sq,H,D]."""
    B, Sq, H, D = q.shape
    HD = H * D
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Sq, HD), lambda b, pos_ref: (b, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec((1, Sq, HD), lambda b, pos_ref: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((bk, HD), ck.dtype),
            pltpu.VMEM((bk, HD), cv.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=1.0 / np.sqrt(D), bk=bk,
                          Sq=Sq, H=H, D=D, nk_max=ck.shape[1] // bk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, HD), q.dtype),
        interpret=_pallas.interpret(),
        name="decode_attention",
    )(jnp.asarray(pos, jnp.int32).reshape(1), q.reshape(B, Sq, HD), ck, cv)
    return out.reshape(B, Sq, H, D)


def decode_attention_reference(q, ck, cv, pos, bias=None, kpos=None,
                               window=None):
    """Plain-jnp full-cache decode attention: the parity reference, and the
    stated path for every shape :func:`kernel_shape_ok` refuses.

    q ``[B, S_q, H, D]`` attends causally to cache positions <= its own
    global position (query i of row b sits at ``pos[b] + i``; ``pos`` is a
    scalar or ``[B]``); cache ``[B, T, Hkv*D]``.  GQA-aware: attention is
    computed GROUPED against the un-expanded cache.  ``bias``: additive
    ``[1|B, H, S_q, T]`` logit bias (ALiBi).  ``kpos [B, T]`` gives each
    cache row its key's position where that is not the row's index (a ring
    of pages; a negative position is no key), and ``window`` lets a query at
    ``t`` see the keys ``t - window + 1 .. t`` only.

    On every path here the rows past the last query's position are masked by
    a probability of exactly 0, not skipped: they must hold finite values
    (the zeros of ``init_kv_cache`` / ``init_arena``, or stale K/V), because
    0 * NaN is NaN."""
    B, Sq, H, D = q.shape
    T = ck.shape[1]
    Hkv = ck.shape[2] // D
    G = H // Hkv
    scale = 1.0 / np.sqrt(D)
    ck = ck.reshape(B, T, Hkv, D)
    cv = cv.reshape(B, T, Hkv, D)
    qg = q.reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   ck.astype(jnp.float32)) * scale       # [B, Hkv, G, Sq, T]
    if bias is not None:
        s = s + bias.astype(jnp.float32).reshape(
            bias.shape[0], Hkv, G, *bias.shape[2:])
    kpos = (jax.lax.broadcasted_iota(jnp.int32, (Sq, T), 1)[None]
            if kpos is None else kpos[:, None, :])
    qpos = (jnp.broadcast_to(pos, (B,))[:, None, None]
            + jax.lax.broadcasted_iota(jnp.int32, (Sq, T), 0)[None])
    seen = kpos <= qpos                                        # [B, Sq, T]
    if window is not None:
        seen = seen & (kpos > qpos - window) & (kpos >= 0)
    s = jnp.where(seen[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(q.dtype), cv)
    return out.reshape(B, Sq, H, D)


# --------------------------------------------------------------------------- #
# Paged (block-table) decode attention — the serving-engine fast path.
#
# The KV cache is a global arena of fixed-size blocks ([NB, BS, Hkv*D] per
# layer); a sequence's logical positions map to physical blocks through its
# block-table row.  Queries for row ``b`` sit at global positions
# ``lengths[b] + arange(S_q)`` and attend causally to the gathered cache —
# the serving-side analogue of ZeRO-Infinity's memory virtualization:
# logical sequence memory decoupled from physical HBM placement.
# --------------------------------------------------------------------------- #
def paged_attention_reference(q, k_pages, v_pages, block_tables, lengths,
                              bias=None, window=None):
    """jnp paged attention: the parity reference, and the stated path for
    every shape :func:`kernel_shape_ok` refuses — the pages gathered through
    the table, then :func:`decode_attention_reference` with per-row
    positions.

    q ``[B, Sq, H, D]``; pages ``[NB, BS, Hkv*D]`` (block 0 is the shared
    trash block); ``block_tables`` ``[B, MB]`` int32 physical block ids in
    logical order; ``lengths`` ``[B]`` int32 — tokens already in the cache
    for each row, i.e. the global position of the row's first query.
    ``bias``: optional additive ``[B, H, Sq, T]`` logit bias (ALiBi),
    T = MB * BS.  With a ``window`` the table is a RING: logical block ``b``
    sits in column ``b % MB`` (``serving/kv_cache.py`` gives a window
    group's pages back and keeps the table as wide as the window needs), so
    a column's block is the newest one congruent to it, counted back from
    the block of the row's last query.
    """
    B = q.shape[0]
    if window is not None:
        MB, BS = block_tables.shape[1], k_pages.shape[1]
        newest = (jnp.asarray(lengths, jnp.int32) + q.shape[1] - 1) // BS
        block = newest[:, None] - (newest[:, None] - jnp.arange(MB)[None]) % MB
        kpos = (block[:, :, None] * BS + jnp.arange(BS)[None, None]).reshape(B, -1)
    else:
        kpos = None
    # gather [B, MB, BS, Hkv*D] -> [B, T, Hkv*D]: the T dim is the
    # sequence's LOGICAL positions 0..T-1 (tables are logically ordered)
    ck = k_pages[block_tables].reshape(B, -1, k_pages.shape[-1])
    cv = v_pages[block_tables].reshape(B, -1, v_pages.shape[-1])
    return decode_attention_reference(q, ck, cv, lengths, bias=bias,
                                      kpos=kpos, window=window)


# One tile of the paged kernel holds about this many cache rows: the 128 keys
# the dense kernel attends at a time (a full MXU pass; the softmax update and
# the 2*H matmul round trips are paid once per tile, not once per page).
_TILE_ROWS = 128
# ... or, where pages are long and narrow (64 keys of ONE K/V head: 16 KiB),
# the eight page copies a tile of 16-key pages carries everywhere else, while
# they stay inside this many bytes: two such pages alone would make a tile
# whose fixed cost is most of its cost.  No tile of the 16-key arenas moves.
_TILE_PAGES = 8
_TILE_BYTES = 128 * 1024
# K and V, two tiles each, may take this much VMEM (of the 16 MiB a v5e
# kernel is given by default, next to q, o and the softmax carries).
_TILE_VMEM_BYTES = 4 * 1024 * 1024


def paged_tile_pages(BS: int, MB: int, Sq: int, lanes: int, dtype) -> int:
    """``G``, the pages the paged kernel fetches and attends as ONE tile —
    from the static shapes alone (page rows ``BS``, table width ``MB``,
    queries per row ``Sq``, cache lanes ``H*D``, cache dtype): as many
    pages as make :data:`_TILE_ROWS` rows (or :data:`_TILE_PAGES` pages
    inside :data:`_TILE_BYTES`, if that is more), halved until the four tile
    buffers fit :data:`_TILE_VMEM_BYTES`, never more than the table holds.
    ``MB`` need not be a multiple of ``G``: the last tile is short.

    ``Sq`` does not enter yet: on the chip decode (``Sq = 1``) ran best at
    128 rows and the prefill chunk (``Sq = 64``) within 0.03 ms a step of
    its best (PERF.md § 6, PR 26); a rule that tells them apart belongs
    here."""
    del Sq
    row_bytes = lanes * np.dtype(dtype).itemsize
    G = max(1, _TILE_ROWS // BS,
            min(_TILE_PAGES, _TILE_BYTES // (BS * row_bytes)))
    while G > 1 and 4 * G * BS * row_bytes > _TILE_VMEM_BYTES:
        G //= 2
    return min(G, MB)


# What a row of SEVERAL queries may take of a kernel's VMEM beside the tile
# buffers: its query and output blocks (two buffers each), the accumulators,
# the running maxima and sums, one product's scores and probabilities.
_CHUNK_VMEM_BYTES = 4 * 1024 * 1024


def paged_chunk_queries(chunk: int, rows_a_query: int, products: int,
                        key_lanes: int, value_lanes: int, tile_keys: int,
                        dtype) -> int:
    """``Sq``, the consecutive queries of ONE sequence that a row of a
    prompt chunk holds, from the static shapes alone: the largest divisor of
    ``chunk`` whose row fits :data:`_CHUNK_VMEM_BYTES`.  A row of ``Sq``
    queries reads each key of its context once where ``Sq`` single-query
    rows read it ``Sq`` times, and the attend's work a key does not change,
    so the most queries that fit is the rule.

    A kernel attends ``products`` times a tile (a K/V head for
    ``paged_gqa_attention``, once for ``paged_mla_attention``, a head for
    ``paged_attention``), each a ``[M, key_lanes] x [key_lanes, tile_keys]``
    product of ``M = rows_a_query * Sq`` rows (the query heads of a group,
    all heads, one), padded to the sublane tile; a running maximum or sum is
    a column and pads to a lane tile."""
    item = np.dtype(dtype).itemsize
    sublane = 8 * 4 // item

    def row_bytes(Sq):
        M = -(-rows_a_query * Sq // sublane) * sublane
        blocks = 2 * (key_lanes + value_lanes) * item
        carries = 4 * (value_lanes + 2 * _LANES)
        return M * (products * (blocks + carries) + 2 * 4 * tile_keys)

    return max((d for d in range(1, chunk + 1)
                if chunk % d == 0 and row_bytes(d) <= _CHUNK_VMEM_BYTES),
               default=1)


def _rows_and_chunk(attend, chunk: int, Sq: int, q, block_tables, lengths,
                    bias=None):
    """``attend(q, block_tables, lengths, bias)`` over rows of ONE query
    each, of which the last ``chunk`` (not all) are consecutive queries of one
    sequence (a prompt chunk: the same table a row, positions rising by
    one): those go as ``chunk / Sq`` rows of ``Sq`` queries, a row the table
    and the position of its first, beside the others a query a row — two
    calls of the one ``attend``, their outputs back in the rows' order.  A
    chunk row whose first query carries nothing (an all-trash table at
    position 0) reads the trash block as its queries did alone; one whose
    later queries carry nothing computes them for nobody.  ``block_tables``
    may be a tuple of arrays a row (a table and what was worked out of it):
    each is cut as the table is."""
    n, rows = q.shape[0] - chunk, chunk // Sq
    bc = None
    if bias is not None:                # [B, H, 1, T] -> [rows, H, Sq, T]
        H, T = bias.shape[1], bias.shape[3]
        bc = bias[n:, :, 0].reshape(rows, Sq, H, T).transpose(0, 2, 1, 3)
        bias = bias[:n]
    cut = lambda rows_of: jax.tree.map(rows_of, block_tables)
    o = attend(q[:n], cut(lambda t: t[:n]), lengths[:n], bias)
    oc = attend(q[n:].reshape(rows, Sq, *q.shape[2:]), cut(lambda t: t[n::Sq]),
                lengths[n::Sq], bc)
    return jnp.concatenate([o, oc.reshape(chunk, 1, *oc.shape[2:])])


# A tile that is fetched as RUNS (``paged_tile_runs``) may hold this many keys,
# and this many bytes of K and V together: the unit of the COPY, where
# :func:`paged_tile_pages` stays the unit of the attend.  On a v5e a tile costs
# 0.24 us whatever it holds beside 0.21 us for every 128 KiB (PERF.md § 6,
# PR 44), so once a tile is one copy it pays to bring more with it, while that
# fixed part is a fifth of the tile and more: ZAYA1's 256 keys (256 KiB) ran
# 18% faster at 512 and SmallThinker's 128 (256 KiB) 24% faster at 512, where
# OLMoE's 128 keys, 1 MiB already, ran 8% SLOWER at 256 (rows of 27 pages in
# the mean: a row's last, short tile is copied page by page, and a larger tile
# makes it longer)
_RUN_TILE_ROWS = 512
_RUN_TILE_BYTES = 1024 * 1024


def paged_run_tile_pages(BS: int, MB: int, lanes: int, dtype) -> int:
    """``G`` of a tile whose pages, where they lie together, are ONE copy an
    operand: the attend's pages (:func:`paged_tile_pages`) doubled while the
    tile stays inside :data:`_RUN_TILE_ROWS` keys and :data:`_RUN_TILE_BYTES`
    (two such tiles an operand are then inside :data:`_TILE_VMEM_BYTES`) and
    the table holds it: whole attend steps."""
    page_bytes = 2 * BS * lanes * np.dtype(dtype).itemsize      # K and V
    G = paged_tile_pages(BS, MB, 1, lanes, dtype)
    while (2 * G * BS <= _RUN_TILE_ROWS and 2 * G <= MB
           and 2 * G * page_bytes <= _RUN_TILE_BYTES):
        G *= 2
    return G


def paged_tile_runs(block_tables, pages: int, G: int):
    """``[B, ceil(MB / G)]`` int32, 1 where a tile of ``G`` pages of a row's
    table is a RUN that a paged kernel fetches with one copy
    (:func:`_tile_copies`): its ``G`` entries are consecutive pages in order,
    all inside the arena of ``pages`` (``tbl[t*G + j] == tbl[t*G] + j`` for
    every ``j < G`` and ``tbl[t*G] + G <= pages``; no alignment is asked, so
    what a prompt's blocks happen to form counts as what
    ``serving/kv_cache.py`` lays down in runs).  A short last tile (``MB %
    G``) is none.  A window group's ring is read the same way, ring tile by
    ring tile: it wants nothing but a width that is a multiple of ``G``
    (logical tile ``T`` is then ring tile ``T % (MB / G)``).  None where ``G``
    is 0 (no kernel that fetches runs reads these tables).  The same for
    every layer: the step works it out once, outside its scan over layers."""
    if not G:
        return None
    B, MB = block_tables.shape
    tiles = jnp.pad(jnp.asarray(block_tables, jnp.int32),
                    ((0, 0), (0, -MB % G)), constant_values=-1).reshape(B, -1, G)
    first = tiles[:, :, :1]
    run = jnp.all(tiles - first == jnp.arange(G, dtype=jnp.int32), axis=-1)
    return (run & (first[:, :, 0] + G <= pages)).astype(jnp.int32)


def _tile_copies(do, operands, entry, first_col, live, G: int, bs: int,
                 run=None):
    """``do`` (start or wait: a wait needs the shapes only, so it shares the
    code) the copies that bring ONE tile of ``G`` pages into a buffer of each
    operand, for every paged kernel that takes the arena whole.  ``operands``:
    a ``(source, buffer, semaphore)`` each, ``source(page, n)`` the ``n``
    pages from physical ``page`` of the layer's arena, ``buffer(rows)`` the
    tile's buffer, whole or its ``rows``, and ``semaphore()`` the buffer's;
    ``entry(col)`` the physical page in column ``col`` of the row's table,
    ``first_col()`` the tile's first column (worked out where it is read,
    inside the loop over pages) and ``live`` how many of its pages the row
    has reached (any integer).

    * ``run`` None: a copy a live page, ``min(G, live)`` signals a semaphore;
    * ``run`` the tile's flag of :func:`paged_tile_runs`: where it is set AND
      all ``G`` pages are live, ONE copy of ``G`` pages from ``entry(
      first_col())`` an operand, which signals its semaphore ONCE; any other
      tile page by page as above.

    A start and its wait are handed the same two words (the flag and the
    row's length), so they choose alike and the semaphores balance on every
    path.  Both ways bring live pages only, so a buffer holds zeros or what
    some table listed, as ``_paged_kernel`` has it: a table may list the
    pages of a run before they are written (a prompt's blocks are all taken
    at admission), and those are fetched when the row has reached them."""
    def pages():
        def page(j, c):
            phys = entry(first_col() + j)
            srcs = [source(phys, 1) for source, _, _ in operands]
            dst = pl.ds(pl.multiple_of(j * bs, bs), bs)
            for src, (_, buffer, sem) in zip(srcs, operands):
                do(pltpu.make_async_copy(src, buffer(dst), sem()))
            return c

        jax.lax.fori_loop(0, jnp.clip(live, 0, G), page, 0)

    if run is None:
        return pages()
    is_run = (run != 0) & (live >= G)

    @pl.when(is_run)
    def _():
        phys = entry(first_col())
        for source, buffer, sem in operands:
            do(pltpu.make_async_copy(source(phys, G), buffer(), sem()))

    pl.when(jnp.logical_not(is_run))(pages)


def _paged_kernel(len_ref, tbl_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
                  sem, slot_ref, *, scale, bs, Sq, H, D, MB, G):
    """Grid (B,): per row, DMA ONLY the ``ceil((len+Sq)/bs)`` live physical
    blocks through the block table (scalar-prefetched, so the dynamic block
    index is known before the DMA is issued) — the same one-copy-serves-
    every-head layout as ``_decode_kernel`` — in TILES of ``G`` pages:

    * page ``j`` of tile ``t`` (logical block ``t*G + j``) lands in rows
      ``[j*bs, (j+1)*bs)`` of a ``[G*bs, H*D]`` buffer; all of a tile's
      live pages are started before any is waited for, and the tile is
      attended ONCE (one online-softmax update per ``G*bs`` keys);
    * two buffers per operand, used in turn: before tile ``t`` is waited
      for, the copies of the NEXT tile are started into the other buffer
      and fly while ``t`` is attended — the row's tile ``t+1``, or after
      the row's last tile the first tile of row ``b+1`` (``slot_ref``
      carries the buffer that tile sits in to the next grid step; only row
      0 starts its own first tile).  The grid runs in order
      (``dimension_semantics`` "arbitrary"), which this rests on;
    * the loop runs over the row's ``ceil(nk/G)`` live tiles.  A tile's
      copies are started and waited for by the SAME loop over its
      ``min(G, nk - t*G)`` live pages, and every tile started is waited
      for in the loop of its row, so the DMA semaphores stay balanced on
      every control path.  ``nk`` is clamped to ``MB``, so the table read
      ``tbl_ref[row*MB + page]`` is in-bounds by construction even when a
      padded prefill chunk pushes ``len + Sq`` past ``MB * bs``; the mask's
      ``cols < nk*bs`` discards what such a chunk would see past the table
      in a short last tile (``MB % G != 0``).

    Only live pages are fetched (an idle slot costs its one trash-block
    DMA).  A tile's page slots that were NOT fetched are masked to a
    probability of exactly 0, and 0 * NaN is NaN in ``p @ v``: both buffers
    are ZEROED ONCE, in the first grid step (scratch outlives a grid step),
    so a slot holds zeros or what an earlier DMA left there from a block
    some table listed — finite either way."""
    b = pl.program_id(0)
    seq_len = len_ref[b]
    qm = _split_heads(q_ref[0], H, D)
    rows_t = G * bs

    def pages_of(row):                            # live (DMA'd) pages
        return jnp.minimum((len_ref[row] + Sq + bs - 1) // bs, MB)

    nk = pages_of(b)
    nt = (nk + G - 1) // G                        # live tiles, >= 1

    def tile_copies(row, t, slot, do):
        """``do`` (start or wait) the K and V copy of every live page of
        tile ``t`` of ``row``; a wait needs the shapes only, so it shares
        the code."""
        def page(j, c):
            phys = tbl_ref[row * MB + t * G + j]  # logical block -> physical
            dst = pl.ds(pl.multiple_of(j * bs, bs), bs)
            do(pltpu.make_async_copy(k_hbm.at[phys], k_buf.at[slot, dst],
                                     sem.at[0, slot]))
            do(pltpu.make_async_copy(v_hbm.at[phys], v_buf.at[slot, dst],
                                     sem.at[1, slot]))
            return c

        jax.lax.fori_loop(0, jnp.clip(pages_of(row) - t * G, 0, G), page, 0)

    start = lambda cp: cp.start()

    @pl.when(b == 0)
    def _():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        tile_copies(0, 0, 0, start)

    slot0 = slot_ref[0]                           # where this row's tile 0 is

    def tile(t, carry):
        slot = (slot0 + t) % 2
        last = t + 1 == nt          # then the next tile is row b+1's first

        @pl.when(jnp.logical_not(last) | (b + 1 < pl.num_programs(0)))
        def _():
            tile_copies(jnp.where(last, b + 1, b), jnp.where(last, 0, t + 1),
                        1 - slot, start)

        tile_copies(b, t, slot, lambda cp: cp.wait())
        rows = jax.lax.broadcasted_iota(jnp.int32, (Sq, rows_t), 0)
        cols = t * rows_t + jax.lax.broadcasted_iota(jnp.int32,
                                                     (Sq, rows_t), 1)
        valid = (cols <= seq_len + rows) & (cols < nk * bs)
        return _attend_block(qm, k_buf.at[slot], v_buf.at[slot], valid, carry,
                             scale=scale, H=H, D=D)

    carry = jax.lax.fori_loop(0, nt, tile, _init_carry(Sq, H, D))
    slot_ref[0] = (slot0 + nt) % 2
    _write_out(o_ref, carry, H, D)


def _paged_call(q, k_pages, v_pages, block_tables, lengths, G):
    B, Sq, H, D = q.shape
    NB, BS, HD = k_pages.shape
    MB = block_tables.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # lengths, flat block tables
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Sq, HD), lambda b, len_ref, tbl_ref: (b, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec((1, Sq, HD),
                               lambda b, len_ref, tbl_ref: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, G * BS, HD), k_pages.dtype),
            pltpu.VMEM((2, G * BS, HD), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),      # [K|V, tile buffer]
            pltpu.SMEM((1,), jnp.int32),          # buffer of the row's tile 0
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=1.0 / np.sqrt(D), bs=BS, Sq=Sq,
                          H=H, D=D, MB=MB, G=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, HD), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_pallas.interpret(),
        name="paged_attention",
    )(jnp.asarray(lengths, jnp.int32),
      jnp.asarray(block_tables, jnp.int32).reshape(-1),
      q.reshape(B, Sq, HD), k_pages, v_pages)
    return out.reshape(B, Sq, H, D)


# --------------------------------------------------------------------------- #
# Paged attention over grouped K/V heads, with an optional window, on the
# WHOLE arena.  The successor of ``_paged_kernel`` (ROADMAP S1): the layer is
# a scalar, so no layer of K and V is sliced out and copied for the kernel.
# --------------------------------------------------------------------------- #
def gqa_kernel_shape_ok(H: int, Hkv: int, D: int, block: int, dtype) -> bool:
    """What :func:`_paged_gqa_kernel` takes: ``H = g * Hkv`` query heads, a
    head a whole number of 128-lane tiles (the K/V heads are then aligned
    lane slices of the folded page), a page a multiple of the sublane tile."""
    sublane = 8 * 4 // np.dtype(dtype).itemsize
    return H % Hkv == 0 and D % _LANES == 0 and block % sublane == 0


def _paged_gqa_kernel(lay_ref, len_ref, tbl_ref, nxt_ref, *refs, scale, bs, Sq,
                      Hkv, D, MB, G, A, window, runs):
    """Grid (B,), a row a step, as ``_paged_kernel`` (tiles of ``G`` pages,
    two buffers an operand, the next tile — the next row's first after the
    row's last — fetched while this one is attended; read its docstring for
    the semaphore and zero-fill invariants, which are kept).  What differs:

    * K and V are the arena ``[layers, pages, bs, Hkv*D]`` whole, and the
      layer's index arrives as a scalar: a page is ``k_hbm.at[layer, phys]``;
    * the tables are not a scalar prefetch (256 rows of 1,024 blocks are the
      whole 1 MiB of SMEM): a step is handed its row's table ``tbl_ref
      [1, MB]`` and the next row's ``nxt_ref`` as SMEM blocks;
    * per K/V head (a ``D``-lane slice of the staged tile) the ``g`` query
      heads that share it are the rows of ONE ``[M, D] x [D, keys]`` product
      (``q_ref`` is ``[1, Hkv, M, D]``, row ``i * Sq + s`` head ``i`` of the
      group at query ``s``, padded to the sublane tile);
    * with a ``window`` a row starts at the page of its first query's oldest
      visible key, ``(len - window + 1) // bs``, masks inside it, and reads
      the table as a ring (logical block ``b`` in column ``b % MB``).  A
      query whose every key of a tile is masked (``Sq > 1`` only) adds
      exactly nothing there: its probabilities are forced to 0;
    * with ``runs`` (a group whose tables grow in runs of a tile, a full
      group's or a window group's ring) the row's flags of
      :func:`paged_tile_runs` and the next row's follow the tables as SMEM
      blocks (``run_ref``, ``nrun_ref`` ``[1, tiles]``), the arenas come
      viewed ``[layers, pages * bs, lanes]``, and a tile whose flag is set and
      whose ``G`` pages are all live is ONE copy an operand
      (:func:`_tile_copies`).  There a tile is the unit of the copy alone: it
      is attended in steps of ``A`` keys, the tile of a call without flags,
      one online-softmax update each, in order (a step past the row's last
      key, or below a window's first, adds exactly nothing), so the flags
      change nothing in what comes out.  Without ``runs`` the kernel is what
      it was: a copy a page, the tile attended whole;
    * with a ``window`` AND ``runs`` the row's tiles are cut on the runs'
      boundaries: its first tile is the run that holds the window's first
      page (that page rounded down to ``G``; the mask discards what lies
      below the window), ``MB`` is a multiple of ``G``, and logical tile ``T``
      reads the flag of ring tile ``T % (MB / G)``, whose ``G`` columns hold
      it.  ``serving/kv_cache.py`` keeps a run until its last key is out of
      the window, so that first tile is still a run."""
    if runs:
        run_ref, nrun_ref, *refs = refs
    q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, slot_ref = refs
    b = pl.program_id(0)
    layer = lay_ref[0]
    seq_len = len_ref[b]
    rows_t = G * bs
    M = q_ref.shape[2]

    def first_page(row):
        if window is None:
            return 0
        page = jnp.maximum(len_ref[row] - (window - 1), 0) // bs
        return page - page % G if runs else page

    def pages_of(row):                            # live (DMA'd) pages
        newest = (len_ref[row] + Sq - 1) // bs
        return jnp.minimum(newest + 1 - first_page(row), MB)

    p0, nk = first_page(b), pages_of(b)
    nt = (nk + G - 1) // G                        # live tiles, >= 1

    def tile_copies(row, t, slot, do):
        first = first_page(row)

        def entry(logical):
            col = logical % MB if window is not None else logical
            return jnp.where(row == b, tbl_ref[0, col], nxt_ref[0, col])

        def operand(hbm, buf, i):
            if runs:        # the arena ``[layers, pages * bs, lanes]``
                source = lambda page, n: hbm.at[layer, pl.ds(
                    pl.multiple_of(page * bs, bs), n * bs)]
            else:           # ``[layers, pages, bs, lanes]``, a page a copy
                source = lambda page, n: hbm.at[layer, page]
            return (source, lambda *rows: buf.at[(slot, *rows)],
                    lambda: sem.at[i, slot])

        live = pages_of(row) - t * G
        run = None
        if runs and k_hbm.shape[1] >= rows_t:   # else an arena under a tile
            at = t if window is None else (first // G + t) % (MB // G)
            run = jnp.where(row == b, run_ref[0, at], nrun_ref[0, at])
        _tile_copies(do, [operand(k_hbm, k_buf, 0), operand(v_hbm, v_buf, 1)],
                     entry, lambda: first + t * G, live, G, bs, run)

    start = lambda cp: cp.start()

    @pl.when(b == 0)
    def _():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        tile_copies(0, 0, 0, start)

    slot0 = slot_ref[0]
    q = [q_ref[0, h] for h in range(Hkv)]         # [M, D] each

    def tile(t, carry):
        slot = (slot0 + t) % 2
        last = t + 1 == nt

        @pl.when(jnp.logical_not(last) | (b + 1 < pl.num_programs(0)))
        def _():
            tile_copies(jnp.where(last, b + 1, b), jnp.where(last, 0, t + 1),
                        1 - slot, start)

        tile_copies(b, t, slot, lambda cp: cp.wait())
        m, l, acc = (list(c) for c in carry)
        for lo in range(0, rows_t, A):            # the tile, A keys a step
            qpos = seq_len + jax.lax.broadcasted_iota(jnp.int32, (M, A), 0) % Sq
            cols = (p0 + t * G) * bs + jax.lax.broadcasted_iota(
                jnp.int32, (M, A), 1)
            if lo:
                cols = cols + lo
            valid = (cols <= qpos) & (cols < (p0 + nk) * bs)
            if window is not None:
                valid = valid & (cols > qpos - window)
            for h in range(Hkv):
                k = k_buf[slot, lo:lo + A, h * D:(h + 1) * D]     # [A, D]
                v = v_buf[slot, lo:lo + A, h * D:(h + 1) * D]
                s = jax.lax.dot_general(q[h], k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32) * scale
                s = jnp.where(valid, s, NEG_INF)                  # [M, A]
                m_new = jnp.maximum(m[h], jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                if window is not None:
                    p = jnp.where(valid, p, 0.0)
                alpha = jnp.exp(m[h] - m_new)
                l[h] = l[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
                m[h] = m_new
                acc[h] = acc[h] * alpha + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        return tuple(m), tuple(l), tuple(acc)

    carry = (tuple(jnp.full((M, 1), NEG_INF, jnp.float32) for _ in range(Hkv)),
             tuple(jnp.zeros((M, 1), jnp.float32) for _ in range(Hkv)),
             tuple(jnp.zeros((M, D), jnp.float32) for _ in range(Hkv)))
    _, l, acc = jax.lax.fori_loop(0, nt, tile, carry)
    slot_ref[0] = (slot0 + nt) % 2
    for h in range(Hkv):
        o_ref[0, h] = (acc[h] / jnp.maximum(l[h], 1e-30)).astype(o_ref.dtype)


def _paged_gqa_call(q, k_arena, v_arena, layer, block_tables, lengths, plan,
                    tile_runs=None):
    B, Sq, H, D = q.shape
    L, NB, BS, lanes = k_arena.shape
    Hkv = lanes // D
    g = H // Hkv
    MB = block_tables.shape[1]
    G, window = plan.tile_pages, plan.window
    A = G * BS      # keys an attend step: the tile of a call without flags
    block_tables = jnp.asarray(block_tables, jnp.int32)[:, None, :]
    sublane = 8 * 4 // np.dtype(q.dtype).itemsize
    M = -(-g * Sq // sublane) * sublane
    # [B, Sq, Hkv, g, D] -> [B, Hkv, g*Sq, D]: a K/V head's queries together
    qg = q.reshape(B, Sq, Hkv, g, D).transpose(0, 2, 3, 1, 4).reshape(
        B, Hkv, g * Sq, D)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, M - g * Sq), (0, 0)))
    row = lambda b, *_: (b, 0, 0, 0)
    # a row's table and the next row's, ``[B, 1, cols]``: a block's last two
    # dimensions are the array's
    smem = lambda cols: [
        pl.BlockSpec((None, 1, cols), at, memory_space=pltpu.MemorySpace.SMEM)
        for at in (lambda b, *_: (b, 0, 0),
                   lambda b, *_: (jnp.minimum(b + 1, B - 1), 0, 0))]
    tables, specs = [block_tables, block_tables], smem(MB)
    if tile_runs is not None:
        assert plan.run_pages, "a tile of chosen pages holds no runs"
        G = plan.run_pages
        assert window is None or MB % G == 0, (
            f"a ring of {MB} pages is no whole number of runs of {G}")
        tile_runs = jnp.asarray(tile_runs, jnp.int32)[:, None, :]
        tiles = tile_runs.shape[2]
        assert tiles == -(-MB // G), (tile_runs.shape, MB, G)
        tables += [tile_runs, tile_runs]
        specs += smem(tiles)
        # a page's rows lie together: a run of pages is one slice
        k_arena = k_arena.reshape(L, NB * BS, lanes)
        v_arena = v_arena.reshape(L, NB * BS, lanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # layer, lengths
        grid=(B,),
        in_specs=specs + [
            pl.BlockSpec((1, Hkv, M, D), row),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hkv, M, D), row),
        scratch_shapes=[
            pltpu.VMEM((2, G * BS, lanes), k_arena.dtype),
            pltpu.VMEM((2, G * BS, lanes), v_arena.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),      # [K|V, tile buffer]
            pltpu.SMEM((1,), jnp.int32),          # buffer of the row's tile 0
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_gqa_kernel, scale=1.0 / np.sqrt(D), bs=BS,
                          Sq=Sq, Hkv=Hkv, D=D, MB=MB, G=G, A=A, window=window,
                          runs=tile_runs is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, M, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_pallas.interpret(),
        name=plan.kernel,
    )(jnp.asarray(layer, jnp.int32).reshape(1), jnp.asarray(lengths, jnp.int32),
      *tables, qg, k_arena, v_arena)
    out = out[:, :, :g * Sq].reshape(B, Hkv, g, Sq, D)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D)


# --------------------------------------------------------------------------- #
# Paged LATENT attention (MLA in its absorbed form): every head reads ONE
# cached vector a token, all its lanes as the key and its first lanes as the
# value.  A sibling of ``_paged_gqa_kernel``, not a case of it: that one walks
# K/V heads over two arenas; here there is one arena, one staged tile and one
# product for all heads.
# --------------------------------------------------------------------------- #
# keys a tile, the unit of the COPY: a key is 768 B here where SmallThinker's
# is 2,048, and what a tile costs is mostly fixed (0.37 us on a v5e beside
# 0.18 us for every 256 keys once a tile is ONE copy; PERF.md § 6, PR 40).  A
# tile of 1,024 would leave rows of a few thousand keys more of a last, short
# tile, which is copied page by page, than it saves
_MLA_TILE_ROWS = 512
# keys an online-softmax update, the unit of the ATTEND: the 32 heads are the
# rows of one small product, so 128 would be mostly its own fixed cost.  A
# tile is attended in steps of this many keys, in order, so what comes out
# does not depend on how many of them a copy brings
_MLA_ATTEND_ROWS = 256


def mla_kernel_shape_ok(lanes: int, value_lanes: int, block: int, dtype) -> bool:
    """What :func:`_paged_mla_kernel` takes: the cached vector and its value
    part whole 128-lane tiles, a page a multiple of the sublane tile."""
    sublane = 8 * 4 // np.dtype(dtype).itemsize
    return (lanes % _LANES == 0 and value_lanes % _LANES == 0
            and 0 < value_lanes <= lanes and block % sublane == 0)


def _mla_tile_pages(BS: int, MB: int) -> int:
    return min(max(1, _MLA_TILE_ROWS // BS), MB)


def _mla_attend_rows(tile_rows: int) -> int:
    """Keys an attend step of a tile of ``tile_rows`` takes: :data:`
    _MLA_ATTEND_ROWS` where they divide the tile, else the whole tile."""
    return tile_rows if tile_rows % _MLA_ATTEND_ROWS else _MLA_ATTEND_ROWS


def paged_mla_attention_reference(q, pages, block_tables, lengths, *, scale,
                                  value_lanes):
    """jnp latent attention over ONE layer's pages ``[NB, BS, W]``: q
    ``[B, Sq, H, W]`` (query ``i`` of row ``b`` at position ``lengths[b] +
    i``) against every cached vector up to its own position, all ``W`` lanes
    the key, the first ``value_lanes`` the value -> ``[B, Sq, H,
    value_lanes]``.  The parity reference, and the path wherever the
    selection rule says no."""
    B, Sq = q.shape[:2]
    c = pages[block_tables].reshape(B, -1, pages.shape[-1])         # [B, T, W]
    s = jnp.einsum("bqhw,bkw->bhqk", q.astype(jnp.float32),
                   c.astype(jnp.float32)) * scale
    T = c.shape[1]
    seen = (jax.lax.broadcasted_iota(jnp.int32, (Sq, T), 1)[None]
            <= jnp.asarray(lengths, jnp.int32)[:, None, None]
            + jax.lax.broadcasted_iota(jnp.int32, (Sq, T), 0)[None])
    p = jax.nn.softmax(jnp.where(seen[:, None], s, NEG_INF), axis=-1)
    return jnp.einsum("bhqk,bkv->bqhv", p.astype(q.dtype), c[..., :value_lanes])


def _paged_mla_kernel(lay_ref, len_ref, tbl_ref, nxt_ref, run_ref, nrun_ref,
                      q_ref, c_hbm, o_ref, c_buf, sem, slot_ref, *, scale, bs,
                      Sq, R, MB, G):
    """Grid (B,), a row a step, as ``_paged_gqa_kernel`` without a window
    (the arena whole and the layer a scalar, the row's table and the next
    row's as SMEM blocks, tiles of ``G`` pages in two buffers, the next tile
    fetched while this one is attended; ``_paged_kernel``'s docstring holds
    the semaphore and zero-fill invariants, restated below for what differs).
    ONE arena and one staged tile, which is the key with all ``W`` lanes and
    the value with its first ``R``; all heads are the rows of one ``[M, W] x
    [W, keys]`` product (``q_ref`` is ``[1, M, W]``, row ``i * Sq + s`` head
    ``i`` at query ``s``).  A tile is the unit of the copy; it is attended in
    steps of :func:`_mla_attend_rows` keys, one online-softmax update each, in
    order (a step past the row's last key adds exactly nothing).

    A tile is copied by :func:`_tile_copies`, under the flag of
    :func:`paged_tile_runs` (``run_ref [1, tiles]`` for the row, ``nrun_ref``
    for the next, beside the tables; the arena comes viewed ``[layers, pages
    * bs, W]``): a RUN whose ``G`` pages are all live (``(t+1)*G <= nk``) is
    ONE copy of ``[G*bs, W]`` from row ``tbl[t*G] * bs`` of the layer, any
    other tile a copy a live page.  The flag is the word row ``b`` read
    through ``nrun_ref`` when it started row ``b + 1``'s first tile and row
    ``b + 1`` reads through ``run_ref`` when it waits."""
    b = pl.program_id(0)
    layer = lay_ref[0]
    seq_len = len_ref[b]
    rows_t = G * bs
    A = _mla_attend_rows(rows_t)
    M = q_ref.shape[1]

    def pages_of(row):                            # live (DMA'd) pages
        return jnp.minimum((len_ref[row] + Sq + bs - 1) // bs, MB)

    nk = pages_of(b)
    nt = (nk + G - 1) // G                        # live tiles, >= 1

    def tile_copies(row, t, slot, do):
        mine = row == b
        entry = lambda col: jnp.where(mine, tbl_ref[0, col], nxt_ref[0, col])
        live = pages_of(row) - t * G
        source = lambda page, n: c_hbm.at[layer, pl.ds(
            pl.multiple_of(page * bs, bs), n * bs)]
        run = None
        if c_hbm.shape[1] >= rows_t:            # else an arena under a tile
            run = jnp.where(mine, run_ref[0, t], nrun_ref[0, t])
        _tile_copies(do, [(source, lambda *rows: c_buf.at[(slot, *rows)],
                           lambda: sem.at[slot])], entry, lambda: t * G, live,
                     G, bs, run)

    start = lambda cp: cp.start()

    @pl.when(b == 0)
    def _():
        c_buf[...] = jnp.zeros_like(c_buf)
        slot_ref[0] = 0
        tile_copies(0, 0, 0, start)

    slot0 = slot_ref[0]
    q = q_ref[0]                                  # [M, W]

    def tile(t, carry):
        slot = (slot0 + t) % 2
        last = t + 1 == nt

        @pl.when(jnp.logical_not(last) | (b + 1 < pl.num_programs(0)))
        def _():
            tile_copies(jnp.where(last, b + 1, b), jnp.where(last, 0, t + 1),
                        1 - slot, start)

        tile_copies(b, t, slot, lambda cp: cp.wait())
        m, l, acc = carry
        for lo in range(0, rows_t, A):            # the tile, A keys a step
            qpos = seq_len + jax.lax.broadcasted_iota(jnp.int32, (M, A), 0) % Sq
            cols = t * rows_t + lo + jax.lax.broadcasted_iota(jnp.int32, (M, A), 1)
            valid = (cols <= qpos) & (cols < nk * bs)
            c = c_buf[slot, lo:lo + A]                        # [A, W]
            s = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG_INF)                  # [M, A]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(c.dtype), c_buf[slot, lo:lo + A, :R],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m = m_new
        return m, l, acc

    carry = (jnp.full((M, 1), NEG_INF, jnp.float32),
             jnp.zeros((M, 1), jnp.float32), jnp.zeros((M, R), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, nt, tile, carry)
    slot_ref[0] = (slot0 + nt) % 2
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_mla_call(q, arena, layer, block_tables, tile_runs, lengths, scale,
                    R, G):
    B, Sq, H, W = q.shape
    L, NB, BS, _ = arena.shape
    MB = block_tables.shape[1]
    block_tables = jnp.asarray(block_tables, jnp.int32)[:, None, :]
    tile_runs = jnp.asarray(tile_runs, jnp.int32)[:, None, :]
    sublane = 8 * 4 // np.dtype(q.dtype).itemsize
    M = -(-H * Sq // sublane) * sublane
    # [B, Sq, H, W] -> [B, H*Sq, W]: a head's queries together
    qm = jnp.pad(q.transpose(0, 2, 1, 3).reshape(B, H * Sq, W),
                 ((0, 0), (0, M - H * Sq), (0, 0)))
    row = lambda b, *_: (b, 0, 0)
    nxt = lambda b, *_: (jnp.minimum(b + 1, B - 1), 0, 0)
    smem = lambda cols, at: pl.BlockSpec((None, 1, cols), at,
                                         memory_space=pltpu.MemorySpace.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # layer, lengths
        grid=(B,),
        in_specs=[
            smem(MB, row), smem(MB, nxt),
            smem(tile_runs.shape[2], row), smem(tile_runs.shape[2], nxt),
            pl.BlockSpec((1, M, W), row),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec((1, M, R), row),
        scratch_shapes=[
            pltpu.VMEM((2, G * BS, W), arena.dtype),
            pltpu.SemaphoreType.DMA((2,)),        # a tile buffer each
            pltpu.SMEM((1,), jnp.int32),          # buffer of the row's tile 0
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_mla_kernel, scale=scale, bs=BS, Sq=Sq, R=R,
                          MB=MB, G=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, M, R), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_pallas.interpret(),
        name="paged_mla_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), jnp.asarray(lengths, jnp.int32),
      block_tables, block_tables, tile_runs, tile_runs, qm,
      arena.reshape(L, NB * BS, W))               # a page's rows lie together
    return out[:, :H * Sq].reshape(B, H, Sq, R).transpose(0, 2, 1, 3)


# --------------------------------------------------------------------------- #
# The plan of a page group: which kernel its layers get and at which sizes.
# Decided HERE, once, from static shapes and the platform, by a constructor a
# family; ``models/gpt.py:GPTConfig.paged_plans`` says which family each of a
# model's page groups is, and ``init_serving``, both step functions and the
# kernels' calls read the answer.
# --------------------------------------------------------------------------- #
class PagedAttention(NamedTuple):
    """The paged attention of ONE page group's layers, static Python.  The
    allocator's ``run_blocks``, the flags of :meth:`tile_runs` and the tile a
    kernel copies are all ``run_pages`` of the one plan, so they cannot
    differ (if they did the tokens would stay right and every tile fall back
    to a copy a page: 11-28% of a step, PERF.md section 6, PRs 40 and 44).
    A window group's plan asks for runs as a full group's does: the groups of
    a model share their lanes and their pages, hence their ``run_pages``, and
    a ring is laid down in the same runs (``serving/kv_cache.py``)."""
    kernel: Optional[str]       # the ``pallas_call``'s name; None: the gather reference
    tile_pages: int             # pages a tile of the ATTEND holds (0 on a reference)
    run_pages: int              # pages ONE copy brings where they lie together
                                # (what the allocator lays in runs); 0: a copy a page
    chunk_queries: int          # ``Sq`` of a prompt chunk's packed row
    rows_a_token: int = 1       # rows of the calls a token (chosen pages: K/V heads)
    window: Optional[int] = None    # K and V: the keys a query sees (the table a ring)
    value_lanes: int = 0        # a latent cache: its value's lanes (0: K and V arenas)
    scale: float = 0.0          # a latent cache: what its logits are multiplied by

    def tile_runs(self, block_tables, pages: int):
        """:func:`paged_tile_runs` of a step's tables at ``run_pages``: the
        same for every layer, so worked out once a group, outside the scan.
        None for a ring that is no whole number of runs wide (its allocator
        deals in single blocks): a copy a page."""
        G = self.run_pages
        if self.window is not None and G and block_tables.shape[1] % G:
            G = 0
        return paged_tile_runs(block_tables, pages, G)

    def attend(self, q, arenas, layer, block_tables, lengths, *, tile_runs=None,
               chunk: int = 0, bias=None):
        """Attention of layer ``layer`` of the group's ``arenas`` (K and V
        ``[layers, pages, BS, lanes]``, or a latent cache's one array and
        None): q ``[B, Sq, H, D]``, row ``b``'s queries at ``lengths[b] +
        arange(Sq)`` under ``block_tables [B, MB]``.  With ``chunk`` the rows
        hold one query each and the last ``chunk`` are a prompt chunk,
        attended packed, ``chunk_queries`` a row (:func:`_rows_and_chunk`).
        ``tile_runs``: :meth:`tile_runs` of these tables, from a caller whose
        tables grow in runs; None copies page by page (a latent cache's kernel
        works the flags out itself), the same numbers to the bit.  The kernel, or
        where ``kernel`` is None the layer sliced out and the reference."""
        k_arena, v_arena = arenas
        if self.value_lanes and self.kernel and tile_runs is None:
            tile_runs = self.tile_runs(block_tables, k_arena.shape[1])
        if chunk:
            attend = lambda q, tables, lens, bias: self.attend(
                q, arenas, layer, tables[0], lens, tile_runs=tables[1], bias=bias)
            return _rows_and_chunk(attend, chunk, self.chunk_queries, q,
                                   (block_tables, tile_runs), lengths, bias)
        if self.value_lanes:
            if not self.kernel:
                pages = jax.lax.dynamic_index_in_dim(k_arena, layer, 0, keepdims=False)
                return paged_mla_attention_reference(
                    q, pages, block_tables, lengths, scale=self.scale,
                    value_lanes=self.value_lanes)
            return _paged_mla_call(q, k_arena, layer, block_tables, tile_runs,
                                   lengths, self.scale, self.value_lanes,
                                   self.run_pages)
        if self.kernel and self.kernel != "paged_attention":
            return _paged_gqa_call(q, k_arena, v_arena, layer, block_tables,
                                   lengths, self, tile_runs)
        kl = jax.lax.dynamic_index_in_dim(k_arena, layer, 0, keepdims=False)
        vl = jax.lax.dynamic_index_in_dim(v_arena, layer, 0, keepdims=False)
        if self.kernel:
            return _paged_call(q, kl, vl, block_tables, lengths, self.tile_pages)
        return paged_attention_reference(q, kl, vl, block_tables, lengths,
                                         bias=bias, window=self.window)


def softmax_plan(H, Hkv, D, BS, MB, chunk, dtype, bias=False, window=None,
                 name=None) -> PagedAttention:
    """Softmax attention over cached K and V: ``H`` query heads on ``Hkv``
    K/V heads of ``D`` lanes, pages of ``BS`` keys under tables of ``MB``
    columns, a prompt chunk of ``chunk`` tokens, the cache's ``dtype``; under
    an additive ``bias`` (ALiBi) or a ``window``.  THE rule: grouped K/V
    heads, a window, or multi-head attention whose heads are whole 128-lane
    tiles (OLMoE's 16 of 128: a group of one) go to ``paged_gqa_attention``,
    which takes the arena whole and a tile of pages that lie together with
    one copy (:func:`paged_run_tile_pages`), over every key and under a
    window alike: a window group's ring is laid down in runs and gives a run
    back whole (``serving/kv_cache.py``), and its kernel cuts a row's tiles on
    the runs' boundaries.  Multi-head attention at ``D = 64`` over
    every key keeps the layer sliced out of the arena and ``paged_attention``:
    the successor has no two-heads-a-lane-slice case (``_attend_block``), and
    ``decode-heavy``'s backlog cannot outlast a 124M step without the copy
    (ROADMAP S1, S0 (l)).  So does a bias, which neither kernel takes: the
    reference.  A packed row is a K/V head's ``H / Hkv`` query heads the rows
    of one product, or a head a product of its own in its lane slice.
    ``name`` is one of the family's kernels' whatever the rule says: an entry
    point called with arrays alone is that kernel or the reference."""
    assert not bias or window is None, (
        "a window layer with an additive bias has no paged path")
    if name is None:
        whole = not bias and (window is not None or Hkv != H or D % _LANES == 0)
        name = "paged_gqa_attention" if whole else "paged_attention"
    whole = name != "paged_attention"       # the arena whole, not a layer's slice
    gate = gqa_kernel_shape_ok if whole else kernel_shape_ok
    runs = (not bias and _pallas.use_kernel(name)
            and gate(H, Hkv, D, BS, dtype) and _pallas.single_device())
    G = paged_tile_pages(BS, MB, 1, Hkv * D, dtype)
    if whole:
        queries = paged_chunk_queries(chunk, H // Hkv, Hkv, D, D, G * BS, dtype)
    else:
        W = _lane_slices(H, D)[0]
        queries = paged_chunk_queries(chunk, 1, H, W, W, G * BS, dtype)
    in_runs = runs and whole
    return PagedAttention(
        name if runs else None, G if runs else 0,
        paged_run_tile_pages(BS, MB, Hkv * D, dtype) if in_runs else 0,
        queries, window=window)


def chosen_plan(Hkv, g, D, BS, columns, dtype) -> PagedAttention:
    """Attention over the pages a query CHOSE (block-sparse attention whose
    block is a page; ``models/hybrid.py``).  The selection differs by token
    and by K/V head, so a row is one (token, K/V head), ``Hkv`` rows a token:
    its table lists the ``columns`` chosen pages in logical order, its length
    counts the keys in them before the query (the query's own block ends the
    list), and its ``g`` query heads are the rows of one product.  That is
    ``_paged_gqa_kernel`` over ONE K/V head, on an arena that gives a K/V
    head a page of its own (``[layers, blocks * Hkv, BS, D]``): the same body
    under a name of its own, so a trace tells the two apart.  Pages that
    were chosen lie in no runs: a copy a page."""
    return softmax_plan(g, 1, D, BS, columns, 0, dtype,
                        name="paged_sparse_attention")._replace(
                            run_pages=0, rows_a_token=Hkv)


def latent_plan(lanes, value_lanes, H, BS, MB, chunk, dtype, scale) -> PagedAttention:
    """Latent attention (MLA in its absorbed form): ``H`` heads read ONE
    cached vector of ``lanes`` a token, all of it the key and its first
    ``value_lanes`` the value, the logits times ``scale``.  A tile of
    :func:`_mla_tile_pages` is the unit of the copy and of the runs, attended
    in steps of :func:`_mla_attend_rows` keys; all heads are the rows of a
    packed row's one product."""
    runs = (_pallas.use_kernel("paged_mla_attention")
            and mla_kernel_shape_ok(lanes, value_lanes, BS, dtype)
            and _pallas.single_device())
    G = _mla_tile_pages(BS, MB)
    queries = paged_chunk_queries(chunk, H, 1, lanes, value_lanes,
                                  _mla_attend_rows(G * BS), dtype)
    tile = G if runs else 0
    return PagedAttention("paged_mla_attention" if runs else None, tile, tile,
                          queries, value_lanes=value_lanes, scale=scale)


# The entry points with arrays alone (the kernels' tests, tools): each builds
# its family's plan from the arrays' shapes, the one derivation.
def paged_attention(q, k_pages, v_pages, block_tables, lengths, bias=None):
    """Block-table attention over ONE layer's pages ``[NB, BS, Hkv*D]``: the
    kernel ``paged_attention`` where :func:`kernel_shape_ok` admits the shape
    (no bias, one device), else the jnp gather reference.  Sharded meshes
    take the reference (the gather partitions cleanly under SPMD; the kernel
    does not shard the global block arena)."""
    H, D = q.shape[2:]
    plan = softmax_plan(H, k_pages.shape[2] // D, D, k_pages.shape[1],
                        block_tables.shape[1], 0, k_pages.dtype,
                        bias is not None, name="paged_attention")
    if plan.kernel:
        return _paged_call(q, k_pages, v_pages, block_tables, lengths,
                           plan.tile_pages)
    return paged_attention_reference(q, k_pages, v_pages, block_tables,
                                     lengths, bias=bias)


def paged_layer_attention(q, k_arena, v_arena, layer, block_tables, lengths,
                          bias=None, window=None, chunk: int = 0,
                          tile_runs=None, name=None):
    """:meth:`PagedAttention.attend` of :func:`softmax_plan` for the arrays'
    shapes: what a step's layer of K and V runs."""
    H, D = q.shape[2:]
    _, _, BS, lanes = k_arena.shape
    plan = softmax_plan(H, lanes // D, D, BS, block_tables.shape[1], chunk,
                        k_arena.dtype, bias is not None, window, name)
    return plan.attend(q, (k_arena, v_arena), layer, block_tables, lengths,
                       tile_runs=tile_runs, chunk=chunk, bias=bias)


def paged_gqa_attention(q, k_arena, v_arena, layer, block_tables, lengths,
                        window=None, tile_runs=None):
    """The same by the kernel ``paged_gqa_attention`` (or the reference)
    whatever the family's rule says of the shapes."""
    return paged_layer_attention(q, k_arena, v_arena, layer, block_tables,
                                 lengths, window=window, tile_runs=tile_runs,
                                 name="paged_gqa_attention")


def paged_sparse_attention(q, k_arena, v_arena, layer, chosen, lengths):
    """q ``[rows, 1, g, D]``, a row a (token, K/V head); the arena ``[layers,
    pages, BS, D]``, a page one K/V head's block; ``chosen [rows, columns]``
    the physical pages the row attends, in logical order; ``lengths [rows]``
    the keys of those pages that lie before the query: :func:`chosen_plan`."""
    _, _, g, D = q.shape
    assert k_arena.shape[3] == D, "a K/V head a page"
    plan = chosen_plan(1, g, D, k_arena.shape[2], chosen.shape[1], k_arena.dtype)
    return plan.attend(q, (k_arena, v_arena), layer, chosen, lengths)


def paged_mla_attention(q, arena, layer, block_tables, lengths, *, scale,
                        value_lanes, chunk: int = 0, tile_runs=None):
    """Block-table latent attention of layer ``layer`` of the ONE-array arena
    ``[layers, pages, BS, W]``: q ``[B, Sq, H, W]`` (a head's query moved
    into the cached vector's space, zeros where the vector is padding)
    against each cached vector up to its position -> ``[B, Sq, H,
    value_lanes]``: :func:`latent_plan` for the arrays' shapes."""
    _, _, BS, W = arena.shape
    plan = latent_plan(W, value_lanes, q.shape[2], BS, block_tables.shape[1],
                       chunk, arena.dtype, scale)
    return plan.attend(q, (arena, None), layer, block_tables, lengths,
                       tile_runs=tile_runs, chunk=chunk)


def _mesh_divisors():
    """(batch, tensor) shard counts of the active mesh, (1, 1) without one."""
    from deepspeed_tpu.parallel import mesh as mesh_lib
    if not mesh_lib.has_mesh():
        return 1, 1
    mesh = mesh_lib.get_mesh()
    return (int(np.prod([mesh.shape[a] for a in mesh_lib.BATCH_AXES])),
            int(mesh.shape["tensor"]))


def decode_attention(q, ck, cv, pos, bias=None, *,
                     block_k: Optional[int] = None):
    """KV-cache attention for prefill/decode: the Pallas kernel where
    :func:`kernel_shape_ok` admits the shape (no bias), under shard_map
    when a mesh is active (batch over data/fsdp/expert, heads over tensor —
    decode never shards the cache length); else the einsum reference."""
    B, Sq, H, D = q.shape
    T = ck.shape[1]
    bk = block_k or min(128, T)
    batch_div, tp = _mesh_divisors()
    if (bias is not None or not _pallas.use_kernel("decode_attention")
            or T % bk != 0 or B % batch_div != 0 or H % tp != 0
            or not kernel_shape_ok(H // tp, ck.shape[2] // D // tp, D, bk,
                                   ck.dtype)):
        return decode_attention_reference(q, ck, cv, pos, bias=bias)
    call = functools.partial(_decode_call, bk=bk)
    if batch_div > 1 or tp > 1:
        from deepspeed_tpu.parallel import mesh as mesh_lib
        qspec = P(mesh_lib.BATCH_AXES, None, "tensor", None)
        cspec = P(mesh_lib.BATCH_AXES, None, "tensor")
        return jax.shard_map(
            call, mesh=mesh_lib.get_mesh(),
            in_specs=(qspec, cspec, cspec, P()),
            out_specs=qspec, check_vma=False)(q, ck, cv, pos)
    return call(q, ck, cv, pos)
