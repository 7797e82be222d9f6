"""A stack whose layers differ in shape and follow no period: the
MiniCPM-SALA family (``models/gpt.py:minicpm_sala_config``) on the serving
path.  ``cfg.pattern`` names EVERY layer; two mixers live here:

* ``sparse`` (the InfLLM-V2 line of MiniCPM4): grouped-query attention
  without rope whose query, once more than ``dense_len`` keys lie before it,
  attends only the ``topk`` blocks it scores highest.  The score comes from
  COMPRESSED keys (the mean of ``kernel`` keys every ``stride``), cached in
  pages beside K and V: a query's softmax over the compressed keys that end
  at or before it, summed over the query heads of a K/V head, a block's the
  max over the compressed keys that overlap it; the first ``init_blocks``
  and the blocks of the last ``window`` keys are chosen whatever they score.
  A block is a PAGE, and the selection goes INTO the block table:
  ``ops/pallas/decode_attention.py:paged_sparse_attention`` walks, a row a
  (token, K/V head), the chosen pages in logical order under a length that
  counts the keys in them.  No key that was not chosen is read, and nothing
  is gathered into a dense array;
* ``linear`` (Lightning attention): a head's cache is a float32 state ``[D,
  D]`` a slot, ``S_t = exp(-s_h) S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t /
  sqrt(D)``: one update a decode row, the chunked form over a prompt chunk
  (``O = ((Q K^T) . D) V + (Q . a) S_in``, every decay ``exp(-s_h n)`` of
  an ``n >= 0``).  A chunk that starts at position 0 starts from a zero
  state, so a slot bound to a new sequence, and one whose request was
  preempted and is prefilled again, needs no other reset.

The leaves are stacked BY KIND (``params["blocks"]["sparse"]`` ``[4, ...]``,
``["linear"]`` ``[12, ...]``: no projection is padded to another kind's
width), the step walks the stack in RUNS of one kind (a ``lax.scan`` a run,
a layer's leaves taken from the kind's stack by its index), and only the
sparse layers own pages: ``cfg.arena_layout``.  What is not K and V rides in
``aux``: the compressed keys' pages and the states (:func:`init_aux`).

A step WITHOUT a prompt chunk (two of three in a long run) is the same
program taking the other side of a few ``lax.cond``: the chunk's rows carry
nothing there, so their selection and their walk are skipped and every
matrix (projections, gates, the MLP, the head) is multiplied by the decode
rows alone (:func:`_rows_that_carry`).
"""

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from deepspeed_tpu.models import gpt

Array = jax.Array
HIGHEST = jax.lax.Precision.HIGHEST


def layer_runs(cfg) -> List[Tuple[str, int, int]]:
    """The stack as runs of one mixer: (mixer, index of the run's first layer
    in its kind's stack, layers)."""
    runs, seen = [], {"sparse": 0, "linear": 0}
    for m in cfg.mixers:
        if runs and runs[-1][0] == m:
            runs[-1][2] += 1
        else:
            runs.append([m, seen[m], 1])
        seen[m] += 1
    return [tuple(r) for r in runs]


def linear_decay(cfg) -> np.ndarray:
    """``s_h`` of every linear layer, ``[linear layers, heads]`` float32:
    ``2^(-8 (h + 1) / H) * (1 - l / (L - 1) + 1e-5)``, ``l`` the layer's
    PUBLISHED index and ``L`` the published depth (Lightning Attention-2)."""
    L = cfg.published_layers or cfg.n_layer
    slope = 2.0 ** (-8.0 * (np.arange(cfg.n_head) + 1) / cfg.n_head)
    depth = [k.depth if k.depth is not None else i
             for i, k in enumerate(cfg.pattern) if k.mixer == "linear"]
    return (slope[None] * (1.0 - np.asarray(depth)[:, None] / max(L - 1, 1)
                           + 1e-5)).astype(np.float32)


# --------------------------------------------------------------------------- #
# Parameters, stacked by kind
# --------------------------------------------------------------------------- #
def _leaf_shapes(cfg, mixer: str) -> Dict[str, Tuple[int, ...]]:
    E, I, D = cfg.n_embd, cfg.ffn_dim, cfg.head_dim
    A = cfg.n_head * D
    shapes = {"ln1_g": (E,), "ln2_g": (E,), "q_norm_g": (D,), "k_norm_g": (D,),
              "gate_w": (E, A), "out_w": (A, E),
              "fc_w": (E, 2 * I), "proj_w": (I, E)}
    if mixer == "sparse":
        shapes.update(q_w=(E, A), kv_w=(E, 2 * cfg.kv_heads * D))
    else:
        shapes.update(qkv_w=(E, 3 * A), onorm_g=(A,))
    return shapes


def init_blocks(cfg, rng: Array) -> Dict:
    """``{"sparse": leaves [sparse layers, ...], "linear": leaves [linear
    layers, ...]}``: norm gains 1, every matrix normal 0.02."""
    def one(mixer, key):
        shapes = _leaf_shapes(cfg, mixer)
        keys = jax.random.split(key, len(shapes))
        return {name: jnp.ones(shape, jnp.float32) if name.endswith("_g")
                else gpt._dense_init(k, shape[0], shape)
                for k, (name, shape) in zip(keys, sorted(shapes.items()))}

    out = {}
    for n, mixer in enumerate(("sparse", "linear")):
        count = cfg.mixers.count(mixer)
        if count:
            # a layer at a time: one layer's random bits are a gigabyte
            out[mixer] = jax.lax.map(
                lambda k, mixer=mixer: one(mixer, k),
                jax.random.split(jax.random.fold_in(rng, n), count))
    return out


def block_partition_specs(cfg) -> Dict:
    column = {"q_w", "kv_w", "qkv_w", "gate_w", "fc_w"}
    row = {"out_w", "proj_w"}
    spec = lambda name: PartitionSpec(
        None, *((None, "tensor") if name in column else ("tensor", None)
                if name in row else ()))
    return {m: {name: spec(name) for name in _leaf_shapes(cfg, m)}
            for m in ("sparse", "linear") if m in cfg.mixers}


def num_params(cfg) -> int:
    per_kind = {m: sum(math.prod(s) for s in _leaf_shapes(cfg, m).values())
                for m in ("sparse", "linear")}
    return (sum(per_kind[m] for m in cfg.mixers)
            + 2 * cfg.padded_vocab * cfg.n_embd + cfg.n_embd)


# --------------------------------------------------------------------------- #
# What the cache holds beside K and V
# --------------------------------------------------------------------------- #
def keys_a_page(cfg) -> int:
    """Compressed keys a page owns: key ``j`` covers the keys ``stride * j ..
    stride * j + kernel - 1`` and belongs to the page of its LAST key, which
    is page ``(j + 1) // keys_a_page`` (``kernel = 2 * stride``): slot ``(j +
    1) % keys_a_page`` there."""
    return cfg.sparse.block // cfg.sparse.stride


def init_aux(cfg, num_blocks: int, block_size: int, slots: int, dtype) -> Dict:
    """``kc [sparse layers, num_blocks, keys_a_page * Hkv * D]``: the
    compressed keys of a page, reached through the sparse layers' block
    table like K and V; ``state [linear layers, slots, H, D, D]`` float32: a
    linear layer's cache, a slot's whatever its length."""
    assert block_size == cfg.sparse.block, (
        f"a sparse layer selects blocks of {cfg.sparse.block} keys and a "
        f"selected block is a page: block_size {block_size}")
    D = cfg.head_dim
    return {"kc": jnp.zeros((cfg.mixers.count("sparse"), num_blocks,
                             keys_a_page(cfg) * cfg.kv_heads * D), dtype),
            "state": jnp.zeros((cfg.mixers.count("linear"), slots,
                                cfg.n_head, D, D), jnp.float32)}


def aux_bytes(cfg, num_blocks: int, slots: int, dtype_bytes: int = 2):
    """(bytes of compressed keys, bytes of state) :func:`init_aux` holds."""
    held = jax.eval_shape(lambda: init_aux(cfg, num_blocks, cfg.sparse.block,
                                           slots, jnp.float32))
    return held["kc"].size * dtype_bytes, held["state"].size * 4


def table_columns(cfg, block_size: int) -> int:
    """Columns of the table a (token, K/V head) row walks: the blocks of
    ``dense_len`` keys (every key is attended up to there), or ``topk``."""
    return max(cfg.sparse.topk, -(-cfg.sparse.dense_len // block_size))


def keys_attended(cfg, positions: np.ndarray) -> np.ndarray:
    """Keys a query at each of ``positions`` attends in ONE sparse layer and
    K/V head: all ``t + 1`` up to ``dense_len``, beyond it ``topk`` blocks of
    which the last is the query's own, full up to the query."""
    sp = cfg.sparse
    t = np.asarray(positions, np.int64)
    held = np.minimum(sp.topk, t // sp.block + 1)
    return np.where(t + 1 <= sp.dense_len, t + 1,
                    (held - 1) * sp.block + t % sp.block + 1)


class _LayerLeaves:
    """Layer ``i``'s leaves of a kind's stack, each taken out of the stack
    WHERE IT IS READ: a slice made once outside a ``lax.cond`` would be the
    branch's operand, and XLA copies an operand out (a layer's 570 MB a
    layer a step), where a slice made inside the branch feeds its dot in
    place."""

    def __init__(self, stack: Dict, i):
        self.stack, self.i = stack, i

    def __getitem__(self, name: str) -> Array:
        return jax.lax.dynamic_index_in_dim(self.stack[name], self.i, 0,
                                            keepdims=False)


def _rows_that_carry(fn, xs, chunk: int, live):
    """``fn(*xs)``, a function of each row alone (a projection, the MLP, the
    head): over all rows in a step with a prompt chunk, over the decode rows
    alone (zeros behind them) in a step without one, where the chunk's rows
    carry nothing and nobody reads what they give.  Two of three steps of a
    long run carry no chunk, and 512 of their 528 rows would go through every
    matrix for nobody."""
    if not chunk:
        return fn(*xs)
    n_dec = xs[0].shape[0] - chunk

    def decode_rows_alone():
        y = fn(*(x[:n_dec] for x in xs))
        return jnp.pad(y, ((0, chunk),) + ((0, 0),) * (y.ndim - 1))

    return jax.lax.cond(live[n_dec], lambda: fn(*xs), decode_rows_alone)


def _gated_out(p, o, h, dt, chunk: int, live):
    """``W_o(o * sigmoid(W_g h))``: both kinds' output gate and projection."""
    return _rows_that_carry(
        lambda o, h: (o * jax.nn.sigmoid(h @ gpt._wget(p, "gate_w", dt)))
        @ gpt._wget(p, "out_w", dt), (o, h), chunk, live)


# --------------------------------------------------------------------------- #
# The sparse mixer
# --------------------------------------------------------------------------- #
def _compress_candidates(cfg, positions, live, tables, chunk: int):
    """The tokens of this step that may END a compressed key: every decode
    row, and of the prompt chunk (consecutive positions from its first row's)
    every ``stride``-th token: -> (position ``[n]``, whether it does end one
    ``[n]``, its table ``[n, MB]``)."""
    sp = cfg.sparse
    n_dec = positions.shape[0] - chunk
    t, ok, tb = positions[:n_dec], live[:n_dec], tables[:n_dec]
    if chunk:
        start = positions[n_dec]
        at = (-(start + 1)) % sp.stride + sp.stride * jnp.arange(chunk // sp.stride)
        t = jnp.concatenate([t, start + at])
        ok = jnp.concatenate([ok, live[n_dec:][at]])
        tb = jnp.concatenate([tb, jnp.broadcast_to(
            tables[n_dec], (at.shape[0], tables.shape[1]))])
    return t, ok & ((t + 1) % sp.stride == 0) & (t + 1 >= sp.kernel), tb


def _write_compressed(cfg, kp, kc, li, positions, live, tables, chunk):
    """Compressed keys that this step's tokens complete: the mean of the last
    ``kernel`` keys (read back from the pages, where this step's own lie
    already), into the slot of the page that holds the key's last token."""
    sp, Hkv, D = cfg.sparse, cfg.kv_heads, cfg.head_dim
    BS, r = kp.shape[2], keys_a_page(cfg)
    t, ends, tb = _compress_candidates(cfg, positions, live, tables, chunk)
    p = jnp.maximum(t[:, None] - sp.kernel + 1 + jnp.arange(sp.kernel)[None], 0)
    page = jnp.take_along_axis(tb, p // BS, axis=1)                # [n, kernel]
    keys = kp[li, page[:, :, None] * Hkv + jnp.arange(Hkv)[None, None],
              (p % BS)[:, :, None]]                               # [n, kernel, Hkv, D]
    mean = jnp.mean(keys.astype(jnp.float32), axis=1).astype(kc.dtype)
    f = (t + 1) // sp.stride - 1                                   # key j = f - 1
    dest = jnp.where(ends, jnp.take_along_axis(tb, (f // r)[:, None], 1)[:, 0], 0)
    lanes = (f % r)[:, None] * (Hkv * D) + jnp.arange(Hkv * D)[None]
    return kc.at[li, dest[:, None], lanes].set(mean.reshape(-1, Hkv * D))


def _select(cfg, q, kc_rows, positions, BS: int):
    """The blocks each query attends, as a table's columns.  ``q [n, Hkv, g,
    D]``; ``kc_rows [n | 1, MB * r, Hkv, D]``: the compressed keys under the
    queries' tables in logical order (entry ``f`` is key ``f - 1``; one row
    where all queries share a table); ``positions [n]``.  -> (logical blocks
    ``[n, Hkv, W]`` in rising order, the position of the query among the
    keys of those blocks ``[n]``)."""
    sp = cfg.sparse
    n, Hkv, g, D = q.shape
    F = kc_rows.shape[1]
    r, MB, W = keys_a_page(cfg), F // keys_a_page(cfg), table_columns(cfg, BS)
    t = positions
    if kc_rows.shape[0] == 1:
        s = jnp.einsum("nhgd,fhd->nhgf", q, kc_rows[0],
                       preferred_element_type=jnp.float32)
    else:
        s = jnp.einsum("nhgd,nfhd->nhgf", q, kc_rows,
                       preferred_element_type=jnp.float32)
    s = s / math.sqrt(D)
    f = jnp.arange(F)[None]
    ends_before = ((f >= 1) & (f <= ((t + 1) // sp.stride - 1)[:, None]))[:, None, None]
    p = jax.nn.softmax(jnp.where(ends_before, s, -1e30), axis=-1)
    a = jnp.where(ends_before, p, 0.0).sum(axis=2)                 # [n, Hkv, F]
    # block b is overlapped by the keys f = r b .. r b + r
    a = a.reshape(n, Hkv, MB, r)
    nxt = jnp.pad(a[:, :, 1:, 0], ((0, 0), (0, 0), (0, 1)))
    score = jnp.maximum(a.max(axis=-1), nxt)                       # [n, Hkv, MB]
    b = jnp.arange(MB)[None, None]
    tq = t[:, None, None]
    forced = (b < sp.init_blocks) | (b >= jnp.maximum(tq - sp.window + 1, 0) // BS)
    score = jnp.where(forced, jnp.inf, score)
    score = jnp.where(b <= tq // BS, score, -jnp.inf)
    # the chosen blocks in rising order, those of no key (a query with fewer
    # than topk blocks behind it) last: the query's own block ends the list
    top, chosen = jax.lax.top_k(score, min(sp.topk, MB))
    chosen = jnp.sort(jnp.where(top > -jnp.inf, chosen, MB), axis=-1)
    chosen = jnp.pad(jnp.minimum(chosen, MB - 1),
                     ((0, 0), (0, 0), (0, W - chosen.shape[-1])))
    dense = (t + 1 <= sp.dense_len)
    blocks = jnp.where(dense[:, None, None], jnp.arange(W)[None, None], chosen)
    held = jnp.minimum(sp.topk, t // BS + 1)
    return blocks, jnp.where(dense, t, (held - 1) * BS + t % BS)


def sparse_mixer(cfg, p, h, dt, kp, vp, kc, li, positions, live, tables,
                 write_blocks, write_offsets, chunk: int):
    """One sparse layer's attention over the rows ``h [B, E]`` (a token a
    row; the last ``chunk`` consecutive tokens of one sequence): -> (the
    mixer's output ``[B, E]`` before the residual, kp, vp, kc)."""
    from deepspeed_tpu.ops.pallas.decode_attention import paged_sparse_attention
    B = h.shape[0]
    H, Hkv, D = cfg.n_head, cfg.kv_heads, cfg.head_dim
    g, BS = H // Hkv, kp.shape[2]
    n_dec = B - chunk
    project = lambda name: _rows_that_carry(
        lambda r: r @ gpt._wget(p, name, dt), (h,), chunk, live)
    q = gpt.rms_norm(project("q_w").reshape(B, Hkv, g, D), p["q_norm_g"], eps=cfg.ln_eps)
    k, v = jnp.split(project("kv_w").reshape(B, 2 * Hkv, D), 2, axis=1)
    k = gpt.rms_norm(k, p["k_norm_g"], eps=cfg.ln_eps)
    # a K/V head a page of its own: page ``block * Hkv + head``
    page = write_blocks * Hkv + jnp.arange(Hkv)[None]              # [B, Hkv]
    kp = kp.at[li, page, write_offsets].set(k.astype(kp.dtype))
    vp = vp.at[li, page, write_offsets].set(v.astype(vp.dtype))
    with jax.named_scope("sparse_select"):
        kc = _write_compressed(cfg, kp, kc, li, positions, live, tables, chunk)

    def attend(rows, shared: bool):
        """The rows ``rows`` (a slice): each chooses its blocks, then walks
        them.  ``shared``: they are one sequence's, under one table."""
        tb, n = tables[rows], q[rows].shape[0]
        with jax.named_scope("sparse_select"):
            under = kc[li, tb[:1] if shared else tb].reshape(
                1 if shared else n, -1, Hkv, D)
            blocks, at = _select(cfg, q[rows], under, positions[rows], BS)
            # logical -> physical through the row's table, a row a (token, head)
            chosen = jnp.take_along_axis(tb[:, None], blocks, axis=2)
            chosen = (chosen * Hkv + jnp.arange(Hkv)[None, :, None]).reshape(n * Hkv, -1)
        with jax.named_scope("sparse_attend"):
            return paged_sparse_attention(
                q[rows].reshape(n * Hkv, 1, g, D), kp, vp, li, chosen,
                jnp.repeat(at, Hkv)).reshape(n, H * D)

    o = attend(slice(0, n_dec), False)
    if chunk:
        # a step without a prompt chunk (two of three, in a long run) skips
        # the chunk rows' selection and their walk: nobody reads them
        o = jnp.concatenate([o, jax.lax.cond(
            live[n_dec], lambda: attend(slice(n_dec, B), True),
            lambda: jnp.zeros((chunk, H * D), o.dtype))])
    return _gated_out(p, o, h, dt, chunk, live), kp, vp, kc


# --------------------------------------------------------------------------- #
# The linear mixer
# --------------------------------------------------------------------------- #
def linear_chunk(q, k, v, s_in, decay, live):
    """The chunked form over ``C`` consecutive tokens of one sequence.  ``q``
    (scaled), ``k``, ``v`` ``[C, H, D]`` float32; ``s_in [H, D, D]``;
    ``decay [H]`` (``s_h``); ``live [C]``: the first ``n`` tokens carry the
    sequence.  -> (o ``[C, H, D]``, the state after those ``n``).  Every
    decay formed is ``exp(-s_h m)`` of an ``m >= 0``."""
    C = q.shape[0]
    i = jnp.arange(C)
    n = jnp.sum(live)
    gap = (i[:, None] - i[None, :]).astype(jnp.float32)            # i - j
    d = jnp.where((gap >= 0) & live[None, :], jnp.exp(
        -decay[:, None, None] * jnp.maximum(gap, 0.0)[None]), 0.0)   # [H, C, C]
    a = jnp.einsum("ihd,jhd->hij", q, k, precision=HIGHEST) * d
    o = jnp.einsum("hij,jhd->ihd", a, v, precision=HIGHEST)
    grown = jnp.exp(-decay[None] * (i[:, None] + 1.0))             # [C, H]
    o = o + jnp.einsum("ihd,hde->ihe", q * grown[:, :, None], s_in, precision=HIGHEST)
    left = jnp.where(live[:, None], jnp.exp(
        -decay[None] * jnp.maximum(n - 1 - i, 0)[:, None].astype(jnp.float32)), 0.0)
    s_out = (jnp.exp(-decay * n.astype(jnp.float32))[:, None, None] * s_in
             + jnp.einsum("jhd,jhe->hde", k * left[:, :, None], v, precision=HIGHEST))
    return o, s_out


def linear_mixer(cfg, p, h, dt, state, li, decay, positions, live, slots,
                 chunk: int):
    """One linear layer over the rows ``h [B, E]``: a decode row updates the
    state of its slot (row ``s`` is slot ``s``) and reads it; the prompt
    chunk runs the chunked form from the state of ITS slot, from zero where
    it starts at position 0.  -> (output ``[B, E]``, state)."""
    B = h.shape[0]
    H, D = cfg.n_head, cfg.head_dim
    n_dec = B - chunk
    qkv = _rows_that_carry(lambda r: r @ gpt._wget(p, "qkv_w", dt), (h,), chunk, live)
    q, k, v = jnp.split(qkv.reshape(B, 1, 3 * H, D), 3, axis=2)
    rope = lambda t, g: gpt.apply_rope(
        gpt.rms_norm(t, g, eps=cfg.ln_eps), positions[:, None], cfg.rope_theta)
    f32 = lambda t: t[:, 0].astype(jnp.float32)
    q, k, v = f32(rope(q, p["q_norm_g"])) / math.sqrt(D), f32(rope(k, p["k_norm_g"])), f32(v)
    s_all = jax.lax.dynamic_index_in_dim(state, li, 0, keepdims=False)
    assert s_all.shape[0] == n_dec, "a decode row a slot"
    grown = (jnp.exp(-decay)[None, :, None, None] * s_all
             + k[:n_dec, :, :, None] * v[:n_dec, :, None, :])
    s_all = jnp.where(live[:n_dec, None, None, None], grown, s_all)
    o = jnp.einsum("nhd,nhde->nhe", q[:n_dec], s_all, precision=HIGHEST)
    if chunk:
        slot, first = slots[n_dec], positions[n_dec]
        kept = jax.lax.dynamic_index_in_dim(s_all, slot, 0, keepdims=False)
        oc, s_out = linear_chunk(q[n_dec:], k[n_dec:], v[n_dec:],
                                 jnp.where(first == 0, 0.0, kept), decay,
                                 live[n_dec:])
        # a step without a chunk leaves slot 0 (what its rows name) as it is
        s_all = jax.lax.dynamic_update_index_in_dim(
            s_all, jnp.where(live[n_dec], s_out, kept), slot, 0)
        o = jnp.concatenate([o, oc])
    state = jax.lax.dynamic_update_index_in_dim(state, s_all, li, 0)
    o = gpt.rms_norm(o.reshape(B, H * D), p["onorm_g"], eps=cfg.ln_eps).astype(dt)
    return _gated_out(p, o, h, dt, chunk, live), state


# --------------------------------------------------------------------------- #
# The step
# --------------------------------------------------------------------------- #
def hybrid_paged_step(cfg, params: Dict, input_ids: Array, positions: Array,
                      k_pages: Array, v_pages: Array, block_tables,
                      write_blocks, write_offsets, chunk: int = 0, aux=None,
                      slots=None, live=None):
    """``models/gpt.py:gpt_paged_step`` for a hybrid stack: ``input_ids [B,
    1]``, a token a row, the last ``chunk`` rows a prompt chunk; the arena is
    the SPARSE layers' (``cfg.arena_layout``), ``block_tables`` and
    ``write_blocks`` one group's; ``aux`` is :func:`init_aux`'s pair,
    ``slots [B]`` the slot a row's sequence holds and ``live [B]`` whether it
    carries one.  -> (logits ``[B, 1, V]`` float32, k_pages, v_pages, aux)."""
    B, S = input_ids.shape
    assert S == 1 and aux is not None, "a token a row, with the stack's state"
    assert not chunk or chunk % cfg.sparse.stride == 0, (
        f"a prompt chunk of {chunk} is not whole strides of {cfg.sparse.stride}")
    if isinstance(block_tables, (tuple, list)):
        (block_tables,), (write_blocks,) = block_tables, write_blocks
    dt, rs = cfg.dtype, cfg.residual_scale
    decays = jnp.asarray(linear_decay(cfg))
    blocks = params["blocks"]
    wb, wo = write_blocks.reshape(B, 1), write_offsets.reshape(B, 1)
    x = params["wte"].astype(dt)[input_ids[:, 0]] * jnp.asarray(cfg.scale_emb, dt)

    def layer(mixer, carry, i):
        x, kp, vp, kc, state = carry
        p = _LayerLeaves(blocks[mixer], i)
        with jax.named_scope("attn"):
            h = gpt.rms_norm(x, p["ln1_g"], eps=cfg.ln_eps)
            if mixer == "sparse":
                with jax.named_scope("attn_sparse"):
                    o, kp, vp, kc = sparse_mixer(
                        cfg, p, h, dt, kp, vp, kc, i, positions, live,
                        block_tables, wb, wo, chunk)
            else:
                with jax.named_scope("attn_linear"):
                    o, state = linear_mixer(
                        cfg, p, h, dt, state, i, decays[i], positions, live,
                        slots, chunk)
        with jax.named_scope("mlp"):
            x = x + rs * o
            mlp = lambda r: gpt._mlp(cfg, p, gpt.rms_norm(r, p["ln2_g"], eps=cfg.ln_eps), dt)
            x = x + rs * _rows_that_carry(mlp, (x,), chunk, live)
        return (x, kp, vp, kc, state), None

    carry = (x, k_pages, v_pages, aux["kc"], aux["state"])
    for mixer, first, count in layer_runs(cfg):
        carry, _ = jax.lax.scan(lambda c, i, m=mixer: layer(m, c, i), carry,
                                first + jnp.arange(count, dtype=jnp.int32))
    x, k_pages, v_pages, kc, state = carry
    with jax.named_scope("head"):
        x = gpt.rms_norm(x, params["lnf_g"], eps=cfg.ln_eps) / jnp.asarray(
            cfg.head_divisor, dt)
        logits = _rows_that_carry(
            lambda r: (r @ params["lm_head"].astype(dt).T).astype(jnp.float32),
            (x,), chunk, live)
    return logits[:, None], k_pages, v_pages, {"kc": kc, "state": state}
