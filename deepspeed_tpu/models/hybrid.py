"""A stack whose layers are not softmax attention over their own token's
keys, on the serving path: ``cfg.pattern`` names EVERY layer (its mixer and
its feed-forward), and ONE walk (:func:`hybrid_paged_step`) runs the stack
in runs of a mixer, dispatching on both.  The MiniCPM-SALA family
(``models/gpt.py:minicpm_sala_config``: sparse and linear layers in no
period over a dense MLP), the ZAYA1 family (``zaya_config``: ``cca`` layers
over an expert bank), the Olmo-Hybrid family (``olmo_hybrid_config``:
``delta`` layers, three to every ``full`` layer, each norm on its sublayer's
output), the Qwen3-Next family (``qwen3_next_config``: ``delta`` layers of
more value heads than key heads, three to every ``full`` layer with a norm a
head, rope on a quarter of its lanes and an output gate, every layer over a
HELD share of a softmax bank beside a gated shared expert), the Keye-VL-2.0
family (``keye_vl2_config``: ``indexed`` layers
over a bank behind a linear softmax router) and the Jamba family
(``jamba_config``: ``mamba`` layers, thirteen to every ``full`` layer of
multi-query attention) are entries of :data:`MIXERS` and
:data:`FEED_FORWARDS`.  The mixers (``cca`` is described at
:func:`cca_mixer`):

* ``indexed`` (DeepSeek Sparse Attention under grouped-query attention, the
  Keye-VL-2.0 line): beside K and V a token caches ONE index key of 64 lanes
  a layer (its own projection, a LayerNorm, rope), in pages under the same
  block tables (``aux["ki"]``).  A query's 16 index heads score EVERY cached
  token (``I = sum_j w_j relu(qI_j . kI_s)``: 128 B a key a layer read), the
  ``topk`` tokens that score highest are found exactly (ties to the lower
  position: a threshold found by bisection over the scores' bit patterns,
  on the chip with the rows' scores held in VMEM,
  ``ops/pallas/index_select.py``; a prompt chunk takes the mask, a decode
  row the positions it gives by rank), and the query's 32 heads attend
  those tokens alone.  A decode row gathers
  their K and V a token at a time out of the pages (a token's four K/V
  heads lie side by side in a page, so a key is one 1 KB row) and reads no
  key it did not choose; a prompt chunk's 512 queries, each with a set of
  its own, attend their sequence's K and V read once under the selection's
  mask (``ops/pallas/indexed_attention.py``).  With ``topk`` keys or fewer
  every key is chosen and the layer is plain causal attention;

* ``sparse`` (the InfLLM-V2 line of MiniCPM4): grouped-query attention
  without rope whose query, once more than ``dense_len`` keys lie before it,
  attends only the ``topk`` blocks it scores highest.  The score comes from
  COMPRESSED keys (the mean of ``kernel`` keys every ``stride``), cached in
  pages beside K and V: a query's softmax over the compressed keys that end
  at or before it, summed over the query heads of a K/V head, a block's the
  max over the compressed keys that overlap it; the first ``init_blocks``
  and the blocks of the last ``window`` keys are chosen whatever they score.
  On the chip the heads' scores never leave VMEM
  (``ops/pallas/sparse_select.py`` writes the block scores alone) and the
  ``topk`` highest are found by the ``indexed`` mixer's bisection, no sort;
  the plain form (:func:`_select`) is the reference, and runs where the
  kernel's tiles do not fit.
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
  preempted and is prefilled again, needs no other reset;
* ``delta`` (the gated delta rule, arXiv:2412.06464): a head's cache is a
  float32 state ``[d_k, d_v]`` a slot that is CORRECTED before it is written:
  ``S' = a_t S; S_t = S' + k_t (b_t (v_t - S'^T k_t))^T; o_t = S_t^T q_t``,
  the decay ``a_t`` and the write strength ``b_t`` (up to 2: the transition's
  eigenvalue reaches -1) computed from the token, behind a causal
  convolution over time of the packed ``[q | k | v]`` whose last ``taps -
  1`` rows a slot keeps too; q and k may have fewer heads than v
  (``delta_key_heads``: a key head's serve the value heads in a row, each
  with a state of its own).  One update a decode row
  (``ops/pallas/delta_rule.py``: the state read once and written once); over
  a prompt chunk the chunked form, whose unit lower-triangular system a head
  is solved by products (:func:`delta_chunk`, :func:`unit_lower_solve`: no
  substitution longer than sixteen rows).  Both states start from zero BY
  POSITION, as the linear layers' do;
* ``mamba`` (Mamba-1's selective scan, arXiv:2312.00752, with the Jamba
  family's three inner norms): a channel's cache is ``mamba_state`` float32
  numbers a slot, a DIAGONAL recurrence ``h_t[n, d] = exp(dt_t[d] A[n, d])
  h_{t-1}[n, d] + dt_t[d] B_t[n] c_t[d]``, ``y_t[d] = sum_n C_t[n] h_t[n, d]
  + D[d] c_t[d]``: a decay of its own for every (channel, state) pair, so no
  chunked matrix form exists and a prompt chunk is scanned token by token
  with the state in registers (``ops/pallas/selective_scan.py``, which also
  says why the state lies ``[states, channels]``), behind a causal
  convolution over time with a bias whose last ``taps - 1`` rows a slot
  keeps too.  Both states start from zero BY POSITION;
* ``full``: plain softmax attention over its own token's K and V, beside a
  mixer that owns no pages: ``models/gpt.py``'s projection (with its q/k
  norm over all lanes or over a head's, rope over ``rope_dim`` lanes), the
  page group's plan and ``models/gpt.py``'s output gate (``attn_gate``),
  CALLED from the walk: the fields the periodic walk reads.

The leaves are stacked BY MIXER (``params["blocks"]["sparse"]`` ``[4, ...]``,
``["linear"]`` ``[12, ...]``: no projection is padded to another kind's
width), the step walks the stack in RUNS of one mixer (a ``lax.scan`` a run,
a layer's leaves taken from the mixer's stack by its index), and only the
mixer that caches K and V owns pages: :func:`arena_layout`.  What is not K
and V rides in ``aux``: the compressed keys' pages and the states
(:func:`init_aux`).

A step WITHOUT a prompt chunk (two of three in a long run) is the same
program taking the other side of a few ``lax.cond``: the chunk's rows carry
nothing there, so their selection and their walk are skipped and every
matrix (projections, gates, the MLP, the head) is multiplied by the decode
rows alone (:func:`_rows_that_carry`).
"""

import math
from functools import partial
from typing import Any, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from deepspeed_tpu.models import gpt
from deepspeed_tpu.models.gpt import _LayerLeaves, _rows_that_carry

Array = jax.Array
HIGHEST = jax.lax.Precision.HIGHEST


def layer_runs(cfg) -> List[Tuple[str, int, int]]:
    """The stack as runs of one mixer: (mixer, index of the run's first layer
    in its mixer's stack, layers)."""
    runs, seen = [], dict.fromkeys(MIXERS, 0)
    for m in cfg.mixers:
        if runs and runs[-1][0] == m:
            runs[-1][2] += 1
        else:
            runs.append([m, seen[m], 1])
        seen[m] += 1
    return [tuple(r) for r in runs]


def ffn_of(cfg, mixer: str) -> str:
    """The feed-forward of ``mixer``'s layers: one kind a mixer, because a
    mixer's leaves are one stack."""
    kinds = {f for m, f in zip(cfg.mixers, cfg.ffns) if m == mixer}
    assert len(kinds) == 1, (
        f"the {mixer} layers' leaves are ONE stack: one feed-forward, {kinds}")
    return kinds.pop()


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


def arena_layout(cfg) -> Tuple[int, int, Tuple[int, ...]]:
    """``GPTConfig.arena_layout`` of a hybrid stack: the sparse layers own
    the pages, a K/V head a page of its own (the selection differs by K/V
    head, so the kernel walks a list of pages a head); the cca layers own
    them, a page a block of all K/V heads like any grouped-query model's,
    and so do the indexed and the full layers (an indexed layer's index keys
    ride in ``aux`` under the same tables); the linear, the delta and the
    mamba layers own none."""
    paged = {m for m in cfg.mixers if m not in ("linear", "delta", "mamba")}
    assert len(paged) <= 1, f"one mixer's layers own the pages, not {paged}"
    for m in ("cca", "indexed", "full"):
        if m in paged:
            return cfg.mixers.count(m), 1, (cfg.kv_heads * cfg.head_dim,) * 2
    return cfg.mixers.count("sparse"), cfg.kv_heads, (cfg.head_dim,) * 2


def what_a_dense_path_lacks(cfg) -> str:
    """Why ``gpt_forward`` / ``gpt_loss`` / ``generate()`` refuse this stack,
    by the mechanisms its mixers would need there."""
    n = cfg.mixers.count
    lacks = {"linear": f"no chunked linear-attention scan (nor its backward) "
                       f"for the {n('linear')} linear layers",
             "sparse": f"no block selection for the {n('sparse')} sparse layers",
             "cca": f"no convolution over time of the packed q/k latents (nor "
                    f"its backward) and no second carry for the router's "
                    f"stream of the {n('cca')} cca layers",
             "delta": f"no chunked delta-rule scan (nor its backward) and no "
                      f"convolution over time of the packed q/k/v for the "
                      f"{n('delta')} delta layers",
             "mamba": f"no selective scan (nor its backward) and no "
                      f"convolution over time of the channels for the "
                      f"{n('mamba')} mamba layers",
             "indexed": f"no lightning indexer, no cache of index keys and no "
                        f"selection of the tokens a query attends for the "
                        f"{n('indexed')} indexed layers"}
    return " and ".join(lacks[m] for m in lacks if m in cfg.mixers)


def what_no_block_carries(cfg) -> str:
    """What this stack caches that no block of K and V holds: why the prefix
    cache and tiered KV refuse it."""
    n = cfg.mixers.count
    holds = {"linear": f"{n('linear')} linear layers hold a recurrent state a slot",
             "sparse": f"{n('sparse')} sparse layers a compressed-key cache",
             "cca": f"{n('cca')} cca layers hold a convolution state a slot "
                    f"(the last two packed latents and the next token's "
                    f"shifted value half)",
             "delta": f"{n('delta')} delta layers hold a recurrent state and "
                      f"a convolution state a slot",
             "mamba": f"{n('mamba')} mamba layers hold a recurrent state and "
                      f"a convolution state a slot",
             "indexed": f"{n('indexed')} indexed layers a cache of index keys "
                        f"the selection scores"}
    return "this model's " + " and its ".join(
        holds[m] for m in holds if m in cfg.mixers)


# --------------------------------------------------------------------------- #
# Parameters, stacked by mixer
# --------------------------------------------------------------------------- #
_GATED = lambda cfg: {"q_norm_g": (cfg.head_dim,), "k_norm_g": (cfg.head_dim,),
                      "gate_w": (cfg.n_embd, cfg.attn_dim),
                      "out_w": (cfg.attn_dim, cfg.n_embd)}


def _cca_widths(cfg) -> Tuple[int, int]:
    """(lanes of the packed latent ``u = [q | k]``, lanes of HALF the value:
    the half the current token gives, as wide as the half the one before it
    gave)."""
    assert cfg.kv_heads % 2 == 0, "half the K/V heads' values are the previous token's"
    return (cfg.n_head + cfg.kv_heads) * cfg.head_dim, cfg.kv_heads * cfg.head_dim // 2


def _delta_lanes(cfg) -> int:
    """Lanes of a delta layer's packed ``[q | k | v]``: q and k a KEY head
    (``delta_key_heads``), v a value head (``delta_heads``)."""
    return (2 * cfg.delta_key_heads * cfg.delta_key_dim
            + cfg.delta_heads * cfg.delta_value_dim)


def _mixer_shapes(cfg, mixer: str) -> Dict:
    E, D, A = cfg.n_embd, cfg.head_dim, cfg.attn_dim
    if mixer == "sparse":
        return dict(_GATED(cfg), q_w=(E, A), kv_w=(E, 2 * cfg.kv_heads * D))
    if mixer == "linear":
        return dict(_GATED(cfg), qkv_w=(E, 3 * A), onorm_g=(A,))
    if mixer == "full":
        # the q/k norm where the configuration has one, over all lanes or
        # one gain of a HEAD's lanes the heads share; the output gate's
        # projection where it has one: ``models/gpt.py``'s leaves
        norms = ({} if not cfg.qk_norm else
                 {"q_norm_g": (D,), "k_norm_g": (D,)} if cfg.qk_norm == "head" else
                 {"q_norm_g": (A,), "k_norm_g": (cfg.kv_heads * D,)})
        gate = {"gate_w": (E, A)} if cfg.attn_gate else {}
        return {"qkv_w": (E, cfg.qkv_dim), "out_w": (A, E), **norms, **gate}
    if mixer == "mamba":
        # in_w: [W_u | W_z], the channels and their gate; conv_w (tap 0 the
        # oldest token's) and conv_b the depthwise convolution; x_w: [W_r |
        # W_B | W_C], the step's ``mamba_dt_rank`` lanes and the token's
        # input and output weights, each under a norm of its own; dt_w and
        # dt_b bring the step up to a channel; scan_a_log ``[states, channels]``
        # as the state lies (ops/pallas/selective_scan.py); skip_d is ``D``
        N, S, R = cfg.mamba_inner, cfg.mamba_state, cfg.mamba_dt_rank
        return {"in_w": (E, 2 * N), "conv_w": (cfg.mamba_conv, N), "conv_b": (N,),
                "x_w": (N, R + 2 * S), "dt_norm_g": (R,), "b_norm_g": (S,),
                "c_norm_g": (S,), "dt_w": (R, N), "dt_b": (N,),
                "scan_a_log": (S, N), "skip_d": (N,), "out_w": (N, E)}
    if mixer == "indexed":
        # index_w: [W_qI (heads x lanes) | W_kI (lanes) | W_w (heads)]; the
        # index key's LayerNorm has a gain and a bias; q and k a norm a head
        ix = cfg.indexer
        return {"qkv_w": (E, cfg.qkv_dim), "out_w": (A, E),
                "q_norm_g": (D,), "k_norm_g": (D,),
                "index_w": (E, (ix.heads + 1) * ix.head_dim + ix.heads),
                "ik_norm_g": (ix.head_dim,), "ik_norm_b": (ix.head_dim,)}
    if mixer == "delta":
        # qkv_w: [W_q | W_k | W_v], the packed lanes the convolution runs
        # over (conv_w: tap 0 the oldest token's); gate_w the output gate z;
        # ba_w: [W_b | W_a], the write strength's and the decay's logit a
        # VALUE head; onorm_g ONE gain for all heads
        H, U = cfg.delta_heads, _delta_lanes(cfg)
        return {"qkv_w": (E, U), "gate_w": (E, H * cfg.delta_value_dim),
                "ba_w": (E, 2 * H), "conv_w": (cfg.delta_conv, U),
                "a_log": (H,), "dt_bias": (H,),
                "onorm_g": (cfg.delta_value_dim,),
                "out_w": (H * cfg.delta_value_dim, E)}
    U, Vh = _cca_widths(cfg)
    # qkv_w: [W_q | W_k | W_v1 (this token's value half) | W_v2 (the next
    # token's)]; conv0 depthwise over the packed latent, conv1 a map a head
    # a tap; k_scale_g the learned scale of a K/V head's normed key
    return {"qkv_w": (E, U + 2 * Vh), "out_w": (A, E),
            "conv0_w": (2, U), "conv0_b": (U,),
            "conv1_w": (2, U // D, D, D), "conv1_b": (U,),
            "k_scale_g": (cfg.kv_heads,)}


def _ffn_shapes(cfg, ffn: str) -> Dict:
    E = cfg.n_embd
    if ffn == "mlp":
        return {"fc_w": (E, 2 * cfg.ffn_dim), "proj_w": (cfg.ffn_dim, E)}
    N, R, I = cfg.moe_num_experts, cfg.moe_router_hidden, cfg.moe_expert_hidden or cfg.ffn_dim
    G = cfg.bank_experts[1]     # held by the bank; the router stays N wide
    experts = {"wi": (G, E, 2 * I), "wo": (G, I, E)}
    # the expert every row goes through, ``moe_shared_experts`` experts wide,
    # and the logit of its gate where it has one
    shared = {}
    if cfg.moe_shared_experts:
        Is = cfg.moe_shared_experts * I
        shared = {"shared_fc_w": (E, 2 * Is), "shared_proj_w": (Is, E)}
        if cfg.moe_shared_gate:
            shared["shared_gate_w"] = (E, 1)
    if ffn == "moe_softmax":
        return {"router_w": (E, N), "experts": experts, **shared}
    # the router: down to its stream's width, the stream of the layer before
    # times stream_g, a norm, three matrices; balance_bias chooses and never
    # weighs.  The bank is a group of its own, as the other MoE families' is
    return {"router_in_w": (E, R), "stream_g": (), "router_norm_g": (R,),
            "router_w1": (R, R), "router_w2": (R, R), "router_w3": (R, N),
            "balance_bias": (N,), "experts": experts, **shared}


def _leaf_shapes(cfg, mixer: str) -> Dict:
    return {"ln1_g": (cfg.n_embd,), "ln2_g": (cfg.n_embd,),
            **_mixer_shapes(cfg, mixer), **_ffn_shapes(cfg, ffn_of(cfg, mixer))}


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def init_blocks(cfg, rng: Array) -> Dict:
    """``{mixer: leaves [that mixer's layers, ...]}``: gains (``*_g``) 1, the
    balancing bias, the decay's bias and the index key's norm's 0, a delta head ``h``'s ``a_log``
    ``log(0.02 (h + 1))`` (with ``a_t`` near 0 the heads then forget over 72
    down to 2.4 tokens: none is dead, none unbounded); Mamba's own for a
    mamba layer: state ``n``'s ``scan_a_log`` ``log(n + 1)``, ``skip_d`` 1,
    ``dt_b`` the inverse softplus of a step drawn log-uniformly in [1e-3,
    1e-1] a channel (decays of 0.999 down to 0.2 a token), the convolution's
    taps and bias uniform within ``taps ** -0.5`` and ``dt_w`` within
    ``dt_rank ** -0.5`` (at 0.02 the convolved input is a fortieth of the
    convolution's input and the mixer's output a sixtieth of the MLP's: the
    logits would not depend on the layer); every other leaf normal 0.02."""
    def leaf(name, key, shape, mixer):
        if mixer == "mamba" and name in ("conv_w", "conv_b", "dt_w"):
            bound = (cfg.mamba_dt_rank if name == "dt_w" else cfg.mamba_conv) ** -0.5
            return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        if name.endswith("_g") or name == "skip_d":
            return jnp.ones(shape, jnp.float32)
        if name in ("balance_bias", "dt_bias", "ik_norm_b"):
            return jnp.zeros(shape, jnp.float32)
        if name == "dt_b":
            step = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            return step + jnp.log(-jnp.expm1(-step))
        if name == "scan_a_log":
            return jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[0] + 1, dtype=jnp.float32))[:, None], shape)
        if name == "a_log":
            return jnp.log(0.02 * (jnp.arange(shape[0], dtype=jnp.float32) + 1.0))
        return gpt._dense_init(key, shape[0], shape)

    def one(shapes, key, mixer):
        keys = jax.random.split(key, len(shapes))
        return {name: (leaf(name, k, shape, mixer) if _is_shape(shape)
                       else one(shape, k, mixer))
                for k, (name, shape) in zip(keys, sorted(shapes.items()))}

    out = {}
    for n, mixer in enumerate(MIXERS):
        count = cfg.mixers.count(mixer)
        if count:
            # a layer at a time: one layer's random bits are a gigabyte
            out[mixer] = jax.lax.map(
                lambda k, mixer=mixer: one(_leaf_shapes(cfg, mixer), k, mixer),
                jax.random.split(jax.random.fold_in(rng, n), count))
    return out


def block_partition_specs(cfg) -> Dict:
    column = {"q_w", "kv_w", "qkv_w", "gate_w", "fc_w", "in_w", "shared_fc_w"}
    row = {"out_w", "proj_w", "shared_proj_w"}
    bank = {"wi": ("expert", None, "tensor"), "wo": ("expert", "tensor", None)}

    def spec(path, _):
        name = path[-1].key
        return PartitionSpec(None, *(bank[name] if len(path) > 1 else
                                     (None, "tensor") if name in column else
                                     ("tensor", None) if name in row else ()))
    return {m: jax.tree_util.tree_map_with_path(spec, _leaf_shapes(cfg, m),
                                                is_leaf=_is_shape)
            for m in MIXERS if m in cfg.mixers}


def num_params(cfg) -> int:
    """Parameters the tree holds (its one zero leaf the sources lack,
    ``lnf_b``, apart): each layer's leaves, the final norm, the embedding
    and, where it is untied, the head."""
    per = {m: sum(math.prod(s) for s in jax.tree.leaves(
        _leaf_shapes(cfg, m), is_leaf=_is_shape)) for m in set(cfg.mixers)}
    return (sum(per[m] for m in cfg.mixers) + cfg.n_embd
            + (1 + cfg.untied_head) * cfg.padded_vocab * cfg.n_embd)


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
    """What the stack's mixers cache beside K and V.  ``kc [sparse layers,
    num_blocks, keys_a_page * Hkv * D]``: the compressed keys of a page,
    reached through the sparse layers' block table like K and V; ``state
    [linear layers, slots, H, D, D]`` float32: a linear layer's cache, a
    slot's whatever its length; ``cca_state [cca layers, slots, 2 U + Vh]``
    (a stack with cca layers): a slot's ``[u_{t-1} | u_{t-2} | W_v2 h_{t-1}]``
    in the type they were computed in; ``delta_state [delta layers, slots,
    d_k, H * d_v]`` float32 (a head's ``[d_k, d_v]`` beside the other heads'
    on the lanes: ``ops/pallas/delta_rule.py`` says why) and ``delta_conv
    [delta layers, slots, taps - 1, U]``: a slot's last packed ``[q | k |
    v]`` rows, the oldest first; ``mamba_state [mamba layers, slots, states,
    channels]`` float32 (the CHANNELS on the lanes:
    ``ops/pallas/selective_scan.py`` says why) and ``mamba_conv [mamba layers,
    slots, taps - 1, channels]``: a slot's last input rows, the oldest first;
    ``ki [indexed layers, num_blocks, block_size,
    index lanes]``: the index keys of a page, reached through the block
    tables like K and V (allocated, freed and re-bound with them: a slot
    bound to a new sequence scores no former tenant's, BY POSITION); the one
    entry a periodic stack with an indexer over its latent cache has
    (``GPTConfig.indexed_layers``)."""
    D, out = cfg.head_dim, {}
    if cfg.indexed_layers:
        out["ki"] = jnp.zeros((cfg.indexed_layers, num_blocks,
                               block_size, cfg.indexer.head_dim), dtype)
    if "delta" in cfg.mixers:
        L, H = cfg.mixers.count("delta"), cfg.delta_heads
        out.update(
            delta_state=jnp.zeros((L, slots, cfg.delta_key_dim,
                                   H * cfg.delta_value_dim), jnp.float32),
            delta_conv=jnp.zeros((L, slots, cfg.delta_conv - 1,
                                  _delta_lanes(cfg)), dtype))
    if "mamba" in cfg.mixers:
        L, N = cfg.mixers.count("mamba"), cfg.mamba_inner
        out.update(
            mamba_state=jnp.zeros((L, slots, cfg.mamba_state, N), jnp.float32),
            mamba_conv=jnp.zeros((L, slots, cfg.mamba_conv - 1, N), dtype))
    if "cca" in cfg.mixers:
        U, Vh = _cca_widths(cfg)
        out["cca_state"] = jnp.zeros((cfg.mixers.count("cca"), slots,
                                      2 * U + Vh), dtype)
    if "sparse" in cfg.mixers or "linear" in cfg.mixers:
        assert "sparse" not in cfg.mixers or block_size == cfg.sparse.block, (
            f"a sparse layer selects blocks of {cfg.sparse.block} keys and a "
            f"selected block is a page: block_size {block_size}")
        out.update(
            kc=jnp.zeros((cfg.mixers.count("sparse"), num_blocks,
                          keys_a_page(cfg) * cfg.kv_heads * D), dtype),
            state=jnp.zeros((cfg.mixers.count("linear"), slots,
                             cfg.n_head, D, D), jnp.float32))
    return out


def aux_bytes(cfg, num_blocks: int, slots: int, dtype_bytes: int = 2):
    """(bytes of compressed keys, bytes of state) :func:`init_aux` holds for
    a stack of sparse and linear layers."""
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


class _Step(NamedTuple):
    """What every layer of a step reads of its rows: ``positions``, ``live``
    and ``slots`` ``[B]``, the one page group's ``tables [B, MB]`` and where
    each row's token is written (``write_blocks``, ``write_offsets`` ``[B,
    1]``), the rows of the prompt chunk (static) and the activations' type;
    ``plan``: the paged attention of the page group (``GPTConfig.
    paged_plans``), and ``tile_runs`` which tiles of ``tables`` its kernel
    fetches with one copy (``plan.tile_runs``; None: none)."""
    positions: Array
    live: Array
    slots: Array
    tables: Array
    write_blocks: Array
    write_offsets: Array
    chunk: int
    dt: Any
    plan: Any
    tile_runs: Any = None


def _gated_out(p, o, h, dt, chunk: int, live):
    """``W_o(o * sigmoid(W_g h))``: both kinds' output gate and projection."""
    return _rows_that_carry(
        lambda o, h: gpt.out_gate(p, o, h, dt) @ gpt._wget(p, "out_w", dt),
        (o, h), chunk, live)


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


def _block_scores(cfg, q, kc_rows, positions, BS: int):
    """Each query's score of each block of its K/V head.  ``q [n, Hkv, g,
    D]``; ``kc_rows [n | 1, MB * r, Hkv, D]``: the compressed keys under the
    queries' tables in logical order (entry ``f`` is key ``f - 1``; one row
    where all queries share a table); ``positions [n]``.  -> ``[n, Hkv, MB]``
    float32, ``+inf`` the blocks a query is made to attend, ``-inf`` those
    past it."""
    sp = cfg.sparse
    n, Hkv, g, D = q.shape
    F = kc_rows.shape[1]
    r, MB = keys_a_page(cfg), F // keys_a_page(cfg)
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
    return jnp.where(b <= tq // BS, score, -jnp.inf)


def _select(cfg, q, kc_rows, positions, BS: int):
    """The blocks each query attends, as a table's columns (arguments as
    :func:`_block_scores`'): -> (logical blocks ``[n, Hkv, W]`` in rising
    order, the position of the query among the keys of those blocks
    ``[n]``).  The selection in plain ``jax.numpy``: what runs where the
    kernel's tiles do not fit the shapes, and the reference
    :func:`_select_on_chip` is held to."""
    sp, MB = cfg.sparse, kc_rows.shape[1] // keys_a_page(cfg)
    score = _block_scores(cfg, q, kc_rows, positions, BS)
    # the chosen blocks in rising order, those of no key (a query with fewer
    # than topk blocks behind it) last: the query's own block ends the list
    top, chosen = jax.lax.top_k(score, min(sp.topk, MB))
    chosen = jnp.sort(jnp.where(top > -jnp.inf, chosen, MB), axis=-1)
    return _as_columns(cfg, jnp.minimum(chosen, MB - 1), positions, BS)


def _as_columns(cfg, chosen, t, BS: int):
    """The blocks the queries at ``t [n]`` chose (``chosen [n, Hkv, <=
    topk]``, rising, the blocks of no key last) as a table's ``W`` columns:
    every block in order for a query at or under ``dense_len``.  -> (blocks
    ``[n, Hkv, W]``, the position of the query among their keys ``[n]``)."""
    sp, W = cfg.sparse, table_columns(cfg, BS)
    chosen = jnp.pad(chosen, ((0, 0), (0, 0), (0, W - chosen.shape[-1])))
    dense = (t + 1 <= sp.dense_len)
    blocks = jnp.where(dense[:, None, None], jnp.arange(W)[None, None], chosen)
    held = jnp.minimum(sp.topk, t // BS + 1)
    return blocks, jnp.where(dense, t, (held - 1) * BS + t % BS)


# blocks whose running count is one triangular product: a lane tile (the
# kernel's gate admits whole lane tiles of blocks)
_RANK_GROUP = 128


def _select_on_chip(cfg, q, kc_pages, positions, BS: int):
    """:func:`_select` without the heads' scores in HBM and without a sort:
    the block scores from ``ops/pallas/sparse_select.py`` (``kc_pages [n | 1,
    MB, r * Hkv * D]``: the compressed keys as the pages hold them), the
    ``topk`` highest of a row by :func:`chosen_tokens`' bisection (ties to
    the lower block, as ``top_k``'s), and the mask as a list by rank: column
    ``c`` is the block with ``c`` chosen blocks before it, which is how many
    blocks' running count stays at or under ``c`` (``MB`` of a column past
    the row's last: the block of no key)."""
    from deepspeed_tpu.ops.pallas.sparse_select import sparse_block_scores
    sp = cfg.sparse
    n, Hkv, MB = q.shape[0], q.shape[1], kc_pages.shape[1]
    score = sparse_block_scores(
        q, kc_pages, positions, stride=sp.stride, block=BS,
        init_blocks=sp.init_blocks, window=sp.window).reshape(n * Hkv, MB)
    K = min(sp.topk, MB)
    before = _running_count(chosen_tokens(score, K, _RANK_GROUP), _RANK_GROUP)
    chosen = jnp.sum(before[:, None, :] <= jnp.arange(K, dtype=jnp.float32)[None, :, None],
                     axis=-1, dtype=jnp.int32)
    return _as_columns(cfg, jnp.minimum(chosen, MB - 1).reshape(n, Hkv, K), positions, BS)


def _entries_at(tables, blocks):
    """``tables [n | 1, MB]`` at the columns ``blocks [n, Hkv, W]``, as a
    compare and a sum over the table's columns: the chip gathers 131,072
    entries a layer one by one (1.35 ms; PERF.md section 6, PR 60)."""
    b = jnp.arange(tables.shape[1])
    return jnp.sum(jnp.where(blocks[..., None] == b, tables[:, None, None, :], 0), axis=-1)


def selects_on_chip(cfg, n: int, blocks: int, shared: bool) -> bool:
    """Whether ``n`` queries over tables of ``blocks`` (one table where
    ``shared``) select through the kernel: on a TPU, at shapes its tiles
    take; :func:`_select` everywhere else."""
    from deepspeed_tpu.ops import pallas
    from deepspeed_tpu.ops.pallas import sparse_select
    return (pallas.use_kernel(sparse_select.KERNEL) and pallas.single_device()
            and sparse_select.kernel_shape_ok(
                n, cfg.n_head // cfg.kv_heads, cfg.head_dim, blocks, shared))


def sparse_mixer(cfg, p, h, kp, vp, held, li, step: _Step):
    """One sparse layer's attention over the rows ``h [B, E]`` (a token a
    row; the last ``chunk`` consecutive tokens of one sequence): -> (the
    mixer's output ``[B, E]`` before the residual, kp, vp, held with its
    compressed keys ``kc`` written)."""
    positions, live, _, tables, write_blocks, write_offsets, chunk, dt, plan, _ = step
    kc = held["kc"]
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
            of = tb[:1] if shared else tb
            under = kc[li, of]                                    # [n | 1, MB, r Hkv D]
            # logical -> physical through the row's table, a row a (token, head)
            if selects_on_chip(cfg, n, tb.shape[1], shared):
                blocks, at = _select_on_chip(cfg, q[rows], under, positions[rows], BS)
                chosen = _entries_at(of, blocks)
            else:
                blocks, at = _select(cfg, q[rows], under.reshape(
                    under.shape[0], -1, Hkv, D), positions[rows], BS)
                chosen = jnp.take_along_axis(tb[:, None], blocks, axis=2)
            chosen = (chosen * Hkv + jnp.arange(Hkv)[None, :, None]).reshape(n * Hkv, -1)
        with jax.named_scope("sparse_attend"):
            return plan.attend(
                q[rows].reshape(n * Hkv, 1, g, D), (kp, vp), li, chosen,
                jnp.repeat(at, Hkv)).reshape(n, H * D)

    o = attend(slice(0, n_dec), False)
    if chunk:
        # a step without a prompt chunk (two of three, in a long run) skips
        # the chunk rows' selection and their walk: nobody reads them
        o = jnp.concatenate([o, jax.lax.cond(
            live[n_dec], lambda: attend(slice(n_dec, B), True),
            lambda: jnp.zeros((chunk, H * D), o.dtype))])
    return _gated_out(p, o, h, dt, chunk, live), kp, vp, dict(held, kc=kc)


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


def linear_mixer(cfg, p, h, kp, vp, held, li, step: _Step):
    """One linear layer over the rows ``h [B, E]``: a decode row updates the
    state of its slot (row ``s`` is slot ``s``) and reads it; the prompt
    chunk runs the chunked form from the state of ITS slot, from zero where
    it starts at position 0.  -> (output ``[B, E]``, the pages as they came,
    held with its ``state`` moved on)."""
    positions, live, slots, _, _, _, chunk, dt, _, _ = step
    state, decay = held["state"], jnp.asarray(linear_decay(cfg))[li]
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
    return _gated_out(p, o, h, dt, chunk, live), kp, vp, dict(held, state=state)


# --------------------------------------------------------------------------- #
# The cca mixer
# --------------------------------------------------------------------------- #
def _cca_neighbours(u, v_next, s_all, slots, positions, live, chunk: int):
    """What each row's two tokens before it gave, and the state after the
    step.  ``u [B, U]`` the rows' packed latents, ``v_next [B, Vh]`` the value
    half each gives the token after it, ``s_all [slots, 2 U + Vh]`` the
    layer's states ``[u_{t-1} | u_{t-2} | value half for t]``.  A decode row
    (row ``s`` is slot ``s``) reads its slot's state and shifts its own
    latent in; the prompt chunk's rows read the rows before them, its first
    two the state of ITS slot, and leave the last live tokens' there.  ->
    (``u_{t-1}``, ``u_{t-2}`` ``[B, U]``, the previous token's value half
    ``[B, Vh]``, s_all), all zero where the position lies before 0."""
    U, n_dec = u.shape[1], u.shape[0] - chunk
    assert s_all.shape[0] == n_dec, "a decode row a slot"
    prev1, prev2, v_prev = s_all[:, :U], s_all[:, U:2 * U], s_all[:, 2 * U:]
    grown = jnp.concatenate([u[:n_dec], prev1, v_next[:n_dec]], axis=1)
    s_all = jnp.where(live[:n_dec, None], grown.astype(s_all.dtype), s_all)
    if chunk:
        slot = slots[n_dec]
        kept = jax.lax.dynamic_index_in_dim(s_all, slot, 0, keepdims=False)
        # the slot's sequence from two tokens before the chunk on
        seq_u = jnp.concatenate([kept[None, U:2 * U], kept[None, :U],
                                 u[n_dec:].astype(kept.dtype)])
        seq_v = jnp.concatenate([kept[None, 2 * U:],
                                 v_next[n_dec:].astype(kept.dtype)])
        prev1 = jnp.concatenate([prev1, seq_u[1:chunk + 1]])
        prev2 = jnp.concatenate([prev2, seq_u[:chunk]])
        v_prev = jnp.concatenate([v_prev, seq_v[:chunk]])
        # behind the chunk's ``n`` live tokens lie the rows n, n + 1 of seq_u
        # (n = 0, a step without a chunk: the state as it was)
        n = jnp.sum(live[n_dec:])
        last = jax.lax.dynamic_slice_in_dim(seq_u, n, 2)
        s_all = jax.lax.dynamic_update_index_in_dim(s_all, jnp.concatenate([
            last[1], last[0],
            jax.lax.dynamic_index_in_dim(seq_v, n, 0, keepdims=False)]), slot, 0)
    behind = lambda a, k: jnp.where((positions >= k)[:, None], a, 0)
    return behind(prev1, 1), behind(prev2, 2), behind(v_prev, 1), s_all


def cca_mixer(cfg, p, h, kp, vp, held, li, step: _Step):
    """One cca layer's attention over the rows ``h [B, E]``: -> (the mixer's
    output ``[B, E]`` before the residual, kp, vp, held with its
    ``cca_state`` moved on).  The arithmetic between the projections and the
    cached K and V (the convolutions, the q-k mean, the norms) is float32;
    the pages and the state keep ``dt``."""
    (positions, live, slots, tables, write_blocks, write_offsets, chunk, dt,
     plan, tile_runs) = step
    state = held["cca_state"]
    B = h.shape[0]
    H, Hkv, D = cfg.n_head, cfg.kv_heads, cfg.head_dim
    g, (U, Vh) = H // Hkv, _cca_widths(cfg)
    f32 = lambda name: p[name].astype(jnp.float32)
    qkv = _rows_that_carry(lambda r: r @ gpt._wget(p, "qkv_w", dt), (h,), chunk, live)
    with jax.named_scope("cca_mix"):
        u, v_now, v_next = qkv[:, :U], qkv[:, U:U + Vh], qkv[:, U + Vh:]
        s_all = jax.lax.dynamic_index_in_dim(state, li, 0, keepdims=False)
        prev1, prev2, v_prev, s_all = _cca_neighbours(
            u, v_next, s_all, slots, positions, live, chunk)
        state = jax.lax.dynamic_update_index_in_dim(state, s_all, li, 0)
        u, prev1, prev2 = (a.astype(jnp.float32) for a in (u, prev1, prev2))
        # two taps over time a channel, then two a head: a_t and a_{t-1}
        w0, b0 = f32("conv0_w"), f32("conv0_b")
        a = jnp.stack([w0[0] * prev2 + w0[1] * prev1 + b0,
                       w0[0] * prev1 + w0[1] * u + b0], axis=1)
        c = jnp.einsum("btgi,tgio->bgo", a.reshape(B, 2, H + Hkv, D),
                       f32("conv1_w"), precision=HIGHEST
                       ) + f32("conv1_b").reshape(H + Hkv, D)
        # the q-k mean of the latents BEFORE the convolutions
        mean_q = 0.5 * (u[:, :H * D].reshape(B, Hkv, g, D)
                        + u[:, H * D:].reshape(B, Hkv, 1, D))
        q = c[:, :H].reshape(B, Hkv, g, D) + mean_q
        k = c[:, H:] + mean_q.mean(axis=2)
        unit = lambda t: t * (math.sqrt(D) * jax.lax.rsqrt(jnp.maximum(
            jnp.sum(jnp.square(t), axis=-1, keepdims=True), 1e-30)))
        rope = lambda t: gpt.apply_rope(t, positions[:, None], cfg.rope_theta,
                                        cfg.rope_dim)
        q = rope(unit(q).reshape(B, 1, H, D)).astype(dt)
        k = rope((unit(k) * f32("k_scale_g")[:, None]).reshape(B, 1, Hkv, D))
        # the first half of the K/V heads' values is this token's, the
        # second the one before it's
        v = jnp.concatenate([v_now, v_prev.astype(v_now.dtype)], axis=1)
        kp = kp.at[li, write_blocks, write_offsets].set(
            k.astype(kp.dtype).reshape(B, 1, Hkv * D))
        vp = vp.at[li, write_blocks, write_offsets].set(
            v.astype(vp.dtype).reshape(B, 1, Hkv * D))
    with jax.named_scope("cca_attend"):
        o = plan.attend(q, (kp, vp), li, tables, positions, chunk=chunk,
                        tile_runs=tile_runs).reshape(B, H * D)
    o = _rows_that_carry(lambda r: r @ gpt._wget(p, "out_w", dt), (o,), chunk, live)
    return o, kp, vp, dict(held, cca_state=state)


# --------------------------------------------------------------------------- #
# The delta mixer
# --------------------------------------------------------------------------- #
# the solve's diagonal blocks: substitution rows up to the first width, two
# blocks made one from there to the second, block rows above (PERF.md § 6,
# PR 65: of the widths the chip was timed at, 16 and 64 at both cells' chunks)
SOLVE_ROWS, SOLVE_BLOCK = 16, 64


def unit_lower_solve(L, rhs):
    """``(I + L)^-1 rhs`` for ``L [H, C, C]`` strictly lower triangular and
    ``rhs [H, C, dv]`` in float32, by products and no substitution longer
    than ``SOLVE_ROWS``: exact in exact arithmetic for every ``C``.  The
    diagonal blocks of ``SOLVE_ROWS`` are inverted by substitution, a row a
    step, every head's every block at once (``T_i = e_i - sum_{j < i} L_ij
    T_j``: what a solve does, so what it keeps of the digits; the finite
    product ``(I - L)(I + L^2)(I + L^4)...`` is as exact and loses them all
    where keys repeat, since the powers grow before they cancel); two
    inverted blocks and the block below the first make one of twice the
    width (``[[A, 0], [X, B]]^-1 = [[A^-1, 0], [-B^-1 X A^-1, B^-1]]``) up to
    ``SOLVE_BLOCK`` or the chunk; then a block row of the unknowns a step,
    ``D_r = T_r (rhs_r - L_r,<r D_<r)``.  ``C`` is padded to whole blocks
    with rows of the identity."""
    H, C, _ = L.shape
    m = SOLVE_ROWS
    while m < min(C, SOLVE_BLOCK):
        m *= 2
    pad = -C % m
    L = jnp.pad(L, ((0, 0), (0, pad), (0, pad)))
    rhs = jnp.pad(rhs, ((0, 0), (0, pad), (0, 0)))
    mm = partial(jnp.matmul, precision=HIGHEST)

    def blocks(w, of, down):
        """Every ``of``-th block of width ``w`` on the diagonal of ``L``,
        or the one ``down`` blocks under it: ``[H, n, w, w]``."""
        return jnp.stack([L[:, (p + down) * w:(p + down + 1) * w, p * w:(p + 1) * w]
                          for p in range(0, (C + pad) // w, of)], axis=1)

    w = SOLVE_ROWS
    N, eye = -blocks(w, 1, 0), jnp.eye(w, dtype=L.dtype)
    T = jnp.broadcast_to(eye[:1], N.shape[:2] + (1, w))
    for i in range(1, w):
        T = jnp.concatenate([T, eye[i] + jnp.sum(
            jnp.swapaxes(N[:, :, i:i + 1, :i], 2, 3) * T, axis=2, keepdims=True)], 2)
    while w < m:
        pairs = T.reshape(H, -1, 2, w, w)
        A, B = pairs[:, :, 0], pairs[:, :, 1]
        X = -mm(mm(B, blocks(w, 2, 1)), A)
        T = jnp.concatenate([jnp.concatenate([A, jnp.zeros_like(A)], -1),
                             jnp.concatenate([X, B], -1)], -2)
        w *= 2
    D = mm(T[:, 0], rhs[:, :m])
    for r in range(m, C + pad, m):
        D = jnp.concatenate([D, mm(T[:, r // m], rhs[:, r:r + m] - mm(L[:, r:r + m, :r], D))], 1)
    return D[:, :C]


def delta_chunk(q, k, v, g, beta, s_in, live):
    """The gated delta rule over ``C`` consecutive tokens of one sequence
    that enters with the state ``s_in [H, dk, dv]``, in its chunked form.
    ``q`` (scaled), ``k`` ``[C, H, dk]``, ``v [C, H, dv]``, the log decay ``g
    <= 0`` and the write strength ``beta`` ``[C, H]``, all float32; ``live
    [C]``: the first ``n`` tokens carry the sequence (the others write
    nothing and decay nothing).  -> (o ``[C, H, dv]``, the state after those
    ``n``).  With ``B_i = exp(sum_{j <= i} g_j)`` and ``R_ij = B_i / B_j``:
    the tokens' writes ``D`` solve the unit lower-triangular ``(I + L) D =
    diag(beta) (V - diag(B) K S_in)``, ``L_ij = beta_i R_ij (k_i . k_j)`` for
    ``j < i`` (token ``i``'s correction reads what the tokens before it
    wrote; :func:`unit_lower_solve` gets them by products); then ``O =
    diag(B) Q S_in + tril(R . Q K^T) D`` and ``S_out = B_C S_in + sum_j R_Cj
    k_j d_j^T``.  Every decay formed is the exponential of a number ``<= 0``:
    the mask goes on the exponent."""
    C = q.shape[0]
    g, beta = jnp.where(live[:, None], g, 0.0), jnp.where(live[:, None], beta, 0.0)
    G = jnp.cumsum(g, axis=0).T                                    # [H, C]
    i = jnp.arange(C)
    after = i[:, None] >= i[None, :]                               # j <= i
    R = jnp.exp(jnp.where(after, G[:, :, None] - G[:, None, :], -jnp.inf))
    B = jnp.exp(G).T[:, :, None]                                   # [C, H, 1]
    kk = jnp.einsum("ihd,jhd->hij", k, k, precision=HIGHEST)
    L = jnp.where(i[:, None] > i[None, :], beta.T[:, :, None] * R * kk, 0.0)
    rhs = beta[:, :, None] * (v - B * jnp.einsum(
        "ihd,hde->ihe", k, s_in, precision=HIGHEST))
    D = unit_lower_solve(L, rhs.transpose(1, 0, 2))                # [H, C, dv]
    A = R * jnp.einsum("ihd,jhd->hij", q, k, precision=HIGHEST)
    o = (jnp.einsum("hij,hje->ihe", A, D, precision=HIGHEST)
         + B * jnp.einsum("ihd,hde->ihe", q, s_in, precision=HIGHEST))
    left = jnp.exp(G[:, -1:] - G)                                  # R_Cj  [H, C]
    s_out = (jnp.exp(G[:, -1])[:, None, None] * s_in
             + jnp.einsum("jhd,hje->hde", k * left.T[:, :, None], D,
                          precision=HIGHEST))
    return o, s_out


def _conv_windows(seq, chunk: int):
    """``seq [T + chunk, U]`` (a sequence from ``T`` rows before a chunk on)
    -> each of the chunk's rows' ``T`` rows before it ``[chunk, T, U]``."""
    T = seq.shape[0] - chunk
    return jnp.stack([seq[j:j + chunk] for j in range(T)], axis=1)


def _conv_neighbours(u, c_all, slots, live, first, chunk: int):
    """What a causal convolution over time reads beside each row's own, and
    its state after the step: the delta layers' over the packed ``[q | k |
    v]``, the mamba layers' over the channels.  ``u [B, U]``; ``c_all [slots,
    taps - 1, U]`` the layer's states, the oldest row first.  A decode row
    (row ``s`` is slot ``s``) reads its slot's state and shifts its own row
    in; the prompt chunk's rows read the rows before them, its first ones the
    state of ITS slot (zero where the chunk starts at position ``first ==
    0``), and leave the last live tokens' there.  -> (the decode rows' rows
    before ``[slots, taps - 1, U]``, the chunk's sequence from ``taps - 1``
    rows before it on ``[taps - 1 + chunk, U]``, of which
    :func:`_conv_windows` gives its rows' rows before (None without a chunk),
    c_all)."""
    n_dec, T = u.shape[0] - chunk, c_all.shape[1]
    assert c_all.shape[0] == n_dec, "a decode row a slot"
    before, seq = c_all, None
    grown = jnp.concatenate([c_all[:, 1:], u[:n_dec, None].astype(c_all.dtype)], 1)
    c_all = jnp.where(live[:n_dec, None, None], grown, c_all)
    if chunk:
        slot = slots[n_dec]
        kept = jax.lax.dynamic_index_in_dim(c_all, slot, 0, keepdims=False)
        seq = jnp.concatenate([jnp.where(first == 0, 0, kept),
                               u[n_dec:].astype(kept.dtype)])          # [T + C, U]
        # behind the chunk's ``n`` live tokens lie the rows n .. n + T - 1
        # (a step without a chunk leaves the slot its rows name as it is)
        n = jnp.sum(live[n_dec:])
        c_all = jax.lax.dynamic_update_index_in_dim(c_all, jnp.where(
            live[n_dec], jax.lax.dynamic_slice_in_dim(seq, n, T), kept), slot, 0)
    return before, seq, c_all


def delta_mixer(cfg, p, h, kp, vp, held, li, step: _Step):
    """One delta layer over the rows ``h [B, E]``: a decode row corrects the
    state of its slot (row ``s`` is slot ``s``), writes it and reads it; the
    prompt chunk runs :func:`delta_chunk` from the state of ITS slot, from
    zero where it starts at position 0.  -> (output ``[B, E]``, the pages as
    they came, held with ``delta_state`` and ``delta_conv`` moved on).
    Between the projections and the output gate everything is float32."""
    from deepspeed_tpu.ops.pallas.delta_rule import delta_state_update
    positions, live, slots, _, _, _, chunk, dt, _, _ = step
    B = h.shape[0]
    H, dk, dv = cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim
    Hk = cfg.delta_key_heads
    n_dec = B - chunk
    first = positions[n_dec] if chunk else None
    f32 = lambda name: p[name].astype(jnp.float32)
    u, z, ba = _rows_that_carry(
        lambda r: tuple(r @ gpt._wget(p, name, dt) for name in ("qkv_w", "gate_w", "ba_w")),
        (h,), chunk, live)
    with jax.named_scope("delta_conv"):
        c_all = jax.lax.dynamic_index_in_dim(held["delta_conv"], li, 0, keepdims=False)
        before, seq, c_all = _conv_neighbours(u, c_all, slots, live, first, chunk)
        if chunk:
            before = jnp.concatenate([before, _conv_windows(seq, chunk)])
        conv = jax.lax.dynamic_update_index_in_dim(held["delta_conv"], c_all, li, 0)
        w = f32("conv_w")
        c = jax.nn.silu(jnp.einsum("btu,tu->bu", before.astype(jnp.float32), w[:-1])
                        + w[-1] * u.astype(jnp.float32))
        unit = lambda t: t * jax.lax.rsqrt(
            jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
        q = unit(c[:, :Hk * dk].reshape(B, Hk, dk)) / math.sqrt(dk)
        k = unit(c[:, Hk * dk:2 * Hk * dk].reshape(B, Hk, dk))
        if Hk != H:
            # a key head's q and k serve H / Hk value heads in a row
            q, k = jnp.repeat(q, H // Hk, axis=1), jnp.repeat(k, H // Hk, axis=1)
        v = c[:, 2 * Hk * dk:].reshape(B, H, dv)
        ba = ba.astype(jnp.float32)
        beta = (2.0 if cfg.delta_neg_eigval else 1.0) * jax.nn.sigmoid(ba[:, :H])
        g = -jnp.exp(f32("a_log")) * jax.nn.softplus(ba[:, H:] + f32("dt_bias"))
    with jax.named_scope("delta_update"):
        state, o = delta_state_update(
            held["delta_state"], li, q[:n_dec], k[:n_dec], v[:n_dec],
            jnp.exp(g[:n_dec]), beta[:n_dec], live[:n_dec])
        if chunk:
            slot = slots[n_dec]
            at = (li, slot, 0, 0)
            kept = jax.lax.dynamic_slice(state, at, (1, 1) + state.shape[2:])

            def over_the_chunk():
                s_in = kept.reshape(dk, H, dv).transpose(1, 0, 2)
                oc, s_out = delta_chunk(q[n_dec:], k[n_dec:], v[n_dec:], g[n_dec:],
                                        beta[n_dec:], jnp.where(first == 0, 0.0, s_in),
                                        live[n_dec:])
                return oc, s_out.transpose(1, 0, 2).reshape(kept.shape)

            # a step without a chunk (three of four here) skips the form,
            # and leaves the slot its rows name (slot 0) as it is
            oc, s_out = jax.lax.cond(
                live[n_dec], over_the_chunk,
                lambda: (jnp.zeros((chunk, H, dv), jnp.float32), kept))
            state = jax.lax.dynamic_update_slice(state, s_out, at)
            o = jnp.concatenate([o, oc])
    # the norm a head (ONE gain for all heads), then the gate
    y = gpt.rms_norm(o, f32("onorm_g"), eps=cfg.ln_eps).reshape(B, H * dv)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(dt)
    out = _rows_that_carry(lambda r: r @ gpt._wget(p, "out_w", dt), (y,), chunk, live)
    return out, kp, vp, dict(held, delta_state=state, delta_conv=conv)


# --------------------------------------------------------------------------- #
# The mamba mixer
# --------------------------------------------------------------------------- #
def mamba_mixer(cfg, p, h, kp, vp, held, li, step: _Step):
    """One mamba layer over the rows ``h [B, E]``: a decode row moves the
    state of its slot on by its token (row ``s`` is slot ``s``) and reads it;
    the prompt chunk is scanned token by token from the state of ITS slot,
    from zero where it starts at position 0.  -> (output ``[B, E]``, the
    pages as they came, held with ``mamba_state`` and ``mamba_conv`` moved
    on).  Between the projections everything is float32: the convolution's
    sum, the three inner norms, the step, the recurrence and the gate."""
    from deepspeed_tpu.ops.pallas.selective_scan import (
        mamba_chunk_scan, mamba_state_update)
    positions, live, slots, _, _, _, chunk, dt, _, _ = step
    N, S, R = cfg.mamba_inner, cfg.mamba_state, cfg.mamba_dt_rank
    n_dec = h.shape[0] - chunk
    first = positions[n_dec] if chunk else None
    f32 = lambda name: p[name].astype(jnp.float32)
    uz = _rows_that_carry(lambda r: r @ gpt._wget(p, "in_w", dt), (h,), chunk, live)
    u, z = uz[:, :N], uz[:, N:]
    with jax.named_scope("mamba_conv"):
        c_all = jax.lax.dynamic_index_in_dim(held["mamba_conv"], li, 0, keepdims=False)
        before, seq, c_all = _conv_neighbours(u, c_all, slots, live, first, chunk)
        conv = jax.lax.dynamic_update_index_in_dim(held["mamba_conv"], c_all, li, 0)

    def of_the_rows(u, before):
        """What the recurrence reads of rows ``u [n, N]`` whose ``taps - 1``
        rows before them are ``before [n, taps - 1, N]``: the convolved input
        ``c`` and the step ``[n, N]``, ``B`` and ``C`` ``[n, S]``."""
        with jax.named_scope("mamba_conv"):
            w, taps = f32("conv_w"), before.shape[1]
            c = jax.nn.silu(sum(before[:, j].astype(jnp.float32) * w[j] for j in range(taps))
                            + u.astype(jnp.float32) * w[-1] + f32("conv_b"))
        with jax.named_scope("mamba_params"):
            x = (c.astype(dt) @ gpt._wget(p, "x_w", dt)).astype(jnp.float32)
            norm = lambda t, g: gpt.rms_norm(t, f32(g), eps=cfg.ln_eps)
            r = norm(x[:, :R], "dt_norm_g")
            moves = jax.nn.softplus(
                (r.astype(dt) @ gpt._wget(p, "dt_w", dt)).astype(jnp.float32)
                + f32("dt_b"))
            return (c, moves, norm(x[:, R:R + S], "b_norm_g"),
                    norm(x[:, R + S:], "c_norm_g"))

    A, D = -jnp.exp(f32("scan_a_log")), f32("skip_d")
    # the decode rows in every step; the chunk's rows, their windows and
    # their scan under the branch a step without a chunk (two of three
    # here) takes the other side of, which leaves the slot its rows name
    # (slot 0) as it is
    rows = of_the_rows(u[:n_dec], before)
    with jax.named_scope("mamba_scan"):
        state, y = mamba_state_update(held["mamba_state"], li, *rows, A, D, live[:n_dec])
    if chunk:
        at = (li, slots[n_dec], 0, 0)
        kept = jax.lax.dynamic_slice(state, at, (1, 1, S, N))

        def over_the_chunk():
            rows = of_the_rows(u[n_dec:], _conv_windows(seq, chunk))
            with jax.named_scope("mamba_scan"):
                s_out, yc = mamba_chunk_scan(jnp.where(first == 0, 0, kept[0, 0]),
                                             *rows, A, D, live[n_dec:])
            return yc, s_out[None, None]

        yc, s_out = jax.lax.cond(
            live[n_dec], over_the_chunk,
            lambda: (jnp.zeros((chunk, N), jnp.float32), kept))
        state = jax.lax.dynamic_update_slice(state, s_out, at)
        y = jnp.concatenate([y, yc])
    out = _rows_that_carry(
        lambda y, z: (y * jax.nn.silu(z.astype(jnp.float32))).astype(dt)
        @ gpt._wget(p, "out_w", dt), (y, z), chunk, live)
    return out, kp, vp, dict(held, mamba_state=state, mamba_conv=conv)


# --------------------------------------------------------------------------- #
# The indexed mixer
# --------------------------------------------------------------------------- #
def indexed_keys_attended(cfg, positions: np.ndarray) -> np.ndarray:
    """Keys a query at each of ``positions`` attends in ONE indexed layer
    (its heads share the set): all ``t + 1`` up to ``topk``, then ``topk``."""
    return np.minimum(np.asarray(positions, np.int64) + 1, cfg.indexer.topk)


# bits of the k-th largest score found a pass: ``2^bits - 1`` counts over the
# scores a pass, ``32 / bits`` passes (PERF.md section 6, PR 51: the chip's
# reading of 1, 2 and 4)
_BITS_A_PASS = 2


def _sortable(x: Array) -> Array:
    """float32 -> uint32 in the same order (-inf lowest)."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))


def _counts_before(x: Array, G: int):
    """``x [n, T]`` of 0 / 1, ``T`` whole groups of ``G`` -> (inclusive count
    inside each group ``[n, T / G, G]``, inclusive count over the groups ``[n,
    T / G]``), float32 and exact: a triangular product a group, then a
    cumulative sum over the groups."""
    n, T = x.shape
    tri = (jnp.arange(G)[:, None] <= jnp.arange(G)[None]).astype(jnp.bfloat16)
    inside = jnp.einsum("npg,gh->nph", x.reshape(n, T // G, G).astype(jnp.bfloat16),
                        tri, preferred_element_type=jnp.float32)
    return inside, jnp.cumsum(inside[..., -1], axis=1)


def _running_count(x: Array, G: int) -> Array:
    """``x [n, T]`` of 0 / 1 -> how many of ``x[:, :i + 1]`` are set ``[n,
    T]``, float32 and exact."""
    inside, groups = _counts_before(x, G)
    return (inside + (groups - inside[..., -1])[..., None]).reshape(x.shape)


def selects_in_vmem(n: int, T: int, k: int) -> bool:
    """Whether ``n`` rows of ``T`` scores choose their ``k`` largest through
    the kernel (``ops/pallas/index_select.py``): on a TPU, at shapes its
    tiles take; the bisection in plain ``jax.numpy`` everywhere else."""
    from deepspeed_tpu.ops import pallas
    from deepspeed_tpu.ops.pallas import index_select
    return (pallas.use_kernel(index_select.KERNEL) and pallas.single_device()
            and index_select.kernel_shape_ok(n, T, k))


def chosen_tokens(scores: Array, k: int, G: int) -> Array:
    """Which ``k`` of ``scores [n, T]`` (float32) a row are largest, EXACTLY
    (of equal scores the lower positions; never one at -inf, so a row with
    fewer than ``k`` scores above it chooses fewer): ``[n, T]`` bool.  ``T``
    is whole groups of ``G``.  No sort: the ``k``-th largest score is found
    by bisection over the scores' bit patterns; what lies above it and the
    first of what equals it is the set.  On a TPU the whole bisection runs
    with the rows' scores held in VMEM (:func:`selects_in_vmem`); elsewhere
    in plain ``jax.numpy``, :data:`_BITS_A_PASS` bits a pass.  A mask is what
    a prompt chunk's attention takes; where the positions themselves are
    wanted (a decode row's gather) :func:`chosen_positions` gives the same
    set."""
    n, T = scores.shape
    assert T % G == 0 and k <= T and 32 % _BITS_A_PASS == 0, (T, G, k)
    if selects_in_vmem(n, T, k):
        from deepspeed_tpu.ops.pallas.index_select import index_select
        return index_select(scores, k)[0] != 0
    u = _sortable(scores)
    kth = jnp.zeros((n,), jnp.uint32)
    digits = jnp.arange(1, 1 << _BITS_A_PASS, dtype=jnp.uint32)
    for shift in range(32 - _BITS_A_PASS, -1, -_BITS_A_PASS):
        cand = kth[:, None] | (digits << shift)[None]
        enough = jnp.sum(u[:, None, :] >= cand[:, :, None], axis=-1) >= k
        kth = kth | (jnp.sum(enough, axis=-1).astype(jnp.uint32) << shift)
    above, equal = u > kth[:, None], u == kth[:, None]
    wanted = (k - jnp.sum(above, axis=-1)).astype(jnp.float32)     # of the equal ones
    rank = _running_count(equal, G)
    return (above | (equal & (rank <= wanted[:, None]))) & (scores > -jnp.inf)


def chosen_positions(scores: Array, k: int):
    """The positions :func:`chosen_tokens` chooses, where they themselves are
    wanted (a decode row's gather): -> (``[n, k]`` int32 in RISING order,
    which of them are real ``[n, k]``: a row with fewer than ``k`` scores
    above -inf has fewer, and the slots past its last hold ``T - 1``).
    No sort, scatter or gather: the keys are placed by the mask's running
    count in two levels (:func:`_counts_before`), inside each group of 128
    keys (of fewer where ``T`` is not whole lane tiles) and over the groups'
    ends.  Slot ``j`` lies in the one group that starts at
    or under ``j`` chosen keys and ends above them, and inside it at the lane
    with ``j`` less the group's start chosen lanes at or under it: the
    group's row of counts (128 at most: exact in bf16) comes by a one-hot
    product, the lane by a compare and a sum."""
    n, T = scores.shape
    G = math.gcd(T, _RANK_GROUP)
    inside, ends = _counts_before(chosen_tokens(scores, k, G), G)
    inside, ends = inside.astype(jnp.bfloat16), ends.astype(jnp.int32)
    slot = jnp.arange(k)[:, None]
    starts = jnp.pad(ends[:, :-1], ((0, 0), (1, 0)))[:, None]
    own = (starts <= slot) & (slot < ends[:, None])                    # [n, k, T / G]
    group = jnp.sum(ends[:, None] <= slot, axis=-1, dtype=jnp.int32)
    before = jnp.sum(jnp.where(own, starts, 0), axis=-1)
    row = jnp.einsum("nkp,npg->nkg", own.astype(jnp.bfloat16), inside)
    lane = jnp.sum(row <= (slot[:, 0] - before)[..., None].astype(jnp.bfloat16),
                   axis=-1, dtype=jnp.int32)
    return jnp.minimum(group * G + lane, T - 1), slot[:, 0] < ends[:, -1:]


def index_scores(cfg, qi: Array, w: Array, keys: Array) -> Array:
    """``I_{t,s} = (lanes heads)^-1/2 sum_j w_{t,j} relu(qI_{t,j} . kI_s)``:
    ``qi [n, heads, lanes]``, ``w [n, heads]`` float32, ``keys [n | 1, T,
    lanes]`` in the type they are cached in (one row where the queries share
    a sequence) -> ``[n, T]`` float32."""
    ix = cfg.indexer
    keys = keys.astype(qi.dtype)
    eq = "njd,td->njt" if keys.shape[0] == 1 else "njd,ntd->njt"
    s = jnp.einsum(eq, qi, keys[0] if keys.shape[0] == 1 else keys,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("njt,nj->nt", jax.nn.relu(s), w) / math.sqrt(
        ix.head_dim * ix.heads)


# queries of a prompt chunk that are scored and select together at 16 index
# heads: a tile's scores are ``[tile, heads, keys]`` float32 (128 x 16 x 46,080
# x 4 B = 377 MB); an indexer of more heads takes as many fewer queries, one
# of fewer no more
_CHUNK_TILE = 128


def indexed_chunk_tile(chunk: int, heads: int = 16) -> int:
    """Queries of a prompt chunk of ``chunk`` that select together under an
    indexer of ``heads`` heads: the whole chunk, or a tile of it; 0 where the
    chunk is not whole tiles (which ``init_serving`` refuses)."""
    tile = min(chunk, _CHUNK_TILE, max(_CHUNK_TILE * 16 // heads, 8))
    return tile if chunk % tile == 0 else 0


def chunk_extent_widths(pages: int, extents: int) -> List[int]:
    """The widths in pages a prompt chunk is compiled for under a table of
    ``pages`` pages taken in ``extents`` parts: the multiples of ``ceil(pages
    / extents)``, the last the table itself.  A chunk whose last position is
    ``last`` takes the ``last // (widths[0] * BS)``-th."""
    per = -(-pages // extents)
    return sorted({min(i * per, pages) for i in range(1, extents + 1)})


def select_and_attend(cfg, qi, ki, w, pages, li, step: _Step, BS: int,
                      attend_rows, attend_chunk, extents: int = 1):
    """What every indexed layer does between its projections and its
    attention, whatever it caches (K and V heads: :func:`indexed_mixer`; a
    latent: ``models/gpt.py:gpt_paged_step``).  ``qi [B, heads, lanes]``, ``ki
    [B, lanes]`` and ``w [B, heads]`` float32 are the rows' index queries, index
    key and heads' weights; ``pages [layers, blocks, BS, lanes]`` the index
    keys' pages, layer ``li`` of which gets the rows' keys written under
    their tables.  Then

        I_{t,s} = (lanes heads)^-1/2 sum_j w_{t,j} relu(qI_{t,j} . kI_s), s <= t
        S_t = the topk positions s <= t of largest I_{t,s} (ties: the lower)

    Nothing sorts: the set is found by bisection (:func:`chosen_tokens`), on
    a TPU with a tile of rows' scores held in VMEM.  A decode row gathers its
    index keys under its own table and takes the set as positions, in rising
    order (:func:`chosen_positions`): ``attend_rows(tables [n, MB], at [n,
    K], real [n, K]) -> [n, ...]``.  The prompt chunk's rows share one table
    and select a tile of queries at a time (:func:`indexed_chunk_tile`), as
    a mask: ``attend_chunk(table [1, pages], chosen [chunk, pages
    * BS], last) -> [chunk, ...]``, ``last`` the chunk's last live position.
    With ``extents`` above 1 the chunk scores, selects among and attends the
    first ``i / extents`` of its table alone, the least that holds ``last``
    (a branch of one ``switch`` an extent: a prompt's early chunks pay for
    the keys they can see and not for the table's width).  A step
    without a prompt chunk skips the chunk rows' scores, their selection and
    their attend: nobody reads them.  The scopes ``index_score``,
    ``index_topk`` and ``index_attend`` are opened here.  -> (the rows'
    attention ``[B, ...]``, pages)."""
    positions, live, _, tables, write_blocks, write_offsets, chunk, _, _, _ = step
    ix = cfg.indexer
    B, T = qi.shape[0], tables.shape[1] * BS
    K, n_dec = min(ix.topk, T), B - chunk
    with jax.named_scope("index_score"):
        pages = pages.at[li, write_blocks, write_offsets].set(
            ki.astype(pages.dtype)[:, None])

    def scores_of(qi, w, at, keys):
        """``I_{t,s}`` of the queries at positions ``at [n]`` over their
        sequences' index keys ``keys [n | 1, keys, lanes]``, -inf past each."""
        with jax.named_scope("index_score"):
            return jnp.where(jnp.arange(keys.shape[1])[None] <= at[:, None],
                             index_scores(cfg, qi, w, keys), -jnp.inf)

    def under(tb):
        """The index keys under the tables ``tb [n, pages]``, in logical order."""
        with jax.named_scope("index_score"):
            return pages[li, tb].reshape(tb.shape[0], -1, ix.head_dim)

    def decode_rows():
        tb = tables[:n_dec]
        scores = scores_of(qi[:n_dec], w[:n_dec], positions[:n_dec], under(tb))
        with jax.named_scope("index_topk"):
            at, real = chosen_positions(scores, K)
        with jax.named_scope("index_attend"):
            return attend_rows(tb, at, real)

    def over_the_chunk(last, n_pages):
        """The chunk's rows over the first ``n_pages`` of their table."""
        tile = indexed_chunk_tile(chunk, ix.heads)
        assert tile, f"a chunk of {chunk} is not whole tiles of queries that select together"
        tb = tables[n_dec:n_dec + 1, :n_pages]
        keys = under(tb)
        tiles = lambda a: a[n_dec:].reshape(chunk // tile, tile, *a.shape[1:])

        def choose(a):
            scores = scores_of(*a, keys)
            with jax.named_scope("index_topk"):
                return chosen_tokens(scores, min(ix.topk, n_pages * BS), BS)

        chosen = jax.lax.map(choose, (tiles(qi), tiles(w), tiles(positions)))
        with jax.named_scope("index_attend"):
            return attend_chunk(tb, chosen.reshape(chunk, -1), last)

    def chunk_rows():
        last = jnp.max(jnp.where(live[n_dec:], positions[n_dec:], 0))
        widths = chunk_extent_widths(tables.shape[1], extents)
        if len(widths) == 1:
            return over_the_chunk(last, widths[0])
        return jax.lax.switch(last // (widths[0] * BS),
                              [partial(over_the_chunk, n_pages=n) for n in widths], last)

    o = decode_rows()
    if chunk:
        o = jnp.concatenate([o, jax.lax.cond(
            live[n_dec], chunk_rows,
            lambda: jnp.zeros((chunk, *o.shape[1:]), o.dtype))])
    return o, pages


def indexed_mixer(cfg, p, h, kp, vp, held, li, step: _Step):
    """One indexed layer's attention over the rows ``h [B, E]``: -> (the
    mixer's output ``[B, E]`` before the residual, kp, vp, held with its
    index keys ``ki`` written).  With ``h_t`` the layer's normed input:

        q_t = rope(rms_h(W_q h_t)), k_t = rope(rms_h(W_k h_t)), v_t = W_v h_t
        qI_{t,j} = rope(W_qI h_t)_j, kI_t = rope(LN(W_kI h_t)), w_t = W_w h_t
        S_t = the tokens :func:`select_and_attend` chooses
        o_{t,h} = sum_{s in S_t} softmax_s(q_{t,h} . k_{s,g(h)} / sqrt(D)) v_{s,g(h)}

    ``rms_h`` an RMS norm over a head's lanes with one gain for all heads,
    ``LN`` a LayerNorm with gain and bias, rope the half-split rotation over
    all lanes of a head (of an index head too).  The index keys are cached in
    the pages' type; the scores are float32.  A decode row gathers the chosen
    tokens' K and V out of the pages a token at a time (a token's K/V heads
    lie side by side in a page: one row a key) and attends them densely; the
    prompt chunk attends the sequence's K and V (read once) under the
    selection's mask (``ops/pallas/indexed_attention.py``): the same
    mathematics."""
    positions, live, _, tables, write_blocks, write_offsets, chunk, dt, _, _ = step
    ix = cfg.indexer
    B = h.shape[0]
    H, Hkv, D = cfg.n_head, cfg.kv_heads, cfg.head_dim
    g, BS, T = H // Hkv, kp.shape[2], tables.shape[1] * kp.shape[2]
    n_dec = B - chunk
    rope = lambda t, at: gpt.apply_rope(t[:, None], at[:, None], cfg.rope_theta)[:, 0]

    def project(r, at):
        q, k, v = gpt._split_qkv(cfg, (r @ gpt._wget(p, "qkv_w", dt))[:, None])
        norm = lambda t, name: gpt.rms_norm(t[:, 0], p[name], eps=cfg.ln_eps)
        return rope(norm(q, "q_norm_g"), at), rope(norm(k, "k_norm_g"), at), v[:, 0]

    def index_project(r, at):
        qi, ki, w = jnp.split(r @ gpt._wget(p, "index_w", dt),
                              [ix.heads * ix.head_dim, (ix.heads + 1) * ix.head_dim], axis=-1)
        ki = gpt.layer_norm(ki, p["ik_norm_g"], p["ik_norm_b"], eps=cfg.ln_eps)
        return (rope(qi.reshape(-1, ix.heads, ix.head_dim), at),
                rope(ki[:, None], at)[:, 0], w.astype(jnp.float32))

    q, k, v = _rows_that_carry(project, (h, positions), chunk, live)
    kp = kp.at[li, write_blocks, write_offsets].set(k.astype(kp.dtype).reshape(B, 1, -1))
    vp = vp.at[li, write_blocks, write_offsets].set(v.astype(vp.dtype).reshape(B, 1, -1))
    with jax.named_scope("index_score"):
        qi, ki, w = _rows_that_carry(index_project, (h, positions), chunk, live)

    def attend_rows(tb, at, real):
        K = at.shape[1]
        page = jnp.take_along_axis(tb, at // BS, axis=1)
        kg = kp[li, page, at % BS].reshape(n_dec, K, Hkv, D)
        vg = vp[li, page, at % BS].reshape(n_dec, K, Hkv, D)
        s = jnp.einsum("nhgd,nkhd->nhgk", q[:n_dec].reshape(n_dec, Hkv, g, D), kg,
                       preferred_element_type=jnp.float32) / math.sqrt(D)
        a = jax.nn.softmax(jnp.where(real[:, None, None], s, -1e30), axis=-1)
        return jnp.einsum("nhgk,nkhd->nhgd", a.astype(vg.dtype), vg,
                          preferred_element_type=jnp.float32
                          ).astype(dt).reshape(n_dec, H * D)

    def attend_chunk(tb, chosen, last):
        from deepspeed_tpu.ops.pallas.indexed_attention import masked_chunk_attention
        return masked_chunk_attention(
            q[n_dec:], kp[li, tb[0]].reshape(T, Hkv * D),
            vp[li, tb[0]].reshape(T, Hkv * D), chosen, last).astype(dt)

    o, pages = select_and_attend(cfg, qi, ki, w, held["ki"], li, step, BS,
                                 attend_rows, attend_chunk)
    o = _rows_that_carry(lambda r: r @ gpt._wget(p, "out_w", dt), (o,), chunk, live)
    return o, kp, vp, dict(held, ki=pages)


# --------------------------------------------------------------------------- #
# The full mixer
# --------------------------------------------------------------------------- #
def full_mixer(cfg, p, h, kp, vp, held, li, step: _Step):
    """One layer of plain softmax attention over its own token's K and V, the
    pages its own: ``models/gpt.py:_project_qkv`` (the fused projection, the
    q/k norm over all lanes or a head's, rope where the layer's kind ropes,
    over ``rope_dim`` lanes), the page group's plan and ``_attn_out`` (the
    output gate where ``attn_gate``), as ``gpt_paged_step`` calls them.  ->
    (output ``[B, E]``, kp, vp with the rows' K and V written, held as it
    came)."""
    (positions, live, _, tables, write_blocks, write_offsets, chunk, dt, plan,
     tile_runs) = step
    B = h.shape[0]
    kind = next(kind for kind in cfg.pattern if kind.mixer == "full")
    q, k, v = _rows_that_carry(
        lambda r, t: gpt._project_qkv(cfg, p, r[:, None], dt, t[:, None], kind),
        (h, positions), chunk, live)
    kp = kp.at[li, write_blocks, write_offsets].set(k.astype(kp.dtype).reshape(B, 1, -1))
    vp = vp.at[li, write_blocks, write_offsets].set(v.astype(vp.dtype).reshape(B, 1, -1))
    o = plan.attend(q, (kp, vp), li, tables, positions, chunk=chunk,
                    tile_runs=tile_runs).reshape(B, cfg.attn_dim)
    # through the output gate where the configuration has one, then ``W_o``
    o = _rows_that_carry(lambda o, h: gpt._attn_out(cfg, p, o, h, dt), (o, h),
                         chunk, live)
    return o, kp, vp, held


# --------------------------------------------------------------------------- #
# The feed-forwards
# --------------------------------------------------------------------------- #
def mlp_ffn(cfg, p, bank, li, x, stream, step: _Step):
    """The dense SwiGLU over the normed residual, or with ``cfg.norm_after``
    over the residual as it is and normed on its way out: -> (its output, the
    routers' stream as it came, no expert counts)."""
    live, chunk, dt = step.live, step.chunk, step.dt
    norm = lambda r: gpt.rms_norm(r, p["ln2_g"], eps=cfg.ln_eps)
    if cfg.norm_after:
        mlp = lambda r: norm(gpt._mlp(cfg, p, r, dt))
    else:
        mlp = lambda r: gpt._mlp(cfg, p, norm(r), dt)
    return _rows_that_carry(mlp, (x,), chunk, live), stream, None


def _stream_route(cfg, p, z, stream):
    """The MLP router that reads the layer's normed input and the stream
    ``[B, R]`` the router before it left (the ZAYA1 line): the largest of
    softmax + balancing bias, weighed by the softmax."""
    from deepspeed_tpu.moe import dropless
    stream, logits = dropless.stream_mlp_logits(
        z, stream, p["router_in_w"], p["stream_g"], p["router_norm_g"],
        [p["router_w1"], p["router_w2"], p["router_w3"]], cfg.ln_eps)
    _, weights, experts = dropless.biased_softmax_topk(
        logits, cfg.moe_top_k, p["balance_bias"])
    return stream, weights, experts


def _softmax_route(cfg, p, z, stream):
    """The linear softmax router over the layer's normed input, in float32,
    as ``models/gpt.py:_ffn`` routes (OLMoE's; with ``moe_norm_topk`` the
    chosen weights renormalised: SmallThinker's, the Keye-VL-2.0 line's).
    No stream: it goes on as it came (None)."""
    from deepspeed_tpu.moe import dropless
    logits = z.astype(jnp.float32) @ p["router_w"].astype(jnp.float32)
    _, weights, experts = dropless.softmax_topk(logits, cfg.moe_top_k,
                                                cfg.moe_norm_topk)
    return stream, weights, experts


def _moe_ffn(route):
    """A bank of SwiGLU experts with ``moe_top_k`` a token behind ``route(cfg,
    p, z, stream) -> (stream, weights, experts)``, the pieces
    ``models/gpt.py:_ffn`` is made of: ``dropless_moe`` over the experts the
    bank HOLDS (``moe_experts_held``: the router chooses among all, the held
    are computed and the partial result goes on; then the rows that carry no
    request lie in no group either, as in ``_ffn``) and ``gpt.shared_expert``
    beside it, behind its gate where ``moe_shared_gate``.  ``bank``: the
    mixer's stacked expert leaves ``[layers, experts held, ...]``, which
    ``grouped_matmul`` reads at layer ``li`` where they lie.  -> (output, the
    stream for the next layer, the live rows' assignments an expert the
    router chooses among ``[experts]`` int32)."""
    def ffn(cfg, p, bank, li, x, stream, step: _Step):
        from deepspeed_tpu.moe import dropless
        live, chunk, dt = step.live, step.chunk, step.dt
        N, held = cfg.moe_num_experts, cfg.moe_experts_held
        leaves = {"fc_w": bank["wi"], "proj_w": bank["wo"]}

        def rows(x, live, stream=None):
            z = gpt.rms_norm(x, p["ln2_g"], eps=cfg.ln_eps)
            with jax.named_scope("moe"):
                with jax.named_scope("moe_router"):
                    stream, weights, experts = route(cfg, p, z, stream)
                y = dropless.dropless_moe(
                    z, weights, experts, N,
                    lambda r, matmul, pick: gpt._mlp(cfg, leaves, r, dt, matmul, pick),
                    held=held, layer=li, live=live if held else None)
                if cfg.moe_shared_experts:
                    y = y + gpt.shared_expert(
                        cfg, p["shared_fc_w"], p["shared_proj_w"], z, dt,
                        p["shared_gate_w"] if cfg.moe_shared_gate else None)
            return y.astype(dt), stream, experts

        y, stream, experts = _rows_that_carry(
            rows, (x, live) if stream is None else (x, live, stream), chunk, live)
        return y, stream, dropless.expert_counts(experts, N, live)
    return ffn


# the entries the walk dispatches on: a layer names one of each
# (``LayerKind.mixer``, ``LayerKind.ffn``); a mixer's is its function and the
# scope its device ops are traced under.  The order is the order of the
# mixers' stacks in ``init_blocks`` (a stack's seed is its place here: a new
# mixer goes last)
MIXERS = {"sparse": (sparse_mixer, lambda: jax.named_scope("attn_sparse")),
          "linear": (linear_mixer, lambda: jax.named_scope("attn_linear")),
          "cca": (cca_mixer, lambda: jax.named_scope("attn_cca")),
          "delta": (delta_mixer, lambda: jax.named_scope("attn_delta")),
          "indexed": (indexed_mixer, lambda: jax.named_scope("attn_indexed")),
          "full": (full_mixer, lambda: jax.named_scope("attn_full")),
          "mamba": (mamba_mixer, lambda: jax.named_scope("attn_mamba"))}
FEED_FORWARDS = {"mlp": mlp_ffn, "moe": _moe_ffn(_stream_route),
                 "moe_softmax": _moe_ffn(_softmax_route)}


# --------------------------------------------------------------------------- #
# The step
# --------------------------------------------------------------------------- #
def hybrid_paged_step(cfg, params: Dict, input_ids: Array, positions: Array,
                      k_pages: Array, v_pages: Array, block_tables,
                      write_blocks, write_offsets, chunk: int = 0, aux=None,
                      slots=None, live=None, with_expert_counts: bool = False):
    """``models/gpt.py:gpt_paged_step`` for a hybrid stack: ``input_ids [B,
    1]``, a token a row, the last ``chunk`` rows a prompt chunk; the arena is
    that of the layers that cache K and V (``cfg.arena_layout``),
    ``block_tables`` and ``write_blocks`` one group's; ``aux`` is
    :func:`init_aux`'s, ``slots [B]`` the slot a row's sequence holds and
    ``live [B]`` whether it carries one.  A layer is ``x + f(norm(x))`` twice
    or, with ``cfg.norm_after``, ``x + norm(f(x))``.  The walk's carry is the
    residual,
    the routers' stream (zero before the first layer; None in a stack whose
    routers carry none) and the caches.  -> (logits ``[B, 1, V]`` float32,
    k_pages, v_pages, aux) and with ``with_expert_counts`` the live rows'
    assignments ``[layers, experts]`` int32."""
    B, S = input_ids.shape
    assert S == 1 and aux is not None, "a token a row, with the stack's state"
    assert not chunk or "sparse" not in cfg.mixers or chunk % cfg.sparse.stride == 0, (
        f"a prompt chunk of {chunk} is not whole strides of {cfg.sparse.stride}")
    if isinstance(block_tables, (tuple, list)):
        (block_tables,), (write_blocks,) = block_tables, write_blocks
    dt, rs = cfg.dtype, cfg.residual_scale
    blocks = params["blocks"]
    # which tiles of the tables the page group's kernel fetches with one copy:
    # the same for every layer, so worked out here and not in the walk
    (plan,) = cfg.paged_plans(k_pages.shape[2], (block_tables.shape[1],), chunk,
                              k_pages.dtype)
    step = _Step(positions, live, slots, block_tables, write_blocks.reshape(B, 1),
                 write_offsets.reshape(B, 1), chunk, dt, plan,
                 plan.tile_runs(block_tables, k_pages.shape[1]))
    x = params["wte"].astype(dt)[input_ids[:, 0]] * jnp.asarray(cfg.scale_emb, dt)

    def layer(mixer, carry, i):
        x, stream, kp, vp, held = carry
        p = _LayerLeaves(blocks[mixer], i)
        mix, scope = MIXERS[mixer]
        norm = lambda t: gpt.rms_norm(t, p["ln1_g"], eps=cfg.ln_eps)
        with jax.named_scope("attn"):
            # where the norm sits is the configuration's: on the mixer's
            # input, or (``norm_after``) on its output, the mixer reading the
            # residual as it is
            h = x if cfg.norm_after else norm(x)
            with scope():
                o, kp, vp, held = mix(cfg, p, h, kp, vp, held, i, step)
            if cfg.norm_after:
                o = norm(o)
        with jax.named_scope("mlp"):
            x = x + rs * o
            y, stream, counts = FEED_FORWARDS[ffn_of(cfg, mixer)](
                cfg, p, blocks[mixer].get("experts"), i, x, stream, step)
            x = x + rs * y
        return (x, stream, kp, vp, held), counts

    stream = (jnp.zeros((B, cfg.moe_router_hidden), jnp.float32)
              if "moe" in cfg.ffns else None)
    carry, counts = (x, stream, k_pages, v_pages, dict(aux)), []
    for mixer, first, count in layer_runs(cfg):
        carry, ys = jax.lax.scan(lambda c, i, m=mixer: layer(m, c, i), carry,
                                 first + jnp.arange(count, dtype=jnp.int32))
        counts.append(ys)
    x, _, k_pages, v_pages, aux = carry
    with jax.named_scope("head"):
        x = gpt.rms_norm(x, params["lnf_g"], eps=cfg.ln_eps) / jnp.asarray(
            cfg.head_divisor, dt)
        head = params["lm_head"] if cfg.untied_head else params["wte"]
        logits = _rows_that_carry(
            lambda r: (r @ head.astype(dt).T).astype(jnp.float32),
            (x,), chunk, live)
    if with_expert_counts:
        return (logits[:, None], k_pages, v_pages, aux,
                jnp.concatenate([c for c in counts if c is not None]))
    return logits[:, None], k_pages, v_pages, aux
