"""GPT-2 model family — the flagship training model, TPU-first.

The reference has no in-tree GPT model (it wraps Megatron/HF modules); this
framework ships one because the north-star benchmark is GPT-2-1.5B ZeRO-3
(BASELINE.json) and the inference stack (reference
``deepspeed/model_implementations/transformers/ds_gpt.py``) needs a concrete
architecture to fuse.

TPU-first design decisions:

* Stacked layers (``scan_layers=True``, a LAYOUT): the block parameters
  carry a leading ``[n_layer, ...]`` dim, which checkpoints, the ZeRO policy
  and every serving path read.  How the dense forward WALKS that stack is
  ``layer_walk``'s to say, from ``cfg`` and the mesh: a ``lax.scan`` (one
  compiled block body whatever the depth, compile time O(1) in ``n_layer``)
  under remat and where the parameters arrive sharded over ``fsdp``; a
  Python loop over static slices everywhere else, because a loop copies
  every residual its backward needs into a stack and out again (compile
  time is then O(``n_layer``)).  The serving steps keep their scans.
* Megatron-style tensor parallelism is expressed purely as sharding
  metadata (``partition_specs``): QKV/MLP-up are column-parallel
  (output-dim ``tensor``), attn-out/MLP-down row-parallel (input-dim
  ``tensor``), token embedding vocab-parallel.  XLA-SPMD inserts the
  per-layer allreduces that Megatron codes by hand.
* Sequence parallelism: activations are sharding-constrained to
  ``[batch, seq, embd]`` = ``(BATCH_AXES, 'seq', None)`` so a ``seq`` mesh
  axis shards the sequence dim end-to-end; the attention op handles the
  head/seq re-sharding (Ulysses) or ring pipelining (see
  ``deepspeed_tpu/ops/attention.py``).
* ``jax.checkpoint`` (remat) on the block body when ``remat=True`` — the
  analogue of the reference's activation checkpointing
  (``runtime/activation_checkpointing/checkpointing.py:474``).
* bf16 activations / fp32 params by default: the engine keeps fp32 masters
  and casts per-step (``runtime/engine.py``).
"""

import dataclasses
import math
from collections.abc import Mapping
from contextlib import nullcontext
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.comm.compression import layered as zero_layered
from deepspeed_tpu.parallel import mesh as mesh_lib

Array = jax.Array


class LayerKind(NamedTuple):
    """What distinguishes one layer of a ``layer_pattern``: ``window`` keys a
    query sees, itself counted (None: every earlier key), whether q and k
    are rotated (``rope``) or carry no position, and the layer's ``mixer``:
    ``softmax`` attention over cached keys (most families), attention over
    the blocks the query chooses (``sparse``: :class:`SparseSpec`), a
    ``linear`` recurrence whose cache is a state, attention inside a latent
    convolved over time (``cca``), the gated ``delta`` rule whose state is
    corrected before it is written, Mamba-1's selective scan whose state is
    a diagonal recurrence by channel (``mamba``), attention over the TOKENS an
    indexer scores highest (``indexed``: :class:`IndexerSpec`), or, beside one of
    those in the same walk, ``full`` softmax attention over pages (all
    ``models/hybrid.py``).
    ``depth`` is the layer's index in the PUBLISHED stack where that differs
    from its place here (a linear layer's decay reads it).  ``ffn`` is the
    layer's feed-forward, ``mlp``, ``moe`` (a bank behind the MLP router with
    its stream) or ``moe_softmax`` (a bank behind the linear softmax router);
    None: the model's one kind, ``moe`` where ``moe_num_experts`` says so."""
    window: Optional[int] = None
    rope: bool = True
    mixer: str = "softmax"
    depth: Optional[int] = None
    ffn: Optional[str] = None


class SparseSpec(NamedTuple):
    """Block-sparse attention that chooses its own blocks (the InfLLM-V2
    line of MiniCPM4): compressed keys are means of ``kernel`` keys every
    ``stride``; a query with more than ``dense_len`` keys attends the
    ``topk`` blocks of ``block`` keys that score highest, the first
    ``init_blocks`` and those of its last ``window`` keys among them."""
    kernel: int = 32
    stride: int = 16
    block: int = 64
    topk: int = 64
    init_blocks: int = 1
    window: int = 2048
    dense_len: int = 8192


class IndexerSpec(NamedTuple):
    """The lightning indexer of DeepSeek Sparse Attention (DeepSeek-V3.2-Exp;
    the Keye-VL-2.0 line's ``sa_config``): ``heads`` index queries of
    ``head_dim`` lanes a token score every cached index key (ONE of
    ``head_dim`` lanes a token a layer), and the query attends the ``topk``
    TOKENS it scores highest: over K and V heads the hybrid walk's
    ``indexed`` mixer (``models/hybrid.py:indexed_mixer``), over a LATENT
    cache (``kv_lora_rank``) the periodic walk's own (``gpt_paged_step``),
    both through ``models/hybrid.py:select_and_attend``."""
    heads: int = 16
    head_dim: int = 64
    topk: int = 2048


class YarnRope(NamedTuple):
    """A rope whose frequencies are stretched by YaRN (the source's
    ``rope_parameters``): lane pair ``i`` turns at ``theta^(-i/half)``, at
    that over ``factor``, or at a blend of the two on the linear ramp between
    the correction dimensions of ``beta_fast`` and ``beta_slow`` turns in
    ``original_positions``.  ``mscale_all_dim`` scales the softmax (by the
    square of :func:`yarn_mscale`; the cos/sin factor ``mscale /
    mscale_all_dim`` is their ratio), and ``query_beta`` the query of
    position ``p`` by ``1 + query_beta * ln(1 + p // original_positions)``
    (``llama_4_scaling_beta``)."""
    factor: float
    original_positions: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    query_beta: float = 0.0


class HyperSpec(NamedTuple):
    """Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
    Hyper-Connections, arXiv:2409.19606; the xing4_0 line's ``hc_mult``,
    ``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min/max``): a token
    carries ``streams`` residual streams ``X [n, E]`` from layer to layer.
    Each SUBLAYER ``F`` reads ``u = Hpre @ X`` and writes ``X' = Hres @ X +
    outer(Hpost, F(u))``; the three maps come from the token's own streams
    (:func:`hyper_maps`), ``Hres`` made doubly stochastic by
    ``sinkhorn_iters`` rounds of Sinkhorn-Knopp on ``exp`` of its logits
    clamped to ``[clamp_min, clamp_max]``; ``eps`` is the eps of both
    normalisations and of the RMSNorm over all ``n E`` lanes.  The embedding
    is copied to the streams and the streams are summed before the final
    norm (:func:`gpt_paged_step`)."""
    streams: int = 4
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    clamp_min: float = -30.0
    clamp_max: float = 30.0


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    # the LAYOUT of params["blocks"]: leaves stacked [n_layer, ...] (True) or
    # a dict of per-layer trees h0, h1, ... (False).  Not the walk: whether a
    # stacked layout is scanned or unrolled is ``layer_walk``'s rule
    scan_layers: bool = True
    remat: bool = False
    attn_impl: str = "auto"   # 'auto' | 'flash' | 'reference' | 'ring'
    dtype: Any = jnp.bfloat16
    # activation of the MLP: 'gelu_tanh' (GPT-2's gelu_new), 'gelu', 'relu'
    # — lets injected foreign architectures (e.g. OPT) reuse the fused block
    activation: str = "gelu_tanh"
    ln_eps: float = 1e-5
    # separate lm_head matrix (HF tie_word_embeddings=False checkpoints);
    # params then carry an extra "lm_head" [padded_vocab, n_embd] leaf
    untied_head: bool = False
    # random-LTD (data_efficiency.data_routing.random_ltd): tokens kept per
    # block in train mode; None/>=seq disables.  Static per compile — the
    # engine swaps it as the schedule advances (one XLA program per value).
    ltd_keep: Optional[int] = None
    # non-scan path only: which block ids drop tokens (None = all); the
    # homogeneous scan path applies LTD to every block when enabled
    ltd_layers: Optional[Tuple[int, ...]] = None
    # --- architecture family knobs (one fused block serves GPT-2, BLOOM
    # (alibi), and LLaMA-style (rope+rmsnorm+swiglu) — the same strategy as
    # the reference's per-arch ds_* model_implementations variants) ------- #
    position_encoding: str = "learned"   # 'learned' | 'rope' | 'alibi'
    norm: str = "layernorm"              # 'layernorm' | 'rmsnorm'
    mlp_type: str = "standard"           # 'standard' | 'swiglu'
    # the gate's activation of a 'swiglu' MLP: 'silu' (SwiGLU), 'relu' (ReGLU)
    glu_activation: str = "silu"
    intermediate_size: Optional[int] = None   # default 4*n_embd
    use_bias: bool = True                # LLaMA-style blocks are bias-free
    rope_theta: float = 10000.0
    # grouped-query attention: number of K/V heads (None = n_head = MHA;
    # 1 = MQA).  The KV cache stores only n_kv_head heads — the GQA win.
    n_kv_head: Optional[int] = None
    # width of a head where it is not n_embd // n_head (a published size:
    # SmallThinker's 28 heads of 128 on a hidden size of 2560)
    head_dim: Optional[int] = None
    # one period of the stack, a LayerKind a layer: layer ``l`` is of kind
    # ``layer_pattern[l % len]``.  None is the one-entry pattern every
    # homogeneous model has (full causal attention, rope iff
    # ``position_encoding`` says so); ``n_layer`` is whole periods
    layer_pattern: Optional[Tuple[LayerKind, ...]] = None
    # pad vocab to a multiple (MXU-friendly, and divisible by tensor axis)
    vocab_multiple: int = 128
    # block topology: 'sequential' (GPT-2/OPT/LLaMA), 'parallel' (GPT-NeoX
    # use_parallel_residual: x + attn(ln1 x) + mlp(ln2 x)), or
    # 'parallel_single_ln' (GPT-J: one LN feeds both attn and mlp)
    block_type: str = "sequential"
    # rotary variants: partial rotary dims (GPT-J rotary_dim / NeoX
    # rotary_pct) and GPT-J's interleaved (rotate-every-two) pairing
    rope_dim: Optional[int] = None
    rope_interleaved: bool = False
    # untied lm_head bias (GPT-J checkpoints carry one)
    head_bias: bool = False
    # RMSNorm over the WHOLE q and k projections (weights [H*D], [Hkv*D]),
    # before the split into heads and before rope (OLMoE); "head": over each
    # HEAD's lanes, after the split and before rope, one gain of ``head_dim``
    # the heads share (the afmoe line; the hybrid mixers norm theirs so)
    qk_norm: Any = False
    # attention's output gate: ``o * sigmoid(h W_g)`` before ``W_o``, ``h``
    # the normed input attention read (leaf ``gate_w [E, H * v_head_dim]``)
    attn_gate: bool = False
    # a second norm a sublayer, on its OUTPUT, beside the one on its input:
    # ``x + norm(f(norm(x)))``, four a layer (leaves ``post_attn_g``,
    # ``post_mlp_g``).  The periodic walk reads it; ``norm_after`` below is
    # the hybrid walk's.  ``post_attn_gain``: what ``post_attn_g`` is SEEDED
    # at (a weight like any other, trained away from it).  At seeded weights
    # softmax attention over thousands of random keys returns nearly one
    # vector whatever the query, which a gain of 1 scales up to the
    # embedding's size: the tokens of a step then share their router input
    # and go to the same few experts (PERF.md § 6, PR 55)
    norm_sandwich: bool = False
    post_attn_gain: float = 1.0
    # activation fake-quant (compression_training.activation_quantization;
    # reference QuantAct, compression/basic_layer.py:404): bits on the
    # normed inputs of the attention and MLP linears, STE gradients
    activation_quant_bits: Optional[int] = None
    activation_quant_type: str = "symmetric"
    # --- mixture-of-experts (reference deepspeed/moe): >0 replaces every
    # block's MLP with a top-k gated expert bank sharded over the 'expert'
    # mesh axis; the load-balance aux loss is added in gpt_loss.  The
    # experts are the block's own MLP (``mlp_type``, ``use_bias``,
    # ``activation``), ``moe_expert_hidden`` (default ``ffn_dim``) wide ---- #
    moe_num_experts: int = 0
    moe_top_k: int = 1
    # 'gshard': top-1/top-2 with a capacity that drops what does not fit
    # (``moe/sharded_moe.py``; the capacity knobs below are its own);
    # 'dropless': softmax over all experts, any top-k, every assignment
    # computed (``moe/dropless.py``): the one a server can use
    moe_router: str = "gshard"
    moe_capacity_factor: float = 1.25
    moe_eval_capacity_factor: float = 2.0
    moe_min_capacity: int = 4
    moe_aux_coeff: float = 0.01
    moe_expert_hidden: Optional[int] = None
    # dropless router: weights are the softmax over the CHOSEN logits
    # (``norm_topk_prob``), not the chosen entries of the softmax over all
    moe_norm_topk: bool = False
    # what the router reads: 'post_attn' (the MLP's normed input) or
    # 'pre_attn' (the normed input attention reads: SmallThinker routes
    # before attention)
    moe_router_input: str = "post_attn"
    # dropless router: what an expert's score is, 'softmax' over all experts
    # or each logit's 'sigmoid' (the DeepSeek-V3 line: the ``moe_top_k``
    # largest of score + bias, weighed by their scores)
    moe_scoring: str = "softmax"
    # a width here makes the router an MLP that wide over a stream the
    # routers carry from layer to layer beside the residual (the ZAYA1 line:
    # ``moe/dropless.py:stream_mlp_logits``; the largest of softmax + bias,
    # weighed by the softmax): a second carry of the layer walk, which
    # ``models/hybrid.py`` alone has
    moe_router_hidden: Optional[int] = None
    # experts every token goes through, beside the routed ones: ONE MLP of
    # ``moe_shared_experts * moe_expert_hidden``, added unweighted
    moe_shared_experts: int = 0
    # the shared expert behind a gate of its own, ``sigmoid(x . w_s) *
    # Shared(x)``, ``w_s [E]`` a leaf (the qwen3_next line).  The hybrid
    # walk reads it; the periodic walk's shared expert is added unweighted
    moe_shared_gate: bool = False
    # times the chosen experts' weights, after they are renormalised (the
    # DeepSeek-V3 line's ``routed_scaling_factor``, afmoe's ``route_scale``)
    moe_route_scale: float = 1.0
    # the FIRST layers of the stack whose feed-forward is the dense MLP,
    # ``ffn_dim`` wide, where every later layer's is the bank
    # (``first_k_dense_replace``, ``num_dense_layers``); the experts are
    # ``moe_expert_hidden`` wide.  The leaves of the two kinds differ in
    # shape, so the feed-forward is stacked BY KIND beside the attention
    # leaves, which stay one stack over all layers: ``blocks["lead"]``
    # ``[moe_dense_layers, ...]`` and ``blocks["moe"]`` ``[n_layer -
    # moe_dense_layers, ...]``, a layer reading its own index of its kind
    moe_dense_layers: int = 0
    # ``(first, count)``: the bank holds only the experts ``first .. first +
    # count - 1`` of the ``moe_num_experts`` the router chooses among (one
    # chip's share of an expert-parallel layer); what the others would add
    # is left out and the partial result goes on
    moe_experts_held: Optional[Tuple[int, int]] = None
    # the sigmoid router chooses inside ``moe_topk_group`` of ``moe_n_group``
    # groups of consecutive experts, a group scored by the sum of its two
    # largest ``score + bias`` (the DeepSeek-V3 line's ``n_group``,
    # ``topk_group``; 1 and 1: no limit)
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # --- latent attention (MLA, the DeepSeek-V2 line); ``kv_lora_rank``
    # selects it.  The query goes down to ``q_lora_rank``, through an RMSNorm
    # and up to ``n_head`` heads of ``head_dim``, whose LAST ``qk_rope_dim``
    # lanes are rotated; keys and values come from ONE latent of
    # ``kv_lora_rank`` a token (normed) and ONE rotated key of
    # ``qk_rope_dim`` all heads share, which is all a cache holds; a head's
    # value is ``v_head_dim`` wide (default ``head_dim``) ------------------ #
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_rope_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    rope_yarn: Optional[YarnRope] = None
    # --- a stack whose layers differ in shape and follow no period (the
    # MiniCPM-SALA family, ``models/hybrid.py``): ``layer_pattern`` then names
    # EVERY layer, some of a ``mixer`` other than softmax, and the leaves are
    # stacked by kind.  ``sparse`` is the sparse layers' selection; the
    # MiniCPM scalings: ``x_0 = scale_emb * wte[ids]`` (every walk reads this
    # one: afmoe's ``mup_enabled`` is ``sqrt(n_embd)``), a block adds
    # ``residual_scale * f(norm(x))``, the head reads ``norm(x) /
    # head_divisor``; ``published_layers`` is the depth the decays and
    # ``residual_scale`` were published for --------------------------------- #
    sparse: Optional[SparseSpec] = None
    scale_emb: float = 1.0
    residual_scale: float = 1.0
    head_divisor: float = 1.0
    published_layers: Optional[int] = None
    # the ``indexed`` layers' indexer (``models/hybrid.py:indexed_mixer``);
    # beside ``kv_lora_rank`` in a periodic stack, EVERY layer's: the index
    # queries come from the query's latent, the first ``qk_rope_dim`` lanes
    # of an index head are rotated (half-split pairs, the model's
    # frequencies), and the chosen tokens are rows of the latent cache
    # (DeepSeek-V3.2-Exp: ``gpt_paged_step``)
    indexer: Optional[IndexerSpec] = None
    # the residual path where it is not ``x + f(norm(x))``: ``streams``
    # residual streams a token, read and written through maps of the token's
    # own (:class:`HyperSpec`; leaves ``hc_{attn,mlp}_{phi,b,alpha}``).  The
    # periodic walk of ``gpt_paged_step`` reads it; the dense paths and the
    # hybrid walk refuse it
    hyper: Optional[HyperSpec] = None
    # --- the gated delta rule (the Olmo-Hybrid family's ``delta`` layers,
    # ``models/hybrid.py:delta_mixer``): ``delta_heads`` heads, a key of
    # ``delta_key_dim`` and a value of ``delta_value_dim`` lanes, behind a
    # causal convolution over time of ``delta_conv`` taps; with
    # ``delta_neg_eigval`` the write strength is ``2 sigmoid`` and the
    # transition's eigenvalue reaches -1.  ``delta_key_heads`` (0: as many
    # as ``delta_heads``): the heads of q and k where they are fewer than the
    # VALUE heads ``delta_heads``, a key head's q and k serving ``delta_heads
    # / delta_key_heads`` value heads in a row, each with a state, a decay
    # and a write strength of its own (the qwen3_next line) --------------- #
    delta_heads: int = 0
    delta_key_heads: int = 0
    delta_key_dim: int = 0
    delta_value_dim: int = 0
    delta_conv: int = 4
    delta_neg_eigval: bool = False
    # --- Mamba-1's selective scan (the Jamba family's ``mamba`` layers,
    # ``models/hybrid.py:mamba_mixer``): ``mamba_inner`` channels, each a
    # recurrence over ``mamba_state`` states with a decay of its own, behind
    # a causal convolution over time of ``mamba_conv`` taps with a bias; the
    # token's step a channel comes up from ``mamba_dt_rank`` lanes ---------- #
    mamba_inner: int = 0
    mamba_state: int = 0
    mamba_conv: int = 4
    mamba_dt_rank: int = 0
    # where a block's two norms sit: on each sublayer's OUTPUT (the OLMo 2/3
    # block, ``x + norm(f(x))``: mixer and MLP read the residual as it is)
    # and not on its input.  The hybrid walk reads it; the dense paths are
    # pre-norm
    norm_after: bool = False

    def __post_init__(self):
        self.padded_vocab = int(
            math.ceil(self.vocab_size / self.vocab_multiple) * self.vocab_multiple)
        if self.head_dim is None:
            assert self.n_embd % self.n_head == 0
            self.head_dim = self.n_embd // self.n_head
        self.attn_dim = self.n_head * self.head_dim
        self.pattern = tuple(LayerKind(*k) for k in self.layer_pattern or (
            LayerKind(None, self.position_encoding == "rope"),))
        assert self.n_layer % len(self.pattern) == 0, (
            f"n_layer {self.n_layer} is not whole periods of "
            f"{len(self.pattern)} layers")
        assert self.glu_activation in ("silu", "relu")
        assert self.moe_router_input in ("post_attn", "pre_attn")
        self.kv_heads = self.n_kv_head or self.n_head
        assert self.n_head % self.kv_heads == 0, \
            f"n_head {self.n_head} not divisible by n_kv_head {self.kv_heads}"
        self.qkv_dim = (self.n_head + 2 * self.kv_heads) * self.head_dim
        self.ffn_dim = self.intermediate_size or 4 * self.n_embd
        assert self.position_encoding in ("learned", "rope", "alibi")
        assert self.block_type in ("sequential", "parallel", "parallel_single_ln")
        assert self.moe_router in ("gshard", "dropless")
        if self.moe_num_experts > 0:
            most = self.moe_num_experts if self.moe_router == "dropless" else 2
            assert 1 <= self.moe_top_k <= most, (
                f"moe_top_k {self.moe_top_k}: the {self.moe_router} router "
                f"takes 1..{most}")
        assert self.norm in ("layernorm", "rmsnorm")
        assert self.mlp_type in ("standard", "swiglu")
        assert self.moe_scoring in ("softmax", "sigmoid")
        if self.moe_experts_held is not None:
            first, count = self.moe_experts_held
            assert self.moe_router == "dropless" and count >= 1 and \
                0 <= first and first + count <= self.moe_num_experts, (
                    f"moe_experts_held {self.moe_experts_held} of "
                    f"{self.moe_num_experts} experts behind the "
                    f"{self.moe_router} router")
        # (first, count) of the experts the bank holds, whole or a share
        self.bank_experts = self.moe_experts_held or (0, self.moe_num_experts)
        assert 1 <= self.moe_topk_group <= self.moe_n_group and (
            self.moe_n_group == 1 or (
                self.moe_scoring == "sigmoid" and self.moe_router == "dropless"
                and self.moe_num_experts % self.moe_n_group == 0
                and self.moe_num_experts // self.moe_n_group >= 2)), (
                    f"moe_topk_group {self.moe_topk_group} of moe_n_group "
                    f"{self.moe_n_group}: whole groups of two experts or more "
                    f"behind the dropless sigmoid router")
        self.v_head_dim = self.v_head_dim or self.head_dim
        if self.rope_yarn is not None:
            self.rope_yarn = YarnRope(*self.rope_yarn)
        if self.hyper is not None:
            self.hyper = HyperSpec(*self.hyper)
            assert self.hyper.streams >= 2 and self.hyper.sinkhorn_iters >= 1 \
                and self.block_type == "sequential" and self.scan_layers and \
                all(k.mixer == "softmax" for k in self.pattern), (
                    "residual streams: two or more, mixed round the two "
                    "sublayers of a sequential block of the periodic walk "
                    "(the hybrid walk carries one stream)")
        if self.kv_lora_rank:
            assert self.q_lora_rank and self.qk_rope_dim and \
                self.qk_rope_dim < self.head_dim and len(self.pattern) == 1 \
                and self.kv_heads == self.n_head and not self.qk_norm and \
                self.position_encoding == "rope", (
                    "latent attention: q_lora_rank, qk_rope_dim (under "
                    "head_dim), rope, one kind of layer, a head a query head")
        else:
            assert self.v_head_dim == self.head_dim
        # each layer's mixer and feed-forward, in the stack's order
        self.mixers = tuple(k.mixer for k in self.pattern)
        self.ffns = tuple(k.ffn or ("moe" if self.moe_num_experts else "mlp")
                          for k in self.pattern)
        self.hybrid = any(m != "softmax" for m in self.mixers)
        if self.hybrid:
            assert len(self.pattern) == self.n_layer and all(
                m in ("sparse", "linear", "cca", "delta", "mamba", "indexed", "full")
                for m in self.mixers) and set(self.mixers) != {"full"}, (
                    "a hybrid stack names every layer: sparse, linear, cca, "
                    "delta, mamba, indexed, or full beside one of them")
            self.delta_key_heads = self.delta_key_heads or self.delta_heads
            assert "delta" not in self.mixers or (
                self.delta_heads and self.delta_key_dim and self.delta_value_dim
                and self.delta_conv >= 2
                and self.delta_heads % self.delta_key_heads == 0), (
                    "a delta layer's heads and widths, its value heads whole "
                    "groups of a key head's")
            assert "mamba" not in self.mixers or (
                self.mamba_inner and self.mamba_state and self.mamba_dt_rank
                and self.mamba_conv >= 2), "a mamba layer's channels and widths"
            self.indexer = IndexerSpec(*(self.indexer or ()))
            self.sparse = SparseSpec(*(self.sparse or ()))
            sp = self.sparse
            assert sp.kernel == 2 * sp.stride and sp.block % sp.stride == 0 \
                and sp.window % sp.block == 0 and sp.topk * sp.block >= \
                sp.window + (sp.init_blocks + 1) * sp.block, sp
            assert (self.norm == "rmsnorm" and self.mlp_type == "swiglu"
                    and not self.use_bias and not self.kv_lora_rank
                    and self.block_type == "sequential")
            # a hybrid stack's expert layers: no token dropped, behind the
            # MLP router with its stream ("moe") or the linear softmax router
            # ("moe_softmax"); the bank whole or a held share of it
            # (``moe_experts_held``), a shared expert beside it, behind a gate
            # of its own where ``moe_shared_gate``.  Still unwritten in the
            # walk: the sigmoid router with its correction bias and a router
            # that reads the mixer's input (ROADMAP M9 (b))
            banks = [f for f in self.ffns if f != "mlp"]
            assert all(f in ("moe", "moe_softmax") for f in banks), self.ffns
            assert not banks or self.moe_router == "dropless", (
                "a hybrid stack's expert layers: a bank behind a dropless "
                "router")
            assert not self.moe_shared_gate or (banks and self.moe_shared_experts), (
                "moe_shared_gate is the gate of a shared expert beside a bank")
            assert "moe" not in banks or self.moe_router_hidden, (
                "the 'moe' feed-forward is the MLP router with its stream: "
                "moe_router_hidden")
            assert "moe_softmax" not in banks or (
                self.moe_scoring == "softmax" and not self.moe_router_hidden
                and self.moe_router_input == "post_attn"), (
                    "the 'moe_softmax' feed-forward is the linear softmax "
                    "router over the MLP's normed input; the sigmoid router "
                    "and a router before attention are the periodic path's")
            assert not self.norm_after or not banks, (
                "the norm on a sublayer's output is the dense MLP's")
        else:
            assert not self.norm_after and not self.moe_shared_gate, (
                "the norm on a sublayer's output and the shared expert's gate "
                "are read by the hybrid walk (models/hybrid.py) alone")
            assert self.qk_norm in (False, True, "head"), self.qk_norm
            assert not self.norm_sandwich or (
                self.norm == "rmsnorm" and self.block_type == "sequential"), (
                    "norms on both sides of a sublayer: RMSNorm, sequential")
            assert not self.attn_gate or not self.kv_lora_rank
            lead = self.moe_dense_layers
            assert not lead or (
                0 < lead < self.n_layer and self.moe_num_experts
                and self.moe_router == "dropless" and self.scan_layers), (
                    f"moe_dense_layers {lead}: the first layers of a stacked "
                    f"stack of {self.n_layer} behind the dropless router")
            assert not self.moe_router_hidden, (
                "the router's stream is a second carry of the layer walk, "
                "which models/hybrid.py alone has")
            if self.indexer is not None:
                self.indexer = IndexerSpec(*self.indexer)
                assert self.kv_lora_rank, (
                    "an indexer in a periodic stack selects rows of a LATENT "
                    "cache (kv_lora_rank); over K and V heads it is the "
                    "hybrid walk's 'indexed' mixer (LayerKind.mixer)")
                assert self.pattern[0].window is None, (
                    "an indexer over a latent under a window is not written")
                assert self.qk_rope_dim <= self.indexer.head_dim, (
                    "the first qk_rope_dim lanes of an index head are rotated")

    @property
    def indexed_layers(self) -> int:
        """Layers with a lightning indexer, each a page of index keys under
        every block (``models/hybrid.py:init_aux``'s ``ki``): a hybrid
        stack's ``indexed`` layers, or every layer of a periodic stack whose
        latent attention has an ``indexer``."""
        if self.hybrid:
            return self.mixers.count("indexed")
        return self.n_layer if self.indexer is not None else 0

    @property
    def arena_layout(self) -> Tuple[int, int, Tuple[int, ...]]:
        """(layers a page holds, pages a block of ALL of them takes, lanes of
        each array): what ``serving/kv_cache.py:init_arena`` builds.  A layer
        pattern of ``P`` kinds keeps ``P`` groups of ``n_layer / P`` layers;
        a hybrid stack pages the layers of its one mixer that caches K and V
        (``models/hybrid.py:arena_layout``)."""
        if self.hybrid:
            from deepspeed_tpu.models import hybrid
            return hybrid.arena_layout(self)
        P = len(self.pattern)
        return self.n_layer // P, P, self.cache_lanes

    @property
    def page_groups(self) -> Tuple[Optional[int], ...]:
        """The layer groups that own pages, each named by its window (None:
        every key is kept): a group a kind of a periodic pattern; a hybrid
        stack's layers that cache K and V ONE group, the others none."""
        if self.hybrid:
            return (None,)
        return tuple(kind.window for kind in self.pattern)

    @property
    def cache_lanes(self) -> Tuple[int, ...]:
        """THE cache spec: lanes of each array the paged arena holds a token
        a layer (``serving/kv_cache.py:init_arena``).  K and V, the heads
        folded into the lanes; under latent attention ONE array, ``[latent |
        rotated key]`` and zeros up to whole 128-lane tiles (the paged
        kernel stages a page whole and multiplies all its lanes: 320 numbers
        lie in 384 lanes, 768 B a token a layer in bf16 for the 640 cached)."""
        if self.kv_lora_rank:
            return (-(-(self.kv_lora_rank + self.qk_rope_dim) // 128) * 128,)
        return (self.kv_heads * self.head_dim,) * 2

    def paged_plans(self, block_size: int, widths, chunk: int, dtype):
        """The paged attention of each page group (:attr:`page_groups`; its
        table ``widths[group]`` columns wide), a ``ops/pallas/
        decode_attention.py:PagedAttention`` each: THE one place a model's
        fields name a family of kernels.  ``init_serving`` reads its stats
        and the allocator's ``run_blocks`` from the plans
        (:meth:`paged_layout`), and the step
        functions, which are also called without an engine, build them again
        from the shapes of the arena they are handed, the same arguments: a
        plan handed down beside them would be a second way in."""
        from deepspeed_tpu.ops.pallas import decode_attention as da
        H, Hkv, D = self.n_head, self.kv_heads, self.head_dim
        if "sparse" in self.mixers:
            # the sparse layers own the pages (``hybrid.arena_layout``); cca
            # layers' are K and V like any grouped-query model's, and a
            # hybrid stack's ``full`` layers' plain softmax attention's: both
            # land on ``softmax_plan`` below as OLMoE's layers do
            from deepspeed_tpu.models import hybrid
            return (da.chosen_plan(
                Hkv, H // Hkv, D, block_size,
                hybrid.table_columns(self, block_size), dtype),)
        if self.kv_lora_rank:
            return tuple(da.latent_plan(
                self.cache_lanes[0], self.kv_lora_rank, H, block_size, MB,
                chunk, dtype, 1.0 / math.sqrt(D)) for MB in widths)
        return tuple(da.softmax_plan(
            H, Hkv, D, block_size, MB, chunk, dtype,
            bias=self.position_encoding == "alibi", window=window)
            for window, MB in zip(self.page_groups, widths))

    def paged_layout(self, block_size: int, max_blocks_per_seq: int,
                     chunk: int, dtype):
        """-> (``run_blocks``, the groups' table widths, their plans) of an
        engine: the blocks the allocator lays down together are the largest
        ``run_pages`` of the plans (the groups share their lanes, hence the
        tile; a plan that copies page by page says 0), read at the widths of
        single blocks because a window group's ring is as wide as its runs
        want; THE widths (the allocator's, the device tables', the step's
        plans') are ``serving/kv_cache.py:table_widths`` at that tile."""
        from deepspeed_tpu.serving.kv_cache import table_widths
        widths_at = lambda run_blocks: table_widths(
            self.page_groups, max_blocks_per_seq, chunk, block_size, run_blocks)
        plans_at = lambda run_blocks: self.paged_plans(
            block_size, widths_at(run_blocks), chunk, dtype)
        run_blocks = max(1, *(plan.run_pages for plan in plans_at(1)))
        return run_blocks, widths_at(run_blocks), plans_at(run_blocks)


# Model zoo (GPT-2 sizes; the 1.5B "xl" is the north-star model).
GPT_PRESETS: Dict[str, Dict] = {
    "tiny":        dict(vocab_size=512, n_positions=128, n_embd=64, n_layer=2, n_head=4),
    "gpt2":        dict(n_embd=768, n_layer=12, n_head=12),
    "gpt2-medium": dict(n_embd=1024, n_layer=24, n_head=16),
    "gpt2-large":  dict(n_embd=1280, n_layer=36, n_head=20),
    "gpt2-xl":     dict(n_embd=1600, n_layer=48, n_head=25),
}


def gpt_config(preset: str = "gpt2", **overrides) -> GPTConfig:
    kw = dict(GPT_PRESETS[preset])
    kw.update(overrides)
    return GPTConfig(**kw)


def llama_config(vocab_size=32000, n_positions=2048, n_embd=512, n_layer=4,
                 n_head=8, intermediate_size=None, **overrides) -> GPTConfig:
    """LLaMA-style family: RoPE + RMSNorm + SwiGLU, bias-free, untied
    head (the reference serves these via its llama containers)."""
    kw = dict(vocab_size=vocab_size, n_positions=n_positions, n_embd=n_embd,
              n_layer=n_layer, n_head=n_head,
              position_encoding="rope", norm="rmsnorm", mlp_type="swiglu",
              use_bias=False, untied_head=True,
              intermediate_size=intermediate_size or int(n_embd * 8 / 3),
              activation="gelu")
    kw.update(overrides)
    return GPTConfig(**kw)


def olmoe_config(vocab_size=50304, n_positions=4096, n_embd=2048, n_layer=16,
                 n_head=16, intermediate_size=1024, num_experts=64, top_k=8,
                 **overrides) -> GPTConfig:
    """OLMoE family (defaults: OLMoE-1B-7B): the LLaMA-style block with an
    RMSNorm over the whole q and k projections, and every MLP a bank of
    SwiGLU experts ``intermediate_size`` wide behind a dropless softmax
    top-k router; SiLU, no bias, untied head."""
    kw = dict(qk_norm=True, moe_num_experts=num_experts, moe_top_k=top_k,
              moe_router="dropless")
    kw.update(overrides)
    return llama_config(vocab_size=vocab_size, n_positions=n_positions,
                        n_embd=n_embd, n_layer=n_layer, n_head=n_head,
                        intermediate_size=intermediate_size, **kw)


def smallthinker_config(vocab_size=151936, n_positions=16384, n_embd=2560,
                        n_layer=52, n_head=28, n_kv_head=4, head_dim=128,
                        intermediate_size=768, num_experts=64, top_k=6,
                        window=4096, **overrides) -> GPTConfig:
    """SmallThinker family (defaults: SmallThinker-21B-A3B): a period of four
    layers, the first full causal attention WITHOUT position encoding, the
    other three attention over a ``window`` of keys with rope; grouped K/V
    heads of a width that is not ``n_embd // n_head``; every MLP a bank of
    ReGLU experts behind a dropless router that reads the input ATTENTION
    reads and weighs its ``top_k`` by the softmax over their own logits;
    RMSNorm (eps 1e-6), no bias, untied head, rope theta 1.5e6."""
    kw = dict(n_kv_head=n_kv_head, head_dim=head_dim, rope_theta=1.5e6,
              ln_eps=1e-6, glu_activation="relu",
              layer_pattern=(LayerKind(None, False),) + 3 * (LayerKind(window, True),),
              moe_num_experts=num_experts, moe_top_k=top_k,
              moe_router="dropless", moe_norm_topk=True,
              moe_router_input="pre_attn")
    kw.update(overrides)
    return llama_config(vocab_size=vocab_size, n_positions=n_positions,
                        n_embd=n_embd, n_layer=n_layer, n_head=n_head,
                        intermediate_size=intermediate_size, **kw)


def mistral4_config(vocab_size=131072, n_positions=1048576, n_embd=4096,
                    n_layer=36, n_head=32, head_dim=128, q_lora_rank=1024,
                    kv_lora_rank=256, qk_rope_dim=64, v_head_dim=128,
                    intermediate_size=2048, num_experts=128, top_k=4,
                    shared_experts=1, experts_held=None,
                    rope_yarn=(128.0, 8192, 32.0, 1.0, 1.0, 1.0, 0.1),
                    **overrides) -> GPTConfig:
    """Mistral 4 family (defaults: Mistral-Small-4-119B; the DeepSeek-V3
    line's layer): latent attention (a low-rank query with an RMSNorm inside,
    ONE normed latent and ONE rotated key a token for keys and values, rope
    on the last ``qk_rope_dim`` lanes of a head in interleaved pairs, YaRN
    frequencies and scales), and every MLP a bank of SwiGLU experts behind a
    dropless sigmoid router (the ``top_k`` largest of score + bias, weighed
    by their scores renormalised) beside ``shared_experts`` every token goes
    through; ``experts_held = (first, count)`` keeps one chip's share of the
    bank.  RMSNorm (eps 1e-6), no bias, untied head."""
    kw = dict(head_dim=head_dim, q_lora_rank=q_lora_rank,
              kv_lora_rank=kv_lora_rank, qk_rope_dim=qk_rope_dim,
              v_head_dim=v_head_dim, rope_interleaved=True,
              rope_yarn=tuple(rope_yarn), ln_eps=1e-6,
              moe_num_experts=num_experts, moe_top_k=top_k,
              moe_router="dropless", moe_scoring="sigmoid",
              moe_norm_topk=True, moe_shared_experts=shared_experts,
              moe_experts_held=tuple(experts_held) if experts_held else None)
    kw.update(overrides)
    return llama_config(vocab_size=vocab_size, n_positions=n_positions,
                        n_embd=n_embd, n_layer=n_layer, n_head=n_head,
                        intermediate_size=intermediate_size, **kw)


def trinity_config(vocab_size=200192, n_positions=262144, n_embd=3072,
                   n_layer=60, n_head=48, n_kv_head=8, head_dim=128,
                   intermediate_size=12288, moe_intermediate_size=3072,
                   num_experts=256, top_k=4, shared_experts=1, dense_layers=6,
                   route_scale=2.448, window=4096, experts_held=None,
                   published_layers=60, **overrides) -> GPTConfig:
    """Trinity family (``model_type`` afmoe; defaults: Trinity-Large-Preview):
    a period of four layers, three attention over a ``window`` of keys with
    rope and then one full causal attention WITHOUT position encoding;
    grouped K/V heads, an RMSNorm a head on q and k, a sigmoid output gate
    before ``W_o``; a norm on each sublayer's input AND output; the first
    ``dense_layers`` layers a dense SwiGLU ``intermediate_size`` wide, every
    later one a bank of SwiGLU experts ``moe_intermediate_size`` wide behind a
    dropless sigmoid router (the ``top_k`` largest of score + bias, weighed
    by their scores renormalised times ``route_scale``) beside
    ``shared_experts`` every token goes through; ``experts_held = (first,
    count)`` keeps one chip's share of the bank.  The embedding times
    ``sqrt(n_embd)`` (``mup_enabled``); RMSNorm (eps 1e-5), no bias, untied
    head, rope theta 10,000.  The norm on attention's output is seeded at
    ``1 / sqrt(published_layers)`` (the family's depth-scaled sandwich norm,
    as far as seeded routing needs it: ``post_attn_gain``), every other gain
    at 1.  Served through ``init_serving()``."""
    kw = dict(n_kv_head=n_kv_head, head_dim=head_dim, ln_eps=1e-5,
              layer_pattern=3 * (LayerKind(window, True),) + (LayerKind(None, False),),
              qk_norm="head", attn_gate=True, norm_sandwich=True,
              post_attn_gain=1.0 / math.sqrt(published_layers),
              published_layers=published_layers, scale_emb=math.sqrt(n_embd),
              moe_num_experts=num_experts, moe_top_k=top_k,
              moe_expert_hidden=moe_intermediate_size,
              moe_dense_layers=dense_layers, moe_router="dropless",
              moe_scoring="sigmoid", moe_norm_topk=True,
              moe_route_scale=route_scale, moe_shared_experts=shared_experts,
              moe_experts_held=tuple(experts_held) if experts_held else None)
    kw.update(overrides)
    return llama_config(vocab_size=vocab_size, n_positions=n_positions,
                        n_embd=n_embd, n_layer=n_layer, n_head=n_head,
                        intermediate_size=intermediate_size, **kw)


def deepseek_v32_config(vocab_size=129280, n_positions=163840, n_embd=7168,
                        n_layer=61, n_head=128, head_dim=192, q_lora_rank=1536,
                        kv_lora_rank=512, qk_rope_dim=64, v_head_dim=128,
                        intermediate_size=18432, moe_intermediate_size=2048,
                        num_experts=256, top_k=8, n_group=8, topk_group=4,
                        shared_experts=1, dense_layers=3, route_scale=2.5,
                        experts_held=None, indexer=(64, 128, 2048),
                        rope_yarn=(40.0, 4096, 32.0, 1.0, 1.0, 1.0, 0.0),
                        **overrides) -> GPTConfig:
    """DeepSeek-V3.2-Exp family (``model_type`` deepseek_v32; defaults: the
    published 671B-A37B): Mistral 4's layer (:func:`mistral4_config`: latent
    attention, ``head_dim`` a head's key lanes, the last ``qk_rope_dim``
    rotated in interleaved pairs under YaRN) under DeepSeek Sparse Attention:
    a lightning ``indexer`` (heads, lanes a head, ``topk``:
    :class:`IndexerSpec`) whose queries come from the QUERY'S LATENT scores
    every cached token against ONE index key a token (a LayerNorm, the first
    ``qk_rope_dim`` lanes rotated in half-split pairs), and the 128 heads
    attend the ``topk`` rows of the latent cache it scores highest, in the
    absorbed form; the first ``dense_layers`` layers a dense SwiGLU
    ``intermediate_size`` wide, every later one a bank of SwiGLU experts
    ``moe_intermediate_size`` wide behind a dropless sigmoid router limited
    to ``topk_group`` of ``n_group`` groups (the ``top_k`` largest of score +
    bias among them, weighed by their scores renormalised times
    ``route_scale``) beside ``shared_experts`` every token goes through;
    ``experts_held = (first, count)`` keeps one chip's share of the bank.
    RMSNorm (eps 1e-6), no bias, untied head.  No multi-token prediction
    module.  Served through ``init_serving()``; the dense paths refuse it."""
    kw = dict(head_dim=head_dim, q_lora_rank=q_lora_rank,
              kv_lora_rank=kv_lora_rank, qk_rope_dim=qk_rope_dim,
              v_head_dim=v_head_dim, rope_interleaved=True,
              rope_yarn=tuple(rope_yarn), ln_eps=1e-6,
              indexer=tuple(indexer) if indexer else None,
              moe_num_experts=num_experts, moe_top_k=top_k,
              moe_expert_hidden=moe_intermediate_size,
              moe_dense_layers=dense_layers, moe_router="dropless",
              moe_scoring="sigmoid", moe_norm_topk=True,
              moe_route_scale=route_scale, moe_n_group=n_group,
              moe_topk_group=topk_group, moe_shared_experts=shared_experts,
              moe_experts_held=tuple(experts_held) if experts_held else None)
    kw.update(overrides)
    return llama_config(vocab_size=vocab_size, n_positions=n_positions,
                        n_embd=n_embd, n_layer=n_layer, n_head=n_head,
                        intermediate_size=intermediate_size, **kw)


def xing4_config(vocab_size=131072, n_positions=262144, n_embd=3584, n_layer=40,
                 n_head=32, q_lora_rank=768, intermediate_size=9216,
                 moe_intermediate_size=1024, num_experts=64, top_k=4,
                 dense_layers=2, route_scale=2.0,
                 rope_yarn=(64.0, 4096, 32.0, 1.0, 1.0, 1.0, 0.0),
                 hyper=(4, 20, 1e-6, -30.0, 30.0), **overrides) -> GPTConfig:
    """Xing4.0 family (``model_type`` xing4_0; defaults: Xing4.0-29B-A4B):
    :func:`deepseek_v32_config`'s layer WITHOUT the indexer and without router
    groups (latent attention over every cached key, the first ``dense_layers``
    layers a dense SwiGLU, every later one a bank behind the sigmoid router
    beside a shared expert) on ``hyper`` residual streams mixed by
    manifold-constrained hyper-connections round both sublayers
    (:class:`HyperSpec`: streams, Sinkhorn iterations, eps, the clamp).  No
    multi-token prediction module.  Served through ``init_serving()``; the
    dense paths refuse it."""
    kw = dict(indexer=None, n_group=1, topk_group=1, hyper=tuple(hyper))
    kw.update(overrides)
    return deepseek_v32_config(
        vocab_size=vocab_size, n_positions=n_positions, n_embd=n_embd,
        n_layer=n_layer, n_head=n_head, q_lora_rank=q_lora_rank,
        intermediate_size=intermediate_size,
        moe_intermediate_size=moe_intermediate_size, num_experts=num_experts,
        top_k=top_k, dense_layers=dense_layers, route_scale=route_scale,
        rope_yarn=rope_yarn, **kw)


_SALA_MIXERS = {"minicpm4": "sparse", "lightning-attn": "linear"}


def minicpm_sala_config(vocab_size=73448, n_positions=524288, n_embd=4096,
                        n_head=32, n_kv_head=2, head_dim=128,
                        intermediate_size=16384, mixer_types=None,
                        first_layer=0, published_layers=32, scale_emb=12.0,
                        scale_depth=1.4, dim_model_base=256, sparse=(),
                        **overrides) -> GPTConfig:
    """MiniCPM-SALA family (defaults: MiniCPM-SALA 9B's widths): a stack of
    block-sparse attention layers (``"minicpm4"``: ``n_head`` query heads on
    ``n_kv_head`` K/V heads, no rope, the query chooses its own blocks:
    :class:`SparseSpec`) among Lightning linear-attention layers
    (``"lightning-attn"``: ``n_head`` heads whose cache is a ``[head_dim,
    head_dim]`` state, rope, an output norm), in the order ``mixer_types``
    gives and in no period; both with an RMSNorm a head on q and k and a
    sigmoid output gate; a dense SwiGLU MLP; the MiniCPM scalings of the
    embedding (``scale_emb``), of every residual branch (``scale_depth /
    sqrt(published_layers)``) and of the head's input (``n_embd /
    dim_model_base``).  ``mixer_types`` may be a slice of the published
    list, ``first_layer`` the published index of its first entry (a linear
    layer's decay reads its published depth).  RMSNorm (eps 1e-6), no bias,
    untied head, rope theta 10,000.  Served through ``init_serving()``
    (``models/hybrid.py``); the dense paths refuse it."""
    assert mixer_types, "mixer_types: 'minicpm4' or 'lightning-attn' a layer"
    pattern = tuple(
        LayerKind(None, _SALA_MIXERS[m] == "linear", _SALA_MIXERS[m],
                  first_layer + i) for i, m in enumerate(mixer_types))
    kw = dict(n_kv_head=n_kv_head, head_dim=head_dim, ln_eps=1e-6,
              layer_pattern=pattern, sparse=tuple(sparse), scale_emb=scale_emb,
              residual_scale=scale_depth / math.sqrt(published_layers),
              head_divisor=n_embd / dim_model_base,
              published_layers=published_layers)
    kw.update(overrides)
    return llama_config(vocab_size=vocab_size, n_positions=n_positions,
                        n_embd=n_embd, n_layer=len(pattern), n_head=n_head,
                        intermediate_size=intermediate_size, **kw)


def zaya_config(vocab_size=262272, n_positions=131072, n_embd=2048, n_layer=40,
                n_head=8, n_kv_head=2, head_dim=128, intermediate_size=2048,
                num_experts=16, top_k=1, router_hidden=256, cca_time0=2,
                cca_time1=2, partial_rotary_factor=0.5, **overrides) -> GPTConfig:
    """ZAYA1 family (defaults: ZAYA1-8B): every layer attention computed
    inside a convolved latent (``cca``, ``models/hybrid.py:cca_mixer``:
    ``n_head`` query heads on ``n_kv_head`` K/V heads whose packed latents go
    through two causal convolutions over time of ``cca_time0`` and
    ``cca_time1`` taps, half the value lanes the previous token's, rope on
    the first ``partial_rotary_factor`` of a head's lanes) and then a bank of
    SwiGLU experts with ``top_k`` a token behind an MLP router
    ``router_hidden`` wide that carries a stream of its own from layer to
    layer.  RMSNorm (eps 1e-5), no bias but the convolutions', the head tied
    to the embedding, rope theta 5e6.  Served through ``init_serving()``
    (``models/hybrid.py``); the dense paths refuse it."""
    assert (cca_time0, cca_time1) == (2, 2), (
        "the cca mixer is written for two taps a convolution: a slot keeps "
        "the last cca_time0 + cca_time1 - 2 = 2 packed latents")
    kw = dict(n_kv_head=n_kv_head, head_dim=head_dim, rope_theta=5e6,
              rope_dim=int(head_dim * partial_rotary_factor), untied_head=False,
              layer_pattern=n_layer * (LayerKind(None, True, "cca", ffn="moe"),),
              moe_num_experts=num_experts, moe_top_k=top_k,
              moe_router="dropless", moe_router_hidden=router_hidden)
    kw.update(overrides)
    return llama_config(vocab_size=vocab_size, n_positions=n_positions,
                        n_embd=n_embd, n_layer=n_layer, n_head=n_head,
                        intermediate_size=intermediate_size, **kw)


# the mixer of a ``layer_types`` entry of the families whose sources name their
# layers so (Olmo-Hybrid, Qwen3-Next)
_LAYER_TYPE_MIXERS = {"linear_attention": "delta", "full_attention": "full"}


def olmo_hybrid_config(vocab_size=100352, n_positions=65536, n_embd=3840,
                       n_head=30, n_kv_head=30, head_dim=128,
                       intermediate_size=11008, layer_types=None,
                       linear_heads=30, linear_key_head_dim=96,
                       linear_value_head_dim=192, linear_conv_kernel_dim=4,
                       linear_allow_neg_eigval=True, **overrides) -> GPTConfig:
    """Olmo-Hybrid family (defaults: Olmo-Hybrid-7B's widths): layers of the
    gated delta rule (``"linear_attention"``: ``linear_heads`` heads whose
    cache is a float32 state ``[linear_key_head_dim, linear_value_head_dim]``
    a slot, corrected by what it returns for the token's key before the
    token is written, the decay and the write strength computed from the
    token, behind a causal convolution of ``linear_conv_kernel_dim`` taps
    over the packed ``[q | k | v]``: ``models/hybrid.py:delta_mixer``) among
    layers of plain softmax attention (``"full_attention"``: ``n_head`` heads
    on ``n_kv_head`` K/V heads, an RMSNorm over the whole q and k
    projections, NO position encoding: the delta layers order the tokens), in
    the order ``layer_types`` gives (a slice of the published list is a
    pipeline stage); the OLMo 2/3 block, each norm on its sublayer's OUTPUT;
    a dense SwiGLU MLP.  RMSNorm (eps 1e-6), no bias, untied head.  Served
    through ``init_serving()`` (``models/hybrid.py``); the dense paths refuse
    it."""
    assert layer_types, "layer_types: 'linear_attention' or 'full_attention' a layer"
    pattern = tuple(LayerKind(None, False, _LAYER_TYPE_MIXERS[t])
                    for t in layer_types)
    kw = dict(n_kv_head=n_kv_head, head_dim=head_dim, ln_eps=1e-6,
              layer_pattern=pattern, qk_norm=True, norm_after=True,
              delta_heads=linear_heads, delta_key_dim=linear_key_head_dim,
              delta_value_dim=linear_value_head_dim,
              delta_conv=linear_conv_kernel_dim,
              delta_neg_eigval=linear_allow_neg_eigval)
    kw.update(overrides)
    return llama_config(vocab_size=vocab_size, n_positions=n_positions,
                        n_embd=n_embd, n_layer=len(pattern), n_head=n_head,
                        intermediate_size=intermediate_size, **kw)


def jamba_config(vocab_size=65536, n_positions=262144, n_embd=2560, n_layer=28,
                 n_head=20, n_kv_head=1, head_dim=128, intermediate_size=8192,
                 attn_layer_period=14, attn_layer_offset=7, mamba_expand=2,
                 mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=160,
                 **overrides) -> GPTConfig:
    """Jamba family, its dense members (defaults: AI21-Jamba2-3B's widths):
    layer ``i`` plain softmax attention where ``i % attn_layer_period ==
    attn_layer_offset`` (``n_head`` query heads on ``n_kv_head`` K/V heads, NO
    position encoding, no q/k norm: the Mamba layers order the tokens) and
    every other layer a Mamba-1 mixer (``mamba_expand * n_embd`` channels
    behind a causal convolution of ``mamba_d_conv`` taps with a bias, each
    channel a recurrence over ``mamba_d_state`` states whose step comes up
    from ``mamba_dt_rank`` lanes, the family's three inner norms on the step,
    the input and the output weights: ``models/hybrid.py:mamba_mixer``);
    pre-norm blocks, every feed-forward a dense SwiGLU MLP (``num_experts``
    1).  RMSNorm (eps 1e-6), no bias but the convolution's and the step's,
    the head tied to the embedding.  Served through ``init_serving()``
    (``models/hybrid.py``); the dense paths refuse it."""
    pattern = tuple(
        LayerKind(None, False, "full" if i % attn_layer_period == attn_layer_offset
                  else "mamba") for i in range(n_layer))
    kw = dict(n_kv_head=n_kv_head, head_dim=head_dim, ln_eps=1e-6,
              layer_pattern=pattern, untied_head=False,
              mamba_inner=mamba_expand * n_embd, mamba_state=mamba_d_state,
              mamba_conv=mamba_d_conv, mamba_dt_rank=mamba_dt_rank)
    kw.update(overrides)
    return llama_config(vocab_size=vocab_size, n_positions=n_positions,
                        n_embd=n_embd, n_layer=n_layer, n_head=n_head,
                        intermediate_size=intermediate_size, **kw)


def keye_vl2_config(vocab_size=151936, n_positions=262144, n_embd=2048,
                    n_layer=48, n_head=32, n_kv_head=4, head_dim=128,
                    intermediate_size=768, num_experts=128, top_k=8,
                    indexer=(16, 64, 2048), **overrides) -> GPTConfig:
    """Keye-VL-2.0 family, the language model (defaults: Keye-VL-2.0-30B-A3B's
    widths): every layer grouped-query attention (``n_head`` query heads on
    ``n_kv_head`` K/V heads, an RMSNorm over each head's lanes of q and of k,
    rope over all lanes) under DeepSeek Sparse Attention: a lightning
    ``indexer`` (heads, lanes a head, ``topk``: :class:`IndexerSpec`) scores
    every cached token and the query attends the ``topk`` TOKENS it scores
    highest (``models/hybrid.py:indexed_mixer``); then a bank of
    ``num_experts`` SwiGLU experts ``intermediate_size`` wide, ``top_k`` a
    token renormalised behind a linear softmax router, no shared expert, no
    token dropped.  RMSNorm (eps 1e-6), no bias, untied head, rope theta 1e7
    (text positions: the three streams of ``mrope_section`` are one).  Served
    through ``init_serving()`` (``models/hybrid.py``); the dense paths refuse
    it."""
    kw = dict(n_kv_head=n_kv_head, head_dim=head_dim, ln_eps=1e-6,
              rope_theta=1e7, indexer=tuple(indexer),
              layer_pattern=n_layer * (
                  LayerKind(None, True, "indexed", ffn="moe_softmax"),),
              moe_num_experts=num_experts, moe_top_k=top_k,
              moe_router="dropless", moe_norm_topk=True)
    kw.update(overrides)
    return llama_config(vocab_size=vocab_size, n_positions=n_positions,
                        n_embd=n_embd, n_layer=n_layer, n_head=n_head,
                        intermediate_size=intermediate_size, **kw)


def qwen3_next_config(vocab_size=151936, n_positions=262144, n_embd=2048,
                      n_head=16, n_kv_head=2, head_dim=256,
                      intermediate_size=5120, layer_types=None,
                      partial_rotary_factor=0.25, linear_heads=32,
                      linear_key_heads=16, linear_key_head_dim=128,
                      linear_value_head_dim=128, linear_conv_kernel_dim=4,
                      num_experts=512, top_k=10, moe_intermediate_size=512,
                      shared_expert_intermediate_size=512, experts_held=None,
                      **overrides) -> GPTConfig:
    """Qwen3-Next family (``model_type`` qwen3_next; defaults:
    Qwen3-Next-80B-A3B's widths): layers of the gated delta rule
    (``"linear_attention"``: ``linear_heads`` VALUE heads, each a float32
    state ``[linear_key_head_dim, linear_value_head_dim]`` a slot, on
    ``linear_key_heads`` heads of q and k, a key head's serving the value
    heads ``j`` with ``j // (linear_heads / linear_key_heads)`` its index;
    the write strength ``sigmoid(b)``; a causal convolution of
    ``linear_conv_kernel_dim`` taps over the packed ``[q | k | v]``:
    ``models/hybrid.py:delta_mixer``) among layers of gated softmax attention
    (``"full_attention"``: ``n_head`` query heads on ``n_kv_head`` K/V heads,
    an RMSNorm over each HEAD's lanes of q and of k, rope on the first
    ``partial_rotary_factor`` of a head's lanes, the output under a sigmoid
    gate a lane before ``W_o``), in the order ``layer_types`` gives (a slice
    of the published list, three ``linear_attention`` to every
    ``full_attention``, is a pipeline stage); pre-norm blocks; EVERY
    feed-forward a bank of ``num_experts`` SwiGLU experts
    ``moe_intermediate_size`` wide, ``top_k`` a token renormalised behind a
    linear softmax router, no token dropped, beside ONE shared expert
    ``shared_expert_intermediate_size`` wide behind a sigmoid gate of its
    own; ``experts_held = (first, count)`` keeps one chip's share of the
    bank.  RMSNorm (eps 1e-6; the family's zero-centred gain ``1 + w`` is
    kept as the gain ``g``), no bias, untied head, rope theta 1e7.  No
    multi-token-prediction module.  Served through ``init_serving()``
    (``models/hybrid.py``); the dense paths refuse it."""
    assert layer_types, "layer_types: 'linear_attention' or 'full_attention' a layer"
    assert shared_expert_intermediate_size % moe_intermediate_size == 0, (
        "the shared expert is whole experts wide (moe_shared_experts)")
    pattern = tuple(LayerKind(None, True, _LAYER_TYPE_MIXERS[t], ffn="moe_softmax")
                    for t in layer_types)
    kw = dict(n_kv_head=n_kv_head, head_dim=head_dim, ln_eps=1e-6,
              rope_theta=1e7, rope_dim=int(head_dim * partial_rotary_factor),
              layer_pattern=pattern, qk_norm="head", attn_gate=True,
              delta_heads=linear_heads, delta_key_heads=linear_key_heads,
              delta_key_dim=linear_key_head_dim,
              delta_value_dim=linear_value_head_dim,
              delta_conv=linear_conv_kernel_dim,
              moe_num_experts=num_experts, moe_top_k=top_k,
              moe_expert_hidden=moe_intermediate_size,
              moe_router="dropless", moe_norm_topk=True,
              moe_shared_experts=(shared_expert_intermediate_size
                                  // moe_intermediate_size),
              moe_shared_gate=True,
              moe_experts_held=tuple(experts_held) if experts_held else None)
    kw.update(overrides)
    return llama_config(vocab_size=vocab_size, n_positions=n_positions,
                        n_embd=n_embd, n_layer=len(pattern), n_head=n_head,
                        intermediate_size=intermediate_size, **kw)


def bloom_config(vocab_size=250880, n_positions=2048, n_embd=512, n_layer=4,
                 n_head=8, **overrides) -> GPTConfig:
    """BLOOM family: ALiBi positions, GELU MLP, tied embeddings
    (reference ``model_implementations/transformers/ds_bloom.py``)."""
    kw = dict(vocab_size=vocab_size, n_positions=n_positions, n_embd=n_embd,
              n_layer=n_layer, n_head=n_head,
              position_encoding="alibi", activation="gelu_tanh")
    kw.update(overrides)
    return GPTConfig(**kw)


# --------------------------------------------------------------------------- #
# Parameter construction / partition specs
# --------------------------------------------------------------------------- #
def _dense_init(rng, fan_in, shape, scale=0.02):
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(jnp.float32)


def _proj_scale(cfg: GPTConfig) -> float:
    """GPT-2 init: the residual projections scaled by 1/sqrt(2L)."""
    return 0.02 / math.sqrt(2 * cfg.n_layer)


def _init_attn(cfg: GPTConfig, rng: Array) -> Dict:
    """One block's leaves outside its feed-forward: attention and the norms."""
    E = cfg.n_embd
    ks = jax.random.split(rng, 4)
    out = {
        "ln1_g": jnp.ones((E,), jnp.float32),
        "ln1_b": jnp.zeros((E,), jnp.float32),
        "qkv_w": _dense_init(ks[0], E, (E, cfg.qkv_dim)),
        "qkv_b": jnp.zeros((cfg.qkv_dim,), jnp.float32),
        "out_w": _dense_init(ks[1], E, (cfg.n_head * cfg.v_head_dim, E),
                             scale=_proj_scale(cfg)),
        "out_b": jnp.zeros((E,), jnp.float32),
        "ln2_g": jnp.ones((E,), jnp.float32),
        "ln2_b": jnp.zeros((E,), jnp.float32),
    }
    if cfg.qk_norm == "head":
        out["q_norm_g"] = jnp.ones((cfg.head_dim,), jnp.float32)
        out["k_norm_g"] = jnp.ones((cfg.head_dim,), jnp.float32)
    elif cfg.qk_norm:
        out["q_norm_g"] = jnp.ones((cfg.n_head * cfg.head_dim,), jnp.float32)
        out["k_norm_g"] = jnp.ones((cfg.kv_heads * cfg.head_dim,), jnp.float32)
    if cfg.attn_gate:
        out["gate_w"] = _dense_init(jax.random.fold_in(rng, 2468), E,
                                    (E, cfg.n_head * cfg.v_head_dim))
    if cfg.norm_sandwich:
        out["post_attn_g"] = jnp.full((E,), cfg.post_attn_gain, jnp.float32)
        out["post_mlp_g"] = jnp.ones((E,), jnp.float32)
    if cfg.hyper is not None:
        for i, sub in enumerate(("attn", "mlp")):
            out.update(_init_hyper(cfg, jax.random.fold_in(rng, 9753 + i), sub))
    if cfg.kv_lora_rank:
        # latent attention: the fused qkv gives way to the two low-rank query
        # projections and the joint K/V down- and up-projection
        Rq, R, H = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.n_head
        ka = jax.random.split(jax.random.fold_in(rng, 4321), 4)
        del out["qkv_w"], out["qkv_b"]
        out.update(
            q_a_w=_dense_init(ka[0], E, (E, Rq)),
            q_a_norm_g=jnp.ones((Rq,), jnp.float32),
            q_b_w=_dense_init(ka[1], Rq, (Rq, H * cfg.head_dim)),
            kv_a_w=_dense_init(ka[2], E, (E, R + cfg.qk_rope_dim)),
            kv_a_norm_g=jnp.ones((R,), jnp.float32),
            kv_b_w=_dense_init(ka[3], R, (R, H * (
                cfg.head_dim - cfg.qk_rope_dim + cfg.v_head_dim))))
        if cfg.indexer is not None:
            # the lightning indexer: index queries from the query's latent;
            # [W_kI | W_w] from the layer's input; the index key's LayerNorm
            ix = cfg.indexer
            ki = jax.random.split(jax.random.fold_in(rng, 8642), 2)
            out.update(
                index_q_w=_dense_init(ki[0], Rq, (Rq, ix.heads * ix.head_dim)),
                index_kw_w=_dense_init(ki[1], E, (E, ix.head_dim + ix.heads)),
                ik_norm_g=jnp.ones((ix.head_dim,), jnp.float32),
                ik_norm_b=jnp.zeros((ix.head_dim,), jnp.float32))
    return out


# what the diagonal of a sublayer's ``Hres`` logits is seeded at, over 0
# elsewhere: near the identity (0.71 on the diagonal after Sinkhorn-Knopp),
# and far enough from it that the streams do mix at seeded weights
HYPER_RES_DIAGONAL = 2.0


def _init_hyper(cfg: GPTConfig, rng: Array, sub: str) -> Dict:
    """The leaves of ONE sublayer's stream maps (:func:`hyper_maps`; ``sub``
    "attn" or "mlp"): ``phi [n E, 2n + n n]`` (columns ``[pre | post | res``
    row by row ``]``) like any matrix, ``alpha [3]`` (pre, post, res) at ``1 /
    (0.02 sqrt(n E))``, at which the token's own part of every logit has a
    deviation of 1, and ``b`` at an even read (``sigmoid = 1 / n``), a unit
    write (``2 sigmoid = 1``) and :data:`HYPER_RES_DIAGONAL`.  Weights like any
    other, trained away from these."""
    n, E = cfg.hyper.streams, cfg.n_embd
    b = jnp.concatenate([
        jnp.full((n,), -math.log(n - 1.0)), jnp.zeros((n,)),
        (HYPER_RES_DIAGONAL * jnp.eye(n)).reshape(-1)]).astype(jnp.float32)
    return {f"hc_{sub}_phi": _dense_init(rng, n * E, (n * E, 2 * n + n * n)),
            f"hc_{sub}_b": b,
            f"hc_{sub}_alpha": jnp.full((3,), 1.0 / (0.02 * math.sqrt(n * E)),
                                        jnp.float32)}


def _init_mlp(cfg: GPTConfig, rng: Array, biases: bool = True) -> Dict:
    """One block's dense MLP, ``ffn_dim`` wide (``biases``: with the two
    bias leaves, which a block holds as zeros even where nothing reads
    them; a dense lead's stack holds them only where ``use_bias``)."""
    E, I = cfg.n_embd, cfg.ffn_dim
    fc_out = 2 * I if cfg.mlp_type == "swiglu" else I   # swiglu fuses gate|up
    ks = jax.random.split(rng, 4)
    out = {"fc_w": _dense_init(ks[2], E, (E, fc_out)),
           "proj_w": _dense_init(ks[3], I, (I, E), scale=_proj_scale(cfg))}
    if biases:
        out.update(fc_b=jnp.zeros((fc_out,), jnp.float32),
                   proj_b=jnp.zeros((E,), jnp.float32))
    return out


def _init_moe(cfg: GPTConfig, rng: Array) -> Dict:
    """One block's gated expert bank (reference moe/layer.py:16): the dense
    fc/proj leaves, stacked over experts, as wi/bi/wo/bo; the router; the
    shared expert."""
    E = cfg.n_embd
    N, Ie = cfg.moe_num_experts, cfg.moe_expert_hidden or cfg.ffn_dim
    km = jax.random.split(jax.random.fold_in(rng, 1234), 3)
    glu = 2 if cfg.mlp_type == "swiglu" else 1
    up = glu * Ie
    G = cfg.bank_experts[1]     # held by the bank; the router stays N wide
    experts = {"wi": _dense_init(km[1], E, (G, E, up)),
               "wo": _dense_init(km[2], Ie, (G, Ie, E), scale=_proj_scale(cfg))}
    if cfg.use_bias:
        experts.update(bi=jnp.zeros((G, up), jnp.float32),
                       bo=jnp.zeros((G, E), jnp.float32))
    moe = {"gate": {"wg": _dense_init(km[0], E, (E, N))}, "experts": experts}
    if cfg.moe_scoring == "sigmoid":
        # the score-correction bias: chooses, never weighs
        moe["gate"]["bias"] = jnp.zeros((N,), jnp.float32)
    if cfg.moe_shared_experts:
        assert not cfg.use_bias, "a shared expert carries no bias"
        Is = cfg.moe_shared_experts * Ie
        ksh = jax.random.split(jax.random.fold_in(rng, 5678), 2)
        moe["shared"] = {
            "wi": _dense_init(ksh[0], E, (E, glu * Is)),
            "wo": _dense_init(ksh[1], Is, (Is, E), scale=_proj_scale(cfg))}
    return moe


def _init_block(cfg: GPTConfig, rng: Array) -> Dict:
    """One transformer block's params: attention, and the feed-forward every
    layer of the stack has (a stack with a dense lead builds its two kinds
    apart: ``init_gpt_params``)."""
    if cfg.moe_num_experts > 0:
        return {**_init_attn(cfg, rng), "moe": _init_moe(cfg, rng)}
    return {**_init_attn(cfg, rng), **_init_mlp(cfg, rng)}


def _init_embed(cfg: GPTConfig, rng: Array) -> Dict:
    ks = jax.random.split(rng, 2)
    out = {"wte": _dense_init(ks[0], cfg.padded_vocab, (cfg.padded_vocab, cfg.n_embd))}
    if cfg.position_encoding == "learned":
        out["wpe"] = _dense_init(ks[1], cfg.n_positions,
                                 (cfg.n_positions, cfg.n_embd), scale=0.01)
    return out


def init_gpt_params(cfg: GPTConfig, rng: Array) -> Dict:
    """Parameter pytree.  Block params are stacked ``[n_layer, ...]`` when
    ``scan_layers`` (matching the lax.scan body)."""
    k_embed, k_blocks = jax.random.split(rng)
    E, L = cfg.n_embd, cfg.n_layer

    if cfg.hybrid:
        from deepspeed_tpu.models import hybrid
        blocks = hybrid.init_blocks(cfg, k_blocks)
    elif cfg.scan_layers:
        # an expert bank is built a layer at a time (lax.map): the random
        # bits of one OLMoE layer are a gigabyte, and a caller that casts
        # the tree inside the same jit then never holds all layers in fp32
        over_layers = jax.lax.map if cfg.moe_num_experts > 0 else (
            lambda f, keys: jax.vmap(f)(keys))
        keys, lead = jax.random.split(k_blocks, L), cfg.moe_dense_layers
        if lead:
            # the feed-forward by kind beside one stack of attention leaves
            blocks = dict(over_layers(partial(_init_attn, cfg), keys),
                          lead=over_layers(partial(_init_mlp, cfg, biases=cfg.use_bias),
                                           keys[:lead]),
                          moe=over_layers(partial(_init_moe, cfg), keys[lead:]))
        else:
            blocks = over_layers(partial(_init_block, cfg), keys)
    else:
        blocks = {f"h{i}": _init_block(cfg, k)
                  for i, k in enumerate(jax.random.split(k_blocks, L))}
    embed = _init_embed(cfg, k_embed)
    params = {
        "wte": embed["wte"],
        "blocks": blocks,
        "lnf_g": jnp.ones((E,), jnp.float32),
        "lnf_b": jnp.zeros((E,), jnp.float32),
    }
    if "wpe" in embed:
        params["wpe"] = embed["wpe"]
    if cfg.untied_head:
        params["lm_head"] = _dense_init(
            jax.random.fold_in(k_embed, 2), E, (cfg.padded_vocab, E))
        if cfg.head_bias:
            params["lm_head_b"] = jnp.zeros((cfg.padded_vocab,), jnp.float32)
    return params


_BLOCK_SPECS = {
    # Megatron TP: column-parallel QKV/fc (shard output dim), row-parallel
    # out/proj (shard input dim); biases of column-parallel layers sharded.
    "ln1_g": PartitionSpec(), "ln1_b": PartitionSpec(),
    "qkv_w": PartitionSpec(None, "tensor"), "qkv_b": PartitionSpec("tensor"),
    "out_w": PartitionSpec("tensor", None), "out_b": PartitionSpec(),
    "ln2_g": PartitionSpec(), "ln2_b": PartitionSpec(),
    "fc_w": PartitionSpec(None, "tensor"), "fc_b": PartitionSpec("tensor"),
    "proj_w": PartitionSpec("tensor", None), "proj_b": PartitionSpec(),
}


def gpt_partition_specs(cfg: GPTConfig) -> Dict:
    """Logical (tensor-parallel) PartitionSpecs matching ``init_gpt_params``.

    The ZeRO policy composes the ``fsdp`` axis on top of these
    (``runtime/zero/policy.py:zero_partition_spec``) — stage-3 + TP gives
    2-D sharded weights, the TPU analogue of Megatron+ZeRO.
    """
    def block_specs(stacked: bool):
        pre = (None,) if stacked else ()
        keys = dict(_BLOCK_SPECS)
        if cfg.moe_num_experts > 0:
            for k in ("fc_w", "fc_b", "proj_w", "proj_b"):
                del keys[k]
        if cfg.qk_norm:
            keys.update(q_norm_g=PartitionSpec(), k_norm_g=PartitionSpec())
        if cfg.attn_gate:
            keys["gate_w"] = PartitionSpec(None, "tensor")
        if cfg.norm_sandwich:
            keys.update(post_attn_g=PartitionSpec(), post_mlp_g=PartitionSpec())
        if cfg.kv_lora_rank:
            # the up-projections make heads: column-parallel; the two
            # down-projections are shared by all heads
            del keys["qkv_w"], keys["qkv_b"]
            keys.update(q_a_w=PartitionSpec(None, None),
                        q_a_norm_g=PartitionSpec(),
                        q_b_w=PartitionSpec(None, "tensor"),
                        kv_a_w=PartitionSpec(None, None),
                        kv_a_norm_g=PartitionSpec(),
                        kv_b_w=PartitionSpec(None, "tensor"))
            if cfg.indexer is not None:
                keys.update(index_q_w=PartitionSpec(None, None),
                            index_kw_w=PartitionSpec(None, None),
                            ik_norm_g=PartitionSpec(), ik_norm_b=PartitionSpec())
        if cfg.hyper is not None:
            for sub in ("attn", "mlp"):     # a token's maps read all its lanes
                keys.update({f"hc_{sub}_phi": PartitionSpec(None, None),
                             f"hc_{sub}_b": PartitionSpec(),
                             f"hc_{sub}_alpha": PartitionSpec()})
        specs = {k: PartitionSpec(*pre, *s) for k, s in keys.items()}
        if cfg.moe_dense_layers:
            # the dense lead's MLP, a stack of its own
            specs["lead"] = {k: PartitionSpec(*pre, *_BLOCK_SPECS[k])
                             for k in ("fc_w", "fc_b", "proj_w", "proj_b")
                             if cfg.use_bias or k.endswith("_w")}
        if cfg.moe_num_experts > 0:
            experts = {"wi": PartitionSpec(*pre, "expert", None, "tensor"),
                       "wo": PartitionSpec(*pre, "expert", "tensor", None)}
            if cfg.use_bias:
                experts.update(bi=PartitionSpec(*pre, "expert", "tensor"),
                               bo=PartitionSpec(*pre, "expert", None))
            specs["moe"] = {"gate": {"wg": PartitionSpec(*pre)},
                            "experts": experts}
            if cfg.moe_scoring == "sigmoid":
                specs["moe"]["gate"]["bias"] = PartitionSpec(*pre)
            if cfg.moe_shared_experts:
                specs["moe"]["shared"] = {
                    "wi": PartitionSpec(*pre, None, "tensor"),
                    "wo": PartitionSpec(*pre, "tensor", None)}
        return specs

    if cfg.hybrid:
        from deepspeed_tpu.models import hybrid
        blocks = hybrid.block_partition_specs(cfg)
    elif cfg.scan_layers:
        blocks = block_specs(True)
    else:
        blocks = {f"h{i}": block_specs(False) for i in range(cfg.n_layer)}
    specs = {
        "wte": PartitionSpec("tensor", None),   # vocab-parallel embedding
        "blocks": blocks,
        "lnf_g": PartitionSpec(),
        "lnf_b": PartitionSpec(),
    }
    if cfg.position_encoding == "learned":
        specs["wpe"] = PartitionSpec()
    if cfg.untied_head:
        specs["lm_head"] = PartitionSpec("tensor", None)
        if cfg.head_bias:
            specs["lm_head_b"] = PartitionSpec("tensor")
    return specs


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #
_constrain = mesh_lib.constrain


def _activation(x: Array, kind: str) -> Array:
    if kind == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if kind == "relu":
        return jax.nn.relu(x)
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu_quick":       # CLIP's quick_gelu: x * sigmoid(1.702x)
        return x * jax.nn.sigmoid(1.702 * x)
    raise ValueError(f"unknown activation {kind!r}")


def rms_norm(x: Array, g: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (xf * g.astype(jnp.float32)).astype(x.dtype)


def _norm(cfg: "GPTConfig", x: Array, g: Array, b: Array) -> Array:
    if cfg.norm == "rmsnorm":
        return rms_norm(x, g, eps=cfg.ln_eps)
    return layer_norm(x, g, b, eps=cfg.ln_eps)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature for a stretch of ``factor``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(rd: int, theta: float, yarn: YarnRope) -> Array:
    """``[rd / 2]`` turns a position of the ``rd`` rotated lanes under YaRN:
    pair ``i`` keeps ``theta^(-2i/rd)`` below the correction dimension of
    ``beta_fast`` (it turns often enough in the original range), takes it
    over ``factor`` above that of ``beta_slow``, and blends the two on the
    linear ramp between.  Worked out on the host in float64 and rounded
    once: the chip's float32 ``pow`` is good to about 1e-6, which 30,000
    positions on turn into 0.03 rad of the fastest pairs (PERF.md section 6,
    PR 61: the program in float32 then read a noise scale of 0.46 against
    its reference there, and 0.0 under 5,000 positions)."""
    import numpy as np
    half = rd // 2
    i = np.arange(half, dtype=np.float64)
    base = float(theta) ** (-i / half)
    # the pair that makes ``turns`` turns over the original positions
    dim_of = lambda turns: rd * math.log(
        yarn.original_positions / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), rd - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return jnp.asarray(base / yarn.factor * ramp + base * (1.0 - ramp), jnp.float32)


def apply_rope(x: Array, positions: Array, theta: float = 10000.0,
               rope_dim: Optional[int] = None,
               interleaved: bool = False,
               yarn: Optional[YarnRope] = None) -> Array:
    """Rotary position embedding on [B, S, H, D].

    Default: LLaMA/NeoX half-split pairing over the full head dim.
    ``rope_dim`` rotates only the first ``rope_dim`` features (GPT-J
    ``rotary_dim``, NeoX ``rotary_pct``); ``interleaved`` uses GPT-J's
    rotate-every-two pairing ((0,1),(2,3),...).  ``positions`` is ``[S]``
    (shared across the batch) or ``[B, S]`` (per-row — the continuous-
    batching decode path, where every slot sits at its own position).
    ``yarn`` stretches the frequencies (:func:`yarn_inv_freq`) and scales
    cos and sin by its ``mscale`` over ``mscale_all_dim``."""
    B, S, H, D = x.shape
    rd = rope_dim or D
    xr = x[..., :rd].astype(jnp.float32)
    half = rd // 2
    if yarn is None:
        freqs = (1.0 / theta) ** (jnp.arange(half, dtype=jnp.float32) / half)
    else:
        freqs = yarn_inv_freq(rd, theta, yarn)
    angles = positions[..., None].astype(jnp.float32) * freqs   # [(B,) S, half]
    cos = jnp.cos(angles)
    sin = jnp.sin(angles)
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        cos, sin = cos * m, sin * m
    if angles.ndim == 2:            # [S, half] -> broadcast over batch
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:                           # [B, S, half] -> per-row positions
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    if interleaved:
        x1, x2 = xr[..., 0::2], xr[..., 1::2]
        rot = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                        axis=-1).reshape(xr.shape)
    else:
        x1, x2 = xr[..., :half], xr[..., half:]
        rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                              axis=-1)
    if rd == D:
        return rot.astype(x.dtype)
    return jnp.concatenate([rot.astype(x.dtype), x[..., rd:]], axis=-1)


def _split_qkv(cfg: "GPTConfig", qkv: Array):
    """[B, S, qkv_dim] → q [B,S,H,D], k/v [B,S,Hkv,D] (GQA-aware)."""
    B, S = qkv.shape[:2]
    H, Hkv, D = cfg.n_head, cfg.kv_heads, cfg.head_dim
    q, k, v = jnp.split(qkv, [H * D, (H + Hkv) * D], axis=-1)
    return (q.reshape(B, S, H, D), k.reshape(B, S, Hkv, D),
            v.reshape(B, S, Hkv, D))


def _project_qkv(cfg: "GPTConfig", p: Dict, h: Array, dt, positions: Array,
                 kind: Optional[LayerKind] = None):
    """The normed input ``h [B, S, E]`` -> q ``[B,S,H,D]``, k and v
    ``[B,S,Hkv,D]``: the fused projection, its bias, the q/k RMSNorm over
    all lanes or over each head's (``qk_norm``), the split into heads, rope
    at ``positions``
    (``[S]`` or ``[B, S]``) where the layer's ``kind`` ropes.  Latent
    attention in its PLAIN form: every head's own key and value made from
    the latent (v ``[B,S,H,v_head_dim]``)."""
    if cfg.kv_lora_rank:
        return _latent_plain_qkv(cfg, p, *_latent_project(cfg, p, h, dt, positions), dt)
    qkv = h @ _wget(p, "qkv_w", dt)
    if cfg.use_bias:
        qkv = qkv + p["qkv_b"].astype(dt)
    if cfg.qk_norm is True:
        nq, nk = cfg.n_head * cfg.head_dim, cfg.kv_heads * cfg.head_dim
        qkv = jnp.concatenate([
            rms_norm(qkv[..., :nq], p["q_norm_g"], eps=cfg.ln_eps),
            rms_norm(qkv[..., nq:nq + nk], p["k_norm_g"], eps=cfg.ln_eps),
            qkv[..., nq + nk:]], axis=-1)
    q, k, v = _split_qkv(cfg, qkv)
    if cfg.qk_norm == "head":
        q = rms_norm(q, p["q_norm_g"], eps=cfg.ln_eps)
        k = rms_norm(k, p["k_norm_g"], eps=cfg.ln_eps)
    if (kind or cfg.pattern[0]).rope:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_dim,
                       cfg.rope_interleaved)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_dim,
                       cfg.rope_interleaved)
    return q, k, v


def _query_latent(cfg: "GPTConfig", p: Dict, h: Array, dt) -> Array:
    """The query's latent ``c^Q = RMSNorm(W_DQ h)`` ``[B, S, q_lora_rank]``:
    what the heads' queries and, under an indexer, the index queries come up
    from."""
    return rms_norm(h @ _wget(p, "q_a_w", dt), p["q_a_norm_g"], eps=cfg.ln_eps)


def _index_project(cfg: "GPTConfig", p: Dict, cq: Array, h: Array, dt,
                   positions: Array):
    """The lightning indexer's projections over a latent layer: -> (index
    queries ``[B, S, heads, lanes]`` from the query's latent ``cq``, the
    token's ONE index key ``[B, S, lanes]`` (a LayerNorm with gain and bias)
    and the heads' weights ``[B, S, heads]`` float32 from the normed input
    ``h``).  The first ``qk_rope_dim`` lanes of an index head and of the key
    are rotated, HALF-SPLIT pairs at the model's own frequencies (the
    attention's rope pairs are interleaved)."""
    ix, B, S = cfg.indexer, *h.shape[:2]
    rope = lambda t: apply_rope(t, positions, cfg.rope_theta,
                                rope_dim=cfg.qk_rope_dim, yarn=cfg.rope_yarn)
    qi = rope(_project(p, "index_q_w", cq, dt).reshape(B, S, ix.heads, ix.head_dim))
    kw = _project(p, "index_kw_w", h, dt)
    ki = layer_norm(kw[..., :ix.head_dim], p["ik_norm_g"], p["ik_norm_b"],
                    eps=cfg.ln_eps)
    return qi, rope(ki[:, :, None])[:, :, 0], kw[..., ix.head_dim:].astype(jnp.float32)


def _latent_project(cfg: "GPTConfig", p: Dict, h: Array, dt, positions: Array,
                    cq: Optional[Array] = None):
    """Latent attention's projections of the normed input ``h [B, S, E]`` ->
    (q ``[B,S,H,head_dim]``: a head's lanes ``[no position | rotated]``,
    cache ``[B,S,kv_lora_rank + qk_rope_dim]``: ``[normed latent | the ONE
    rotated key]``), which is all that either form of the attention reads.
    The softmax's YaRN scale (``mscale_all_dim``: the scores times
    :func:`yarn_mscale` squared) and the query's scale by its position are
    IN q, so both forms divide by ``sqrt(head_dim)`` and nothing else.
    ``cq``: the query's latent where the caller has it (:func:`_query_latent`)."""
    B, S, _ = h.shape
    H, R, dr = cfg.n_head, cfg.kv_lora_rank, cfg.qk_rope_dim
    yarn = cfg.rope_yarn
    rope = lambda t: apply_rope(t, positions, cfg.rope_theta,
                                interleaved=cfg.rope_interleaved, yarn=yarn)
    if cq is None:
        cq = _query_latent(cfg, p, h, dt)
    q = _project(p, "q_b_w", cq, dt).reshape(B, S, H, cfg.head_dim)
    q = jnp.concatenate([q[..., :-dr], rope(q[..., -dr:])], axis=-1)
    if yarn is not None:
        pos = positions if positions.ndim == 2 else positions[None]
        by = yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2 * (
            1.0 + yarn.query_beta * jnp.log1p(
                (pos // yarn.original_positions).astype(jnp.float32)))
        q = (q.astype(jnp.float32) * by[:, :, None, None]).astype(dt)
    kv = _project(p, "kv_a_w", h, dt)
    c = rms_norm(kv[..., :R], p["kv_a_norm_g"], eps=cfg.ln_eps)
    k_rope = rope(kv[..., None, R:])[:, :, 0]
    return q, jnp.concatenate([c, k_rope], axis=-1)


def _latent_up(cfg: "GPTConfig", p: Dict, dt):
    """The K/V up-projection by head: ``W_UK [R, H, head_dim - qk_rope_dim]``
    and ``W_UV [R, H, v_head_dim]``."""
    w = _proj_weight(p, "kv_b_w", dt).reshape(cfg.kv_lora_rank, cfg.n_head, -1)
    return w[..., :cfg.head_dim - cfg.qk_rope_dim], w[..., -cfg.v_head_dim:]


def _latent_plain_qkv(cfg: "GPTConfig", p: Dict, q: Array, cache: Array, dt):
    """The plain form: each head's key ``[its own from the latent | the
    shared rotated key]`` and its value from the latent."""
    R = cfg.kv_lora_rank
    w_uk, w_uv = _latent_up(cfg, p, dt)
    c, k_rope = cache[..., :R], cache[..., None, R:]
    k_nope = jnp.einsum("bsr,rhd->bshd", c, w_uk)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (*k_nope.shape[:3], k_rope.shape[-1]))],
        axis=-1)
    return q, k, jnp.einsum("bsr,rhd->bshd", c, w_uv)


def _wleaf(w, dt) -> Array:
    """A weight leaf as the matmul reads it: an int8-injected one
    (``module_inject/quantization.py``; reference GroupQuantizer +
    ``dequantize.cu``) dequantized, so the same model code serves fp and
    int8 weights."""
    from deepspeed_tpu.module_inject.quantization import (dequantize_weight,
                                                          is_quantized_leaf)
    if is_quantized_leaf(w):
        return dequantize_weight(w, dt)
    return w.astype(dt)


def _wget(p: Dict, key: str, dt) -> Array:
    return _wleaf(p[key], dt)


# The projections of a latent layer as a serving engine keeps them
# (:func:`serving_params`): the canonical leaf ``[L, K, N]`` -> the name of
# the same matrices TRANSPOSED, ``[L, N, K]``
SERVING_LEAVES = {"q_b_w": "q_b_t", "kv_b_w": "kv_b_t", "index_q_w": "index_q_t",
                  "kv_a_w": "kv_a_t", "index_kw_w": "index_kw_t"}


def _proj_weight(p: Dict, key: str, dt) -> Array:
    """A latent layer's projection ``key`` as ``[K, N]``, read where the
    layer's tree holds it: the serving tree's transposed leaf through ``.T``
    (:data:`SERVING_LEAVES`), or the canonical leaf.  One contraction over two
    layouts of one operand.  XLA's dot reads its right operand with the
    contraction dimension minor-most, and where the leaf is a layer of a
    stack read at a traced index it relays the WHOLE stack to get it so, in
    every region that reads it (DeepSeek-V3.2's five layers: 671 MB twice a
    step, PERF.md § 6, PR 67); the serving tree's leaf lies so."""
    if SERVING_LEAVES[key] in p:
        return _wleaf(p[SERVING_LEAVES[key]], dt).T
    return _wget(p, key, dt)


def _project(p: Dict, key: str, x: Array, dt) -> Array:
    """``x @ W`` for a latent layer's projection ``key``
    (:func:`_proj_weight`).  Over the serving tree's leaf the product is held
    whole (a barrier: the identity): its lanes go on to be split (a head's
    rotated lanes from the others, a rope's pairs, the latent from the
    rotated key), which XLA would make a split of the weight's columns,
    copying the layer out of the stack to cut them (what the compiled step
    holds: ``tests/unit/ops/test_chip_compile.py``)."""
    y = x @ _proj_weight(p, key, dt)
    return jax.lax.optimization_barrier(y) if SERVING_LEAVES[key] in p else y


def serving_params(params: Dict) -> Tuple[Dict, Dict[str, int]]:
    """The tree a serving engine keeps of ``params`` -> (the tree, the bytes
    of each canonical leaf it relaid).  A stack's latent projections
    (:data:`SERVING_LEAVES`) give way to their transposes over the last two
    dimensions, made by ONE jitted program; every other leaf is the caller's
    own array, and a tree that holds none of them (no latent; layers not
    stacked) comes back as it is.  An int8-injected leaf stays as it is (its
    scales run along the output channels).  Under a mesh a relaid leaf keeps
    its leaf's ``PartitionSpec``, transposed with it.  The caller's tree is
    not touched: checkpoints, the dense paths and training read the
    canonical layout."""
    blocks = params.get("blocks", {})
    names = [k for k in SERVING_LEAVES if isinstance(blocks.get(k), jax.Array)]
    if not names:
        return params, {}

    def relaid_sharding(a):
        sharding = getattr(a, "sharding", None)
        if not isinstance(sharding, NamedSharding):
            return None
        spec = (*sharding.spec, *(None,) * a.ndim)[:a.ndim]
        return NamedSharding(sharding.mesh,
                             PartitionSpec(*spec[:-2], spec[-1], spec[-2]))

    relaid = jax.jit(
        lambda ws: {SERVING_LEAVES[k]: jnp.swapaxes(w, -1, -2) for k, w in ws.items()},
        out_shardings={SERVING_LEAVES[k]: relaid_sharding(blocks[k]) for k in names})(
            {k: blocks[k] for k in names})
    kept = {k: v for k, v in blocks.items() if k not in names}
    return (dict(params, blocks={**kept, **relaid}),
            {k: blocks[k].size * blocks[k].dtype.itemsize for k in names})


def out_gate(p: Dict, o: Array, h: Array, dt) -> Array:
    """Attention's output under its gate, ``o * sigmoid(h W_g)``: ``o`` the
    heads' outputs side by side, ``h`` the normed input attention read."""
    return o * jax.nn.sigmoid(h @ _wget(p, "gate_w", dt))


def _attn_out(cfg: "GPTConfig", p: Dict, o: Array, h: Array, dt) -> Array:
    """``o [..., H * v_head_dim]`` through the gate where the model has one
    (``attn_gate``), then ``W_o`` and its bias."""
    if cfg.attn_gate:
        with jax.named_scope("attn_gate"):
            o = out_gate(p, o, h, dt)
    o = o @ _wget(p, "out_w", dt)
    if cfg.use_bias:
        o = o + p["out_b"].astype(dt)
    return o


def _mlp(cfg: "GPTConfig", p: Dict, h: Array, dt, matmul=None,
         bias=lambda b: b) -> Array:
    """The block's MLP.  An expert bank runs the same arithmetic on stacked
    leaves: ``matmul(rows, leaf)`` then multiplies each row by its own
    expert's matrix, taking the leaf AS IT IS STORED (it converts what it
    reads, which need not be the whole leaf), and ``bias`` picks each row
    its expert's bias (``_ffn``)."""
    if matmul is None:
        matmul = lambda a, w: a @ _wleaf(w, dt)
    up = matmul(h, p["fc_w"])
    if cfg.use_bias:
        up = up + bias(p["fc_b"]).astype(dt)
    if cfg.mlp_type == "swiglu":
        gate, val = jnp.split(up, 2, axis=-1)
        h = _activation(gate, cfg.glu_activation) * val
    else:
        h = _activation(up, cfg.activation)
    out = matmul(h, p["proj_w"])
    if cfg.use_bias:
        out = out + bias(p["proj_b"]).astype(dt)
    return out


def shared_expert(cfg: "GPTConfig", wi, wo, x: Array, dt, gate_w=None) -> Array:
    """The expert every row goes through beside the routed ones, both walks':
    the block's own MLP on the leaves ``wi`` and ``wo``, and with ``gate_w
    [E, 1]`` behind a gate of its own, ``sigmoid(x . w_s) * Shared(x)`` (the
    gate's logit and the product float32).  The scope ``moe_shared`` holds
    the expert AND its gate."""
    with jax.named_scope("moe_shared"):
        y = _mlp(cfg, {"fc_w": wi, "proj_w": wo}, x, dt)
        if gate_w is not None:
            y = y.astype(jnp.float32) * jax.nn.sigmoid(jnp.dot(
                x, _wleaf(gate_w, dt), preferred_element_type=jnp.float32))
        return y


_EXPERT_LEAVES = {"wi": "fc_w", "bi": "fc_b", "wo": "proj_w", "bo": "proj_b"}


def _ffn(cfg: "GPTConfig", p: Dict, h: Array, dt, rng=None,
         train: bool = False, live: Optional[Array] = None,
         attn_in: Optional[Array] = None,
         bank_at: Optional[Tuple[Dict, Array]] = None
         ) -> Tuple[Array, Array, Optional[Array]]:
    """Dense MLP or top-k gated MoE expert bank (reference ``moe/layer.py:16``),
    by the leaves ``p`` holds: the bank where it carries ``"moe"`` (every
    layer of an MoE model but its dense lead).  Returns ``(y, aux_loss, expert_counts)``:
    on the dense path the aux loss is zero and the counts None; the counts
    (``[experts]`` int32, assignments of this call) leave out the rows
    ``live [tokens]`` marks as carrying no request, and the dropless bank
    computes nothing for them.  ``attn_in`` is the
    normed input attention read, which a ``moe_router_input`` of
    ``pre_attn`` routes by.  The expert bank is ``p["moe"]["experts"]``, one
    layer's, or with ``bank_at = (experts, layer)`` the layer ``layer`` (an
    int32 scalar) of the STACKED leaves ``[L, experts, ...]``, which the
    dropless router's kernel reads where they lie (the paged step; there
    ``p["moe"]`` holds no ``experts``)."""
    if "moe" not in p:
        # a layer of an MoE model's dense lead says so in the trace; a dense
        # model's MLP keeps the names it has
        with jax.named_scope("lead_mlp") if cfg.moe_num_experts else nullcontext():
            return _mlp(cfg, p, h, dt), jnp.zeros((), jnp.float32), None
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.moe.sharded_moe import (moe_dispatch_combine,
                                               top1gating, top2gating)
    E, N = cfg.n_embd, cfg.moe_num_experts
    lead = h.shape[:-1]
    xt = h.reshape(-1, E)
    experts, layer = bank_at or (p["moe"]["experts"], None)
    bank = {_EXPERT_LEAVES[k]: v for k, v in experts.items()}
    with jax.named_scope("moe"):
        with jax.named_scope("moe_router"):
            routed = (attn_in.reshape(-1, E)
                      if cfg.moe_router_input == "pre_attn" else xt)
            logits = routed.astype(jnp.float32) @ p["moe"]["gate"]["wg"].astype(
                jnp.float32)
            if cfg.moe_router == "dropless":
                if cfg.moe_scoring == "sigmoid":
                    probs, weights, experts = dropless.sigmoid_topk(
                        logits, cfg.moe_top_k, p["moe"]["gate"]["bias"],
                        cfg.moe_norm_topk, cfg.moe_route_scale,
                        cfg.moe_n_group, cfg.moe_topk_group)
                else:
                    probs, weights, experts = dropless.softmax_topk(
                        logits, cfg.moe_top_k, cfg.moe_norm_topk)
                l_aux = dropless.load_balance_loss(probs, experts)
                counts = dropless.expert_counts(experts, N, live)
        if cfg.moe_router == "dropless":
            y = dropless.dropless_moe(
                xt, weights, experts, N,
                lambda rows, matmul, pick: _mlp(cfg, bank, rows, dt, matmul, pick),
                held=cfg.moe_experts_held, layer=layer, live=live)
        else:
            assert layer is None, "a stacked bank is the dropless router's"
            cf = cfg.moe_capacity_factor if train else cfg.moe_eval_capacity_factor
            gating = top1gating if cfg.moe_top_k == 1 else top2gating
            l_aux, combine, dispatch, counts = gating(
                logits, capacity_factor=cf, min_capacity=cfg.moe_min_capacity,
                noise_rng=rng if train else None)
            y = moe_dispatch_combine(
                xt, combine, dispatch,
                lambda q, rows: _mlp(cfg, q, rows, dt), bank)
            counts = counts.astype(jnp.int32)
        if cfg.moe_shared_experts:
            shared = p["moe"]["shared"]
            y = y + shared_expert(cfg, shared["wi"], shared["wo"], xt, dt).astype(y.dtype)
    return y.reshape(*lead, E).astype(dt), l_aux.astype(jnp.float32), counts


def sinkhorn_knopp(m: Array, iters: int, eps: float) -> Array:
    """``iters`` rounds over the positive ``m [n, n, ...]`` (row ``i``,
    column ``j``, then whatever the matrices are batched over): every column
    over its sum, then every row over its sum (each sum ``+ eps``).  Rows sum
    to 1 and columns to 1 within the iteration's error.  Unrolled, and XLA
    still makes kernels of every round: a divide with several users is never
    duplicated into them, so a projection is some ninety small fusions
    (compiled ahead of time for the v5e; ROADMAP M16 (a) is the kernel that
    keeps a tile of tokens' matrices in VMEM for all of it)."""
    for _ in range(iters):
        m = m / (m.sum(axis=0, keepdims=True) + eps)
        m = m / (m.sum(axis=1, keepdims=True) + eps)
    return m


def hyper_maps(cfg: "GPTConfig", p: Dict, sub: str, x: Array, compute=jnp.float32):
    """The maps of ONE sublayer (``sub``: "attn" or "mlp") over a token's
    streams ``x [..., n, E]`` (:class:`HyperSpec`): -> (``Hpre [..., n]``,
    ``Hpost [..., n]``, ``Hres [..., n, n]``) in ``compute``.

        xb    = RMSNorm(vec(x))                         n E lanes, no gain
        H~    = alpha * (xb @ phi) + b                  [pre n | post n | res n n]
        Hpre  = sigmoid(Hpre~);  Hpost = 2 sigmoid(Hpost~)
        Hres  = Sinkhorn-Knopp(exp(clip(Hres~)))        doubly stochastic

    The model computes them in float32 whatever the streams' type (the
    product with ``phi`` too: a float32 dot is a bf16 one on the chip unless
    it says ``HIGHEST``); ``compute`` is here for the benchmark's control that
    plants a lower type and must be refused.  The logits are made TOKENS
    LAST, ``[2n + n n, tokens]``: a token's 4 x 4 matrix in the two minor
    dimensions would take a vector register of 1,024 numbers to itself in
    each of the projection's hundred passes.  Scope ``hc_coeff``."""
    hs = cfg.hyper
    n, lead = hs.streams, x.shape[:-2]
    with jax.named_scope("hc_coeff"):
        xf = x.astype(compute).reshape(-1, n * x.shape[-1])
        xb = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + hs.eps)
        raw = jnp.einsum("tk,kc->ct", xb, p[f"hc_{sub}_phi"].astype(compute),
                         precision=jax.lax.Precision.HIGHEST)
        alpha, b = p[f"hc_{sub}_alpha"].astype(compute), p[f"hc_{sub}_b"].astype(compute)
        pre, post, res = (alpha[i] * raw[lo:hi] + b[lo:hi, None] for i, (lo, hi) in
                          enumerate(((0, n), (n, 2 * n), (2 * n, 2 * n + n * n))))
        hres = sinkhorn_knopp(
            jnp.exp(jnp.clip(res, hs.clamp_min, hs.clamp_max)).reshape(n, n, -1),
            hs.sinkhorn_iters, hs.eps)
        # tokens first again: the walk slices and pads what it carries by row
        return tuple(jnp.moveaxis(t, -1, 0).reshape(*lead, *t.shape[:-1])
                     for t in (jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), hres))


def hyper_read(cfg: "GPTConfig", p: Dict, sub: str, x: Array, dt):
    """What ONE sublayer reads of a token's streams ``x [..., n, E]``, and
    the maps it will write them back through (:func:`hyper_maps`): ->
    (``u = Hpre @ x`` ``[..., E]`` in ``dt``, (``Hres``, ``Hpost``)); a
    float32 product, the streams stay in their own type.  Scope ``hc_pre``."""
    hpre, hpost, hres = hyper_maps(cfg, p, sub, x)
    with jax.named_scope("hc_pre"):
        xf = x.astype(jnp.float32)
        u = sum(hpre[..., j, None] * xf[..., j, :] for j in range(x.shape[-2])).astype(dt)
    return u, (hres, hpost)


def hyper_write(x: Array, maps, f: Array) -> Array:
    """``Hres @ x + outer(Hpost, f)``: the streams ``x [..., n, E]`` after a
    sublayer whose output is ``f [..., E]``, through the ``maps`` its
    :func:`hyper_read` gave; float32 products (``n`` explicit terms: one
    fusion reads the streams once and writes them once), the streams' own
    type out.  Scope ``hc_post``."""
    hres, hpost = maps
    with jax.named_scope("hc_post"):
        xf, ff = x.astype(jnp.float32), f.astype(jnp.float32)
        mixed = sum(hres[..., j, None] * xf[..., None, j, :] for j in range(x.shape[-2]))
        return (mixed + hpost[..., None] * ff[..., None, :]).astype(x.dtype)


def _block_tail(cfg: "GPTConfig", p: Dict, x: Array, h: Array, o: Array, dt,
                live: Optional[Array] = None,
                bank_at: Optional[Tuple[Dict, Array]] = None,
                maps=None) -> Tuple[Array, Optional[Array]]:
    """The block after attention, by ``block_type``, on the inference
    paths: ``x`` the block's input, ``h`` its normed form (what attention
    read), ``o`` attention's output; ``bank_at`` is ``_ffn``'s.  Returns the
    block's output and ``_ffn``'s expert counts.  Under ``cfg.hyper`` ``x``
    is the token's streams and ``maps`` what attention's :func:`hyper_read`
    gave: each sublayer's output is written through its own maps where a
    single stream adds it."""
    add = lambda x, maps, f: x + f if maps is None else hyper_write(x, maps, f)
    if cfg.block_type == "sequential":
        if cfg.norm_sandwich:
            o = rms_norm(o, p["post_attn_g"], eps=cfg.ln_eps)
        x = add(x, maps, o)
        u, maps = (x, None) if cfg.hyper is None else hyper_read(cfg, p, "mlp", x, dt)
        z = _norm(cfg, u, p["ln2_g"], p["ln2_b"])
    else:
        z = h if cfg.block_type == "parallel_single_ln" else _norm(
            cfg, x, p["ln2_g"], p["ln2_b"])
        x = x + o
    f, _, counts = _ffn(cfg, p, z, dt, live=live, attn_in=h, bank_at=bank_at)
    if cfg.norm_sandwich:
        f = rms_norm(f, p["post_mlp_g"], eps=cfg.ln_eps)
    return add(x, maps, f), counts


def layer_norm(x: Array, g: Array, b: Array, eps: float = 1e-5) -> Array:
    # fp32 statistics regardless of activation dtype (bf16-safe)
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * g + b).astype(x.dtype)


def _dropout(x: Array, rate: float, rng: Optional[Array], train: bool) -> Array:
    if not train or rate == 0.0 or rng is None:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def _maybe_actq(cfg: "GPTConfig", h: Array) -> Array:
    if cfg.activation_quant_bits is None:
        return h
    from deepspeed_tpu.compression.basic_ops import quantize_activation
    return quantize_activation(h, bits=cfg.activation_quant_bits,
                               quant_type=cfg.activation_quant_type)


def _walk_layers(n_kinds: int, layer_fn: Callable, carry, n_layer: int,
                 start: int = 0):
    """``lax.scan`` over the periods of a stack of ``n_layer`` layers that
    repeats in a period of ``n_kinds``: ``layer_fn(j, carry, l) -> (carry,
    y)`` runs layer ``l`` (an int32 scalar, its index in the stack), which is
    of the period's ``j``-th kind (static), and takes what it reads of layer
    ``l`` out of the stack ITSELF.  ``start`` (whole periods) leaves the
    first layers to the caller, who has walked them itself."""
    def period(carry, i):
        ys = []
        for j in range(n_kinds):
            carry, y = layer_fn(j, carry, i * n_kinds + j)
            ys.append(y)
        return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)

    carry, ys = jax.lax.scan(period, carry,
                             jnp.arange(start // n_kinds, n_layer // n_kinds))
    return carry, jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), ys)


def _scan_layers(n_kinds: int, layer_fn: Callable, carry, xs):
    """The dense forward's walk where ``layer_walk`` says "scan":
    ``lax.scan`` over the stacked layers ``xs`` (leaves ``[L, ...]``) of a
    stack that repeats in a period of ``n_kinds`` layers:
    ``layer_fn(j, carry, x) -> (carry, y)`` runs one layer of the period's
    ``j``-th kind (static) on its slice ``x`` of every leaf.  A period of one
    is the plain scan over layers; a longer one is :func:`_walk_layers`
    taking layer ``period * n_kinds + j`` out of each leaf (one dynamic
    slice of the whole stack a layer, as a scan over layers takes it: a
    slice of a slice is a copy of the period's weights).  A slice that feeds
    XLA's own dot is read in place.  One that feeds a Pallas call, or that
    is made OUTSIDE a ``lax.cond`` whose branch reads it, is COPIED out
    first: the serving step hands its layers the stack and an index instead
    (``gpt_paged_step``: :func:`_walk_layers`, :class:`_LayerLeaves`)."""
    if n_kinds == 1:
        return jax.lax.scan(partial(layer_fn, 0), carry, xs)
    return _walk_layers(
        n_kinds,
        lambda j, carry, l: layer_fn(j, carry, jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False), xs)),
        carry, jax.tree.leaves(xs)[0].shape[0])


class _LayerLeaves(Mapping):
    """Layer ``i``'s leaves of a stack (leaves ``[L, ...]``), read like the
    layer's own tree (``p["qkv_w"]``, ``p["moe"]["gate"]["wg"]``, ``"moe" in
    p``), each taken out of the stack WHERE IT IS READ: a slice made once
    outside a ``lax.cond`` would be the branch's operand, and XLA copies an
    operand out (a hybrid layer's 570 MB a layer a step, PERF.md § 6, PR 39;
    1.79 GB a step of Trinity's), where a slice made inside the branch feeds
    its dot in place.  ``beside(stack, i)`` adds the leaves of another stack
    at an index of its own (behind a dense lead a layer's feed-forward lies
    in its kind's stack); a name is looked up in the stacks in order."""

    def __init__(self, stack: Dict, i, *more):
        self.parts = ((stack, i),) + more

    def beside(self, stack: Dict, i) -> "_LayerLeaves":
        return _LayerLeaves(*self.parts[0], *self.parts[1:], (stack, i))

    def __iter__(self):
        return iter(dict.fromkeys(k for stack, _ in self.parts for k in stack))

    def __contains__(self, name) -> bool:      # slices nothing
        return any(name in stack for stack, _ in self.parts)

    def __len__(self) -> int:
        return len(tuple(self))

    def __getitem__(self, name: str):
        from deepspeed_tpu.module_inject.quantization import is_quantized_leaf
        for stack, i in self.parts:
            if name not in stack:
                continue
            if isinstance(stack[name], dict) and not is_quantized_leaf(stack[name]):
                return _LayerLeaves(stack[name], i)
            return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, i, 0, keepdims=False), stack[name])
        raise KeyError(name)


def _rows_that_carry(fn, xs, chunk: int, live, totals: int = 0):
    """``fn(*xs)``, a function of each row alone (a projection, the MLP, the
    head; an array or a tuple of them): over all rows in a step with a prompt
    chunk, over the decode rows alone (zeros behind them) in a step without
    one, where the chunk's rows carry nothing and nobody reads what they
    give.  ``xs`` are by row (``live [rows]`` among them where ``fn`` reads
    it); the last ``totals`` of a tuple's results are sums over the live rows
    (an expert's count) and come back as they are.  Most steps of a long run
    carry no chunk, and the chunk's rows (512 of 544) would go through every
    matrix for nobody.  What ``fn`` reads of a layer it takes out of the
    stack itself (:class:`_LayerLeaves`)."""
    if not chunk:
        return fn(*xs)
    n_dec = xs[0].shape[0] - chunk
    behind = lambda y: jnp.pad(y, ((0, chunk),) + ((0, 0),) * (y.ndim - 1))

    def decode_rows_alone():
        ys = fn(*(x[:n_dec] for x in xs))
        if not totals:
            return jax.tree.map(behind, ys)
        return (*jax.tree.map(behind, ys[:-totals]), *ys[-totals:])

    return jax.lax.cond(live[n_dec], lambda: fn(*xs), decode_rows_alone)


def layer_walk(cfg: "GPTConfig") -> str:
    """How ``gpt_forward`` walks a STACKED ``params["blocks"]``: ``"scan"``,
    one ``lax.scan`` over the layers, or ``"unrolled"``, a Python loop over
    static slices ``leaf[i]`` that leaves no loop in the program.  The rule,
    stated here and nowhere else, reads ``cfg`` and the mesh:

    * a loop keeps what its backward needs STACKED: every residual of a layer
      is copied into row ``i`` of an ``[n_layer, ...]`` buffer and copied out
      again by the backward (at GPT-2 124M, micro 8 x 1024: 2.4 GB a step
      each way, a sixth of the step, PERF.md § 6, PR 54).  Unrolled, a
      residual stays where its producer wrote it.  So: unrolled, unless
    * ``cfg.remat``: the loop then stashes ONE tensor a layer, the block's
      input, and the block's own residuals live and die inside the body:
      2.5% of GPT-2 XL's step, for which 48 layers unrolled would compile
      for minutes; or
    * the parameters reach the forward sharded over ``fsdp`` (ZeRO-3, the
      layered prefetch): one body holds one gather a leaf, and the loop keeps
      each next to its use.  Unrolled, the step's text held 448 all-gathers
      where the scan's holds 25 (GPT-2 XL's widths, 16 layers, compiled ahead
      of time for four v5e chips, PERF.md § 6, PR 54) and where they run is
      the scheduler's to choose; that compile did NOT hoist them (its
      temporaries fell, 3.1 to 1.6 GB), but no cell measures a sharded step
      without remat, and 48 layers unrolled compile for minutes: the scan
      stays until a cell says otherwise.

    The per-layer layout (``scan_layers=False``) has no stack to scan.  The
    rule cannot see whether a backward will follow (``train`` says dropout,
    and a forward with ``train=False`` is differentiated too), so the
    forward alone takes the same walk as its training step."""
    if not cfg.scan_layers:
        return "unrolled"
    if cfg.remat or zero_layered.current_prefetch() is not None:
        return "scan"
    if mesh_lib.has_mesh() and mesh_lib.axis_size("fsdp") > 1:
        return "scan"
    return "unrolled"


def _refuse_hybrid(cfg: "GPTConfig", path: str) -> None:
    """The dense paths walk ONE stack of softmax layers over ONE residual
    stream: a stack with layers of another mixer, an indexer or residual
    streams is refused by the mechanisms it would need."""
    if cfg.hybrid:
        from deepspeed_tpu.models import hybrid
        raise NotImplementedError(
            f"{path} has {hybrid.what_a_dense_path_lacks(cfg)}; serve this "
            f"stack through init_serving() (models/hybrid.py)")
    if cfg.indexer is not None:
        raise NotImplementedError(
            f"{path} has no lightning indexer, no cache of index keys and no "
            f"selection of the rows of the latent cache a query attends for "
            f"the {cfg.n_layer} layers; serve this stack through "
            f"init_serving() (gpt_paged_step)")
    if cfg.hyper is not None:
        raise NotImplementedError(
            f"{path} carries ONE residual stream a token: it has no "
            f"{cfg.hyper.streams} streams, no maps of a token's own round a "
            f"sublayer (hyper_read, hyper_write) and no Sinkhorn-Knopp "
            f"projection for the {cfg.n_layer} layers; serve this stack "
            f"through init_serving() (gpt_paged_step)")


def _window_bias(S: int, window: int) -> Array:
    """``[S, S]`` additive mask of a window layer on the dense path: query
    ``t`` sees keys ``t - window + 1 .. t`` (the causal half is the
    attention op's)."""
    far = jnp.arange(S)[:, None] - jnp.arange(S)[None, :] >= window
    return jnp.where(far, -1e30, 0.0).astype(jnp.float32)


def _embed(cfg: "GPTConfig", wte: Array, input_ids: Array, dt) -> Array:
    """``scale_emb * wte[ids]``, the product in float32 where there is one
    (``sqrt(n_embd)`` has no bf16)."""
    x = wte.astype(dt)[input_ids]
    if cfg.scale_emb != 1.0:
        x = (x.astype(jnp.float32) * cfg.scale_emb).astype(dt)
    return x


def _row(tree, i: int):
    """Row ``i`` (static) of every leaf of a stacked tree."""
    return jax.tree.map(
        lambda a: jax.lax.index_in_dim(a, i, 0, keepdims=False), tree)


def _layer_of(cfg: "GPTConfig", blocks: Dict, l: int) -> Dict:
    """Layer ``l`` (static) of the stacked ``blocks`` as one block's tree:
    row ``l`` of every leaf, or behind a dense lead (``moe_dense_layers``)
    row ``l`` of the attention leaves beside the layer's own row of its
    feed-forward's kind."""
    lead = cfg.moe_dense_layers
    if not lead:
        return _row(blocks, l)
    attn = {k: v for k, v in blocks.items() if k not in ("lead", "moe")}
    if l < lead:
        return {**_row(attn, l), **_row(blocks["lead"], l)}
    return {**_row(attn, l), "moe": _row(blocks["moe"], l - lead)}


def gpt_block(cfg: GPTConfig, p: Dict, x: Array, rng: Optional[Array],
              train: bool, attention_fn: Callable,
              kind: Optional[LayerKind] = None) -> Tuple[Array, Array]:
    """One transformer block on ``x: [batch, seq, embd]``, of the pattern's
    ``kind`` (default: its first).  Returns ``(x, moe_aux)``; the aux term
    is zero for dense blocks."""
    B, S, E = x.shape
    H, D = cfg.n_head, cfg.head_dim
    dt = x.dtype
    kind = kind or cfg.pattern[0]
    r = (jax.random.split(rng, 3) if rng is not None else (None, None, None))

    with jax.named_scope("attn"):
        h = _maybe_actq(cfg, _norm(cfg, x, p["ln1_g"], p["ln1_b"]))
        q, k, v = _project_qkv(cfg, p, h, dt, jnp.arange(S), kind)
        # grouped K/V go to the attention op as-is: the Pallas kernel (and
        # the GQA-aware jnp reference) consume Hkv < H heads natively, so
        # training saves the K/V-expansion HBM the round-3 path paid here
        # heads sharded over tensor axis (Megatron attention parallelism)
        q = _constrain(q, mesh_lib.BATCH_AXES, "seq", "tensor", None)
        k = _constrain(k, mesh_lib.BATCH_AXES, "seq", "tensor", None)
        v = _constrain(v, mesh_lib.BATCH_AXES, "seq", "tensor", None)
        if cfg.position_encoding == "alibi":
            # slopes-only ALiBi: every attention path synthesizes the bias
            # from iotas (O(H) memory — no [S, S] bias tensor ever exists)
            from deepspeed_tpu.ops.attention import alibi_slopes
            o = attention_fn(q, k, v, causal=True,
                             alibi=jnp.asarray(alibi_slopes(H)))
        elif kind.window is not None:
            # the flash kernel has no window: the masked einsum, whatever
            # ``attn_impl`` says (README: a window layer is served, not trained)
            from deepspeed_tpu.ops.attention import reference_attention
            o = reference_attention(q, k, v, causal=True,
                                    bias=_window_bias(S, kind.window))
        elif cfg.v_head_dim != D:
            # the kernels take values as wide as keys: the einsum
            from deepspeed_tpu.ops.attention import reference_attention
            o = reference_attention(q, k, v, causal=True)
        else:
            o = attention_fn(q, k, v, causal=True)
        o = _attn_out(cfg, p, o.reshape(B, S, H * cfg.v_head_dim), h, dt)
        if cfg.norm_sandwich:
            o = rms_norm(o, p["post_attn_g"], eps=cfg.ln_eps)
        o = _dropout(o, cfg.dropout, r[0], train)

    with jax.named_scope("mlp"):
        if cfg.block_type == "sequential":
            x = _constrain(x + o, mesh_lib.BATCH_AXES, "seq", None)
            h2 = _maybe_actq(cfg, _norm(cfg, x, p["ln2_g"], p["ln2_b"]))
            f, moe_aux, _ = _ffn(cfg, p, h2, dt, rng=r[1], train=train,
                                 attn_in=h)
            if cfg.norm_sandwich:
                f = rms_norm(f, p["post_mlp_g"], eps=cfg.ln_eps)
            x = x + _dropout(f, cfg.dropout, r[2], train)
        elif cfg.block_type == "parallel":
            # GPT-NeoX use_parallel_residual: x + attn(ln1 x) + mlp(ln2 x)
            h2 = _norm(cfg, x, p["ln2_g"], p["ln2_b"])
            f, moe_aux, _ = _ffn(cfg, p, h2, dt, rng=r[1], train=train,
                                 attn_in=h)
            x = x + o + _dropout(f, cfg.dropout, r[2], train)
        else:   # parallel_single_ln (GPT-J): one LN feeds attn AND mlp
            f, moe_aux, _ = _ffn(cfg, p, h, dt, rng=r[1], train=train,
                                 attn_in=h)
            x = x + o + _dropout(f, cfg.dropout, r[2], train)
    return _constrain(x, mesh_lib.BATCH_AXES, "seq", None), moe_aux


def gpt_forward(cfg: GPTConfig, params: Dict, input_ids: Array,
                rng: Optional[Array] = None, train: bool = False,
                attention_fn: Optional[Callable] = None,
                pld_theta: Optional[Array] = None,
                return_hidden: bool = False,
                with_aux: bool = False) -> Array:
    """Logits ``[batch, seq, padded_vocab]`` (bf16 compute, fp32 logits).

    ``pld_theta`` enables progressive layer drop (reference
    ``runtime/progressive_layer_drop.py``; engine feeds the annealed theta
    per step): block *i* is kept with probability
    ``1 - (i+1)/L * (1 - theta)`` — deeper blocks drop more, theta→1
    disables dropping.  A dropped block is the identity via ``lax.cond``,
    which TPU executes as a real dynamic branch — dropped blocks skip
    their FLOPs, matching the reference's speedup story.
    """
    from deepspeed_tpu.ops.attention import get_attention_fn
    _refuse_hybrid(cfg, "gpt_forward (training and the dense forward pass)")
    attention_fn = attention_fn or get_attention_fn(cfg.attn_impl)

    B, S = input_ids.shape
    dt = cfg.dtype
    with jax.named_scope("embed"):
        # Explicit ZeRO-3 gather for the embedding table: under stage 3 the
        # policy shards wte's E dim over fsdp, and a table gather with a
        # sharded E produces E-sharded activations that the partitioner can
        # only reshard to the batch/seq layout by full replication (the
        # "involuntary full rematerialization" warnings of MULTICHIP_r03).
        # Constraining the table to its logical (vocab-parallel, E-whole)
        # spec first makes the gather-at-use all-gather explicit — which is
        # what ZeRO-3 does for every parameter anyway — and the gather then
        # lands batch/seq-sharded directly.
        input_ids = _constrain(input_ids, mesh_lib.BATCH_AXES, "seq")
        wte = _constrain(params["wte"], "tensor", None)
        x = _embed(cfg, wte, input_ids, dt)
        x = _constrain(x, mesh_lib.BATCH_AXES, "seq", None)
        if cfg.position_encoding == "learned":
            x = x + params["wpe"].astype(dt)[:S][None]
        x = _dropout(x, cfg.dropout, rng, train)

    # one body a kind of the layer pattern (a kind is static)
    def body_of(kind):
        def layer(p, x, r):
            return gpt_block(cfg, p, x, r, train, attention_fn, kind)
        return layer

    bodies = [body_of(kind) for kind in cfg.pattern]
    walk = layer_walk(cfg)
    assert walk == "unrolled" or not cfg.moe_dense_layers, (
        "a stack with a dense lead (moe_dense_layers) keeps its feed-forward "
        "leaves by kind, which the scan over layers cannot walk: no remat and "
        "no fsdp axis over it; it is served through init_serving()")
    if cfg.remat:
        from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import (
            checkpoint_policy)
        bodies = [jax.checkpoint(b, policy=checkpoint_policy()) for b in bodies]
    elif walk == "unrolled":
        # traced, differentiated and lowered ONCE a kind, called n_layer times
        # (XLA inlines the calls): what an unrolled walk costs in set-up is
        # then the compile alone, which the persistent cache answers
        bodies = [jax.jit(b) for b in bodies]
    n_kinds = len(bodies)

    # random-LTD: each block trains on its own sorted random token subset,
    # the rest riding the residual stream (data_pipeline/data_routing)
    ltd_on = (train and rng is not None and cfg.ltd_keep is not None
              and cfg.ltd_keep < S)
    if ltd_on:
        from deepspeed_tpu.runtime.data_pipeline.data_routing.basic_layer import (
            sample_token_indices)
        ltd_idx = sample_token_indices(jax.random.fold_in(rng, 99), S,
                                       cfg.ltd_keep, cfg.n_layer)
    # progressive layer drop: per-block keep flags, progressive with depth
    pld_on = train and rng is not None and pld_theta is not None
    if pld_on:
        depth_frac = jnp.arange(1, cfg.n_layer + 1, dtype=jnp.float32) / cfg.n_layer
        keep_p = 1.0 - depth_frac * (1.0 - pld_theta)
        pld_keep = jax.random.bernoulli(jax.random.fold_in(rng, 55), keep_p)

    zero_aux = jnp.zeros((), jnp.float32)
    use_rngs = rng is not None and train

    def run_layer(j, carry, layer):
        """One layer of the pattern's ``j``-th kind: ``layer`` holds its
        parameters ``p``, its rng ``r``, and where they are on its random-LTD
        index row ``idx`` and its PLD flag ``keep``."""
        x, aux_sum = carry
        body, p, idx = bodies[j], layer["p"], layer.get("idx")
        r = layer["r"] if use_rngs else None

        def run(xx):
            if idx is None:
                return body(p, xx, r)
            sub, aux = body(p, jnp.take(xx, idx, axis=1), r)
            return xx.at[:, idx].set(sub), aux

        if pld_on:   # lax.cond: a dropped block really skips its FLOPs
            x, aux = jax.lax.cond(layer["keep"], run,
                                  lambda xx: (xx, zero_aux), x)
        else:
            x, aux = run(x)
        return (x, aux_sum + aux), None

    if cfg.scan_layers:
        xs = {"r": (jax.random.split(jax.random.fold_in(rng, 7), cfg.n_layer)
                    if use_rngs else jnp.zeros((cfg.n_layer, 2), jnp.uint32))}
        if ltd_on:
            xs["idx"] = ltd_idx
        if pld_on:
            xs["keep"] = pld_keep
    pf = zero_layered.current_prefetch()
    carry = (x, zero_aux)
    with jax.named_scope("blocks"):
        if walk == "unrolled":
            if cfg.scan_layers:
                # static slices leaf[i], for XLA's own dots a view; inside ONE
                # jit, so that jax.grad meets one equation where it would
                # differentiate n_layer x leaves slices one by one
                layers = jax.jit(lambda t: [
                    dict(_row(t["xs"], i), p=_layer_of(cfg, t["p"], i))
                    for i in range(cfg.n_layer)])({"xs": xs, "p": params["blocks"]})
            else:
                layers = [{"p": params["blocks"][f"h{i}"],
                           "r": jax.random.fold_in(rng, i) if use_rngs else None}
                          for i in range(cfg.n_layer)]
                for i, layer in enumerate(layers):
                    if ltd_on and (cfg.ltd_layers is None or i in cfg.ltd_layers):
                        layer["idx"] = ltd_idx[i]
                    if pld_on:
                        layer["keep"] = pld_keep[i]
            for i, layer in enumerate(layers):
                carry, _ = run_layer(i % n_kinds, carry, layer)
        elif pf is None:
            carry, _ = _scan_layers(n_kinds, run_layer, carry,
                                    dict(xs, p=params["blocks"]))
        else:
            # Layered ZeRO-3: params["blocks"] are still SHARDED here —
            # the carry holds a ring of `depth` already-gathered block
            # slices, and each iteration issues block i+depth's gather
            # (independent of block i's compute, so XLA's async collective
            # start/done hides it under the matmuls) before consuming the
            # ring head.  The gathers' custom-vjp backward reduce-scatters
            # each block's grads as its backward slice completes.
            assert n_kinds == 1, (
                "layered ZeRO-3 prefetch walks identical layers; a layer "
                "pattern of several kinds is not trained (README)")
            blocks = params["blocks"]
            depth = pf.clamped_depth(cfg.n_layer)
            ring = tuple(pf.gather_block(blocks, jnp.int32(k))
                         for k in range(depth))
            xs["i"] = jnp.arange(cfg.n_layer, dtype=jnp.int32)

            def ring_body(carry, layer):
                carry, ring = carry
                nxt = pf.gather_block(
                    blocks, jnp.minimum(layer["i"] + depth, cfg.n_layer - 1))
                carry, _ = run_layer(0, carry, dict(layer, p=ring[0]))
                return (carry, ring[1:] + (nxt,)), None

            (carry, _), _ = jax.lax.scan(ring_body, (carry, ring), xs)
    x, aux_total = carry

    with jax.named_scope("head"):
        x = _norm(cfg, x, params["lnf_g"], params["lnf_b"])
        if return_hidden:   # training loss path: chunked CE owns the head
            return (x, aux_total) if with_aux else x
        # tied embedding projection (or the untied lm_head when the source
        # checkpoint has one); vocab-parallel → logits sharded over tensor
        head = params["lm_head"] if cfg.untied_head else params["wte"]
        logits = (x @ head.astype(dt).T).astype(jnp.float32)
        if cfg.head_bias:
            logits = logits + params["lm_head_b"].astype(jnp.float32)
    logits = _constrain(logits, mesh_lib.BATCH_AXES, "seq", "tensor")
    return (logits, aux_total) if with_aux else logits


def chunked_cross_entropy(x: Array, head: Array, labels: Array,
                          vocab_size: int, n_chunks: int = 0,
                          head_b: Optional[Array] = None) -> Array:
    """Cross-entropy over the unembedding WITHOUT materializing [N, V]
    logits: rows are processed in chunks under ``jax.checkpoint``, so both
    forward and backward hold one [chunk, V] logits block at a time (the
    backward recomputes the chunk's logits and forms softmax-minus-onehot
    in place).  At GPT-2 vocab and micro-batch 16×512 this removes ~5 GiB
    of fp32 logits/softmax temporaries from the training step — the memory
    cliff that capped the round-3 headline bench at micro 16.

    x: [B, S, E] final hidden; head: [V, E]; labels: [B, S].
    ``n_chunks=0`` picks the smallest count keeping a chunk's logits block
    under ~256 MiB.
    """
    B, S, E = x.shape
    V = head.shape[0]
    N = B * S
    from deepspeed_tpu.ops import pallas as _pallas
    from deepspeed_tpu.ops.pallas import cross_entropy as _pce
    if _pallas.use_kernel("ce") and _pce.ce_supported(N, E, V):
        return _pce.fused_cross_entropy(x.reshape(N, E), head,
                                        labels.reshape(N), vocab_size,
                                        head_b=head_b)
    if n_chunks <= 0:
        # chunking trades ~1/3 extra head FLOPs (backward recompute) for
        # the [N, V] memory, so only chunk past 900 MiB, where capacity
        # forces it (micro 8 x 512 x 50k = 823 MiB stays whole).  The
        # threshold is not measured since the record it came from went
        # (ROADMAP D10); S6 decides
        threshold = 900 * 2 ** 20
        if N * V * 4 <= threshold:
            n_chunks = 1
        else:
            target_rows = max(1, threshold // (4 * V))
            n_chunks = max(1, -(-N // target_rows))
    # rows are PADDED up to n_chunks * rows (pad rows masked out of the
    # mean) — never a divisor hunt, which degenerates for prime-ish N
    rows = -(-N // n_chunks)
    n_pad = n_chunks * rows - N
    if n_chunks == 1:
        logits = (x.reshape(N, E) @ head.astype(x.dtype).T).astype(jnp.float32)
        if head_b is not None:
            logits = logits + head_b.astype(jnp.float32)
        if V != vocab_size:
            logits = jnp.where(jnp.arange(V)[None] < vocab_size, logits, -1e9)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.sum(logits * jax.nn.one_hot(labels.reshape(N), V,
                                             dtype=logits.dtype), axis=-1)
        return jnp.mean(lse - ll)
    xf = x.reshape(N, E)
    lf = labels.reshape(N)
    valid = None
    if n_pad:
        xf = jnp.concatenate([xf, jnp.zeros((n_pad, E), xf.dtype)])
        lf = jnp.concatenate([lf, jnp.zeros((n_pad,), lf.dtype)])
        valid = (jnp.arange(n_chunks * rows) < N).reshape(n_chunks, rows)
    xc = xf.reshape(n_chunks, rows, E)
    lc = lf.reshape(n_chunks, rows)
    mask_pad = V != vocab_size

    def chunk(total, xs):
        xch, lch = xs[0], xs[1]
        logits = (xch @ head.astype(xch.dtype).T).astype(jnp.float32)  # [rows, V]
        if head_b is not None:
            logits = logits + head_b.astype(jnp.float32)
        if mask_pad:
            logits = jnp.where(jnp.arange(V)[None] < vocab_size, logits, -1e9)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        # one-hot contraction, not take_along_axis: under TP the logits are
        # vocab-parallel and a gather's vjp (scatter on the sharded dim)
        # provokes pathological SPMD partitioner compiles (same issue as
        # gpt_ce_loss_fn); XLA fuses the one-hot select without
        # materializing it
        ll = jnp.sum(logits * jax.nn.one_hot(lch, V, dtype=logits.dtype),
                     axis=-1)
        nll = lse - ll
        if valid is not None:
            nll = jnp.where(xs[2], nll, 0.0)
        return total + jnp.sum(nll), None

    xs = (xc, lc) if valid is None else (xc, lc, valid)
    total, _ = jax.lax.scan(jax.checkpoint(chunk), jnp.zeros((), jnp.float32),
                            xs)
    return total / N


def gpt_loss(cfg: GPTConfig, params: Dict, input_ids: Array, labels: Array,
             rng: Optional[Array] = None, train: bool = True,
             attention_fn: Optional[Callable] = None,
             pld_theta: Optional[Array] = None) -> Array:
    """Next-token cross-entropy, masking padded vocab entries.  Computed
    chunked over the head projection (no [B, S, V] logits tensor exists)."""
    x, aux = gpt_forward(cfg, params, input_ids, rng, train, attention_fn,
                         pld_theta=pld_theta, return_hidden=True,
                         with_aux=True)
    head = params["lm_head"] if cfg.untied_head else params["wte"]
    with jax.named_scope("cross_entropy"):
        ce = chunked_cross_entropy(x, head, labels, cfg.vocab_size,
                                   head_b=params.get("lm_head_b")
                                   if cfg.head_bias else None)
    if cfg.moe_num_experts > 0:
        # load-balance aux loss (reference l_aux, sharded_moe.py:179)
        ce = ce + cfg.moe_aux_coeff * aux
    return ce


# --------------------------------------------------------------------------- #
# Inference: KV cache + decode step (the analogue of the reference's
# softmax_context kernel + inference_context.h workspace, SURVEY.md §2.3)
# --------------------------------------------------------------------------- #
def init_kv_cache(cfg: GPTConfig, batch: int, max_len: int) -> Dict:
    """Per-layer K/V cache, stacked [L, B, max_len, Hkv*D] (scan-friendly;
    GQA stores only the kv heads).  Heads are folded into the lane
    dimension — the layout the decode kernel can DMA at D=64
    (``ops/pallas/decode_attention.py``).  Sharded: batch over DP, heads
    over tensor."""
    shape = (cfg.n_layer, batch, max_len, cfg.kv_heads * cfg.head_dim)
    k = jnp.zeros(shape, cfg.dtype)
    v = jnp.zeros(shape, cfg.dtype)
    spec = (None, mesh_lib.BATCH_AXES, None, "tensor")
    return {"k": _constrain(k, *spec), "v": _constrain(v, *spec),
            "pos": jnp.zeros((), jnp.int32)}


def gpt_apply_with_cache(cfg: GPTConfig, params: Dict, input_ids: Array,
                         cache: Dict) -> Tuple[Array, Dict]:
    """Run ``input_ids`` [B, S_new] starting at cache position ``pos``;
    returns (logits [B, S_new, V], updated cache).  Covers both prefill
    (S_new = prompt length) and decode (S_new = 1) — one compiled program
    per S_new."""
    assert cfg.scan_layers, "KV-cache path requires scan_layers"
    _refuse_hybrid(cfg, "the dense-cache generate() path")
    assert len(cfg.pattern) == 1 and not cfg.moe_dense_layers, (
        "the dense-cache generate() path walks identical layers; a model "
        "with a layer pattern or a dense lead is served through init_serving()")
    assert cfg.v_head_dim == cfg.head_dim, (
        "the dense cache holds K and V of one width (latent attention in its "
        "plain form, every head's own); init_serving() caches the latent")
    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention
    B, S = input_ids.shape
    H, D, E = cfg.n_head, cfg.head_dim, cfg.n_embd
    dt = cfg.dtype
    pos = cache["pos"]

    x = _embed(cfg, params["wte"], input_ids, dt)
    if cfg.position_encoding == "learned":
        x = x + params["wpe"].astype(dt)[jnp.clip(pos + jnp.arange(S), 0,
                                                  cfg.n_positions - 1)][None]
    x = _constrain(x, mesh_lib.BATCH_AXES, None, None)

    T = cache["k"].shape[2]
    if cfg.position_encoding == "alibi":
        from deepspeed_tpu.ops.attention import alibi_slopes
        slopes = jnp.asarray(alibi_slopes(H))
        kpos = jnp.arange(T)[None, :]
        qpos = (pos + jnp.arange(S))[:, None]
        attn_bias = (slopes[:, None, None]
                     * (kpos - qpos).astype(jnp.float32))[None]
    else:
        attn_bias = None

    def layer(carry, p):
        # the FULL stacked [L, B, T, Hkv*D] cache rides the scan carry and
        # is updated in place per layer — stacked scan outputs (`ys`) would
        # copy the whole cache every decode step (measured: ~40% of decode
        # time went to those copies before this layout)
        x, ck_full, cv_full, li = carry
        h = _norm(cfg, x, p["ln1_g"], p["ln1_b"])
        q, k, v = _project_qkv(cfg, p, h, dt, pos + jnp.arange(S))
        # the cache stores only kv_heads heads (the GQA memory win);
        # expansion to n_head happens at attention time
        zero = jnp.zeros((), jnp.int32)
        ck_full = jax.lax.dynamic_update_slice(
            ck_full, k.astype(ck_full.dtype).reshape(1, B, S, -1),
            (li, zero, pos, zero))
        cv_full = jax.lax.dynamic_update_slice(
            cv_full, v.astype(cv_full.dtype).reshape(1, B, S, -1),
            (li, zero, pos, zero))
        ck = jax.lax.dynamic_index_in_dim(ck_full, li, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(cv_full, li, 0, keepdims=False)
        o = decode_attention(q, ck, cv, pos, bias=attn_bias).reshape(
            B, S, cfg.attn_dim)
        x, _ = _block_tail(cfg, p, x, h, _attn_out(cfg, p, o, h, dt), dt)
        return (x, ck_full, cv_full, li + 1), None

    (x, new_k, new_v, _), _ = jax.lax.scan(
        layer, (x, cache["k"], cache["v"], jnp.zeros((), jnp.int32)),
        params["blocks"])
    x = _norm(cfg, x, params["lnf_g"], params["lnf_b"])
    head = params["lm_head"] if cfg.untied_head else params["wte"]
    logits = (x @ head.astype(dt).T).astype(jnp.float32)
    if cfg.head_bias:
        logits = logits + params["lm_head_b"].astype(jnp.float32)
    new_cache = {"k": new_k, "v": new_v, "pos": pos + S}
    return logits, new_cache


def gpt_generate(cfg: GPTConfig, params: Dict, input_ids: Array,
                 max_new_tokens: int, rng: Optional[Array] = None,
                 temperature: float = 0.0, max_len: Optional[int] = None,
                 prompt_len: Optional[Array] = None) -> Array:
    """Greedy (temperature=0) or sampled autoregressive generation.
    The decode loop is one ``lax.scan`` — a single compiled program for all
    steps (the analogue of the reference's CUDA-graph'd generate,
    ``inference/engine.py:500-528``)."""
    B, S = input_ids.shape
    assert S + max_new_tokens <= cfg.n_positions, (
        f"prompt ({S}) + max_new_tokens ({max_new_tokens}) exceeds "
        f"n_positions ({cfg.n_positions}); the KV cache cannot grow past it")
    max_len = max_len or (S + max_new_tokens)
    # The barrier keeps the zeros: the TPU compiler otherwise turns a cache
    # that is born inside this program into an uninitialised AllocateBuffer
    # (it takes the layer loop's partial dynamic-update-slice for a full
    # overwrite), and attention then multiplies the never-written rows'
    # garbage by its zero probabilities: 0 * NaN.  Seen on the chip as
    # token 0 for every position (PERF.md, PR 21).
    cache = jax.lax.optimization_barrier(init_kv_cache(cfg, B, max_len))
    logits, cache = gpt_apply_with_cache(cfg, params, input_ids, cache)
    if prompt_len is None:
        last = logits[:, -1]
    else:
        # bucketed serving: the prompt is right-padded to a bucketed S and
        # ``prompt_len`` (traced) marks the real length — one compiled
        # program covers every prompt length in the bucket.  Causality makes
        # right-padding benign: positions < prompt_len never attend to the
        # pad tail, and decode overwrites the tail's K/V slot-by-slot
        # (step i writes position prompt_len + i before reading it).
        idx = jnp.broadcast_to(jnp.reshape(prompt_len - 1, (1, 1, 1)),
                               (B, 1, logits.shape[-1]))
        last = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
        cache = dict(cache, pos=jnp.asarray(prompt_len, jnp.int32))
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    def sample(logits, r):
        if cfg.padded_vocab != cfg.vocab_size:
            vmask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
            logits = jnp.where(vmask[None], logits, -1e30)
        if temperature and temperature > 0:
            return jax.random.categorical(r, logits / temperature, axis=-1)
        return jnp.argmax(logits, axis=-1)

    def step(carry, r):
        cache, last_logits = carry
        tok = sample(last_logits, r)
        logits, cache = gpt_apply_with_cache(cfg, params, tok[:, None], cache)
        return (cache, logits[:, -1]), tok

    rngs = jax.random.split(rng, max_new_tokens)
    (_, _), toks = jax.lax.scan(step, (cache, last), rngs)
    return jnp.concatenate([input_ids, toks.T], axis=1)


# --------------------------------------------------------------------------- #
# Paged (block-table) serving step — the continuous-batching decode path.
# The KV cache is a global block arena (deepspeed_tpu/serving/kv_cache.py)
# instead of a per-call [B, max_len] tensor: physical blocks are reached
# through each row's block table, so batch composition can change every step
# without recompiling (tables/positions are traced int32 inputs).
# --------------------------------------------------------------------------- #
# Extents of its table a prompt chunk of an indexed latent layer is compiled
# for (``models/hybrid.py:select_and_attend``): a chunk scores, selects among
# and attends the least eighth-multiple of the table that holds its last
# position, so a prompt's cost grows with what it has cached
CHUNK_EXTENTS = 8


def gpt_paged_step(cfg: GPTConfig, params: Dict, input_ids: Array,
                   positions: Array, k_pages: Array, v_pages: Array,
                   block_tables: Array, write_blocks: Array,
                   write_offsets: Array, with_expert_counts: bool = False,
                   chunk: int = 0, aux: Optional[Dict] = None, slots=None,
                   live=None):
    """One fused step over the paged arena.

    ``input_ids`` [B, S] — a row holds S consecutive tokens of one sequence;
    the serving engine runs S = 1, a decode slot or one token of the step's
    prompt chunk a row, and names the chunk: the last ``chunk`` rows are
    consecutive tokens of ONE sequence (or carry nothing).  Everything but
    attention takes them as ``B`` rows like any others; a layer's attention
    takes them PACKED, ``chunk / Sq`` rows of ``Sq`` queries under the table
    and the position of the first, beside the other rows at one query each:
    two calls of the same kernel, and each key of the chunk's context read
    once a packed row and not once a token (``ops/pallas/decode_attention
    .py:paged_chunk_queries`` takes ``Sq`` from the shapes).  All new K/V
    is scattered before a layer attends, so a chunk's query sees the chunk's
    earlier tokens through its block table;
    ``positions`` [B] — per-row global position of the first token (tokens
    already resident in the row's cache); ``k_pages``/``v_pages``
    [L, NB, BS, Hkv*D] — the global arena (block 0 is the trash block);
    ``block_tables`` [B, MB] — logical→physical block map per row;
    ``write_blocks``/``write_offsets`` [B, S] — physical (block, offset)
    each new token's K/V lands in (invalid/padded tokens point at the trash
    block).  The arena is the arrays of ``cfg.cache_lanes``: under latent
    attention ``k_pages`` is its ONE array ``[L, NB, BS, lanes]`` (a token's
    ``[latent | rotated key]``, attended in the absorbed form: the queries
    moved into the latent's space, the values read from the same vector)
    and ``v_pages`` None, in and out.  A model with a ``layer_pattern`` of
    ``P`` kinds keeps its layers in ``P`` GROUPS by position in the period:
    the arena is ``[n_layer / P, pages, BS, Hkv*D]`` (layer ``l`` is index ``l // P`` of
    group ``l % P``; a page holds one block of every layer of ONE group),
    and ``block_tables`` and ``write_blocks`` are sequences of ``P`` arrays,
    a group each — a window group's table is a ring, logical block ``b`` in
    column ``b % MB`` (``serving/kv_cache.py``).  Returns (logits [B, S, V] fp32, k_pages, v_pages), and with
    ``with_expert_counts`` (an MoE model) a fourth: the assignments per
    expert ``[experts]`` int32, summed over layers, of the rows that carry a
    request (those whose K/V does not go to the trash block).

    Latent attention under an ``indexer`` (``cfg.indexed_layers``; the
    DeepSeek-V3.2-Exp line) takes and returns ``aux`` behind the pages, as a
    hybrid stack's step does (``{"ki": [n_layer, blocks, BS, index lanes]}``,
    ``models/hybrid.py:init_aux``; ``slots`` and ``live`` are the hybrid
    walk's and are not read here): a token's index key is written under the
    same table as its latent, the layer's rows select through
    ``models/hybrid.py:select_and_attend`` and attend the chosen rows of the
    latent cache (``ops/pallas/indexed_attention.py``: a decode row
    ``chosen_latent_attention``, the absorbed form over gathered rows; the
    prompt chunk ``masked_latent_attention``, the plain form over its
    sequence under a mask: the heads' keys and values made from the latent
    by XLA, the attend a Pallas kernel that holds the chunk's queries in
    VMEM and skips the tiles of keys past the chunk's last position), under
    the scope ``attn_indexed``.  Tables of
    ``topk`` positions or fewer select nothing: the layer is
    ``paged_mla_attention`` over every key.

    Under ``cfg.hyper`` (residual streams: the xing4_0 line) what the walk
    carries from layer to layer is a token's STREAMS ``[B, S, n, E]``, each
    the embedding at the start and summed before the final norm: each of a
    layer's two sublayers reads them through :func:`hyper_read` (in the
    region before the scatter for attention, in the block's tail for the
    feed-forward) and its output is written through :func:`hyper_write`
    where a single stream adds it; attention's maps ride from the one region
    to the other beside its output, by row like everything else there.

    The walk (:func:`_walk_layers`) hands a layer the STACK and the layer's
    index, and every leaf is sliced where it is read (:class:`_LayerLeaves`).
    Under the dropless router the expert bank (``params["blocks"]["moe"]
    ["experts"]``, leaves ``[L, experts, ...]``) is not sliced at all: a
    layer hands ``grouped_matmul`` the stack and its own index, and the
    kernel reads the layer's tiles where they lie.  (A slice handed to a
    Pallas call is copied out first: every layer's bank would be written
    and read once more a step, more device time than its matmuls: PERF.md
    § 6, PR 38.)  Behind a dense lead (``moe_dense_layers``) the bank's
    stack is ``[expert layers, experts, ...]`` and a layer hands the kernel
    its index among the EXPERT layers.

    Rows without a request (idle decode slots, the rows past a prompt
    chunk's tokens) are discarded by the caller.  In a step WITH a chunk
    they run through every layer like the others (the bank alone computes
    nothing for them: ``dropless_moe(live=)``).  In a step WITHOUT one, which
    the step reads off its own input (the chunk's first row writes to the
    trash block), the chunk's rows go through nothing that is a function of
    a row alone (:func:`_rows_that_carry`: the norms, the projections, the
    gate, the MLP or the router and its shared expert, the head: two
    ``lax.cond`` a layer, before the scatter and after attention, and one
    round the head; each region a jitted function of the stacks and the
    layer's index, one trace for all layers of a kind); they come back as
    zeros, their K/V goes to the trash block and their logits are zeros.  The scatter and attention stay
    outside: the arena is no branch's operand.  Under the dropless router
    idle rows displace nothing; a router with a capacity would let them
    push live tokens out (and would count its capacity from the rows it is
    given), so ``init_serving`` refuses it.
    """
    assert cfg.scan_layers, "paged serving path requires scan_layers"
    B, S = input_ids.shape
    assert not chunk or S == 1, "a prompt chunk is named among one-token rows"
    H, E = cfg.n_head, cfg.n_embd
    n_kinds = len(cfg.pattern)
    if not isinstance(block_tables, (tuple, list)):
        block_tables, write_blocks = (block_tables,), (write_blocks,)
    assert len(block_tables) == len(write_blocks) == n_kinds
    MB = block_tables[0].shape[1]
    BS = k_pages.shape[2]
    T = MB * BS
    dt = cfg.dtype
    pos2d = positions[:, None] + jnp.arange(S)[None]          # [B, S]
    # the rows that carry a request; ``live[B - chunk]``, the chunk's first
    # row, says whether the step carries a chunk at all
    del slots, live
    live = (write_blocks[0] != 0).reshape(-1)
    by_row = partial(_rows_that_carry, chunk=chunk, live=live)
    # whether the layers select the rows of the latent cache they attend
    indexed = cfg.indexer is not None and T > cfg.indexer.topk
    assert not indexed or (aux is not None and S == 1 and (
        not mesh_lib.has_mesh() or mesh_lib.get_mesh().size == 1)), (
            "an indexer over a latent: a token a row, the index keys' pages "
            "in aux, one device (under a mesh it is not written)")
    scope = lambda name: jax.named_scope(name) if indexed else nullcontext()
    mixer_scope = partial(scope, "attn_indexed")

    x = _embed(cfg, params["wte"], input_ids, dt)
    if cfg.position_encoding == "learned":
        x = x + params["wpe"].astype(dt)[
            jnp.clip(pos2d, 0, cfg.n_positions - 1)]
    if cfg.hyper is not None:
        # the carry is a token's STREAMS [B, S, n, E], each the embedding
        x = jnp.repeat(x[:, :, None], cfg.hyper.streams, axis=2)
    x = _constrain(x, mesh_lib.BATCH_AXES, *(None,) * (x.ndim - 1))

    if cfg.position_encoding == "alibi":
        from deepspeed_tpu.ops.attention import alibi_slopes
        slopes = jnp.asarray(alibi_slopes(H))
        kpos = jnp.arange(T)[None, None, None, :]
        qpos = pos2d[:, None, :, None]
        attn_bias = slopes[None, :, None, None] * (
            kpos - qpos).astype(jnp.float32)                  # [B, H, S, T]
    else:
        attn_bias = None

    blocks, bank = params["blocks"], None
    if cfg.moe_num_experts and cfg.moe_router == "dropless":
        moe = dict(blocks["moe"])
        bank = moe.pop("experts")
        blocks = {**blocks, "moe": moe}
    # behind a dense lead the feed-forward leaves are stacked by kind: a
    # layer reads the attention leaves at its index, its feed-forward's at
    # its index among the layers of its kind
    lead, by_kind = cfg.moe_dense_layers, None
    if lead:
        blocks = dict(blocks)
        by_kind = {"lead": blocks.pop("lead"), "moe": {"moe": blocks.pop("moe")}}
    # which tiles of each group's tables its kernel fetches with one copy
    # (None: it copies page by page): the same for every layer, so worked
    # out here and not in the scan
    plans = cfg.paged_plans(BS, [t.shape[1] for t in block_tables], chunk,
                            k_pages.dtype)
    tile_runs = [plan.tile_runs(tables, k_pages.shape[1])
                 for plan, tables in zip(plans, block_tables)]
    W, pages_dt, dr = k_pages.shape[-1], k_pages.dtype, cfg.qk_rope_dim
    lanes = lambda t, width: jnp.pad(
        t, ((0, 0),) * (t.ndim - 1) + ((0, width - t.shape[-1]),))

    # What is a function of a row alone runs in TWO regions a layer, before
    # the scatter and after attention, over the rows that carry (``by_row``);
    # the arena stays out of both.  Each region is a jitted function of the
    # STACKS and the layer's index, so that the layers of one kind share ONE
    # trace and one lowering of each of its two bodies (all rows; the decode
    # rows alone): traced a layer, the second bodies added 3.6 s to the start
    # of an engine of eight layers (PERF.md § 6, PR 56)
    @partial(jax.jit, static_argnums=0)
    def project(j, blocks, l, x, pos):
        """-> the normed input, the queries as attention takes them, what
        the arena caches of each token (K and V, or the latent) and the maps
        attention's output is written through (:func:`hyper_read`; () where a
        token carries one stream)."""
        p = _LayerLeaves(blocks, l)
        u, maps = (x, ()) if cfg.hyper is None else hyper_read(cfg, p, "attn", x, dt)
        h = _norm(cfg, u, p["ln1_g"], p["ln1_b"])
        if not cfg.kv_lora_rank:
            q, k, v = _project_qkv(cfg, p, h, dt, pos, cfg.pattern[j])
            return (h, q, k.astype(pages_dt).reshape(*k.shape[:2], -1),
                    v.astype(pages_dt).reshape(*v.shape[:2], -1), maps)
        cq = _query_latent(cfg, p, h, dt)
        with scope("latent_project"):
            plain, cache = _latent_project(cfg, p, h, dt, pos, cq)
            # a head's query in the cached vector's lanes: its no-position
            # part through W_UK, its rotated part as it is
            q = jnp.concatenate(
                [jnp.einsum("bshd,rhd->bshr", plain[..., :-dr],
                            _latent_up(cfg, p, dt)[0]), plain[..., -dr:]], axis=-1)
        out = h, lanes(q, W), lanes(cache.astype(pages_dt), W), None, maps
        if not indexed:
            return out
        with jax.named_scope("index_score"):
            qi, ki, w = _index_project(cfg, p, cq, h, dt, pos)
        return (*out, plain, qi[:, 0], ki[:, 0], w[:, 0])

    @partial(jax.jit, static_argnums=0)
    def tail(dense, stacks, l, x, h, o, live, *maps):
        """-> the block's output and its expert counts, from attention's
        output; ``dense``: a layer of the dense lead; ``maps``: what
        ``project`` gave of them."""
        blocks, by_kind, bank = stacks
        p = _LayerLeaves(blocks, l)
        if dense:
            p = p.beside(by_kind["lead"], l)
        elif lead:
            p = p.beside(by_kind["moe"], l - lead)
        with jax.named_scope("attn"), mixer_scope():
            if cfg.kv_lora_rank and not indexed:    # an indexed layer's heads are up already
                with scope("latent_project"):
                    o = jnp.einsum("bshr,rhd->bshd", o, _latent_up(cfg, p, dt)[1])
            o = _attn_out(cfg, p, o.reshape(*o.shape[:2], -1), h, dt)
        with jax.named_scope("mlp"):
            return _block_tail(
                cfg, p, x, h, o, dt, live,
                bank_at=None if bank is None or dense else (bank, l - lead),
                maps=maps or None)

    def attend_chosen(q, plain, up, kp, index, ki, li, j):
        """The rows' attention over the rows of the latent cache their
        indexer chose: -> (the heads' outputs ``[B, 1, H, v_head_dim]``, the
        index keys' pages with the rows' keys written).  A decode row gathers
        its ``topk`` rows of layer ``li``'s pages and attends them in the
        absorbed form (``q [B, 1, H, W]``), its output brought up through
        W_UV; the prompt chunk's queries (``plain [B, 1, H, head_dim]``), a
        set of its own each, attend their sequence's rows, read once, in the
        plain form under the selection's mask (the kernel
        ``masked_latent_attention``), and no more of them than the chunk can
        see: the least extent that holds it (:data:`CHUNK_EXTENTS`), and of
        that the key tiles up to its last position.  ``up``: the layer's W_UK and
        W_UV (:func:`_latent_up`)."""
        from deepspeed_tpu.models import hybrid
        from deepspeed_tpu.ops.pallas.indexed_attention import (
            chosen_latent_attention, masked_latent_attention)
        n_dec = B - chunk

        def attend_rows(tb, at, real):
            rows = kp[li, jnp.take_along_axis(tb, at // BS, axis=1), at % BS]
            o = chosen_latent_attention(q[:n_dec, 0], rows, real, scale=plans[j].scale,
                                        value_lanes=cfg.kv_lora_rank)
            return jnp.einsum("nhr,rhd->nhd", o, up[1])

        def attend_chunk(tb, chosen, last):
            return masked_latent_attention(
                plain[n_dec:, 0], kp[li, tb[0]].reshape(chosen.shape[1], -1), chosen,
                last, *up, scale=plans[j].scale)

        step = hybrid._Step(positions, live, None, block_tables[j],
                            write_blocks[j], write_offsets, chunk, dt, None)
        o, ki = hybrid.select_and_attend(
            cfg, *index, ki, li, step, BS, attend_rows, attend_chunk,
            extents=CHUNK_EXTENTS)
        return o[:, None], ki

    def layer(j, carry, l):
        # ``l``: the layer's index in the stack, a Python int for the layers
        # walked before the scan, which says whether it is of the dense lead;
        # ``li``: its index inside its group ``j`` (the period); ``ki``: the
        # index keys' pages (None without an indexer)
        x, kp, vp, li, ki = carry
        kind, wblocks = cfg.pattern[j], write_blocks[j]
        dense = isinstance(l, int) and l < lead
        at = jnp.asarray(l, jnp.int32)
        with jax.named_scope("attn"), mixer_scope():
            h, q, k, v, maps, *index = by_row(partial(project, j, blocks, at), (x, pos2d))
            # scatter the new K/V into the arena through the write map; rows
            # that must not write (padding, inactive slots) carry trash-block
            # coordinates, so the scatter itself needs no predication
            kp = kp.at[li, wblocks, write_offsets].set(k)
            if indexed:
                plain, *index = index
                with scope("latent_project"):
                    up = _latent_up(cfg, _LayerLeaves(blocks, at), dt)
                o, ki = attend_chosen(q, plain, up, kp, index, ki, li, j)
            elif cfg.kv_lora_rank:
                with jax.named_scope("attn_latent"):
                    o = plans[j].attend(
                        q, (kp, None), li, block_tables[j], positions,
                        tile_runs=tile_runs[j], chunk=chunk)
            else:
                vp = vp.at[li, wblocks, write_offsets].set(v)
                with jax.named_scope(
                        "attn_full" if kind.window is None else "attn_window"):
                    o = plans[j].attend(
                        q, (kp, vp), li, block_tables[j], positions,
                        tile_runs=tile_runs[j], chunk=chunk, bias=attn_bias)
        x, counts = by_row(partial(tail, dense, (blocks, by_kind, bank), at),
                           (x, h, o, live, *maps), totals=1)
        if dense:
            counts = jnp.zeros((cfg.moe_num_experts,), jnp.int32)
        return (x, kp, vp, li + int(j == n_kinds - 1), ki), counts

    # behind a dense lead, the layers up to the first whole period of expert
    # layers are walked here at static indices; the scan takes the rest
    carry = (x, k_pages, v_pages, jnp.zeros((), jnp.int32),
             aux["ki"] if indexed else None)
    first, walked = -(-lead // n_kinds) * n_kinds, []
    for l in range(first):
        carry, c = layer(l % n_kinds, carry, l)
        walked.append(c)
    (x, k_pages, v_pages, _, ki), counts = _walk_layers(
        n_kinds, layer, carry, cfg.n_layer, first)
    if walked:
        counts = jnp.concatenate([jnp.stack(walked), counts])

    def to_logits(x):
        if cfg.hyper is not None:       # the streams leave as their sum
            x = x.astype(jnp.float32).sum(axis=-2).astype(dt)
        x = _norm(cfg, x, params["lnf_g"], params["lnf_b"])
        head = params["lm_head"] if cfg.untied_head else params["wte"]
        logits = (x @ head.astype(dt).T).astype(jnp.float32)
        if cfg.head_bias:
            logits = logits + params["lm_head_b"].astype(jnp.float32)
        return logits

    with jax.named_scope("head"):
        logits = by_row(to_logits, (x,))
    out = (logits, k_pages, v_pages)
    if aux is not None:
        out += (dict(aux, ki=ki) if indexed else aux,)
    if with_expert_counts:
        out += (counts.sum(axis=0),)
    return out


# --------------------------------------------------------------------------- #
# Pipeline-parallel layer classes (for PipelineModule / PipelineEngine)
# --------------------------------------------------------------------------- #
class GPTEmbedLayer:
    """Token+position embedding as pipeline stage-0 layer."""

    def __init__(self, cfg: GPTConfig):
        self.cfg = cfg

    def init_params(self, rng):
        return _init_embed(self.cfg, rng)

    def partition_specs(self):
        return {"wte": PartitionSpec("tensor", None), "wpe": PartitionSpec()}

    def __call__(self, p, ids, rng=None, train=False):
        dt = self.cfg.dtype
        S = ids.shape[-1]
        x = p["wte"].astype(dt)[ids] + p["wpe"].astype(dt)[:S][None]
        x = _dropout(x, self.cfg.dropout, rng, train)
        return _constrain(x, mesh_lib.BATCH_AXES, "seq", None)


class GPTBlockLayer:
    """One transformer block as a homogeneous pipeline middle layer."""

    def __init__(self, cfg: GPTConfig):
        self.cfg = cfg

    def init_params(self, rng):
        return _init_block(self.cfg, rng)

    def partition_specs(self):
        return dict(_BLOCK_SPECS)

    def __call__(self, p, x, rng=None, train=False):
        from deepspeed_tpu.ops.attention import get_attention_fn
        assert self.cfg.moe_num_experts == 0, (
            "MoE blocks in the pipeline engine are not supported yet — "
            "use the scan (non-pipeline) model for MoE training")
        _refuse_hybrid(self.cfg, "the pipeline engine's block")
        x, _ = gpt_block(self.cfg, p, x, rng=rng, train=train,
                         attention_fn=get_attention_fn(self.cfg.attn_impl))
        return x


class GPTHeadLayer:
    """Final LN + (untied) unembedding projection."""

    def __init__(self, cfg: GPTConfig):
        self.cfg = cfg

    def init_params(self, rng):
        cfg = self.cfg
        return {"lnf_g": jnp.ones((cfg.n_embd,), jnp.float32),
                "lnf_b": jnp.zeros((cfg.n_embd,), jnp.float32),
                "unembed": _dense_init(rng, cfg.n_embd, (cfg.n_embd, cfg.padded_vocab))}

    def partition_specs(self):
        return {"lnf_g": PartitionSpec(), "lnf_b": PartitionSpec(),
                "unembed": PartitionSpec(None, "tensor")}

    def __call__(self, p, x, rng=None, train=False):
        x = layer_norm(x, p["lnf_g"], p["lnf_b"])
        logits = (x @ p["unembed"].astype(x.dtype)).astype(jnp.float32)
        return _constrain(logits, mesh_lib.BATCH_AXES, "seq", "tensor")


def gpt_ce_loss_fn(cfg: GPTConfig):
    def loss_fn(logits, labels):
        if cfg.padded_vocab != cfg.vocab_size:
            mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
            logits = jnp.where(mask[None, None, :], logits, -1e9)
        logp = jax.nn.log_softmax(logits, axis=-1)
        # one-hot contraction, NOT take_along_axis: logits are
        # vocab-parallel (sharded 'tensor'), and the vjp of a gather on a
        # sharded dim (a scatter) sends the SPMD partitioner into a
        # pathological compile inside the 1F1B pipeline's scan; the
        # contraction partitions as a local reduce + psum and XLA fuses
        # the one-hot select without materializing it
        onehot = jax.nn.one_hot(labels, logp.shape[-1], dtype=logp.dtype)
        ll = jnp.sum(logp * onehot, axis=-1)
        return -jnp.mean(ll)
    return loss_fn


class GPTTiedHeadLayer:
    """Final LN + unembedding through the TIED token embedding: the tied
    params arrive as the embed layer's pytree (reference ``TiedLayerSpec``
    reuse-site ``forward_fn``, ``pipe/module.py:76``)."""

    def __init__(self, cfg: GPTConfig):
        self.cfg = cfg

    def init_params(self, rng):
        return {"lnf_g": jnp.ones((self.cfg.n_embd,), jnp.float32),
                "lnf_b": jnp.zeros((self.cfg.n_embd,), jnp.float32)}

    def partition_specs(self):
        return {"lnf_g": PartitionSpec(), "lnf_b": PartitionSpec()}

    def __call__(self, p, x, tied=None, rng=None, train=False):
        x = layer_norm(x, p["lnf_g"], p["lnf_b"])
        logits = (x @ tied["wte"].astype(x.dtype).T).astype(jnp.float32)
        return _constrain(logits, mesh_lib.BATCH_AXES, "seq", "tensor")


def gpt_pipeline_module(cfg: GPTConfig, num_stages: int, tied_embedding: bool = False):
    """Layer-list GPT for the PipelineEngine (the analogue of building a
    Megatron GPT from ``LayerSpec``s, reference ``pipe/module.py:85``).
    ``tied_embedding=True`` shares wte between embed and head via
    ``TiedLayerSpec`` (reference embedding/unembedding tying)."""
    from deepspeed_tpu.runtime.pipe.module import (LayerSpec, PipelineModule,
                                                   TiedLayerSpec)
    blocks = [LayerSpec(GPTBlockLayer, cfg) for _ in range(cfg.n_layer)]
    if tied_embedding:
        specs = ([TiedLayerSpec("embed", GPTEmbedLayer, cfg)] + blocks
                 + [TiedLayerSpec("embed", GPTTiedHeadLayer, cfg)])
    else:
        specs = ([LayerSpec(GPTEmbedLayer, cfg)] + blocks
                 + [LayerSpec(GPTHeadLayer, cfg)])
    return PipelineModule(layers=specs, num_stages=num_stages,
                          loss_fn=gpt_ce_loss_fn(cfg))


class GPT:
    """Engine-compatible model object (``.apply``-free callable convention:
    ``fn(params, batch, rng, train) -> loss``) with ``init_params``."""

    # the scan branch consumes per-block slices through the layered ZeRO-3
    # prefetch context (engine gates the overlapped step on this attribute)
    supports_layered_zero3 = True

    def __init__(self, cfg: GPTConfig):
        self.cfg = cfg

    def __call__(self, params, batch, rng, train, pld_theta=None, **_ignored):
        input_ids, labels = batch
        return gpt_loss(self.cfg, params, input_ids, labels, rng, train,
                        pld_theta=pld_theta)

    def init_params(self, rng):
        return init_gpt_params(self.cfg, rng)

    def partition_specs(self):
        return gpt_partition_specs(self.cfg)

    # ---- inference decode protocol (InferenceEngine contract) --------- #
    def init_cache(self, batch: int, max_len: int):
        return init_kv_cache(self.cfg, batch, max_len)

    def apply_with_cache(self, params, input_ids, cache):
        return gpt_apply_with_cache(self.cfg, params, input_ids, cache)

    def forward_logits(self, params, input_ids):
        return gpt_forward(self.cfg, params, input_ids, rng=None, train=False)

    def generate(self, params, input_ids, max_new_tokens, rng=None,
                 temperature: float = 0.0, prompt_len=None):
        return gpt_generate(self.cfg, params, input_ids, max_new_tokens,
                            rng=rng, temperature=temperature,
                            prompt_len=prompt_len)

    def serving_params(self, params):
        """Serving-engine protocol: the tree its step reads, made once as the
        engine takes ``params`` (:func:`serving_params`)."""
        return serving_params(params)

    def paged_step(self, params, input_ids, positions, k_pages, v_pages,
                   block_tables, write_blocks, write_offsets, **kw):
        """Serving-engine protocol: one step over the paged KV arena
        (``deepspeed_tpu/serving/engine.py``).  A hybrid stack's step takes
        and returns its state beside the pages (``aux``, ``slots``,
        ``live``: ``models/hybrid.py:hybrid_paged_step``)."""
        if self.cfg.hybrid:
            from deepspeed_tpu.models import hybrid
            return hybrid.hybrid_paged_step(
                self.cfg, params, input_ids, positions, k_pages, v_pages,
                block_tables, write_blocks, write_offsets, **kw)
        return gpt_paged_step(self.cfg, params, input_ids, positions,
                              k_pages, v_pages, block_tables,
                              write_blocks, write_offsets, **kw)

    def num_params(self, active: bool = False) -> int:
        """Parameters held; ``active``: those one token multiplies by (an
        MoE model's router and ``moe_top_k`` of its experts)."""
        cfg = self.cfg
        if cfg.hybrid:
            from deepspeed_tpu.models import hybrid
            return hybrid.num_params(cfg)
        E, L = cfg.n_embd, cfg.n_layer
        b = int(cfg.use_bias)
        I = (cfg.moe_num_experts and cfg.moe_expert_hidden) or cfg.ffn_dim
        fc_out = 2 * I if cfg.mlp_type == "swiglu" else I
        mlp = E * fc_out + I * E + b * (fc_out + E)     # up (gate|up), down
        lead = 0
        if cfg.moe_num_experts:
            # the dense lead's MLPs, ``ffn_dim`` wide, in place of a bank each
            If = cfg.ffn_dim * (2 if cfg.mlp_type == "swiglu" else 1)
            lead_mlp = E * If + cfg.ffn_dim * E + b * (If + E)
            # the router (and its bias), the experts HELD (or a token's),
            # the shared expert
            mlp = (cfg.moe_num_experts * (E + int(cfg.moe_scoring == "sigmoid"))
                   + mlp * (cfg.moe_top_k if active else cfg.bank_experts[1])
                   + mlp * cfg.moe_shared_experts)
            lead = cfg.moe_dense_layers * (lead_mlp - mlp)
        qk_norm = ((2 if cfg.qk_norm == "head" else cfg.n_head + cfg.kv_heads)
                   * cfg.head_dim * int(bool(cfg.qk_norm)))
        # gain (and shift), and the two on a sublayer's output
        norm = (2 if cfg.norm == "layernorm" else 1) * E
        gate = E * cfg.n_head * cfg.v_head_dim * int(cfg.attn_gate)
        if cfg.kv_lora_rank:        # two low-rank chains and their norms
            Rq, R, H = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.n_head
            qkv = (E * Rq + Rq + Rq * H * cfg.head_dim
                   + E * (R + cfg.qk_rope_dim) + R
                   + R * H * (cfg.head_dim - cfg.qk_rope_dim + cfg.v_head_dim))
            if cfg.indexer is not None:     # queries, [key | weights], LayerNorm
                ix = cfg.indexer
                qkv += (Rq * ix.heads * ix.head_dim
                        + E * (ix.head_dim + ix.heads) + 2 * ix.head_dim)
        else:
            qkv = E * cfg.qkv_dim + b * cfg.qkv_dim      # qkv (GQA-sized)
        hyper = 0
        if cfg.hyper is not None:   # phi, b and alpha, a sublayer
            n = cfg.hyper.streams
            hyper = 2 * ((n * E + 1) * (2 * n + n * n) + 3)
        per_block = (qkv + gate + cfg.n_head * cfg.v_head_dim * E + b * E  # attn out
                     + mlp + qk_norm + (4 if cfg.norm_sandwich else 2) * norm + hyper)
        total = cfg.padded_vocab * E + L * per_block + lead + norm
        if cfg.position_encoding == "learned":
            total += cfg.n_positions * E
        if cfg.untied_head:
            total += cfg.padded_vocab * E
        return total

    def flops_per_token(self, seq_len: int) -> float:
        """Training FLOPs/token ≈ 6N + attention term (PaLM appendix B),
        N the parameters a token is multiplied by."""
        cfg = self.cfg
        n = self.num_params(active=True)
        attn = 12 * cfg.n_layer * cfg.n_embd * seq_len
        return 6 * n + attn
