"""Dropless top-k routing: every assignment the router makes is computed.

The GShard router beside this one (``sharded_moe.py``) gives each expert a
fixed capacity and drops what does not fit, through dense one-hot
``[T, E, C]`` tensors.  Models of the OLMoE / DeepSeek line never drop a
token, and at 64 experts with 8 a token that tensor is larger than the work.
Here the ``T*k`` assignments are sorted by expert, the rows gathered in that
order, and the bank runs as grouped matrix multiplications
(``ops/pallas/grouped_matmul.py``: a kernel that streams the bank once on
one TPU chip, ``jax.lax.ragged_dot`` elsewhere), so the shapes are static and
nothing depends on how even the routing is.

The four stages open the scopes ``moe_router``, ``moe_dispatch``,
``moe_experts`` and ``moe_combine`` (the caller opens ``moe`` round them, and
``moe_shared`` inside it round an expert every token goes through):
per-layer metrics read a trace by these names.
"""

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas.grouped_matmul import (grouped_matmul,
                                                     rows_to_whole_tiles)

Array = jax.Array


def softmax_topk(logits: Array, k: int, renormalise: bool = False
                 ) -> Tuple[Array, Array, Array]:
    """``logits [T, E]`` -> (probs ``[T, E]``, weights ``[T, k]``, experts
    ``[T, k]`` int32).  The softmax is over ALL experts, in float32, and the
    weights are the raw probabilities of the chosen ``k`` (OLMoE's
    ``norm_topk_prob`` false) or, with ``renormalise``, those divided by
    their sum: the softmax over the ``k`` chosen logits alone
    (SmallThinker's ``norm_topk_prob`` true), computed as that."""
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    if renormalise:
        chosen, experts = jax.lax.top_k(logits, k)
        weights = jax.nn.softmax(chosen, axis=-1)
    else:
        weights, experts = jax.lax.top_k(probs, k)
    return probs, weights, experts.astype(jnp.int32)


def limit_to_groups(chosen: Array, n_group: int, topk_group: int) -> Array:
    """``chosen [T, E]`` (what the router chooses by) with all but
    ``topk_group`` of its ``n_group`` groups of ``E / n_group`` consecutive
    experts at -inf: a group's score is the sum of its TWO largest entries,
    the groups that score highest stay (of equal scores the lower group)."""
    T, E = chosen.shape
    assert E % n_group == 0 and 1 <= topk_group <= n_group, (E, n_group, topk_group)
    groups = chosen.reshape(T, n_group, E // n_group)
    score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)           # [T, n_group]
    kept = jax.lax.top_k(score, topk_group)[1]
    stays = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None], axis=1)
    return jnp.where(stays[:, :, None], groups, -jnp.inf).reshape(T, E)


def sigmoid_topk(logits: Array, k: int, bias: Optional[Array] = None,
                 renormalise: bool = True, scale: float = 1.0,
                 n_group: int = 1, topk_group: int = 1
                 ) -> Tuple[Array, Array, Array]:
    """As :func:`softmax_topk` for the router of the DeepSeek-V3 line: an
    expert's score is the SIGMOID of its own logit, in float32; the ``k``
    largest of ``score + bias`` are chosen (the score-correction bias
    chooses and never weighs), with ``topk_group`` under ``n_group`` among
    the groups that stay alone (:func:`limit_to_groups`, under the scope
    ``route_groups``; at 1 and 1 there is no limit), and weighed by their
    scores, with
    ``renormalise`` divided by their sum (``norm_topk_prob``), then times
    ``scale`` (``routed_scaling_factor``, ``route_scale``).  The first
    result is the scores over their sum, for the load-balance loss."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    chosen = scores if bias is None else scores + bias.astype(jnp.float32)
    if topk_group < n_group:
        with jax.named_scope("route_groups"):
            chosen = limit_to_groups(chosen, n_group, topk_group)
    experts = jax.lax.top_k(chosen, k)[1]
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    if scale != 1.0:
        weights = weights * scale
    return (scores / jnp.sum(scores, axis=-1, keepdims=True), weights,
            experts.astype(jnp.int32))


def stream_mlp_logits(h: Array, prev: Array, w_in: Array, scale: Array,
                      norm_g: Array, mlp: Sequence[Array], eps: float
                      ) -> Tuple[Array, Array]:
    """The logits of a router that carries a stream of its own from layer to
    layer (the ZAYA1 line): ``stream = h W_in + scale * prev`` (``h [T, M]``
    the layer's normed input, ``prev [T, R]`` the stream the layer before
    left, ``scale`` a learned scalar: the stream is an average over depth),
    then an RMSNorm and an MLP of tanh-GELUs without bias, ``mlp`` its
    matrices ``[R, R] ... [R, E]``.  Everything in float32 at the highest
    precision: with ONE expert a token a swapped expert is the token's whole
    routed output.  -> (stream ``[T, R]`` for the next layer, logits ``[T,
    E]``)."""
    f32 = lambda a: a.astype(jnp.float32)
    dot = lambda a, w: jnp.dot(a, f32(w), precision=jax.lax.Precision.HIGHEST)
    stream = dot(f32(h), w_in) + f32(scale) * prev
    z = stream * jax.lax.rsqrt(jnp.mean(jnp.square(stream), -1, keepdims=True) + eps)
    z = z * f32(norm_g)
    for w in mlp[:-1]:
        z = jax.nn.gelu(dot(z, w), approximate=True)
    return stream, dot(z, mlp[-1])


def biased_softmax_topk(logits: Array, k: int, bias: Array
                        ) -> Tuple[Array, Array, Array]:
    """As :func:`softmax_topk` under a balancing bias: the softmax over ALL
    experts in float32, the ``k`` largest of ``probs + bias`` chosen (the
    bias chooses and never weighs), weighed by their own probabilities, not
    renormalised."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    experts = jax.lax.top_k(probs + bias.astype(jnp.float32), k)[1]
    return (probs, jnp.take_along_axis(probs, experts, axis=-1),
            experts.astype(jnp.int32))


def load_balance_loss(probs: Array, experts: Array) -> Array:
    """Switch/GShard's ``E * sum_e(mean prob_e * share of assignments_e)``
    over all ``k`` choices: 1.0 when routing is even."""
    E = probs.shape[-1]
    share = expert_counts(experts, E).astype(jnp.float32) / experts.size
    return E * jnp.sum(jnp.mean(probs, axis=0) * share)


def expert_counts(experts: Array, num_experts: int,
                  live: Optional[Array] = None) -> Array:
    """Assignments per expert, ``[E]`` int32; ``live [T]`` leaves the rows
    that carry no request out of the count."""
    flat = experts.reshape(-1)
    w = None if live is None else jnp.repeat(live.astype(jnp.int32),
                                             experts.shape[-1])
    return jnp.bincount(flat, weights=w, length=num_experts).astype(jnp.int32)


def dropless_moe(x: Array, weights: Array, experts: Array, num_experts: int,
                 expert_fn: Callable,
                 held: Optional[Tuple[int, int]] = None,
                 layer: Optional[Array] = None,
                 live: Optional[Array] = None) -> Array:
    """``x [T, M]`` through its ``k`` experts each, weighted and summed, in
    float32.

    ``held = (first, count)``: the bank holds the experts ``first .. first +
    count - 1`` alone (its stacked leaves are ``count`` long).  An
    assignment to an expert that is not here sorts behind the last group and
    lies in none, as the rows added for whole tiles do, so the bank computes
    nothing for it, and it adds nothing to the sum: the result is this
    bank's PART of the layer.  ``live [T]``: the rows that carry a request
    (a serve step's idle slots and idle chunk rows do not); the others'
    assignments lie in no group either and their result is zero.  Nobody
    reads an idle row, but idle rows hold one token at one position and so
    choose the SAME experts: left in, each of those experts multiplied
    hundreds of rows for nobody, and which of them a held share holds is
    the seed's (PERF.md § 6, PR 55).

    ``expert_fn(rows, matmul, pick)`` is the expert's own arithmetic on the
    sorted rows ``[T*k, M]``: ``matmul(rows, w)`` multiplies each row by ITS
    expert's slice of a stacked ``w [E, in, out]``, and ``pick(b)`` gives
    each row its expert's slice of a stacked ``b [E, out]`` (a bias).  Both
    take the leaf AS STORED, of any type (``grouped_matmul`` converts what
    it reads).  With ``layer`` (an int32 scalar) the leaves are those of ALL
    layers, ``w [L, E, in, out]`` and ``b [L, E, out]``, and the two read
    layer ``layer`` of them in place: the inference paths' form, which has
    no gradient (``grouped_matmul``)."""
    T, k = experts.shape
    # rows added behind the sorted assignments so that the kernel's whole
    # row tiles hold them (0 where they already do, or no kernel runs): they
    # lie in no group, so the bank computes nothing for them, and are cut off
    pad = rows_to_whole_tiles(T * k, x.shape[1], x.dtype)
    here = None             # [T, k]: the assignments this bank computes (None: all)
    if held is not None:
        first, num_experts = held
        here = (experts >= first) & (experts < first + num_experts)
        experts = experts - first
    if live is not None:
        here = live[:, None] if here is None else here & live[:, None]
    if here is not None:
        experts = jnp.where(here, experts, num_experts)
    with jax.named_scope("moe_dispatch"):
        flat = experts.reshape(-1)
        order = jnp.argsort(flat, stable=True)        # assignments by expert
        sizes = expert_counts(experts, num_experts)
        rows = x[order // k]                          # [T*k, M]
        if pad:
            rows = jnp.pad(rows, ((0, pad), (0, 0)))
    with jax.named_scope("moe_experts"):
        of_row = jnp.pad(flat[order], (0, pad))
        y = expert_fn(rows, lambda a, w: grouped_matmul(a, w, sizes, layer),
                      lambda b: b[of_row] if layer is None else b[layer, of_row])
        if pad:
            y = y[:T * k]
    with jax.named_scope("moe_combine"):
        # back to token order by a gather (no scatter-add), then the
        # weighted sum over the k choices
        y = y[jnp.argsort(order)].reshape(T, k, -1)
        if here is not None:
            # a row in no group is whatever the kernel's output buffer held
            y = jnp.where(here[..., None], y, 0)
        return jnp.einsum("tkm,tk->tm", y.astype(jnp.float32), weights)
