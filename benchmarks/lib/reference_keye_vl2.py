"""The plain reference for the language model of Keye-VL-2.0-30B-A3B
(``model_type`` ``KeyeVL2`` of
``https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B``; text requests, no
vision tower): the forward pass in straightforward ``jax.numpy`` and float32
under ``default_matmul_precision("highest")``.  No kernel, no cache, no pages,
no batching, no gather of chosen keys and no grouped product: a layer's
attention is a dense score matrix with the selection applied as a MASK from
an exact top-k, and every expert's product is computed for every token and
weighted by the routed weights.

``h = RMSNorm(x)`` (eps 1e-6); ``E`` = hidden, ``d`` = head_dim (128), ``dI``
= indexer_head_dim (64), ``J`` = indexer_num_heads (16); rope is the
half-split rotation at ``rope_theta`` over ALL lanes of a head (text
positions make the three streams of ``mrope_section`` one); every layer:

    q_t = rope(rms_h(W_q h_t))   32 heads of d      rms_h: an RMS norm over a
    k_t = rope(rms_h(W_k h_t))   4 heads of d       head's lanes, one learned
    v_t = W_v h_t                                   gain [d] for all heads
    qI_{t,j} = rope(W_qI h_t)_j  in R^dI, j = 1..J
    kI_t = rope(LN(W_kI h_t))    in R^dI, ONE head; LN a LayerNorm with gain
                                 and bias (the published DSA code's k_norm)
    w_t = W_w h_t                in R^J
    I_{t,s} = dI^-1/2 J^-1/2 sum_j w_{t,j} relu(qI_{t,j} . kI_s)     s <= t
    S_t = the topk positions s <= t of largest I_{t,s}, ties to the lower
          position; all of them while t < topk.  One set a token, shared by
          its 32 heads
    o_{t,h} = sum_{s in S_t} softmax_s(q_{t,h} . k_{s,g(h)} / sqrt(d)) v_{s,g(h)}
    x <- x + W_o o                                  no output gate, no bias
    z = RMSNorm_2(x);  p = softmax(W_r z) over the experts; the top_k largest
    divided by their sum (norm_topk_prob)
    x <- x + sum_e p_e W2_e (silu(W1_e z) * W3_e z)     no shared expert
    logits = lm_head(RMSNorm(x))                    untied

It reads the program's parameter tree by its leaf names:
``blocks/indexed/{ln1_g, qkv_w, q_norm_g, k_norm_g, out_w, index_w, ik_norm_g,
ik_norm_b, ln2_g, router_w, experts/{wi, wo}}``; ``wte``, ``lnf_g``,
``lm_head``.  The weights are the system's, the arithmetic is not.
Departures and assumptions:

* W_q, W_k, W_v are the column blocks of ``qkv_w``; W_qI, W_kI, W_w those of
  ``index_w``; W1 (gate) and W3 (up) the two column halves of ``wi``;
* the indexer reads ``h_t`` (DSA reads the query's latent, which grouped-query
  attention has none of) and ropes all ``dI`` lanes of an index head at the
  model's theta;
* the indexer is computed in float32: DeepSeek's FP8 indexer and the Hadamard
  rotation before it are an implementation's, not the model's;
* ``q_chunk_size`` / ``kv_chunk_size`` are tile sizes of the published
  prefill kernel and change no number;
* rows of the embedding and the head beyond the vocabulary are cut off;
* everything a token does alone and a layer's scores run a block of
  ``q_block`` rows at a time, a head at a time, an expert at a time, and one
  layer's matrices at a time are made float32, so that 46,080 positions fit
  beside 8.75 GB of resident bf16 weights: the blocks change the order of
  nothing that is summed.
"""

import math

import jax
import jax.numpy as jnp


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _rope(x, theta):
    """``x [S, heads, D]`` at positions 0..S-1, half-split pairing over all D."""
    S, _, D = x.shape
    half = D // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None])[:, None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _rows(fn, qb, *xs):
    """``fn`` over blocks of ``qb`` rows of each of ``xs``, the results laid
    end to end again."""
    S = xs[0].shape[0]
    out = jax.lax.map(lambda b: fn(*(jax.lax.dynamic_slice_in_dim(
        x, b * qb, qb) for x in xs)), jnp.arange(S // qb))
    return out.reshape(S, *out.shape[2:])


def chosen_mask(scores, k):
    """``scores [rows, S]`` (-inf where a key is not seen) -> which keys each
    row attends ``[rows, S]``: the ``k`` largest, of equal ones the lower
    positions, never an unseen one."""
    kth = jax.lax.top_k(scores, min(k, scores.shape[-1]))[0][:, -1:]
    above, equal = scores > kth, scores == kth
    wanted = k - above.sum(axis=-1, keepdims=True)
    chosen = above | (equal & (jnp.cumsum(equal, axis=-1) <= wanted))
    return chosen & (scores > -jnp.inf)


def index_scores(qi, ki, w, t):
    """``qi [rows, J, dI]``, ``ki [S, dI]``, ``w [rows, J]`` of queries at the
    positions ``t [rows]`` -> ``I [rows, S]``, -inf at the keys after each."""
    J, dI = qi.shape[1], qi.shape[2]

    def head(total, j):
        s = jax.lax.dynamic_index_in_dim(qi, j, 1, False) @ ki.T
        return total + jax.lax.dynamic_index_in_dim(w, j, 1, True) * jax.nn.relu(s), None

    total, _ = jax.lax.scan(head, jnp.zeros((qi.shape[0], ki.shape[0]), jnp.float32),
                            jnp.arange(J))
    total = total / math.sqrt(dI * J)
    return jnp.where(jnp.arange(ki.shape[0])[None] <= t[:, None], total, -jnp.inf)


def _mixer(p, x, norm, *, H, Hkv, D, J, dI, topk, eps, theta, qb):
    f32 = lambda a: a.astype(jnp.float32)
    S, g = x.shape[0], H // Hkv
    qkv = _rows(lambda r: norm(r) @ f32(p["qkv_w"]), qb, x)
    q, k, v = jnp.split(qkv, [H * D, (H + Hkv) * D], axis=-1)
    q = _rope(_rms(q.reshape(S, H, D), f32(p["q_norm_g"]), eps), theta)
    k = _rope(_rms(k.reshape(S, Hkv, D), f32(p["k_norm_g"]), eps), theta)
    v = v.reshape(S, Hkv, D)
    ind = _rows(lambda r: norm(r) @ f32(p["index_w"]), qb, x)
    qi, ki, w = jnp.split(ind, [J * dI, (J + 1) * dI], axis=-1)
    qi = _rope(qi.reshape(S, J, dI), theta)
    ki = _rope(_layer_norm(ki, f32(p["ik_norm_g"]), f32(p["ik_norm_b"]), eps)[:, None],
               theta)[:, 0]

    def block_of_queries(b):
        t = b * qb + jnp.arange(qb)
        part = lambda a: jax.lax.dynamic_slice_in_dim(a, b * qb, qb)
        keys = chosen_mask(index_scores(part(qi), ki, part(w), t), topk)
        qs = part(q)

        def head(i):
            s = jax.lax.dynamic_index_in_dim(qs, i, 1, False) @ \
                jax.lax.dynamic_index_in_dim(k, i // g, 1, False).T
            s = jnp.where(keys, s / math.sqrt(D), -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ jax.lax.dynamic_index_in_dim(
                v, i // g, 1, False)                                # [qb, D]

        return jax.lax.map(head, jnp.arange(H)).transpose(1, 0, 2).reshape(qb, H * D)

    o = jax.lax.map(block_of_queries, jnp.arange(S // qb)).reshape(S, H * D)
    return _rows(lambda r: r @ f32(p["out_w"]), qb, o)


def routed_weights(p, z, top_k):
    """``z [S, E]`` (the MLP's normed input) -> each token's weight an expert
    ``[S, experts]``: the softmax over the ``top_k`` largest logits at those
    experts (the softmax over all, the top_k divided by their sum), 0
    elsewhere."""
    logits = z @ p["router_w"].astype(jnp.float32)
    chosen_logits, chosen = jax.lax.top_k(logits, top_k)
    return jnp.einsum("sk,ske->se", jax.nn.softmax(chosen_logits, axis=-1),
                      jax.nn.one_hot(chosen, logits.shape[-1], dtype=jnp.float32))


def _bank(p, z, weight):
    """Every expert's product for every token, weighted: ``[S, E]``."""
    f32 = lambda a: a.astype(jnp.float32)

    def expert(y, e):
        gate, up = jnp.split(z @ f32(p["experts"]["wi"][e]), 2, axis=-1)
        return y + jax.lax.dynamic_index_in_dim(weight, e, 1, True) * (
            (jax.nn.silu(gate) * up) @ f32(p["experts"]["wo"][e])), None

    return jax.lax.scan(expert, jnp.zeros_like(z),
                        jnp.arange(weight.shape[-1]))[0]


def keye_vl2_hidden(params, ids, *, n_head, n_kv_head, head_dim, top_k,
                    indexer_heads=16, indexer_head_dim=64, topk=2048, eps=1e-6,
                    rope_theta=1e7, q_block=1024, **_):
    """``ids [S]`` -> the stack's output after the final norm, ``[S, hidden]``
    float32.  ``S`` is a multiple of ``q_block`` or under it."""
    f32 = lambda a: a.astype(jnp.float32)
    S = ids.shape[0]
    qb = min(q_block, S)
    assert S % qb == 0, f"{S} positions are not whole blocks of {qb} rows"

    def layer(x, p):
        norm = lambda r: _rms(r, f32(p["ln1_g"]), eps)
        x = x + _mixer(p, x, norm, H=n_head, Hkv=n_kv_head, D=head_dim,
                       J=indexer_heads, dI=indexer_head_dim, topk=topk, eps=eps,
                       theta=rope_theta, qb=qb)
        z = _rms(x, f32(p["ln2_g"]), eps)
        return x + _bank(p, z, routed_weights(p, z, top_k)), None

    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"][ids])
        x, _ = jax.lax.scan(layer, x, params["blocks"]["indexed"])
        return _rms(x, f32(params["lnf_g"]), eps)


def keye_vl2_head(params, hidden, *, vocab_size, **_):
    """Rows of :func:`keye_vl2_hidden` -> their logits ``[rows, vocab_size]``
    in float32."""
    with jax.default_matmul_precision("highest"):
        return (hidden @ params["lm_head"].astype(jnp.float32).T)[:, :vocab_size]


def keye_vl2_logits(params, ids, lo=0, hi=None, **kw):
    """``ids [S]`` -> logits of the positions ``lo .. hi - 1`` (all of them
    by default), ``[hi - lo, vocab_size]`` in float32: one full forward pass,
    the head over the asked range alone."""
    return keye_vl2_head(params, keye_vl2_hidden(params, ids, **kw)[lo:hi], **kw)
