"""The plain reference for DeepSeek-V3.2-Exp (``model_type`` ``deepseek_v32``
of ``https://huggingface.co/deepseek-ai/DeepSeek-V3.2-Exp``): the forward pass
in straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``.  No kernel, no cache, no pages, no
absorbed form, no gather of chosen rows and no grouped product: every head's
own key and value are made from the latent, a layer's attention is a dense
score matrix with the selection applied as a MASK from an exact top-k (the
``k``-th largest score found by counting, not by a sort), every
held expert's product is computed for every token and weighted by the routed
weights, and the router's group limit is a mask over the experts.

``h = RMSNorm(x)`` (eps 1e-6), ``H`` heads of ``dn + dr`` key lanes and ``dv``
value lanes, ``J`` index heads of ``dI`` lanes; layer ``l``:

    cQ_t = RMSNorm(W_DQ h_t);  [qN_{t,i} ; qR_{t,i}] = (W_UQ cQ_t)_i
    [c_t ; kR_t] = W_DKV h_t;  c_t <- RMSNorm(c_t)
    qR, kR rotated: the pairs (0,1), (2,3), ... (INTERLEAVED), YaRN frequencies
    qI_{t,j} = (W_QI cQ_t)_j            from the QUERY'S LATENT, not from h
    kI_t = LayerNorm(W_KI h_t)          gain and bias;  w_t = W_w h_t
    the first dr lanes of qI_j and of kI rotated: lane i with lane i + dr/2
    (HALF-SPLIT), the same frequencies
    I_{t,s} = dI^-1/2 J^-1/2 sum_j w_{t,j} relu(qI_{t,j} . kI_s),  s <= t
    S_t = the topk positions of largest I_{t,s}, ties to the lower position
    [kN_{s,i} ; v_{s,i}] = (W_UKV c_s)_i
    o_{t,i} = sum_{s in S_t} softmax_s(scale (qN_{t,i} . kN_{s,i} + qR_{t,i} . kR_s)) v_{s,i}
    x <- x + W_O [o_{t,1} .. o_{t,H}]
    z = RMSNorm_2(x)
    l < first_k_dense_replace:  x <- x + W2 (silu(W1 z) * W3 z)
    else: s = sigmoid(W_g z);  b = s + bias;  n_group groups of consecutive
          experts, a group's score the sum of its 2 largest b, the topk_group
          best groups stay (ties: the lower group); the top_k largest b among
          them chosen; weight_e = s_e / sum of the chosen s * routed_scaling_factor
          x <- x + shared(z) + sum_{e held} weight_e expert_e(z)
    logits = lm_head(RMSNorm(x))

``scale = (dn + dr)^-1/2 m^2`` with ``m = 0.1 mscale_all_dim ln(factor) + 1``;
cos and sin times ``mscale`` over that ``m`` (1 here).  YaRN: pair ``i`` of the
``dr`` rotated lanes turns ``theta^(-2i/dr)`` a position, divided by
``factor`` where it makes fewer than ``beta_slow`` turns in the ``original``
positions, kept where it makes more than ``beta_fast``, blended on the linear
ramp between.

``experts_held = (first, count)``: the parameter tree's bank holds the experts
``first .. first + count - 1`` of the ``n_routed_experts`` the router chooses
among (one chip's share of an expert-parallel layer).  What the others would
add is left out, here as in the program, and the partial result goes on.

It reads the program's parameter tree by its leaf names: ``blocks/{ln1_g,
q_a_w, q_a_norm_g, q_b_w, kv_a_w, kv_a_norm_g, kv_b_w, index_q_w, index_kw_w,
ik_norm_g, ik_norm_b, out_w, ln2_g}`` stacked over all layers,
``blocks/lead/{fc_w, proj_w}`` over the dense ones, ``blocks/moe/{gate/{wg,
bias}, experts/{wi, wo}, shared/{wi, wo}}`` over the expert layers; ``wte``,
``lnf_g``, ``lm_head``.  The weights are the system's, the arithmetic is not.
Departures and assumptions:

* W_KI and W_w are the column blocks of ``index_kw_w``; W1 (gate) and W3 (up)
  the two column halves of ``fc_w`` / ``wi``;
* the indexer is computed in float32 and its index keys are not rounded: the
  published FP8 index keys and the Hadamard rotation before them (orthogonal,
  on query and key alike: it changes no score) are an implementation's;
* no multi-token-prediction module (``num_nextn_predict_layers``): a draft
  head for self-speculation, no part of a token's logits;
* rows of the embedding and the head beyond the vocabulary are cut off;
* a layer runs a block of ``q_block`` rows at a time, a head at a time, an
  expert at a time, one matrix at a time made float32, so that 46,080
  positions fit beside 9.3 GB of resident bf16 weights; the rows go in
  :data:`SEGMENTS` of whole blocks, and a segment's scores (the indexer's and the
  heads') are computed over the keys before its END alone: what lies further
  on is after every one of its rows, where the mask is.  A head's keys and
  values are made from the latent once a segment.  Neither changes the order
  of anything that is summed; the expert layers, alike but for their
  weights, are one loop's body.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np


# segments of a layer's rows (``deepseek_v32_hidden``): with four, five eighths
# of a layer's score matrices are computed (what lies past a segment's end is
# not); eight compile twice as long for a tenth less
SEGMENTS = 4


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(d, theta, factor, original, beta_fast, beta_slow):
    """Turns a position of each of the ``d / 2`` lane pairs, ``float64``."""
    i = np.arange(d // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / d)
    pair_of = lambda turns: d * math.log(original / (turns * 2 * math.pi)) / (
        2 * math.log(theta))
    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), d - 1)
    stretched = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain * (1.0 - stretched) + plain / factor * stretched


def rope_interleaved(x, t, inv_freq, by=1.0):
    """``x [rows, d]`` at the positions ``t [rows]``: the pairs (0,1), (2,3), ..."""
    ang = t.astype(jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(ang) * by, jnp.sin(ang) * by
    even, odd = x[:, 0::2], x[:, 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def rope_half_split(x, t, inv_freq, by=1.0):
    """``x [rows, d]`` at the positions ``t [rows]``: lane ``i`` with lane
    ``i + d / 2``."""
    half = x.shape[-1] // 2
    ang = t.astype(jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(ang) * by, jnp.sin(ang) * by
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def chosen_mask(scores, k):
    """``scores [rows, S]`` (-inf where a key is not seen) -> which keys each
    row attends ``[rows, S]``: the ``k`` largest, of equal ones the lower
    positions, never an unseen one.  The ``k``-th largest score of a row is
    found by counting, a bit of it a pass from the highest down (the largest
    number that ``k`` scores reach), over the integers whose order is the
    floats': exact as a sort is, which 46,080 scores a row make the slower."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    order = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def with_bit(i, kth):
        more = kth | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        return jnp.where((order >= more[:, None]).sum(axis=-1) >= k, more, kth)

    kth = jax.lax.fori_loop(0, 32, with_bit, jnp.zeros(scores.shape[:1], jnp.uint32))[:, None]
    above, equal = order > kth, order == kth
    wanted = k - above.sum(axis=-1, keepdims=True)
    chosen = above | (equal & (jnp.cumsum(equal, axis=-1) <= wanted))
    return chosen & (scores > -jnp.inf)


def index_scores(qi, ki, w, t):
    """``qi [rows, J, dI]``, ``ki [S, dI]``, ``w [rows, J]`` of queries at the
    positions ``t [rows]`` -> ``I [rows, S]``, -inf at the keys after each;
    four heads' scores at a time."""
    J, dI = qi.shape[1], qi.shape[2]
    G = math.gcd(J, 4)

    def heads(total, j):
        s = jnp.einsum("qjd,sd->qjs", jax.lax.dynamic_slice_in_dim(qi, j, G, 1), ki)
        return total + jnp.einsum("qjs,qj->qs", jax.nn.relu(s),
                                  jax.lax.dynamic_slice_in_dim(w, j, G, 1)), None

    total, _ = jax.lax.scan(heads, jnp.zeros((qi.shape[0], ki.shape[0]), jnp.float32),
                            jnp.arange(0, J, G))
    total = total / math.sqrt(dI * J)
    return jnp.where(jnp.arange(ki.shape[0])[None] <= t[:, None], total, -jnp.inf)


def routed_weights(gate, z, *, top_k, n_group, topk_group, scale):
    """``z [rows, E]`` (the feed-forward's normed input) -> each token's
    weight an expert ``[rows, experts]``, 0 at the experts it did not choose:
    the group limit a mask over the experts."""
    score = jax.nn.sigmoid(z @ gate["wg"].astype(jnp.float32))
    b = score + gate["bias"].astype(jnp.float32)
    rows, N = b.shape
    group = jnp.sort(b.reshape(rows, n_group, N // n_group), axis=-1)[..., -2:].sum(-1)
    kept = jax.nn.one_hot(jax.lax.top_k(group, topk_group)[1], n_group).sum(1) > 0
    b = jnp.where(jnp.repeat(kept, N // n_group, axis=1), b, -jnp.inf)
    chosen = jax.lax.top_k(b, top_k)[1]
    picked = jnp.take_along_axis(score, chosen, axis=-1)
    return jnp.einsum("sk,ske->se", scale * picked / picked.sum(-1, keepdims=True),
                      jax.nn.one_hot(chosen, N, dtype=jnp.float32))


def index_query_input(c_q, h):
    """What the index queries come up from: the QUERY'S LATENT, not the
    layer's normed input (Keye-VL-2.0's indexer, over K and V heads, reads
    ``h``: ``reference_keye_vl2.py``)."""
    del h
    return c_q


def _swiglu(z, wi, wo):
    gate, up = jnp.split(z @ wi.astype(jnp.float32), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ wo.astype(jnp.float32)


def deepseek_v32_hidden(params, ids, *, n_head, q_lora_rank, kv_lora_rank,
                        qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                        index_n_heads, index_head_dim, index_topk, top_k,
                        n_routed_experts, n_group, topk_group,
                        first_k_dense_replace, rope_scaling, rope_theta=10000.0,
                        experts_held=None, routed_scaling_factor=1.0, eps=1e-6,
                        q_block=1024, rows_from=0, **_):
    """``ids [S]`` -> the stack's output after the final norm, ``[S, hidden]``
    float32.  ``S`` is a multiple of ``q_block`` or under it.  The LAST layer
    runs the rows from the block of ``rows_from`` on alone: a caller that
    reads the rows of the generated positions, as a cell's check does, says
    where they start, and the rows before come back as they entered that
    layer (every layer before it needs them all: they are its keys)."""
    f32 = lambda a: a.astype(jnp.float32)
    H, R, dn, dr, dv = (n_head, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                        v_head_dim)
    J, dI, rs = index_n_heads, index_head_dim, rope_scaling
    S = ids.shape[0]
    qb = min(q_block, S)
    assert S % qb == 0, f"{S} positions are not whole blocks of {qb} rows"
    blocks = params["blocks"]
    n_layer, lead = blocks["ln1_g"].shape[0], first_k_dense_replace
    first, count = experts_held or (0, n_routed_experts)
    assert blocks["lead"]["fc_w"].shape[0] == lead
    assert blocks["moe"]["experts"]["wi"].shape[:2] == (n_layer - lead, count)
    assert blocks["moe"]["gate"]["wg"].shape[-1] == n_routed_experts
    assert blocks["q_a_w"].shape[2] == q_lora_rank
    inv_freq = jnp.asarray(yarn_inv_freq(
        dr, rope_theta, rs["factor"], rs["original_max_position_embeddings"],
        rs["beta_fast"], rs["beta_slow"]), jnp.float32)
    m_all = mscale(rs["factor"], rs["mscale_all_dim"])
    by = mscale(rs["factor"], rs["mscale"]) / m_all
    scale = (dn + dr) ** -0.5 * m_all * m_all
    starts = jnp.arange(S // qb) * qb
    rows = lambda a, start: jax.lax.dynamic_slice_in_dim(a, start, qb)
    # whole blocks of rows a segment: rows lo .. hi - 1 see the keys before hi
    cuts = sorted({round(g * (S // qb) / SEGMENTS) * qb for g in range(SEGMENTS + 1)})

    def layer(x, l, dense, row0=0):
        """Layer ``l`` (traced among the expert layers) over ``x [S, E]``,
        the rows from ``row0`` on (a block's start); every row is a key."""
        p = {k: v[l] for k, v in blocks.items() if k not in ("lead", "moe")}
        w_uq = p["q_b_w"].reshape(q_lora_rank, H, dn + dr)
        w_ukv = p["kv_b_w"].reshape(R, H, dn + dv)
        w_o = p["out_w"].reshape(H, dv, -1)

        def cached(start):
            """What a token leaves for later queries: latent, rope key, index key."""
            t = start + jnp.arange(qb)
            h = _rms(rows(x, start), f32(p["ln1_g"]), eps)
            kv = h @ f32(p["kv_a_w"])
            ki = _layer_norm((h @ f32(p["index_kw_w"]))[:, :dI],
                             f32(p["ik_norm_g"]), f32(p["ik_norm_b"]), eps)
            ki = jnp.concatenate([rope_half_split(ki[:, :dr], t, inv_freq, by),
                                  ki[:, dr:]], axis=-1)
            return (_rms(kv[:, :R], f32(p["kv_a_norm_g"]), eps),
                    rope_interleaved(kv[:, R:], t, inv_freq, by), ki)

        c, k_rope, ki = (a.reshape(S, -1) for a in jax.lax.map(cached, starts))

        def attention(lo, hi):
            """The heads' output, through W_O, of the rows ``lo .. hi - 1``
            over the keys before ``hi``: ``[hi - lo, E]``."""
            at = lo + jnp.arange((hi - lo) // qb) * qb

            def queries(start):
                """A block's query latents and which keys each row attends."""
                t = start + jnp.arange(qb)
                h = _rms(rows(x, start), f32(p["ln1_g"]), eps)
                c_q = _rms(h @ f32(p["q_a_w"]), f32(p["q_a_norm_g"]), eps)
                qi = (index_query_input(c_q, h) @ f32(p["index_q_w"])).reshape(qb, J, dI)
                qi = jnp.concatenate(
                    [jax.vmap(lambda a: rope_half_split(a, t, inv_freq, by), 1, 1)(
                        qi[..., :dr]), qi[..., dr:]], axis=-1)
                w = (h @ f32(p["index_kw_w"]))[:, dI:]
                return c_q, chosen_mask(index_scores(qi, ki[:hi], w, t), index_topk)

            c_q, keys = jax.lax.map(queries, at)            # [blocks, qb, ...]

            def head(o, i):
                kv = c[:hi] @ f32(w_ukv[:, i])                         # [hi, dn + dv]

                def block(a):
                    start, c_q, keys = a
                    q = c_q @ f32(w_uq[:, i])                          # [qb, dn + dr]
                    s = (q[:, :dn] @ kv[:, :dn].T + rope_interleaved(
                        q[:, dn:], start + jnp.arange(qb), inv_freq, by) @ k_rope[:hi].T
                         ) * scale
                    s = jnp.where(keys, s, -jnp.inf)
                    a = jnp.exp(s - s.max(axis=-1, keepdims=True))  # the sum divided out below
                    return (a @ kv[:, dn:] / a.sum(axis=-1, keepdims=True)) @ f32(w_o[i])

                return o + jax.lax.map(block, (at, c_q, keys)), None

            o, _ = jax.lax.scan(head, jnp.zeros((len(at), qb, x.shape[1]), jnp.float32),
                                jnp.arange(H))
            return o.reshape(hi - lo, -1)

        def feed_forward(z):
            if dense:
                return _swiglu(z, blocks["lead"]["fc_w"][l], blocks["lead"]["proj_w"][l])
            moe = jax.tree.map(lambda a: a[l - lead], blocks["moe"])
            weight = routed_weights(moe["gate"], z, top_k=top_k, n_group=n_group,
                                    topk_group=topk_group, scale=routed_scaling_factor)

            def expert(y, e):
                out = _swiglu(z, moe["experts"]["wi"][e], moe["experts"]["wo"][e])
                return y + jnp.take(weight, first + e, axis=1)[:, None] * out, None

            y, _ = jax.lax.scan(expert, jnp.zeros_like(z), jnp.arange(count))
            return y + _swiglu(z, moe["shared"]["wi"], moe["shared"]["wo"])

        ends = [row0] + [c for c in cuts if c > row0]
        x1 = x.at[row0:].add(jnp.concatenate(
            [attention(lo, hi) for lo, hi in zip(ends, ends[1:])]))
        return x.at[row0:].set(jax.lax.map(
            lambda start: rows(x1, start) + feed_forward(
                _rms(rows(x1, start), f32(p["ln2_g"]), eps)),
            starts[row0 // qb:]).reshape(S - row0, -1))

    with jax.default_matmul_precision("highest"):
        x, last = f32(params["wte"][ids]), n_layer - 1
        for l in range(min(lead, last)):
            x = layer(x, l, True)
        x = jax.lax.fori_loop(lead, last, lambda l, x: layer(x, l, False), x)
        x = layer(x, last, last < lead, row0=min(rows_from, S - 1) // qb * qb)
        return _rms(x, f32(params["lnf_g"]), eps)


def deepseek_v32_head(params, hidden, *, vocab_size, **_):
    """Rows of :func:`deepseek_v32_hidden` -> their logits ``[rows,
    vocab_size]`` in float32."""
    with jax.default_matmul_precision("highest"):
        return (hidden @ params["lm_head"].astype(jnp.float32).T)[:, :vocab_size]


def deepseek_v32_logits(params, ids, lo=0, hi=None, **kw):
    """``ids [S]`` -> logits of the positions ``lo .. hi - 1`` (all of them
    by default), ``[hi - lo, vocab_size]`` in float32: one full forward pass,
    the head over the asked range alone."""
    return deepseek_v32_head(params, deepseek_v32_hidden(params, ids, **kw)[lo:hi], **kw)
