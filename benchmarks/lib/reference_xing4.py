"""The plain reference for Xing4.0 (``model_type`` ``xing4_0`` of
``https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B``): the forward pass in
straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``.  No kernel, no cache, no absorbed
form, no sorting of tokens: every head's own key and value are made from the
latent, every expert is computed for EVERY token, the unchosen weighted by
zero, and the Sinkhorn-Knopp projection is a loop.

The sublayers are the DeepSeek-V3 line's (``reference_mistral4.py`` states
them line by line; below they are that file's, with a dense lead).  The
RESIDUAL PATH is manifold-constrained hyper-connections (mHC,
arXiv:2512.24880, over Hyper-Connections, arXiv:2409.19606): a token carries
``n = hc_mult`` streams ``X [n, C]``, and EACH of a layer's two sublayers
``F`` (latent attention behind RMSNorm_1; the feed-forward behind RMSNorm_2)
has its own ``phi [n C, 2n + n n]``, ``b`` and ``alpha``:

    xb     = RMSNorm(vec(X))                      over the n C lanes, no gain
    Hpre~  = a_pre  * (xb @ phi_pre)  + b_pre     [n]
    Hpost~ = a_post * (xb @ phi_post) + b_post    [n]
    Hres~  = a_res  * mat(xb @ phi_res) + b_res   [n, n]
    Hpre = sigmoid(Hpre~);  Hpost = 2 sigmoid(Hpost~)
    M = exp(clip(Hres~, clamp_min, clamp_max))
    hc_sinkhorn_iters times: M <- M / (its columns' sums + eps)
                             M <- M / (its rows' sums + eps)
    Hres = M
    u  = Hpre @ X                                 [C]: what the sublayer's norm reads
    X' = Hres @ X + outer(Hpost, F(u))            [n, C]

``X_0`` is the embedding copied to the ``n`` streams; after the last layer
the streams are summed, then the final RMSNorm and the untied head (the
Hyper-Connections paper's entry and exit).

A layer, ``H`` heads, ``F`` as above:

    attention(u):  h = RMSNorm_1(u)
        c_q = RMSNorm(h W_qa);  q = c_q W_qb          a head: [q_nope | q_rope]
        [c_raw | k_rope_raw] = h W_kva;  c = RMSNorm(c_raw)
        k_rope = RoPE(k_rope_raw)   ONE a token, shared by all heads
        q_rope = RoPE(q_rope);  [k_nope_h | v_h] = c W_kvb   a head
        score_h(t, s) = (q_nope_h(t) k_nope_h(s) + q_rope_h(t) k_rope(s)) * scale
        causal, softmax;  concat_h(softmax v_h) W_o
    feed-forward(u):  z = RMSNorm_2(u)
        l < first_k_dense_replace:  (silu(z W1) * (z W3)) W2
        else: s = sigmoid(z W_g); the k largest of s + b chosen,
              w = s[chosen] / sum s[chosen] * routed_scaling_factor
              sum_e w_e E_e(z) + E_shared(z),  E(z) = (silu(z Wgate) * (z Wup)) Wdown

RoPE pairs the lanes ``(0,1), (2,3), ...``; pair ``i`` of the ``d`` rotated
lanes turns ``theta^(-2i/d)`` a position, stretched by YaRN (divided by
``factor`` where it makes fewer than ``beta_slow`` turns in the ``original``
positions, kept where it makes more than ``beta_fast``, blended on the linear
ramp between).  ``scale = (q_nope + q_rope lanes)^-0.5 * m^2`` with ``m = 0.1
mscale_all_dim ln(factor) + 1``, and cos and sin times ``mscale`` over that
``m`` (1 here).

It reads the program's parameter tree by its leaf names: ``blocks/{ln1_g,
q_a_w, q_a_norm_g, q_b_w, kv_a_w, kv_a_norm_g, kv_b_w, out_w, ln2_g,
hc_attn_phi, hc_attn_b, hc_attn_alpha, hc_mlp_phi, hc_mlp_b, hc_mlp_alpha}``
stacked over all layers, ``blocks/lead/{fc_w, proj_w}`` over the dense ones,
``blocks/moe/{gate/{wg, bias}, experts/{wi, wo}, shared/{wi, wo}}`` over the
expert layers; ``wte``, ``lnf_g``, ``lm_head``.  The weights are the
system's, the arithmetic is not.  Departures from the papers and assumptions
(the configuration file lists them under ``assumed``):

* the columns of ``phi`` are ``[pre | post | res`` row by row ``]`` and
  ``alpha`` is ``(a_pre, a_post, a_res)``: one matrix where the paper writes
  three;
* inside one Sinkhorn iteration the columns come first; ``hc_eps`` is the eps
  of both normalisations and of the RMSNorm over the ``n C`` lanes; the clamp
  is on ``Hres~`` before the ``exp``;
* W1 (gate) and W3 (up) are the two column halves of ``fc_w`` / ``wi``;
* the published rope un-interleaves q and k and rotates half against half,
  which gives every score what rotating the pairs in place gives;
* no multi-token-prediction module (``num_nextn_predict_layers``): a draft
  head for self-speculation, no part of a token's logits;
* rows of the embedding and the head beyond the vocabulary are cut off;
* attention runs a block of ``q_block`` queries and one head at a time, the
  stream maps a block of ``q_block`` tokens at a time and one expert at a
  time is made float32, so that 16,896 positions of the full-width model fit
  beside resident bf16 weights: the blocks change the order of nothing that
  is summed.
"""

import jax
import jax.numpy as jnp

from benchmarks.lib.reference_mistral4 import _rms, _rope, mscale, yarn_inv_freq


def sinkhorn(m, iters, eps):
    """``m [n, n]`` positive -> doubly stochastic to the iteration's error:
    ``iters`` times the columns over their sums, then the rows over theirs."""
    def one(_, m):
        m = m / (m.sum(axis=0, keepdims=True) + eps)
        return m / (m.sum(axis=1, keepdims=True) + eps)
    return jax.lax.fori_loop(0, iters, one, m)


def stream_maps(X, phi, b, alpha, *, iters, eps, clamp):
    """ONE token's streams ``X [n, C]`` -> (``Hpre [n]``, ``Hpost [n]``,
    ``Hres [n, n]``)."""
    n = X.shape[0]
    v = X.reshape(-1)
    xb = v / jnp.sqrt(jnp.mean(v * v) + eps)
    raw = xb @ phi
    pre = alpha[0] * raw[:n] + b[:n]
    post = alpha[1] * raw[n:2 * n] + b[n:2 * n]
    res = alpha[2] * raw[2 * n:].reshape(n, n) + b[2 * n:].reshape(n, n)
    return (jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
            sinkhorn(jnp.exp(jnp.clip(res, *clamp)), iters, eps))


def xing4_first_maps(params, ids, *, hc_mult, hc_sinkhorn_iters, hc_eps,
                     mhc_h_res_clamp_min, mhc_h_res_clamp_max, **_):
    """``ids [S]`` -> the maps of the stack's FIRST sublayer (the first
    layer's attention) over each token's entering streams, the embedding
    copied ``hc_mult`` times: (``Hpre [S, n]``, ``Hpost [S, n]``, ``Hres [S,
    n, n]``) float32.  Nothing the served program rounds lies before them."""
    f32 = lambda a: a.astype(jnp.float32)
    phi, b, alpha = (f32(params["blocks"][f"hc_attn_{leaf}"][0])
                     for leaf in ("phi", "b", "alpha"))
    with jax.default_matmul_precision("highest"):
        X = jnp.repeat(f32(params["wte"][ids])[:, None], hc_mult, axis=1)
        return jax.vmap(lambda Xt: stream_maps(
            Xt, phi, b, alpha, iters=hc_sinkhorn_iters, eps=hc_eps,
            clamp=(mhc_h_res_clamp_min, mhc_h_res_clamp_max)))(X)


def xing4_hidden(params, ids, *, n_head, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim, top_k,
                 n_routed_experts, first_k_dense_replace, rope_theta,
                 rope_scaling, hc_mult, hc_sinkhorn_iters, hc_eps,
                 mhc_h_res_clamp_min, mhc_h_res_clamp_max,
                 routed_scaling_factor=1.0, eps=1e-6, q_block=1024, **_):
    """``ids [S]`` -> the stack's output after the final norm, ``[S, hidden]``
    float32.  ``S`` is a multiple of ``q_block`` or under it."""
    f32 = lambda a: a.astype(jnp.float32)
    H, R, dn, dr, dv = (n_head, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                        v_head_dim)
    rs, n, lead = rope_scaling, hc_mult, first_k_dense_replace
    S = ids.shape[0]
    qb = min(q_block, S)
    assert S % qb == 0, f"{S} positions are not whole blocks of {qb} queries"
    blocks = params["blocks"]
    assert blocks["moe"]["experts"]["wi"].shape[1] == n_routed_experts
    assert blocks["q_a_w"].shape[2] == q_lora_rank
    assert blocks["hc_attn_phi"].shape[2] == 2 * n + n * n
    inv_freq = jnp.asarray(yarn_inv_freq(
        dr, rope_theta, rs["factor"], rs["original_max_position_embeddings"],
        rs["beta_fast"], rs["beta_slow"]), jnp.float32)
    m_all = mscale(rs["factor"], rs["mscale_all_dim"])
    by = mscale(rs["factor"], rs["mscale"]) / m_all
    scale = (dn + dr) ** -0.5 * m_all * m_all
    t_key = jnp.arange(S)[None, :]
    hc = dict(iters=hc_sinkhorn_iters, eps=hc_eps,
              clamp=(mhc_h_res_clamp_min, mhc_h_res_clamp_max))
    by_block = lambda fn, *a: jax.tree.map(          # ``fn`` of a token, by blocks
        lambda y: y.reshape(S, *y.shape[2:]),
        jax.lax.map(lambda blocks: jax.vmap(fn)(*blocks),
                    tuple(t.reshape(S // qb, qb, *t.shape[1:]) for t in a)))

    def attention(u, p):
        h = _rms(u, f32(p["ln1_g"]), eps)
        c_q = _rms(h @ f32(p["q_a_w"]), f32(p["q_a_norm_g"]), eps)
        q = (c_q @ f32(p["q_b_w"])).reshape(S, H, dn + dr).transpose(1, 0, 2)
        kv = h @ f32(p["kv_a_w"])
        c = _rms(kv[:, :R], f32(p["kv_a_norm_g"]), eps)
        k_rope = _rope(kv[:, R:], inv_freq, by)                    # [S, dr]
        kvb = (c @ f32(p["kv_b_w"])).reshape(S, H, dn + dv).transpose(1, 0, 2)

        def head(i):
            q_nope, q_rope = q[i, :, :dn], _rope(q[i, :, dn:], inv_freq, by)
            k_nope, v = kvb[i, :, :dn], kvb[i, :, dn:]

            def block(b):
                t_query = b * qb + jnp.arange(qb)[:, None]
                rows = lambda a: jax.lax.dynamic_slice_in_dim(a, b * qb, qb)
                s = (rows(q_nope) @ k_nope.T + rows(q_rope) @ k_rope.T) * scale
                return jax.nn.softmax(jnp.where(t_key <= t_query, s, -jnp.inf),
                                      axis=-1) @ v

            return jax.lax.map(block, jnp.arange(S // qb)).reshape(S, dv)

        o = jax.lax.map(head, jnp.arange(H))                       # [H, S, dv]
        return o.transpose(1, 0, 2).reshape(S, H * dv) @ f32(p["out_w"])

    def swiglu(z, wi, wo):
        gate, up = jnp.split(z @ f32(wi), 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ f32(wo)

    def experts(z, moe):
        score = jax.nn.sigmoid(z @ f32(moe["gate"]["wg"]))         # [S, N]
        chosen = jax.lax.top_k(score + f32(moe["gate"]["bias"]), top_k)[1]
        picked = jnp.take_along_axis(score, chosen, axis=-1)
        weight = jnp.einsum(
            "sk,ske->se", picked / picked.sum(-1, keepdims=True) * routed_scaling_factor,
            jax.nn.one_hot(chosen, n_routed_experts, dtype=jnp.float32))

        def expert(y, e):
            wi, wo = moe["experts"]["wi"][e], moe["experts"]["wo"][e]
            return y + jnp.take(weight, e, axis=1)[:, None] * swiglu(z, wi, wo), None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(z), jnp.arange(n_routed_experts))
        return y + swiglu(z, moe["shared"]["wi"], moe["shared"]["wo"])

    def sublayer(X, p, sub, F):
        """``X [S, n, C]`` -> ``X'``: the sublayer ``F`` under its own maps."""
        phi, b, alpha = (f32(p[f"hc_{sub}_{leaf}"]) for leaf in ("phi", "b", "alpha"))
        hpre, hpost, hres = by_block(
            lambda Xt: stream_maps(Xt, phi, b, alpha, **hc), X)
        f = F(by_block(lambda a, Xt: a @ Xt, hpre, X))
        return by_block(lambda m, a, Xt, ft: m @ Xt + jnp.outer(a, ft), hres, hpost, X, f)

    def layer(X, p, ffn):
        X = sublayer(X, p, "attn", lambda u: attention(u, p))
        return sublayer(X, p, "mlp", lambda u: ffn(_rms(u, f32(p["ln2_g"]), eps)))

    attn = {k: v for k, v in blocks.items() if k not in ("lead", "moe")}
    row = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    with jax.default_matmul_precision("highest"):
        X = jnp.repeat(f32(params["wte"][ids])[:, None], n, axis=1)
        for l in range(lead):
            dense = row(blocks["lead"], l)
            X = layer(X, row(attn, l), lambda z: swiglu(z, dense["fc_w"], dense["proj_w"]))
        X, _ = jax.lax.scan(
            lambda X, p: (layer(X, p[0], lambda z: experts(z, p[1])), None), X,
            (jax.tree.map(lambda a: a[lead:], attn), blocks["moe"]))
        return _rms(X.sum(axis=1), f32(params["lnf_g"]), eps)


def xing4_head(params, hidden, *, vocab_size, **_):
    """Rows of :func:`xing4_hidden` -> their logits ``[rows, vocab_size]`` in
    float32."""
    with jax.default_matmul_precision("highest"):
        return (hidden @ params["lm_head"].astype(jnp.float32).T)[:, :vocab_size]


def xing4_logits(params, ids, lo=0, hi=None, **kw):
    """``ids [S]`` -> logits of the positions ``lo .. hi - 1`` (all of them
    by default), ``[hi - lo, vocab_size]`` in float32: one full forward pass,
    the head over the asked range alone."""
    return xing4_head(params, xing4_hidden(params, ids, **kw)[lo:hi], **kw)
