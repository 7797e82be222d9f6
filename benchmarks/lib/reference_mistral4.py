"""The plain reference for Mistral-Small-4 (``model_type`` ``mistral4`` of
``https://huggingface.co/mistralai/Mistral-Small-4-119B-2603``; the layer of
the DeepSeek-V3 line): the forward pass in straightforward ``jax.numpy`` and
float32 under ``default_matmul_precision("highest")``.  No kernel, no cache,
no absorbed form, no sorting of tokens: every head's own key and value are
made from the latent, and every expert that is held is computed for EVERY
token, the unchosen weighted by zero.

Layer ``l`` of the stack, ``x [S, hidden]``, ``H`` heads:

    h = RMSNorm_1(x)
    c_q = RMSNorm(h W_qa);  q = c_q W_qb          a head: [q_nope | q_rope]
    [c_raw | k_rope_raw] = h W_kva;  c = RMSNorm(c_raw)
    k_rope = RoPE(k_rope_raw)   ONE a token, shared by all heads
    q_rope = RoPE(q_rope);  [k_nope_h | v_h] = c W_kvb   a head
    score_h(t, s) = (q_nope_h(t) k_nope_h(s) + q_rope_h(t) k_rope(s))
                    * scale * (1 + beta ln(1 + t // original))
    causal, softmax;  x1 = x + concat_h(softmax v_h) W_o
    z = RMSNorm_2(x1);  s = sigmoid(z W_g)
    the k largest of s + b chosen, w = s[chosen] / sum s[chosen]
    x2 = x1 + sum_e w_e E_e(z) + E_shared(z),  E(u) = (silu(u Wgate) * (u Wup)) Wdown
    after the last layer RMSNorm, then the untied head

RoPE pairs the lanes ``(0,1), (2,3), ...`` (``rope_interleave``); pair ``i``
of the ``d`` rotated lanes turns ``theta^(-2i/d)`` a position, stretched by
YaRN: divided by ``factor`` where it makes fewer than ``beta_slow`` turns in
the ``original`` positions, kept where it makes more than ``beta_fast``, and
blended on the linear ramp between the two pairs
``d ln(original / (2 pi beta)) / (2 ln theta)`` (the first rounded down, the
second up).  ``scale = (q_nope + q_rope lanes)^-0.5 * m^2`` with ``m = 0.1
mscale_all_dim ln(factor) + 1`` (the DeepSeek-V3 convention), and cos and sin
times ``mscale`` over ``mscale_all_dim``'s ``m`` (1 here).

``experts_held = (first, count)``: the parameter tree's bank holds the
experts ``first .. first + count - 1`` of the ``n_routed_experts`` the router
chooses among (one chip's share of an expert-parallel layer).  What the
others would add is left out, here as in the program, and the partial result
goes on.

It reads the program's parameter tree by its leaf names, stacked over
layers: ``blocks/{ln1_g, q_a_w, q_a_norm_g, q_b_w, kv_a_w, kv_a_norm_g,
kv_b_w, out_w, ln2_g}``, ``blocks/moe/gate/{wg, bias}``,
``blocks/moe/experts/{wi, wo}``, ``blocks/moe/shared/{wi, wo}``; ``wte``,
``lnf_g``, ``lm_head``.  The weights are the system's, the arithmetic is not.
Departures from the published code, none from its arithmetic: Wgate and Wup
are the two column halves of one ``wi`` (gate first); the published rope
un-interleaves q and k and rotates half against half, which gives every
score what rotating the pairs in place gives; rows of the embedding and the
head beyond the vocabulary are cut off the logits; attention runs a block of
``q_block`` queries and one head at a time and one expert at a time is made
float32, so that 12,288 positions of the full-width model fit beside
resident bf16 weights: the blocks change the order of nothing that is summed.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


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


def _rope(x, inv_freq, by):
    """``x [S, d]`` at positions 0..S-1, the pairs ``(0,1), (2,3), ...``."""
    S, d = x.shape
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(ang) * by, jnp.sin(ang) * by
    even, odd = x[:, 0::2], x[:, 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(S, d)


def mistral4_hidden(params, ids, *, n_head, q_lora_rank, kv_lora_rank,
                    qk_nope_head_dim, qk_rope_head_dim, v_head_dim, top_k,
                    n_routed_experts, rope_parameters, experts_held=None,
                    routed_scaling_factor=1.0, eps=1e-6, q_block=1024, **_):
    """``ids [S]`` -> the stack's output after the final norm, ``[S, hidden]``
    float32.  ``S`` is a multiple of ``q_block`` or under it."""
    f32 = lambda a: a.astype(jnp.float32)
    H, R, dn, dr, dv = (n_head, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                        v_head_dim)
    rp = rope_parameters
    S = ids.shape[0]
    qb = min(q_block, S)
    assert S % qb == 0, f"{S} positions are not whole blocks of {qb} queries"
    first, count = experts_held or (0, n_routed_experts)
    assert params["blocks"]["moe"]["experts"]["wi"].shape[1] == count
    assert params["blocks"]["q_a_w"].shape[2] == q_lora_rank
    inv_freq = jnp.asarray(yarn_inv_freq(
        dr, rp["rope_theta"], rp["factor"], rp["original_max_position_embeddings"],
        rp["beta_fast"], rp["beta_slow"]), jnp.float32)
    m_all = mscale(rp["factor"], rp["mscale_all_dim"])
    by = mscale(rp["factor"], rp["mscale"]) / m_all
    scale = (dn + dr) ** -0.5 * m_all * m_all
    t_key = jnp.arange(S)[None, :]
    q_scale = 1.0 + rp["llama_4_scaling_beta"] * jnp.log1p(jnp.floor(
        jnp.arange(S, dtype=jnp.float32) / rp["original_max_position_embeddings"]))

    def layer(x, p):
        h = _rms(x, f32(p["ln1_g"]), eps)
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
                s = s * rows(q_scale)[:, None]
                return jax.nn.softmax(jnp.where(t_key <= t_query, s, -jnp.inf),
                                      axis=-1) @ v

            return jax.lax.map(block, jnp.arange(S // qb)).reshape(S, dv)

        o = jax.lax.map(head, jnp.arange(H))                       # [H, S, dv]
        x1 = x + o.transpose(1, 0, 2).reshape(S, H * dv) @ f32(p["out_w"])

        z = _rms(x1, f32(p["ln2_g"]), eps)
        score = jax.nn.sigmoid(z @ f32(p["moe"]["gate"]["wg"]))    # [S, N]
        chosen = jax.lax.top_k(score + f32(p["moe"]["gate"]["bias"]), top_k)[1]
        picked = jnp.take_along_axis(score, chosen, axis=-1)
        weight = jnp.einsum(
            "sk,ske->se", picked / picked.sum(-1, keepdims=True) * routed_scaling_factor,
            jax.nn.one_hot(chosen, n_routed_experts, dtype=jnp.float32))

        def mlp(wi, wo):
            gate, up = jnp.split(z @ f32(wi), 2, axis=-1)
            return (jax.nn.silu(gate) * up) @ f32(wo)

        def expert(y, e):
            wi, wo = p["moe"]["experts"]["wi"][e], p["moe"]["experts"]["wo"][e]
            return y + jnp.take(weight, first + e, axis=1)[:, None] * mlp(wi, wo), None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(x1), jnp.arange(count))
        return x1 + y + mlp(p["moe"]["shared"]["wi"], p["moe"]["shared"]["wo"]), None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, f32(params["wte"][ids]), params["blocks"])
        return _rms(x, f32(params["lnf_g"]), eps)


def mistral4_head(params, hidden, *, vocab_size, **_):
    """Rows of :func:`mistral4_hidden` -> their logits ``[rows, vocab_size]``
    in float32."""
    with jax.default_matmul_precision("highest"):
        return (hidden @ params["lm_head"].astype(jnp.float32).T)[:, :vocab_size]


def mistral4_logits(params, ids, lo=0, hi=None, **kw):
    """``ids [S]`` -> logits of the positions ``lo .. hi - 1`` (all of them
    by default), ``[hi - lo, vocab_size]`` in float32: one full forward pass,
    the head over the asked range alone."""
    return mistral4_head(params, mistral4_hidden(params, ids, **kw)[lo:hi], **kw)
