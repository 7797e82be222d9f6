"""The plain reference for OLMoE (Muennighoff et al. 2024; ``model_type``
``olmoe`` of ``https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct``):
the forward pass and next-token loss in straightforward ``jax.numpy`` and
float32 under ``default_matmul_precision("highest")``.  No kernels, no cache,
no batching, no sorting of tokens: EVERY expert is computed for EVERY token
and the unchosen are masked out.

    a = RMSNorm_in(x);  q = Wq a, k = Wk a, v = Wv a          (no bias)
    q = RMSNorm_q(q), k = RMSNorm_k(k)     over all lanes, BEFORE the heads
    heads of D; rope (rotate-half, all D dims) on q and k;
    causal softmax(q k^T / sqrt(D)) v; Wo;   h = x + attention
    z = RMSNorm_post(h);  p = softmax(Wr z) over all experts;  top k of p,
    weights the raw p (``norm_topk_prob`` false)
    y = h + sum over the k chosen e of p_e * Wdown_e(silu(Wgate_e z) * Wup_e z)
    final RMSNorm, untied head

It reads the program's parameter tree by its leaf names, stacked over
layers: ``blocks/{ln1_g, qkv_w, q_norm_g, k_norm_g, out_w, ln2_g}``,
``blocks/moe/gate/wg`` and ``blocks/moe/experts/{wi, wo}``; ``wte``,
``lnf_g``, ``lm_head``.  The weights are the system's, the arithmetic is
not.  Departures from the published layout, none from its arithmetic: Wq, Wk
and Wv are the three column blocks of one ``qkv_w``; Wgate and Wup are the
two column halves of one ``wi`` (gate first); rows of the embedding and the
head beyond the vocabulary (padding to the MXU's multiple; OLMoE's 50304
has none) are cut off the logits.

Layers and experts are walked in loops and one expert at a time is made
float32, so that 4096 positions of the full-width model hold in under 3 GB
beside resident bf16 weights (attention one head at a time).
"""

import math

import jax
import jax.numpy as jnp


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """``x [S, D]`` at positions 0..S-1, rotate-half pairing over all D."""
    S, D = x.shape
    half = D // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    rotated = jnp.concatenate([-x[:, half:], x[:, :half]], axis=-1)
    return x * cos + rotated * sin


def _forward(params, ids, *, n_head, vocab_size, top_k, eps=1e-5,
             rope_theta=10000.0):
    """``ids [S]`` -> (logits ``[S, vocab_size]`` in float32, the experts
    each layer's router chose ``[layers, S, top_k]``)."""
    f32 = lambda a: a.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        S = ids.shape[0]
        x = f32(params["wte"][ids])
        E = x.shape[-1]
        D = E // n_head
        causal = jnp.tril(jnp.ones((S, S), bool))

        def head(qkv):
            q, k, v = qkv                                       # [S, D] each
            s = _rope(q, rope_theta) @ _rope(k, rope_theta).T / math.sqrt(D)
            s = jnp.where(causal, s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v

        def block(x, p):
            a = _rms(x, f32(p["ln1_g"]), eps)
            q, k, v = jnp.split(a @ f32(p["qkv_w"]), 3, axis=-1)
            q = _rms(q, f32(p["q_norm_g"]), eps)
            k = _rms(k, f32(p["k_norm_g"]), eps)
            heads = lambda t: t.reshape(S, n_head, D).transpose(1, 0, 2)
            o = jax.lax.map(head, (heads(q), heads(k), heads(v)))   # [H, S, D]
            h = x + o.transpose(1, 0, 2).reshape(S, E) @ f32(p["out_w"])

            z = _rms(h, f32(p["ln2_g"]), eps)
            prob = jax.nn.softmax(z @ f32(p["moe"]["gate"]["wg"]), axis=-1)
            _, chosen = jax.lax.top_k(prob, top_k)
            n_experts = prob.shape[-1]
            weight = prob * jax.nn.one_hot(chosen, n_experts).sum(axis=1)

            def expert(y, e):
                wi = f32(p["moe"]["experts"]["wi"][e])
                wo = f32(p["moe"]["experts"]["wo"][e])
                gate, up = jnp.split(z @ wi, 2, axis=-1)
                return y + weight[:, e, None] * ((jax.nn.silu(gate) * up) @ wo), None

            y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(n_experts))
            return h + y, chosen

        x, chosen = jax.lax.scan(block, x, params["blocks"])
        x = _rms(x, f32(params["lnf_g"]), eps)
        return (x @ f32(params["lm_head"]).T)[:, :vocab_size], chosen


def olmoe_logits(params, ids, **kw):
    """``ids [S]`` -> logits ``[S, vocab_size]`` in float32."""
    return _forward(params, ids, **kw)[0]


def olmoe_router_choices(params, ids, **kw):
    """``ids [S]`` -> the ``top_k`` experts of every token in every layer,
    ``[layers, S, top_k]``: what a lower-precision router is compared with."""
    return _forward(params, ids, **kw)[1]


def olmoe_loss_sum(params, ids, labels, **kw):
    """Sum over the sequence of the next-token cross-entropy."""
    logp = jax.nn.log_softmax(olmoe_logits(params, ids, **kw), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()
