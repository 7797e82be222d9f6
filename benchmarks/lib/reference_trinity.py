"""The plain reference for Trinity (``model_type`` ``afmoe`` of
``https://huggingface.co/arcee-ai/Trinity-Large-Preview``): the forward pass
in straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``.  No kernel, no cache, no batching, no
sorting of tokens: every key of a sequence is kept whole, and every expert
that is held is computed for EVERY token, the unchosen weighted by zero.

Layer ``l`` of the stack, ``h [S, E]``, ``H`` query heads on ``Hkv`` K/V
heads of ``D`` lanes, ``g = H / Hkv``:

    x_0 = sqrt(E) * wte[ids]                              (mup_enabled)
    a = RMSNorm(h; ln1_g)
    q = a W_q, k = a W_k, v = a W_v, gate = a W_g         (no bias)
    q <- RMSNorm(q; q_norm_g), k <- RMSNorm(k; k_norm_g)  over a head's D lanes,
                                                          one gain the heads share
    layer_types[l] == "sliding_attention": rope (half-split pairs over all D,
        theta 10,000) on q and k, and a query at t sees the keys t-W+1 .. t;
    "full_attention": NO position encoding, the keys 0 .. t
    query head i attends K/V head i // g, scale 1/sqrt(D), softmax
    o = (concat_heads(o) * sigmoid(gate)) W_o
    h1 = h + RMSNorm(o; post_attn_g)
    m = RMSNorm(h1; ln2_g)
    l < num_dense_layers:  f = (silu(m W_gate) * (m W_up)) W_down
    else:  s = sigmoid(m W_r); the k largest of s + bias chosen (the bias
           chooses and never weighs); w = route_scale * s[chosen] /
           (sum of s[chosen] + 1e-20)
           f = E_shared(m) + sum over the chosen e HELD here of w_e E_e(m)
    h2 = h1 + RMSNorm(f; post_mlp_g)
    after the last layer RMSNorm(h; lnf_g), then the untied head

``experts_held = (first, count)``: the parameter tree's bank holds the
experts ``first .. first + count - 1`` of the ``num_experts`` the router
chooses among (one chip's share of an expert-parallel layer).  What the
others would add is left out, here as in the program, and the partial result
goes on.

It reads the program's parameter tree by its leaf names: ``blocks/{ln1_g,
qkv_w, q_norm_g, k_norm_g, gate_w, out_w, post_attn_g, ln2_g, post_mlp_g}``
stacked over ALL layers, ``blocks/lead/{fc_w, proj_w}`` over the leading
dense layers, ``blocks/moe/gate/{wg, bias}``, ``blocks/moe/shared/{wi, wo}``
and ``blocks/moe/experts/{wi, wo}`` over the expert layers; ``wte``,
``lnf_g``, ``lm_head``.  The weights are the system's, the arithmetic is not.
Departures from the published code, none from its arithmetic: W_q, W_k and
W_v are the three column blocks of one ``qkv_w``, W_gate and W_up the two
column halves of one ``fc_w`` / ``wi`` (gate first); the window mask is the
band ``0 <= t_query - t_key < W`` of one full pass; rows of the embedding and
the head beyond the vocabulary are cut off the logits.  So that 38,912
positions of the full-width model fit beside resident bf16 weights, a layer
first makes every position's key and value and then walks the positions a
block of ``q_block`` at a time through everything else (a row of every other
step depends on no other row), attention a head at a time, one expert at a
time made float32: the blocks change the order of nothing that is summed.
"""

import math

import jax
import jax.numpy as jnp


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, positions, theta):
    """``x [S, heads, D]`` at ``positions [S]``, half-split pairing over all
    ``D`` lanes."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _swiglu(m, wi, wo):
    gate, up = jnp.split(m @ wi.astype(jnp.float32), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ wo.astype(jnp.float32)


def trinity_hidden(params, ids, *, n_head, n_kv_head, head_dim, top_k,
                   num_experts, layer_types, window, num_dense_layers,
                   route_scale, experts_held=None, eps=1e-5, rope_theta=10000.0,
                   q_block=1024, **_):
    """``ids [S]`` -> the stack's output after the final norm, ``[S, hidden]``
    float32.  ``S`` is a multiple of ``q_block`` or under it."""
    f32 = lambda a: a.astype(jnp.float32)
    H, Hkv, D = n_head, n_kv_head, head_dim
    g = H // Hkv
    S = ids.shape[0]
    qb = min(q_block, S)
    assert S % qb == 0, f"{S} positions are not whole blocks of {qb} queries"
    blocks = params["blocks"]
    n_layer = blocks["ln1_g"].shape[0]
    layer_types = list(layer_types)[:n_layer]
    assert len(layer_types) == n_layer and set(layer_types) <= {
        "sliding_attention", "full_attention"}
    first, count = experts_held or (0, num_experts)
    assert blocks["lead"]["fc_w"].shape[0] == num_dense_layers
    assert blocks["moe"]["experts"]["wi"].shape[:2] == (n_layer - num_dense_layers, count)
    assert blocks["moe"]["gate"]["wg"].shape[-1] == num_experts
    t_key = jnp.arange(S)[None, :]
    starts = jnp.arange(S // qb) * qb
    rows = lambda a, start: jax.lax.dynamic_slice_in_dim(a, start, qb)

    def layer(x, l):
        p = {k: v[l] for k, v in blocks.items() if k not in ("lead", "moe")}
        sliding = layer_types[l] == "sliding_attention"
        w_q, w_k, w_v = jnp.split(p["qkv_w"], [H * D, (H + Hkv) * D], axis=-1)

        def keys_and_values(start):
            a = _rms(rows(x, start), f32(p["ln1_g"]), eps)
            k = _rms((a @ f32(w_k)).reshape(qb, Hkv, D), f32(p["k_norm_g"]), eps)
            if sliding:
                k = _rope(k, start + jnp.arange(qb), rope_theta)
            return k, (a @ f32(w_v)).reshape(qb, Hkv, D)

        k, v = jax.lax.map(keys_and_values, starts)
        k = k.reshape(S, Hkv, D).transpose(1, 0, 2)                 # [Hkv, S, D]
        v = v.reshape(S, Hkv, D).transpose(1, 0, 2)

        def feed_forward(m):
            if l < num_dense_layers:
                lead = blocks["lead"]
                return _swiglu(m, lead["fc_w"][l], lead["proj_w"][l])
            moe = jax.tree.map(lambda a: a[l - num_dense_layers], blocks["moe"])
            score = jax.nn.sigmoid(m @ f32(moe["gate"]["wg"]))       # [qb, N]
            chosen = jax.lax.top_k(score + f32(moe["gate"]["bias"]), top_k)[1]
            picked = jnp.take_along_axis(score, chosen, axis=-1)
            weight = jnp.einsum(
                "sk,ske->se",
                route_scale * picked / (picked.sum(-1, keepdims=True) + 1e-20),
                jax.nn.one_hot(chosen, num_experts, dtype=jnp.float32))

            def expert(y, e):
                out = _swiglu(m, moe["experts"]["wi"][e], moe["experts"]["wo"][e])
                return y + jnp.take(weight, first + e, axis=1)[:, None] * out, None

            y, _ = jax.lax.scan(expert, jnp.zeros_like(m), jnp.arange(count))
            return y + _swiglu(m, moe["shared"]["wi"], moe["shared"]["wo"])

        def block(start):
            h = rows(x, start)
            t_query = start + jnp.arange(qb)
            a = _rms(h, f32(p["ln1_g"]), eps)
            q = _rms((a @ f32(w_q)).reshape(qb, H, D), f32(p["q_norm_g"]), eps)
            if sliding:
                q = _rope(q, t_query, rope_theta)
            seen = t_key <= t_query[:, None]
            if sliding:
                seen = seen & (t_query[:, None] - t_key < window)

            def head(i):
                s = jnp.take(q, i, axis=1) @ k[i // g].T / math.sqrt(D)
                return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ v[i // g]

            o = jax.lax.map(head, jnp.arange(H))                     # [H, qb, D]
            o = o.transpose(1, 0, 2).reshape(qb, H * D)
            o = (o * jax.nn.sigmoid(a @ f32(p["gate_w"]))) @ f32(p["out_w"])
            h1 = h + _rms(o, f32(p["post_attn_g"]), eps)
            f = feed_forward(_rms(h1, f32(p["ln2_g"]), eps))
            return h1 + _rms(f, f32(p["post_mlp_g"]), eps)

        return jax.lax.map(block, starts).reshape(S, -1)

    with jax.default_matmul_precision("highest"):
        x = math.sqrt(params["wte"].shape[1]) * f32(params["wte"][ids])
        for l in range(n_layer):
            x = layer(x, l)
        return _rms(x, f32(params["lnf_g"]), eps)


def trinity_head(params, hidden, *, vocab_size, **_):
    """Rows of :func:`trinity_hidden` -> their logits ``[rows, vocab_size]``
    in float32."""
    with jax.default_matmul_precision("highest"):
        return (hidden @ params["lm_head"].astype(jnp.float32).T)[:, :vocab_size]


def trinity_logits(params, ids, lo=0, hi=None, **kw):
    """``ids [S]`` -> logits of the positions ``lo .. hi - 1`` (all of them
    by default), ``[hi - lo, vocab_size]`` in float32: one full forward pass,
    the head over the asked range alone."""
    return trinity_head(params, trinity_hidden(params, ids, **kw)[lo:hi], **kw)
