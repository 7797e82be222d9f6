"""The plain reference for Qwen3-Next-80B-A3B (``model_type`` ``qwen3_next`` of
``https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct``): the forward pass
in straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``.  No kernel, no cache, no pages, no
state between calls, no batching, no sorting of tokens: the convolution over
time is shifts of the WHOLE sequence, the gated delta rule a ``lax.scan`` over
the tokens of its recurrence exactly as written below (NOT the chunked form
the program uses over a prompt chunk, nor the multiplied-out read its decode
kernel uses), full attention a dense causal score matrix a block of queries at
a time, and every expert that is held is computed for EVERY token, the
unchosen weighted by zero.  It imports nothing from ``deepspeed_tpu``.

eps 1e-6; no bias anywhere; ``E`` the hidden size.  The widths are the
catalog's config; what the config has no key for is under ``assumed`` in
``benchmarks/configs/qwen3-next-80b-a3b.json``:

    N(x; g) = x / sqrt(mean(x^2) + eps) * g        (the family's zero-centred
              gain ``1 + w`` is kept as the gain ``g``, as every leaf here)
    x_0 = wte[ids]                                              not scaled
    x <- x + Mixer_l(N(x; ln1_g));  x <- x + FFN_l(N(x; ln2_g))
    logits = lm_head N(x; lnf_g)                                untied head

    linear_attention (Hk key heads, Hv value heads, dk and dv lanes, value
    head j on key head j // (Hv / Hk)), h the normed input:
    1. u_t = h_t W_qkv  [q: Hk dk | k: Hk dk | v: Hv dv];  z_t = h_t W_z
       [Hv dv];  [b_t | a_t] = h_t W_ba  [Hv | Hv]
    2. c_t[ch] = silu(sum_{j=0..3} w[j, ch] u_{t-3+j}[ch]), u_s = 0 for s < 0
    3. a key head: q = c_q / sqrt(|c_q|^2 + 1e-6) / sqrt(dk), k = c_k /
       sqrt(|c_k|^2 + 1e-6)
    4. a value head: beta_t = sigmoid(b_t);  g_t = -exp(A_log) softplus(a_t +
       dt_bias);  alpha_t = exp(g_t)
    5. S_{-1} = 0;  S' = alpha_t S_{t-1};  m = S'^T k_t;  d_t = beta_t (v_t -
       m);  S_t = S' + k_t d_t^T;  o_t = S_t^T q_t
    6. y_t[j] = o_t[j] / sqrt(mean(o_t[j]^2) + eps) * gamma * silu(z_t[j]);
       Mixer(h)_t = [y_t[0] .. y_t[Hv-1]] W_o

    full_attention (H query heads on Hkv K/V heads of D lanes):
    q = h W_q, gate = h W_g [H D each], k = h W_k, v = h W_v [Hkv D];
    q <- N(q; q_norm_g), k <- N(k; k_norm_g) over a HEAD's D lanes, one gain
    the heads share;  rope on the first ``rope_dim`` lanes in half-split pairs
    (i, i + rope_dim / 2), theta 1e7;  o_t[i] = sum_{s <= t} softmax_s(q_t[i] .
    k_s[i // (H / Hkv)] / sqrt(D)) v_s[i // (H / Hkv)];
    Mixer(h)_t = ([o_t[0] .. o_t[H-1]] * sigmoid(gate_t)) W_o

    FFN(m): p = softmax(m W_r) over all ``num_experts`` in float32; the top_k
    largest, their weights divided by their sum;  Expert_e(m) = (silu(m
    W_gate,e) * (m W_up,e)) W_down,e;
    FFN(m) = sum over the chosen e HELD here of p_e Expert_e(m)
             + sigmoid(m . w_s) * Shared(m)        (``shared`` False: without)

``experts_held = (first, count)``: the parameter tree's bank holds the
experts ``first .. first + count - 1`` of the ``num_experts`` the router
chooses among (one chip's share of an expert-parallel layer).  What the
others would add is left out, here as in the program, and the partial result
goes on; the chip that holds the other experts runs with ``shared`` False,
so that the shares of a layer add up to the layer.

It reads the program's parameter tree by its leaf names:
``blocks/delta/{qkv_w, gate_w, ba_w, conv_w, a_log, dt_bias, onorm_g, out_w}``
(``qkv_w``'s column blocks are W_q, W_k, W_v in that order, ``ba_w``'s W_b then
W_a, ``conv_w [taps, lanes]`` holds the oldest token's tap first),
``blocks/full/{qkv_w, q_norm_g, k_norm_g, gate_w, out_w}``, both with
``ln1_g``, ``ln2_g``, ``router_w``, ``experts/{wi, wo}`` (gate then up),
``shared_fc_w`` (gate then up), ``shared_proj_w``, ``shared_gate_w``; ``wte``,
``lnf_g``, ``lm_head``.  The weights are the system's, the arithmetic is not.
Everything a token does alone runs a block of ``q_block`` rows at a time and
attention a head and a block of queries at a time: the blocks change the order
of nothing summed.
"""

import math

import jax
import jax.numpy as jnp

MIXER_OF = {"linear_attention": "delta", "full_attention": "full"}


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rows(fn, qb, *xs):
    """``fn`` over blocks of ``qb`` rows of each of ``xs``, the results laid
    end to end again."""
    S = xs[0].shape[0]
    out = jax.lax.map(lambda b: fn(*(jax.lax.dynamic_slice_in_dim(
        x, b * qb, qb) for x in xs)), jnp.arange(S // qb))
    return jax.tree.map(lambda a: a.reshape(S, *a.shape[2:]), out)


def rope(x, rope_dim, theta):
    """``x [S, heads, D]`` at the positions ``0 .. S - 1``: the first
    ``rope_dim`` lanes rotated in half-split pairs ``(i, i + rope_dim / 2)``,
    the others as they are."""
    half = rope_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv_freq
    a, b, rest = x[..., :half], x[..., half:rope_dim], x[..., rope_dim:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            a * jnp.sin(ang) + b * jnp.cos(ang), rest], axis=-1)


def _delta_tokens(p, h, *, Hk, Hv, dk, dv, qb):
    """Steps 1 to 4 over ``h [S, E]``: what the recurrence reads of every
    token: q, k ``[S, Hk, dk]``, v ``[S, Hv, dv]``, alpha, beta ``[S, Hv]``,
    and the output gate's z ``[S, Hv dv]``."""
    f32 = lambda a: a.astype(jnp.float32)
    S = h.shape[0]
    u = _rows(lambda r: r @ f32(p["qkv_w"]), qb, h)
    z = _rows(lambda r: r @ f32(p["gate_w"]), qb, h)
    ba = h @ f32(p["ba_w"])
    # 2. the convolution, as shifts of the whole sequence
    w = f32(p["conv_w"])
    taps = w.shape[0]
    shifted = lambda n: jnp.pad(u, ((n, 0), (0, 0)))[:S]           # row t is u_{t-n}
    c = jax.nn.silu(sum(w[j] * shifted(taps - 1 - j) for j in range(taps)))
    q = c[:, :Hk * dk].reshape(S, Hk, dk)
    k = c[:, Hk * dk:2 * Hk * dk].reshape(S, Hk, dk)
    v = c[:, 2 * Hk * dk:].reshape(S, Hv, dv)
    # 3. and 4.
    unit = lambda t: t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) / math.sqrt(dk), unit(k)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    alpha = jnp.exp(-jnp.exp(f32(p["a_log"])) * jax.nn.softplus(
        ba[:, Hv:] + f32(p["dt_bias"])))
    return q, k, v, alpha, beta, z


def _delta_scan(q, k, v, alpha, beta, live=None):
    """Step 5, a token at a time: -> (the state after the last live token
    ``[Hv, dk, dv]``, o ``[S, Hv, dv]``).  ``live [S]``: the tokens that
    carry the sequence (all of them: None); the others leave the state as it
    is."""
    S, Hv = v.shape[:2]
    of = jnp.arange(Hv) // (Hv // q.shape[1])      # value head j's key head
    live = jnp.ones(S, bool) if live is None else live

    def token(state, row):
        q_t, k_t, v_t, a_t, b_t, on = row
        q_t, k_t = q_t[of], k_t[of]                                # [Hv, dk]
        decayed = a_t[:, None, None] * state                       # [Hv, dk, dv]
        m = jnp.einsum("hkv,hk->hv", decayed, k_t)
        d = b_t[:, None] * (v_t - m)
        new = decayed + k_t[:, :, None] * d[:, None, :]
        return jnp.where(on, new, state), jnp.einsum("hkv,hk->hv", new, q_t)

    return jax.lax.scan(token, jnp.zeros((Hv, q.shape[2], v.shape[2]), jnp.float32),
                        (q, k, v, alpha, beta, live))


def delta_layer(p, h, *, Hk, Hv, dk, dv, eps, qb):
    """``h [S, E]`` the normed input -> the delta mixer's output ``[S, E]``."""
    S = h.shape[0]
    q, k, v, alpha, beta, z = _delta_tokens(p, h, Hk=Hk, Hv=Hv, dk=dk, dv=dv, qb=qb)
    _, o = _delta_scan(q, k, v, alpha, beta)
    # 6. the norm a head, then the gate
    y = _rms(o, p["onorm_g"].astype(jnp.float32), eps) * jax.nn.silu(z.reshape(S, Hv, dv))
    return _rows(lambda r: r @ p["out_w"].astype(jnp.float32), qb, y.reshape(S, Hv * dv))


def full_layer(p, h, *, H, Hkv, D, rope_dim, theta, eps, qb):
    """``h [S, E]`` the normed input -> gated softmax attention's output
    ``[S, E]``."""
    f32 = lambda a: a.astype(jnp.float32)
    S, g = h.shape[0], H // Hkv
    qkv = _rows(lambda r: r @ f32(p["qkv_w"]), qb, h)
    gate = _rows(lambda r: r @ f32(p["gate_w"]), qb, h)
    q, k, v = jnp.split(qkv, [H * D, (H + Hkv) * D], axis=-1)
    q = rope(_rms(q.reshape(S, H, D), f32(p["q_norm_g"]), eps), rope_dim, theta)
    k = rope(_rms(k.reshape(S, Hkv, D), f32(p["k_norm_g"]), eps), rope_dim, theta)
    v = v.reshape(S, Hkv, D)
    pos = jnp.arange(S)

    def head(i):
        keys = jax.lax.dynamic_index_in_dim(k, i // g, 1, False)
        values = jax.lax.dynamic_index_in_dim(v, i // g, 1, False)
        queries = jax.lax.dynamic_index_in_dim(q, i, 1, False)

        def block(b):
            at = b * qb + jnp.arange(qb)
            s = jax.lax.dynamic_slice_in_dim(queries, b * qb, qb) @ keys.T / math.sqrt(D)
            s = jnp.where(pos[None] <= at[:, None], s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ values
        return jax.lax.map(block, jnp.arange(S // qb)).reshape(S, D)

    o = jax.lax.map(head, jnp.arange(H)).transpose(1, 0, 2).reshape(S, H * D)
    return _rows(lambda r, t: (r * jax.nn.sigmoid(t)) @ f32(p["out_w"]), qb, o, gate)


def routed_weights(p, m, top_k):
    """``m [S, E]`` (the feed-forward's normed input) -> each token's weight
    an expert the router chooses among ``[S, num_experts]``: of the softmax
    over ALL, the ``top_k`` largest divided by their sum, 0 elsewhere."""
    probs = jax.nn.softmax(m @ p["router_w"].astype(jnp.float32), axis=-1)
    top, chosen = jax.lax.top_k(probs, top_k)
    return jnp.einsum("sk,ske->se", top / top.sum(axis=-1, keepdims=True),
                      jax.nn.one_hot(chosen, probs.shape[-1], dtype=jnp.float32))


def _swiglu(m, wi, wo):
    gate, up = jnp.split(m @ wi.astype(jnp.float32), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ wo.astype(jnp.float32)


def ffn(p, m, *, top_k, experts_held, shared=True):
    """The bank's HELD part and the gated shared expert over ``m [S, E]``."""
    weight = routed_weights(p, m, top_k)
    first, count = experts_held or (0, weight.shape[-1])
    weight = weight[:, first:first + count]

    def expert(y, e):
        return y + jax.lax.dynamic_index_in_dim(weight, e, 1, True) * _swiglu(
            m, p["experts"]["wi"][e], p["experts"]["wo"][e]), None

    y = jax.lax.scan(expert, jnp.zeros_like(m), jnp.arange(count))[0]
    if shared:
        y = y + jax.nn.sigmoid(m @ p["shared_gate_w"].astype(jnp.float32)) * _swiglu(
            m, p["shared_fc_w"], p["shared_proj_w"])
    return y


def qwen3_next_hidden(params, ids, *, layer_types, n_head, n_kv_head, head_dim,
                      rope_dim, linear_key_heads, linear_heads,
                      linear_key_head_dim, linear_value_head_dim, top_k,
                      experts_held=None, shared=True, rope_theta=1e7, eps=1e-6,
                      q_block=512, **_):
    """``ids [S]`` -> the final norm's output ``[S, hidden]`` in float32.
    ``S`` is a multiple of ``q_block`` or under it."""
    f32 = lambda a: a.astype(jnp.float32)
    S = ids.shape[0]
    qb = min(q_block, S)
    assert S % qb == 0, (S, qb)
    mixers = {
        "delta": lambda p, h: delta_layer(
            p, h, Hk=linear_key_heads, Hv=linear_heads, dk=linear_key_head_dim,
            dv=linear_value_head_dim, eps=eps, qb=qb),
        "full": lambda p, h: full_layer(
            p, h, H=n_head, Hkv=n_kv_head, D=head_dim, rope_dim=rope_dim,
            theta=rope_theta, eps=eps, qb=qb)}
    seen = dict.fromkeys(mixers, 0)
    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"][ids])
        for kind in layer_types:
            name = MIXER_OF[kind]
            p = jax.tree.map(lambda a, i=seen[name]: a[i], params["blocks"][name])
            seen[name] += 1
            x = x + mixers[name](p, _rms(x, f32(p["ln1_g"]), eps))
            x = x + ffn(p, _rms(x, f32(p["ln2_g"]), eps), top_k=top_k,
                        experts_held=experts_held, shared=shared)
        return _rms(x, f32(params["lnf_g"]), eps)


def qwen3_next_first_state(params, ids, n, *, layer_types, linear_key_heads, linear_heads,
                           linear_key_head_dim, linear_value_head_dim, eps=1e-6,
                           q_block=512, **_):
    """``ids [S]`` -> the FIRST layer's (a delta layer's) state after its
    first ``n`` tokens, ``[value heads, dk, dv]`` float32: what a slot that
    has taken in those tokens keeps.  That layer's input is the embedding, so
    what parts a served state from this one is the layer's own arithmetic."""
    assert layer_types[0] == "linear_attention", layer_types
    f32 = lambda a: a.astype(jnp.float32)
    qb = min(q_block, ids.shape[0])
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a[0], {k: v for k, v in params["blocks"]["delta"].items()
                                          if k != "experts"})
        h = _rms(f32(params["wte"][ids]), f32(p["ln1_g"]), eps)
        q, k, v, alpha, beta, _ = _delta_tokens(
            p, h, Hk=linear_key_heads, Hv=linear_heads, dk=linear_key_head_dim,
            dv=linear_value_head_dim, qb=qb)
        return _delta_scan(q, k, v, alpha, beta, jnp.arange(ids.shape[0]) < n)[0]


def qwen3_next_head(params, hidden, *, vocab_size, **_):
    """Rows of :func:`qwen3_next_hidden` -> their logits ``[rows,
    vocab_size]`` in float32, through the untied head."""
    with jax.default_matmul_precision("highest"):
        return (hidden @ params["lm_head"].astype(jnp.float32).T)[:, :vocab_size]


def qwen3_next_logits(params, ids, lo=0, hi=None, **kw):
    """``ids [S]`` -> logits of the positions ``lo .. hi - 1`` (all of them
    by default), ``[hi - lo, vocab_size]`` in float32: one full forward pass,
    the head over the asked range alone."""
    return qwen3_next_head(params, qwen3_next_hidden(params, ids, **kw)[lo:hi], **kw)
