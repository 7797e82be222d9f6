"""The plain reference for Olmo-Hybrid-7B (``model_type`` ``olmo_hybrid`` of
``https://huggingface.co/allenai/Olmo-Hybrid-7B``): the forward pass in
straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``.  No kernel, no cache, no pages, no
state between calls, no batching: the convolution over time is shifts of the
WHOLE sequence, the gated delta rule a ``lax.scan`` over the tokens of its
recurrence exactly as written below (NOT the chunked form the program uses
over a prompt chunk, nor the multiplied-out read its decode kernel uses: the
reference shares none of the program's algebra), full attention a dense
causal score matrix.  It imports nothing from ``deepspeed_tpu``.

eps 1e-6; no bias anywhere.  The widths are the catalog's config, the form
of the linear layers the gated delta rule's (arXiv:2412.06464, with
``linear_allow_neg_eigval`` of arXiv:2411.12537), the block OLMo 2/3's; what
the config does not fix is under ``assumed`` in
``benchmarks/configs/olmo-hybrid-7b.json``:

    x_0 = wte[ids]
    x <- x + RMSNorm_a(Mixer_l(x));  x <- x + RMSNorm_f(MLP_l(x))
    logits = lm_head RMSNorm(x)                                  untied head
    MLP(x) = W_down(silu(W_gate x) * W_up x)

    linear_attention (H heads, a key of dk and a value of dv lanes):
    1. q~_t = W_q x_t, k~_t = W_k x_t [H dk]; v~_t = W_v x_t, z_t = W_z x_t
       [H dv]; b_t = W_b x_t, a_t = W_a x_t [H]
    2. u_t = [q~_t ; k~_t ; v~_t];  c_t[ch] = silu(sum_{j=0..3} w[j, ch]
       u_{t-3+j}[ch]), u_s = 0 for s < 0; split c_t into q_t, k_t, v_t
    3. a head: q <- q / sqrt(|q|^2 + 1e-6), k likewise; q <- q / sqrt(dk)
    4. beta_t = 2 sigmoid(b_t);  g_t = -exp(A_log) softplus(a_t + dt_bias);
       alpha_t = exp(g_t)
    5. S_{-1} = 0;  S' = alpha_t S_{t-1};  m = S'^T k_t;  d_t = beta_t (v_t -
       m);  S_t = S' + k_t d_t^T;  o_t = S_t^T q_t
    6. y_t[h] = o_t[h] / sqrt(mean(o_t[h]^2) + 1e-6) * gamma * silu(z_t[h]);
       Mixer(x)_t = W_o [y_t[0] .. y_t[H-1]]

    full_attention (H heads on H K/V heads of D lanes):
    q_t = RMSNorm_q(W_q x_t), k_t = RMSNorm_k(W_k x_t) over all H D lanes;
    v_t = W_v x_t;  NO rotary embedding;  o_t[h] = sum_{s <= t} softmax_s(
    q_t[h] . k_s[h] / sqrt(D)) v_s[h];  Mixer(x)_t = W_o [o_t[0] .. o_t[H-1]]

It reads the program's parameter tree by its leaf names:
``blocks/delta/{qkv_w, gate_w, ba_w, conv_w, a_log, dt_bias, onorm_g, out_w}``
(``qkv_w``'s column blocks are W_q, W_k, W_v in that order, ``ba_w``'s W_b
then W_a, ``conv_w [taps, lanes]`` holds the oldest token's tap first),
``blocks/full/{qkv_w, q_norm_g, k_norm_g, out_w}``, both with ``ln1_g``
(RMSNorm_a), ``ln2_g`` (RMSNorm_f), ``fc_w`` (W_gate then W_up), ``proj_w``;
``wte``, ``lnf_g``, ``lm_head``.  The weights are the system's, the
arithmetic is not.  Everything a token does alone runs a block of
``q_block`` rows at a time and attention a head and a block of queries at a
time: the blocks change the order of nothing summed.
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


def _delta(p, x, *, H, dk, dv, eps, neg_eigval, qb):
    f32 = lambda a: a.astype(jnp.float32)
    S = x.shape[0]
    u = _rows(lambda r: r @ f32(p["qkv_w"]), qb, x)
    z = _rows(lambda r: r @ f32(p["gate_w"]), qb, x)
    ba = x @ f32(p["ba_w"])
    # 2. the convolution, as shifts of the whole sequence
    w = f32(p["conv_w"])
    taps = w.shape[0]
    shifted = lambda n: jnp.pad(u, ((n, 0), (0, 0)))[:S]           # row t is u_{t-n}
    c = jax.nn.silu(sum(w[j] * shifted(taps - 1 - j) for j in range(taps)))
    q = c[:, :H * dk].reshape(S, H, dk)
    k = c[:, H * dk:2 * H * dk].reshape(S, H, dk)
    v = c[:, 2 * H * dk:].reshape(S, H, dv)
    # 3. and 4.
    unit = lambda t: t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) / math.sqrt(dk), unit(k)
    beta = (2.0 if neg_eigval else 1.0) * jax.nn.sigmoid(ba[:, :H])
    alpha = jnp.exp(-jnp.exp(f32(p["a_log"])) * jax.nn.softplus(
        ba[:, H:] + f32(p["dt_bias"])))

    # 5. the recurrence, a token at a time
    def token(state, row):
        q_t, k_t, v_t, a_t, b_t = row
        decayed = a_t[:, None, None] * state                       # [H, dk, dv]
        m = jnp.einsum("hkv,hk->hv", decayed, k_t)
        d = b_t[:, None] * (v_t - m)
        state = decayed + k_t[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, v, alpha, beta))
    # 6. the norm a head, then the gate
    y = _rms(o, f32(p["onorm_g"]), eps) * jax.nn.silu(z.reshape(S, H, dv))
    return _rows(lambda r: r @ f32(p["out_w"]), qb, y.reshape(S, H * dv))


def _full(p, x, *, H, D, eps, qb):
    f32 = lambda a: a.astype(jnp.float32)
    S = x.shape[0]
    qkv = _rows(lambda r: r @ f32(p["qkv_w"]), qb, x)
    q = _rms(qkv[:, :H * D], f32(p["q_norm_g"]), eps).reshape(S, H, D)
    k = _rms(qkv[:, H * D:2 * H * D], f32(p["k_norm_g"]), eps).reshape(S, H, D)
    v = qkv[:, 2 * H * D:].reshape(S, H, D)
    pos = jnp.arange(S)

    def head(j):
        def block(b):
            rows = jax.lax.dynamic_slice_in_dim(q[:, j], b * qb, qb)
            at = b * qb + jnp.arange(qb)
            s = rows @ k[:, j].T / math.sqrt(D)
            s = jnp.where(pos[None] <= at[:, None], s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v[:, j]
        return jax.lax.map(block, jnp.arange(S // qb)).reshape(S, D)

    o = jax.lax.map(head, jnp.arange(H))                           # [H, S, D]
    return _rows(lambda r: r @ f32(p["out_w"]), qb,
                 o.transpose(1, 0, 2).reshape(S, H * D))


def _mlp(p, x, qb):
    def rows(r):
        gate, up = jnp.split(r @ p["fc_w"].astype(jnp.float32), 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ p["proj_w"].astype(jnp.float32)
    return _rows(rows, qb, x)


def olmo_hybrid_hidden(params, ids, *, layer_types, n_head, head_dim,
                       linear_heads, linear_key_head_dim, linear_value_head_dim,
                       linear_allow_neg_eigval=True, eps=1e-6, q_block=512, **_):
    """``ids [S]`` -> the final norm's output ``[S, hidden]`` in float32.
    ``S`` is a multiple of ``q_block`` or under it."""
    f32 = lambda a: a.astype(jnp.float32)
    S = ids.shape[0]
    qb = min(q_block, S)
    assert S % qb == 0, (S, qb)
    mixers = {
        "delta": lambda p, x: _delta(
            p, x, H=linear_heads, dk=linear_key_head_dim, dv=linear_value_head_dim,
            eps=eps, neg_eigval=linear_allow_neg_eigval, qb=qb),
        "full": lambda p, x: _full(p, x, H=n_head, D=head_dim, eps=eps, qb=qb)}
    seen = dict.fromkeys(mixers, 0)
    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"][ids])
        for kind in layer_types:
            name = MIXER_OF[kind]
            p = jax.tree.map(lambda a, i=seen[name]: a[i], params["blocks"][name])
            seen[name] += 1
            # the mixer and the MLP read the residual as it is; each norm is
            # on its sublayer's output
            x = x + _rms(mixers[name](p, x), f32(p["ln1_g"]), eps)
            x = x + _rms(_mlp(p, x, qb), f32(p["ln2_g"]), eps)
        return _rms(x, f32(params["lnf_g"]), eps)


def olmo_hybrid_head(params, hidden, *, vocab_size, **_):
    """Rows of :func:`olmo_hybrid_hidden` -> their logits ``[rows,
    vocab_size]`` in float32, through the untied head."""
    with jax.default_matmul_precision("highest"):
        return (hidden @ params["lm_head"].astype(jnp.float32).T)[:, :vocab_size]


def olmo_hybrid_logits(params, ids, lo=0, hi=None, **kw):
    """``ids [S]`` -> logits of the positions ``lo .. hi - 1`` (all of them
    by default), ``[hi - lo, vocab_size]`` in float32: one full forward pass,
    the head over the asked range alone."""
    return olmo_hybrid_head(params, olmo_hybrid_hidden(params, ids, **kw)[lo:hi], **kw)
