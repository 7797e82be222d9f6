"""The plain reference for ZAYA1-8B (``model_type`` ``zaya`` of
``https://huggingface.co/Zyphra/ZAYA1-8B``): the forward pass in
straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``.  No kernel, no cache, no pages, no
state, no batching, no sorting of tokens: the convolutions over time are
shifts of the WHOLE sequence, attention a dense causal score matrix, and
EVERY expert is computed for EVERY token and weighed by 0 where the router
did not choose it.

``h = RMSNorm(x)`` (eps 1e-5, one gain a sublayer); no bias anywhere but the
convolutions'.  ``H`` query heads on ``Hkv`` K/V heads of ``D`` lanes, ``G =
H / Hkv``; the widths are the catalog's config, the FORM of the mixing the
CCA paper's (arXiv:2510.04476), the form of the router the ZAYA1 report's
(arXiv:2511.17127); what neither fixes is under ``assumed`` in
``benchmarks/configs/zaya1-8b.json``:

    x_0 = wte[ids]
    x <- x + CCA_l(RMSNorm_1(x));  x <- x + MoE_l(RMSNorm_2(x))
    logits = wte RMSNorm(x)                                      tied head

    CCA:
    1. q~_t = W_q h_t [H D], k~_t = W_k h_t [Hkv D]; u_t = [q~_t ; k~_t]
    2. u is padded ONCE on the left with two zero vectors, then
       a_t[c] = w0[c,0] u_{t-1}[c] + w0[c,1] u_t[c] + b0[c]   (depthwise)
       c_t[g] = W1[g,0] a_{t-1}[g] + W1[g,1] a_t[g] + b1[g]   (grouped by
       head: H + Hkv groups, each tap a D x D map), with a_{-1} = b0: the
       first convolution of the padding
    3. the q-k mean of the latents BEFORE the convolutions:
       m^q_t[j] = (q~_t[j] + k~_t[j // G]) / 2;  m^k_t[i] = mean_j m^q_t[j]
       over the G heads of group i;  q_t = c^q_t + m^q_t, k_t = c^k_t + m^k_t
    4. q_t[j] <- sqrt(D) q_t[j] / |q_t[j]|;  k_t[i] <- tau_i sqrt(D) k_t[i] /
       |k_t[i]|, tau [Hkv] learned
    5. rope on the first D / 2 lanes of every head of q and k (lane i with
       lane i + D / 4 of the rotated half), theta 5e6, at the token's position
    6. v_t = [W_v1 h_t ; W_v2 h_{t-1}], h_{-1} = 0: the first half of the
       K/V heads' values is the current token's, the second the previous one's
    7. o_t[j] = sum_{s <= t} softmax_s(q_t[j] . k_s[j // G] / sqrt(D))
       v_s[j // G];  CCA(h)_t = W_o [o_t[0] .. o_t[H - 1]]

    MoE (E experts, ONE a token, router width R):
    1. r^l_t = W_r h_t + gamma_l r^{l-1}_t   (the routers' own stream; 0
       before the first layer held)
    2. z = W_3 gelu(W_2 gelu(W_1 RMSNorm(r^l_t)));  p = softmax(z)
    3. e = argmax(p + beta);  MoE(h)_t = p_e W_down^e(silu(W_gate^e h_t) *
       (W_up^e h_t))

It reads the program's parameter tree by its leaf names:
``blocks/cca/{ln1_g, qkv_w, conv0_w, conv0_b, conv1_w, conv1_b, k_scale_g,
out_w, ln2_g, router_in_w, stream_g, router_norm_g, router_w1, router_w2,
router_w3, balance_bias, experts/{wi, wo}}``; ``wte``, ``lnf_g``.  The
weights are the system's, the arithmetic is not.  Departures:

* W_q, W_k, W_v1, W_v2 are the column blocks of ``qkv_w`` in that order;
  ``conv0_w [2, U]`` and ``conv1_w [2, H + Hkv, D, D]`` hold tap 0 (the
  token before) first; W_gate and W_up the two column halves of
  ``experts/wi`` (gate first);
* ``described_as`` names a residual scaling and "MoD"; the config has no key
  for either, so neither is computed;
* everything a token does alone runs a block of ``q_block`` rows at a time,
  attention a head and a block of queries at a time, and one expert at a
  time is made float32, so that 12,288 positions fit beside 9.4 GB of
  resident bf16 weights: the blocks change the order of nothing summed.
"""

import math

import jax
import jax.numpy as jnp


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta, rotated):
    """``x [S, heads, D]`` at positions 0..S-1: the first ``rotated`` lanes
    turned, lane ``i`` with lane ``i + rotated / 2``; the rest as they are."""
    S = x.shape[0]
    half = rotated // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None])[:, None]
    x1, x2 = x[..., :half], x[..., half:rotated]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang),
                            x[..., rotated:]], axis=-1)


def _rows(fn, qb, *xs):
    """``fn`` over blocks of ``qb`` rows of each of ``xs``, the results laid
    end to end again (a tuple of results each)."""
    S = xs[0].shape[0]
    out = jax.lax.map(lambda b: fn(*(jax.lax.dynamic_slice_in_dim(
        x, b * qb, qb) for x in xs)), jnp.arange(S // qb))
    return jax.tree.map(lambda a: a.reshape(S, *a.shape[2:]), out)


def _before(x):
    """The sequence shifted one token later: row ``t`` is ``x_{t-1}``, row 0
    the zero padding."""
    return jnp.pad(x, ((1, 0), (0, 0)))[:-1]


def _cca(p, h, *, H, Hkv, D, theta, rotary, qb):
    f32 = lambda a: a.astype(jnp.float32)
    S, G, U = h.shape[0], H // Hkv, (H + Hkv) * D
    proj = _rows(lambda r: r @ f32(p["qkv_w"]), qb, h)
    u, v1, v2 = proj[:, :U], proj[:, U:U + Hkv * D // 2], proj[:, U + Hkv * D // 2:]
    # 2. the two convolutions, as shifts of the whole sequence
    w0, b0 = f32(p["conv0_w"]), f32(p["conv0_b"])
    a = w0[0] * _before(u) + w0[1] * u + b0
    a_before = jnp.concatenate([b0[None], a[:-1]])      # a_{-1}: the padding's
    w1 = f32(p["conv1_w"])
    grouped = lambda t: t.reshape(S, H + Hkv, D)
    c = (jnp.einsum("sgi,gio->sgo", grouped(a_before), w1[0])
         + jnp.einsum("sgi,gio->sgo", grouped(a), w1[1])
         + f32(p["conv1_b"]).reshape(H + Hkv, D))
    # 3. the q-k mean
    q_lat, k_lat = grouped(u)[:, :H], grouped(u)[:, H:]
    mean_q = (q_lat + jnp.repeat(k_lat, G, axis=1)) / 2
    mean_k = mean_q.reshape(S, Hkv, G, D).mean(axis=2)
    q, k = c[:, :H] + mean_q, c[:, H:] + mean_k
    # 4. and 5.
    norm = lambda t: math.sqrt(D) * t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = _rope(norm(q), theta, rotary)
    k = _rope(norm(k) * f32(p["k_scale_g"])[None, :, None], theta, rotary)
    # 6. the value: this token's half, then the previous token's
    v = jnp.concatenate([v1, _before(v2)], axis=1).reshape(S, Hkv, D)
    # 7. causal attention over every key, a head and a block of queries at a time
    pos = jnp.arange(S)

    def head(j):
        kj, vj = k[:, j // G], v[:, j // G]

        def block(b):
            rows = jax.lax.dynamic_slice_in_dim(q[:, j], b * qb, qb)
            at = b * qb + jnp.arange(qb)
            s = rows @ kj.T / math.sqrt(D)
            s = jnp.where(pos[None] <= at[:, None], s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ vj
        return jax.lax.map(block, jnp.arange(S // qb)).reshape(S, D)

    o = jax.lax.map(head, jnp.arange(H))                           # [H, S, D]
    return _rows(lambda r: r @ f32(p["out_w"]), qb,
                 o.transpose(1, 0, 2).reshape(S, H * D))


def _moe(p, h, stream, *, eps, qb):
    """-> (the layer's routed output, the stream for the next router, the
    expert of every token)."""
    f32 = lambda a: a.astype(jnp.float32)
    stream = _rows(lambda r: r @ f32(p["router_in_w"]), qb, h) + f32(p["stream_g"]) * stream
    z = _rms(stream, f32(p["router_norm_g"]), eps)
    for name in ("router_w1", "router_w2"):
        z = jax.nn.gelu(z @ f32(p[name]), approximate=True)
    prob = jax.nn.softmax(z @ f32(p["router_w3"]), axis=-1)
    chosen = jnp.argmax(prob + f32(p["balance_bias"]), axis=-1)
    weight = prob * jax.nn.one_hot(chosen, prob.shape[-1])          # [S, E]

    def expert(y, e):
        wi, wo = f32(p["experts"]["wi"][e]), f32(p["experts"]["wo"][e])

        def rows(r, w):
            gate, up = jnp.split(r @ wi, 2, axis=-1)
            return ((jax.nn.silu(gate) * up) @ wo) * w[:, None]
        return y + _rows(rows, qb, h, weight[:, e]), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(prob.shape[-1]))
    return y, stream, chosen


def zaya_hidden(params, ids, *, n_head, n_kv_head, head_dim, eps=1e-5,
                rope_theta=5e6, partial_rotary_factor=0.5, q_block=1024,
                with_experts=False, **_):
    """``ids [S]`` -> the final norm's output ``[S, hidden]`` in float32
    (with ``with_experts`` also every token's expert a layer ``[L, S]``).
    ``S`` is a multiple of ``q_block`` or under it."""
    f32 = lambda a: a.astype(jnp.float32)
    S = ids.shape[0]
    qb = min(q_block, S)
    assert S % qb == 0, (S, qb)
    stack = params["blocks"]["cca"]

    def layer(carry, p):
        x, stream = carry
        x = x + _cca(p, _rms(x, f32(p["ln1_g"]), eps), H=n_head, Hkv=n_kv_head,
                     D=head_dim, theta=rope_theta,
                     rotary=int(head_dim * partial_rotary_factor), qb=qb)
        y, stream, chosen = _moe(p, _rms(x, f32(p["ln2_g"]), eps), stream,
                                 eps=eps, qb=qb)
        return (x + y, stream), chosen

    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"][ids])
        stream = jnp.zeros((S, stack["router_in_w"].shape[-1]), jnp.float32)
        (x, _), chosen = jax.lax.scan(layer, (x, stream), stack)
        hidden = _rms(x, f32(params["lnf_g"]), eps)
    return (hidden, chosen) if with_experts else hidden


def zaya_head(params, hidden, *, vocab_size, **_):
    """Rows of :func:`zaya_hidden` -> their logits ``[rows, vocab_size]`` in
    float32, through the embedding the head is tied to."""
    with jax.default_matmul_precision("highest"):
        return (hidden @ params["wte"].astype(jnp.float32).T)[:, :vocab_size]


def zaya_logits(params, ids, lo=0, hi=None, **kw):
    """``ids [S]`` -> logits of the positions ``lo .. hi - 1`` (all of them
    by default), ``[hi - lo, vocab_size]`` in float32: one full forward pass,
    the head over the asked range alone."""
    return zaya_head(params, zaya_hidden(params, ids, **kw)[lo:hi], **kw)
