"""The plain reference for MiniCPM-SALA (``model_type`` ``minicpm_sala`` of
``https://huggingface.co/openbmb/MiniCPM-SALA``): the forward pass in
straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``.  No kernel, no cache, no pages, no
batching, no chunked scan: a linear layer is the plain recurrence a token at
a time, a sparse layer a dense score matrix with the selection applied as a
mask.

``h = RMSNorm(x)`` (eps 1e-6), ``d = head_dim``; the layers follow
``mixer_types`` in its own order:

    x_0 = scale_emb * wte[ids]
    x <- x + (scale_depth / sqrt(published_layers)) * mixer(RMSNorm_1(x))
    x <- x + (scale_depth / sqrt(published_layers)) * MLP(RMSNorm_2(x))
    MLP(h) = W_down(silu(W_gate h) * (W_up h))                     no bias
    logits = lm_head(RMSNorm(x) / (hidden_size / dim_model_base))   untied

    "lightning-attn" (n_head heads, K/V heads as many):
    q, k, v = W_q h, W_k h, W_v h;  q, k <- RMSNorm over a head's d lanes
    (one gain [d] for all heads), then rope (theta, half-split pairing) at
    the token's position;
    S_t = exp(-s_h) S_{t-1} + k_t^T v_t     [d, d] a head, S_{-1} = 0
    o_t = (q_t / sqrt(d)) S_t
    y = W_o( RMSNorm_{all lanes}(o) * sigmoid(W_g h) )
    s_h = 2^(-8 (h + 1) / n_head) * (1 - l / (published_layers - 1) + 1e-5),
    l the layer's PUBLISHED index.

    "minicpm4" (n_head query heads on n_kv_head K/V heads, NO rope):
    q, k, v projected, q and k normed a head as above.
    kc_j = mean(k[stride j : stride j + kernel]) a K/V head.
    A query at t with more than dense_len keys (t + 1 > dense_len):
      a_{h,j} = softmax_j(q_h . kc_j / sqrt(d)) over the j with
                stride j + kernel - 1 <= t;
      summed over the query heads of a K/V head;
      a block's score (block b = keys block b .. block (b + 1) - 1) the MAX
      over the compressed keys that overlap it;
      the first init_blocks blocks and the blocks of the keys t - window + 1
      .. t score +inf; the topk highest among the blocks 0 .. t // block are
      the query's blocks for that K/V head;
      o_h = softmax(q_h . k_s / sqrt(d)) v_s over the keys s <= t of them.
    With dense_len keys or fewer every key s <= t is attended.
    y = W_o( o * sigmoid(W_g h) )

It reads the program's parameter tree by its leaf names, stacked by kind:
``blocks/sparse/{ln1_g, q_w, kv_w, q_norm_g, k_norm_g, gate_w, out_w, ln2_g,
fc_w, proj_w}``, ``blocks/linear/{ln1_g, qkv_w, q_norm_g, k_norm_g, onorm_g,
gate_w, out_w, ln2_g, fc_w, proj_w}``; ``wte``, ``lnf_g``, ``lm_head``.  The
weights are the system's, the arithmetic is not.  Departures:

* W_q, W_k, W_v are the column blocks of ``qkv_w`` (linear) or ``q_w`` and
  the two halves of ``kv_w`` (sparse); W_gate and W_up the two column halves
  of ``fc_w`` (gate first);
* the selection's softmax is EXACT over the compressed keys; the released
  kernels approximate its normaliser from a second, coarser compression;
* two blocks TIE exactly whenever the compressed key that straddles their
  border scores highest for both; of blocks that tie, the earlier ones are
  "the topk highest" (``jax.lax.top_k``'s order), the published kernels'
  order being whatever their sort gives;
* ``mup_denominator`` is read by no equation of the forward pass;
* rows of the embedding and the head beyond the vocabulary are cut off;
* everything a token does alone (projections, gates, the MLP) and a sparse
  layer's scores run a block of ``q_block`` rows at a time, a head at a
  time, a linear layer's recurrence eight heads at a time, and one matrix at
  a time is made float32, so that 46,080 positions fit beside 10 GB of
  resident bf16 weights: the blocks change the order of nothing that is
  summed.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

SPARSE = {"kernel": 32, "stride": 16, "block": 64, "topk": 64,
          "init_blocks": 1, "window": 2048, "dense_len": 8192}


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


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


def _gated_out(p, o, x, norm, qb):
    """``W_o(o * sigmoid(W_g RMSNorm_1(x)))``, a block of rows at a time."""
    f32 = lambda a: a.astype(jnp.float32)
    return _rows(lambda o, r: (o * jax.nn.sigmoid(norm(r) @ f32(p["gate_w"])))
                 @ f32(p["out_w"]), qb, o, x)


def _linear_mixer(p, x, norm, *, H, D, eps, theta, decay, qb, heads_at_once=8):
    f32 = lambda a: a.astype(jnp.float32)
    S, A, G = x.shape[0], H * D, min(heads_at_once, H)

    def heads(first):
        """The recurrence of the heads ``first .. first + G - 1``: ``[S, G, D]``."""
        part = lambda n: _rows(lambda r: norm(r) @ f32(jax.lax.dynamic_slice_in_dim(
            p["qkv_w"], n * A + first * D, G * D, axis=1)), qb, x).reshape(S, G, D)
        q = _rope(_rms(part(0), f32(p["q_norm_g"]), eps), theta) / math.sqrt(D)
        k = _rope(_rms(part(1), f32(p["k_norm_g"]), eps), theta)
        shrink = jnp.exp(-jax.lax.dynamic_slice_in_dim(decay, first, G))[:, None, None]

        def token(state, qkv_t):
            q_t, k_t, v_t = qkv_t                                   # [G, D]
            state = shrink * state + k_t[:, :, None] * v_t[:, None, :]
            return state, jnp.einsum("hd,hde->he", q_t, state)

        return jax.lax.scan(token, jnp.zeros((G, D, D), jnp.float32),
                            (q, k, part(2)))[1]

    o = jax.lax.map(heads, G * jnp.arange(H // G)).transpose(1, 0, 2, 3)
    return _gated_out(p, _rms(o.reshape(S, A), f32(p["onorm_g"]), eps), x, norm, qb)


def _sparse_mixer(p, x, norm, *, H, Hkv, D, eps, sp, qb):
    f32 = lambda a: a.astype(jnp.float32)
    S, g = x.shape[0], H // Hkv
    kernel, stride, block = sp["kernel"], sp["stride"], sp["block"]
    q = _rms(_rows(lambda r: norm(r) @ f32(p["q_w"]), qb, x).reshape(S, H, D),
             f32(p["q_norm_g"]), eps)
    kv = _rows(lambda r: norm(r) @ f32(p["kv_w"]), qb, x).reshape(S, 2, Hkv, D)
    k, v = _rms(kv[:, 0], f32(p["k_norm_g"]), eps), kv[:, 1]
    # compressed key j: the mean of the keys stride j .. stride j + kernel - 1
    J = max((S - kernel) // stride + 1, 1)
    covers = stride * np.arange(J)[:, None] + np.arange(kernel)[None]
    kc = k[np.minimum(covers, S - 1)].mean(axis=1)                  # [J, Hkv, D]
    ends = jnp.asarray(covers[:, -1])              # past S - 1 where S < kernel
    # the compressed keys that overlap block b are j_lo[b] .. j_hi[b]
    n_blocks = -(-S // block)
    first_key = block * np.arange(n_blocks)
    j_lo = np.maximum(-(-(first_key - kernel + 1) // stride), 0)
    j_hi = (first_key + block - 1) // stride
    over = j_lo[:, None] + np.arange((j_hi - j_lo).max() + 1)[None]   # [blocks, w]
    real = jnp.asarray((over <= j_hi[:, None]) & (over < J))
    over = np.minimum(over, J - 1)
    t_key = jnp.arange(S)

    def chosen_keys(qs, kc_h, t):
        """``qs [qb, g, D]`` of one K/V head at positions ``t [qb]`` -> which
        keys each query attends, ``[qb, S]``."""
        seen = (ends[None] <= t[:, None])[:, None]                  # [qb, 1, J]
        s = jnp.einsum("qgd,jd->qgj", qs, kc_h) / math.sqrt(D)
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        a = jnp.where(seen, a, 0.0).sum(axis=1)                     # [qb, J]
        score = jnp.where(real[None], a[:, over], 0.0).max(axis=-1)   # [qb, blocks]
        b = jnp.arange(n_blocks)[None]
        forced = (b < sp["init_blocks"]) | (
            jnp.asarray(first_key)[None] + block - 1 >= t[:, None] - sp["window"] + 1)
        score = jnp.where(forced, jnp.inf, score)
        score = jnp.where(b <= t[:, None] // block, score, -jnp.inf)
        top, index = jax.lax.top_k(score, min(sp["topk"], n_blocks))
        chosen = (jax.nn.one_hot(index, n_blocks, dtype=bool)
                  & (top > -jnp.inf)[:, :, None]).any(axis=1)
        chosen = chosen | (t[:, None] + 1 <= sp["dense_len"])
        return jnp.repeat(chosen, block, axis=-1)[:, :S] & (t_key[None] <= t[:, None])

    def block_of_queries(b):
        t = b * qb + jnp.arange(qb)
        qs = jax.lax.dynamic_slice_in_dim(q, b * qb, qb)            # [qb, H, D]
        out = []
        for hk in range(Hkv):
            keys = chosen_keys(qs[:, hk * g:(hk + 1) * g], kc[:, hk], t)

            def head(i, hk=hk, keys=keys):
                s = jax.lax.dynamic_index_in_dim(qs, hk * g + i, 1, False) @ k[:, hk].T
                s = jnp.where(keys, s / math.sqrt(D), -jnp.inf)
                return jax.nn.softmax(s, axis=-1) @ v[:, hk]        # [qb, D]

            out.append(jax.lax.map(head, jnp.arange(g)))            # [g, qb, D]
        return jnp.concatenate(out).transpose(1, 0, 2).reshape(qb, H * D)

    o = jax.lax.map(block_of_queries, jnp.arange(S // qb)).reshape(S, H * D)
    return _gated_out(p, o, x, norm, qb)


def sala_hidden(params, ids, *, n_head, n_kv_head, head_dim, mixer_types,
                first_layer=0, published_layers=32, sparse=None, scale_emb=12.0,
                scale_depth=1.4, dim_model_base=256, eps=1e-6, rope_theta=1e4,
                q_block=1024, **_):
    """``ids [S]`` -> the stack's output after the final norm and the head's
    divisor, ``[S, hidden]`` float32.  ``S`` is a multiple of ``q_block`` or
    under it."""
    f32 = lambda a: a.astype(jnp.float32)
    sp = dict(SPARSE, **(sparse or {}))
    H, Hkv, D = n_head, n_kv_head, head_dim
    S = ids.shape[0]
    qb = min(q_block, S)
    assert S % qb == 0, f"{S} positions are not whole blocks of {qb} rows"
    rs = scale_depth / math.sqrt(published_layers)
    slope = 2.0 ** (-8.0 * (jnp.arange(H, dtype=jnp.float32) + 1) / H)

    def mlp(p, r):
        gate, up = jnp.split(r @ f32(p["fc_w"]), 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ f32(p["proj_w"])

    def layer(x, p, mixer, depth):
        # the mixer's input is normed a block of rows at a time, where it is
        # multiplied: RMSNorm_1(x) is never held whole beside x
        norm = lambda r: _rms(r, f32(p["ln1_g"]), eps)
        if mixer == "lightning-attn":
            decay = slope * (1.0 - depth / (published_layers - 1) + 1e-5)
            y = _linear_mixer(p, x, norm, H=H, D=D, eps=eps, theta=rope_theta,
                              decay=decay, qb=qb)
        else:
            y = _sparse_mixer(p, x, norm, H=H, Hkv=Hkv, D=D, eps=eps, sp=sp, qb=qb)
        x = x + rs * y
        return x + rs * _rows(lambda r: mlp(p, _rms(r, f32(p["ln2_g"]), eps)), qb, x)

    with jax.default_matmul_precision("highest"):
        x = scale_emb * f32(params["wte"][ids])
        # runs of one kind, a scan a run: a layer's leaves are taken from its
        # kind's stack one layer at a time
        i, seen = 0, {"minicpm4": 0, "lightning-attn": 0}
        kinds = {"minicpm4": "sparse", "lightning-attn": "linear"}
        while i < len(mixer_types):
            mixer, n = mixer_types[i], 1
            while i + n < len(mixer_types) and mixer_types[i + n] == mixer:
                n += 1
            stack = params["blocks"][kinds[mixer]]

            def one(x, at, mixer=mixer, stack=stack):
                index, depth = at
                p = jax.tree.map(lambda a: a[index], stack)
                return layer(x, p, mixer, depth), None

            x, _ = jax.lax.scan(one, x, (
                seen[mixer] + jnp.arange(n),
                (first_layer + i + jnp.arange(n)).astype(jnp.float32)))
            seen[mixer] += n
            i += n
        hidden = x.shape[-1]
        return _rms(x, f32(params["lnf_g"]), eps) / (hidden / dim_model_base)


def sala_head(params, hidden, *, vocab_size, **_):
    """Rows of :func:`sala_hidden` -> their logits ``[rows, vocab_size]`` in
    float32."""
    with jax.default_matmul_precision("highest"):
        return (hidden @ params["lm_head"].astype(jnp.float32).T)[:, :vocab_size]


def sala_logits(params, ids, lo=0, hi=None, **kw):
    """``ids [S]`` -> logits of the positions ``lo .. hi - 1`` (all of them
    by default), ``[hi - lo, vocab_size]`` in float32: one full forward pass,
    the head over the asked range alone."""
    return sala_head(params, sala_hidden(params, ids, **kw)[lo:hi], **kw)
