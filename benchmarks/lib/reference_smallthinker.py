"""The plain reference for SmallThinker (``model_name``
``smallthinker_21b_instruct`` of
``https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct``): the
forward pass in straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``.  No kernel, no cache, no batching,
no sorting of tokens: the experts are walked in a loop and EVERY expert is
computed for EVERY token, the unchosen weighted by zero.

Layer ``l`` of the stack, ``x [S, hidden]``:

    h = RMSNorm_1(x);  r = h W_r          the router reads what attention reads
    q = h Wq, k = h Wk, v = h Wv          (no bias) heads of D; H = g * Hkv
    rope_layout[l] == 1: rope (half-split pairing over all D) on q and k;
                    == 0: NO position encoding
    query head i attends K/V head i // g, scale 1/sqrt(D), causal, and where
    sliding_window_layout[l] == 1 a query at t sees the keys t-W+1 .. t
    x1 = x + softmax(q k^T) v Wo
    h2 = RMSNorm_2(x1);  the k largest of r, weights softmax over THOSE k
    x2 = x1 + sum_e p_e (relu(h2 Wgate_e) * (h2 Wup_e)) Wdown_e
    after the last layer RMSNorm, then the untied head

It reads the program's parameter tree by its leaf names, stacked over
layers: ``blocks/{ln1_g, qkv_w, out_w, ln2_g}``, ``blocks/moe/gate/wg``,
``blocks/moe/experts/{wi, wo}``; ``wte``, ``lnf_g``, ``lm_head``.  The
weights are the system's, the arithmetic is not.  Departures from the
published code, none from its arithmetic:

* Wq, Wk and Wv are the three column blocks of one ``qkv_w``; Wgate and Wup
  the two column halves of one ``wi`` (gate first);
* the published router takes its top k of the logits and then applies the
  softmax to them, which is what is written here (``norm_topk_prob`` then
  changes nothing: the k weights already sum to one);
* the published window mask is built for a cache; here it is the band
  ``0 <= t_query - t_key < W`` of one full pass;
* rows of the embedding and the head beyond the vocabulary (padding to the
  MXU's multiple; 151,936 has none) are cut off the logits;
* attention runs a block of ``q_block`` queries and one head at a time, and
  one expert at a time is made float32, so that 13,312 positions of the
  full-width model fit beside resident bf16 weights: the blocks change the
  order of nothing that is summed.

The layers of one period of the two layouts are written out and the periods
scanned, so a 52-layer stack traces four layers.
"""

import math

import jax
import jax.numpy as jnp


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """``x [S, D]`` at positions 0..S-1, half-split pairing over all D."""
    S, D = x.shape
    half = D // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    rotated = jnp.concatenate([-x[:, half:], x[:, :half]], axis=-1)
    return x * cos + rotated * sin


def _period(rope_layout, window_layout):
    """The shortest period both per-layer lists repeat in."""
    kinds = list(zip(rope_layout, window_layout))
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return p
    raise AssertionError("unreachable: a list repeats in its own length")


def smallthinker_hidden(params, ids, *, n_head, n_kv_head, head_dim, top_k,
                        rope_layout, sliding_window_layout, window,
                        eps=1e-6, rope_theta=1.5e6, q_block=1024, **_):
    """``ids [S]`` -> the stack's output after the final norm, ``[S, hidden]``
    float32.  ``S`` is a multiple of ``q_block`` or under it."""
    f32 = lambda a: a.astype(jnp.float32)
    H, Hkv, D = n_head, n_kv_head, head_dim
    g = H // Hkv
    S = ids.shape[0]
    qb = min(q_block, S)
    assert S % qb == 0, f"{S} positions are not whole blocks of {qb} queries"
    n_layer = params["blocks"]["ln1_g"].shape[0]
    rope_layout = list(rope_layout)[:n_layer]
    window_layout = list(sliding_window_layout)[:n_layer]
    assert len(rope_layout) == len(window_layout) == n_layer
    P = _period(rope_layout, window_layout)
    t_key = jnp.arange(S)[None, :]

    def layer(x, p, roped, windowed):
        h = _rms(x, f32(p["ln1_g"]), eps)
        router = h @ f32(p["moe"]["gate"]["wg"])                  # [S, N]
        q, k, v = jnp.split(h @ f32(p["qkv_w"]), [H * D, (H + Hkv) * D], axis=-1)
        heads = lambda t, n: t.reshape(S, n, D).transpose(1, 0, 2)   # [n, S, D]
        q, k, v = heads(q, H), heads(k, Hkv), heads(v, Hkv)
        if roped:
            q = jax.vmap(lambda t: _rope(t, rope_theta))(q)
            k = jax.vmap(lambda t: _rope(t, rope_theta))(k)

        def head(i):
            ki, vi = k[i // g], v[i // g]

            def block(b):
                t_query = b * qb + jnp.arange(qb)[:, None]
                s = jax.lax.dynamic_slice_in_dim(q[i], b * qb, qb) @ ki.T / math.sqrt(D)
                seen = t_key <= t_query
                if windowed:
                    seen = seen & (t_query - t_key < window)
                return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ vi

            return jax.lax.map(block, jnp.arange(S // qb)).reshape(S, D)

        o = jax.lax.map(head, jnp.arange(H))                      # [H, S, D]
        x1 = x + o.transpose(1, 0, 2).reshape(S, H * D) @ f32(p["out_w"])

        h2 = _rms(x1, f32(p["ln2_g"]), eps)
        chosen_logits, chosen = jax.lax.top_k(router, top_k)
        n_experts = router.shape[-1]
        weight = jnp.einsum("sk,ske->se", jax.nn.softmax(chosen_logits, axis=-1),
                            jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32))

        def expert(y, e):
            wi = f32(p["moe"]["experts"]["wi"][e])
            wo = f32(p["moe"]["experts"]["wo"][e])
            gate, up = jnp.split(h2 @ wi, 2, axis=-1)
            return y + weight[:, e, None] * ((jax.nn.relu(gate) * up) @ wo), None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(x1), jnp.arange(n_experts))
        return x1 + y

    def period(x, ps):
        for j in range(P):
            x = layer(x, jax.tree.map(lambda a: a[j], ps),
                      bool(rope_layout[j]), bool(window_layout[j]))
        return x, None

    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"][ids])
        periods = jax.tree.map(
            lambda a: a.reshape(n_layer // P, P, *a.shape[1:]), params["blocks"])
        x, _ = jax.lax.scan(period, x, periods)
        return _rms(x, f32(params["lnf_g"]), eps)


def smallthinker_head(params, hidden, *, vocab_size, **_):
    """Rows of :func:`smallthinker_hidden` -> their logits ``[rows,
    vocab_size]`` in float32."""
    with jax.default_matmul_precision("highest"):
        return (hidden @ params["lm_head"].astype(jnp.float32).T)[:, :vocab_size]


def smallthinker_logits(params, ids, lo=0, hi=None, **kw):
    """``ids [S]`` -> logits of the positions ``lo .. hi - 1`` (all of them
    by default), ``[hi - lo, vocab_size]`` in float32: one full forward pass,
    the head over the asked range alone."""
    return smallthinker_head(params, smallthinker_hidden(params, ids, **kw)[lo:hi],
                             **kw)
