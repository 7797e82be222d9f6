"""The plain reference for AI21-Jamba2-3B (``model_type`` ``jamba`` of
``https://huggingface.co/ai21labs/AI21-Jamba2-3B``): the forward pass in
straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``.  No kernel, no cache, no pages, no
state between calls, no batching: the convolution over time is shifts of the
WHOLE sequence, the selective scan a ``lax.scan`` over the tokens of its
recurrence exactly as written below with the state ``[channels, states]`` as
the paper has it (NOT the lane-blocked kernels the program uses, nor their
``[states, channels]`` layout), attention a dense causal score matrix a head
and a block of queries.  It imports nothing from ``deepspeed_tpu``.

eps 1e-6; no bias but the convolution's and the step's.  The widths are the
catalog's config; what the config does not fix is under ``assumed`` in
``benchmarks/configs/jamba2-3b.json``:

    x_0 = wte[ids]
    x <- x + Mixer_l(RMSNorm_a(x));  x <- x + MLP_l(RMSNorm_f(x))
    logits = wte RMSNorm(x)                                        tied head
    MLP(m) = W_down(silu(W_gate m) * W_up m)

    mamba (N channels, S states, R step lanes), a = RMSNorm_a(x):
    1. [u_t | z_t] = W_in a_t                                      2 x N
    2. c_t[d] = silu(sum_{j=0..3} w[j, d] u_{t-3+j}[d] + b[d]), u_s = 0 for s < 0
    3. [r | B | C] = W_x c_t  (R, S, S);  r <- RMSNorm(r; g_dt),
       B <- RMSNorm(B; g_B), C <- RMSNorm(C; g_C)
    4. dt_t = softplus(W_dt r + b_dt) [N];  A = -exp(A_log) [N, S]
    5. h_{-1} = 0;  h_t[d, n] = exp(dt_t[d] A[d, n]) h_{t-1}[d, n]
       + dt_t[d] B[n] c_t[d];  y_t[d] = sum_n C[n] h_t[d, n] + D[d] c_t[d]
    6. Mixer(a)_t = W_out (y_t * silu(z_t))

    attention (H heads on Hkv K/V heads of D lanes), a = RMSNorm_a(x):
    q_t = W_q a_t, k_t = W_k a_t, v_t = W_v a_t;  NO rotary embedding, no
    norm, no bias;  o_t[h] = sum_{s <= t} softmax_s(q_t[h] . k_s[h // (H /
    Hkv)] / sqrt(D)) v_s[...];  Mixer(a)_t = W_o [o_t[0] .. o_t[H-1]]

It reads the program's parameter tree by its leaf names:
``blocks/mamba/{in_w, conv_w, conv_b, x_w, dt_norm_g, b_norm_g, c_norm_g,
dt_w, dt_b, scan_a_log, skip_d, out_w}`` (``in_w``'s column blocks are W_u then
W_z, ``x_w``'s W_r, W_B, W_C, ``conv_w [taps, channels]`` holds the oldest
token's tap first, ``scan_a_log [states, channels]`` is ``A_log`` transposed,
``skip_d`` is ``D``), ``blocks/full/{qkv_w, out_w}`` (``qkv_w``'s column
blocks W_q, W_k, W_v), both with ``ln1_g`` (RMSNorm_a), ``ln2_g``
(RMSNorm_f), ``fc_w`` (W_gate then W_up), ``proj_w``; ``wte``, ``lnf_g``.  The
weights are the system's, the arithmetic is not.  Everything a token does
alone runs a block of ``q_block`` rows at a time and attention a head and a
block of queries at a time: the blocks change the order of nothing summed.
"""

import math

import jax
import jax.numpy as jnp


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rows(fn, qb, *xs):
    """``fn`` over blocks of ``qb`` rows of each of ``xs``, the results laid
    end to end again."""
    S = xs[0].shape[0]
    out = jax.lax.map(lambda b: fn(*(jax.lax.dynamic_slice_in_dim(
        x, b * qb, qb) for x in xs)), jnp.arange(S // qb))
    return jax.tree.map(lambda a: a.reshape(S, *a.shape[2:]), out)


def _mamba(p, a, *, N, S, R, eps, qb, parts, upto=None):
    """-> (the mixer's output ``[T, hidden]``, the state ``[N, S]`` after the
    last token, or after the first ``upto`` where that is given: a token past
    them steps by 0 and leaves the state to the bit).  ``parts``: what of the
    mixer is computed (all of it by default; a test leaves one out to see
    that the comparison then fails)."""
    f32 = lambda name: p[name].astype(jnp.float32)
    T = a.shape[0]
    uz = _rows(lambda r: r @ f32("in_w"), qb, a)
    u, z = uz[:, :N], uz[:, N:]
    # 2. the convolution, as shifts of the whole sequence
    w = f32("conv_w")
    taps = w.shape[0]
    shifted = lambda n: jnp.pad(u, ((n, 0), (0, 0)))[:T]           # row t is u_{t-n}
    c = sum(w[j] * shifted(taps - 1 - j) for j in range(taps))
    if "conv_bias" in parts:
        c = c + f32("conv_b")
    c = jax.nn.silu(c)
    # 3. and 4.
    x = _rows(lambda r: r @ f32("x_w"), qb, c)
    norm = lambda t, g, part: _rms(t, f32(g), eps) if part in parts else t
    r = norm(x[:, :R], "dt_norm_g", "dt_norm")
    B = norm(x[:, R:R + S], "b_norm_g", "b_norm")
    C = norm(x[:, R + S:], "c_norm_g", "c_norm")
    dt = jax.nn.softplus(r @ f32("dt_w") + f32("dt_b"))
    if upto is not None:
        dt = jnp.where((jnp.arange(T) < upto)[:, None], dt, 0.0)
    A = -jnp.exp(f32("scan_a_log")).T                                   # [N, S]

    # 5. the recurrence, a token at a time
    def token(h, row):
        c_t, dt_t, b_t, c_out = row
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * c_t)[:, None] * b_t[None]
        return h, h @ c_out

    h, y = jax.lax.scan(token, jnp.zeros((N, S), jnp.float32), (c, dt, B, C))
    if "skip" in parts:
        y = y + f32("skip_d") * c
    if "gate" in parts:
        y = y * jax.nn.silu(z)
    return _rows(lambda r: r @ f32("out_w"), qb, y), h


def _full(p, a, *, H, Hkv, D, qb):
    f32 = lambda name: p[name].astype(jnp.float32)
    T = a.shape[0]
    qkv = _rows(lambda r: r @ f32("qkv_w"), qb, a)
    q = qkv[:, :H * D].reshape(T, H, D)
    k = qkv[:, H * D:(H + Hkv) * D].reshape(T, Hkv, D)
    v = qkv[:, (H + Hkv) * D:].reshape(T, Hkv, D)
    pos = jnp.arange(T)

    def head(j):
        kv = j // (H // Hkv)

        def block(b):
            rows = jax.lax.dynamic_slice_in_dim(q[:, j], b * qb, qb)
            at = b * qb + jnp.arange(qb)
            s = rows @ k[:, kv].T / math.sqrt(D)
            s = jnp.where(pos[None] <= at[:, None], s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v[:, kv]
        return jax.lax.map(block, jnp.arange(T // qb)).reshape(T, D)

    o = jax.lax.map(head, jnp.arange(H))                           # [H, T, D]
    return _rows(lambda r: r @ f32("out_w"), qb,
                 o.transpose(1, 0, 2).reshape(T, H * D))


def _mlp(p, x, qb):
    def rows(r):
        gate, up = jnp.split(r @ p["fc_w"].astype(jnp.float32), 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ p["proj_w"].astype(jnp.float32)
    return _rows(rows, qb, x)


ALL_PARTS = ("conv_bias", "dt_norm", "b_norm", "c_norm", "skip", "gate")


def _forward(params, ids, upto=None, *, n_layer, attn_layer_period, attn_layer_offset,
             n_head, n_kv_head, head_dim, mamba_inner, mamba_d_state,
             mamba_dt_rank, eps=1e-6, q_block=512, parts=ALL_PARTS, **_):
    """``ids [T]`` (a multiple of ``q_block`` or under it) -> (the final
    norm's output ``[T, hidden]``, the mamba layers' states ``[mamba layers,
    N, S]`` after the last token, or after the first ``upto``), float32."""
    f32 = lambda a: a.astype(jnp.float32)
    T = ids.shape[0]
    qb = min(q_block, T)
    assert T % qb == 0, (T, qb)
    states = []
    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"][ids])
        for i in range(n_layer):
            name = "full" if i % attn_layer_period == attn_layer_offset else "mamba"
            j = len(states) if name == "mamba" else i - len(states)   # of its kind
            p = jax.tree.map(lambda a: a[j], params["blocks"][name])
            a = _rms(x, f32(p["ln1_g"]), eps)
            if name == "mamba":
                mixed, h = _mamba(p, a, N=mamba_inner, S=mamba_d_state, R=mamba_dt_rank,
                                  eps=eps, qb=qb, parts=parts, upto=upto)
                states.append(h)
            else:
                mixed = _full(p, a, H=n_head, Hkv=n_kv_head, D=head_dim, qb=qb)
            x = x + mixed
            x = x + _mlp(p, _rms(x, f32(p["ln2_g"]), eps), qb)
        return _rms(x, f32(params["lnf_g"]), eps), jnp.stack(states)


def jamba_hidden(params, ids, **kw):
    """``ids [T]`` -> the final norm's output ``[T, hidden]`` in float32.
    ``T`` is a multiple of ``q_block`` or under it."""
    return _forward(params, ids, **kw)[0]


def jamba_states(params, ids, upto, **kw):
    """``ids [T]`` -> every mamba layer's state ``[mamba layers, N, S]`` in
    float32 after the first ``upto`` tokens: what a slot that has taken those
    tokens in must hold (the program keeps it ``[S, N]``)."""
    return _forward(params, ids, upto, **kw)[1]


def jamba_head(params, hidden, *, vocab_size, **_):
    """Rows of :func:`jamba_hidden` -> their logits ``[rows, vocab_size]`` in
    float32, through the head tied to the embedding."""
    with jax.default_matmul_precision("highest"):
        return (hidden @ params["wte"].astype(jnp.float32).T)[:, :vocab_size]


def jamba_logits(params, ids, lo=0, hi=None, **kw):
    """``ids [T]`` -> logits of the positions ``lo .. hi - 1`` (all of them
    by default), ``[hi - lo, vocab_size]`` in float32: one full forward pass,
    the head over the asked range alone."""
    return jamba_head(params, jamba_hidden(params, ids, **kw)[lo:hi], **kw)
