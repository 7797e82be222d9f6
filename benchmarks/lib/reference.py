"""The plain reference: GPT-2's forward pass and next-token loss in
straightforward ``jax.numpy`` and float32, under
``default_matmul_precision("highest")`` (on a TPU a float32 matrix
multiplication otherwise runs in bf16 passes).  No kernels, no cache, no
batching, no sharding rule of the program's: it follows Radford et al. 2019
(pre-LayerNorm blocks, learned positions, tanh GELU, tied head).

It reads the program's parameter tree by its leaf names (``wte``, ``wpe``,
``blocks/{ln1_g, qkv_w, ...}`` stacked over layers, ``lnf_g``): the weights
are the system's, the arithmetic is not.  One departure: the embedding has
rows beyond the vocabulary (padding to the MXU's multiple); they are cut off
the logits.
"""

import math

import jax
import jax.numpy as jnp


def _ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def gpt2_logits(params, ids, *, n_head, vocab_size, eps=1e-5, gather=lambda p: p):
    """``ids [S]`` -> logits ``[S, vocab_size]`` in float32.  ``gather`` is
    applied to one layer's parameters at a time (identity on one chip; where
    the system holds them sharded, the caller's rule to make them whole)."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        S = ids.shape[0]
        wte = f32(params["wte"])
        x = wte[ids] + f32(params["wpe"])[:S]
        E = x.shape[-1]
        D = E // n_head
        causal = jnp.tril(jnp.ones((S, S), bool))

        def block(x, p):
            p = f32(gather(p))
            h = _ln(x, p["ln1_g"], p["ln1_b"], eps)
            q, k, v = jnp.split(h @ p["qkv_w"] + p["qkv_b"], 3, axis=-1)
            q, k, v = (t.reshape(S, n_head, D) for t in (q, k, v))
            s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(D)
            s = jnp.where(causal[None], s, -jnp.inf)
            a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
            x = x + a.reshape(S, E) @ p["out_w"] + p["out_b"]
            h = _ln(x, p["ln2_g"], p["ln2_b"], eps)
            x = x + _gelu_new(h @ p["fc_w"] + p["fc_b"]) @ p["proj_w"] + p["proj_b"]
            return x, None

        x, _ = jax.lax.scan(block, x, params["blocks"])
        x = _ln(x, f32(params["lnf_g"]), f32(params["lnf_b"]), eps)
        return (x @ wte.T)[:, :vocab_size]


def gpt2_loss_sum(params, ids, labels, **kw):
    """Sum over the sequence of the next-token cross-entropy."""
    logp = jax.nn.log_softmax(gpt2_logits(params, ids, **kw), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()
