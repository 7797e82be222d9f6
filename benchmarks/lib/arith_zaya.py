"""What a ZAYA1 serve step MUST read and compute, from shapes alone: the
family's function for ``step_mfu_pct`` (``lib/arith_step.py`` says what such
a function gives; ``readers/step_share.py`` reads it through the
configuration file's ``step_work`` block).  Nothing here looks at an op's
name, so the count is the same work whatever implements it.

A layer outside its experts, at the published widths (E 2048, 8 query heads
on 2 K/V heads of 128, router 256, 16 experts):

* the mixer: W_q ``E x H D``, W_k ``E x Hkv D``, W_v1 and W_v2 ``E x Hkv D /
  2`` each, W_o ``H D x E``; the depthwise convolution ``2 U + U`` (two taps
  and a bias over the ``U = (H + Hkv) D`` packed lanes), the convolution
  grouped by head ``2 (H + Hkv) D D + U``; a key scale a K/V head: 5,575,682;
* two RMSNorm gains: 4,096;
* the router: ``E x R`` down, the stream's scale, a norm ``R``, ``R x R``
  twice, ``R x experts``, a balancing bias: 659,729.

Every row goes through all of it, and through the final norm and the
embedding, which IS the head (tied: every row multiplies all its rows, so it
is among the dense weights and nothing is only gathered).  The bank: 16
experts of ``3 E I`` a layer, ONE a token, so a step of ``B`` live rows
reaches ``16 (1 - (15/16)^B)`` of a layer's experts
(``arith_moe.experts_reached``).
"""


def zaya_weights(kw):
    """``lib/arith_step.py``'s family function for ``model.kwargs`` of a
    ZAYA1 configuration."""
    E, L, V = kw["n_embd"], kw["n_layer"], kw["vocab_size"]
    H, Hkv, D = kw["n_head"], kw["n_kv_head"], kw["head_dim"]
    R, N = kw["router_hidden"], kw["num_experts"]
    U = (H + Hkv) * D
    mixer = E * (U + Hkv * D) + H * D * E + 3 * U + 2 * (H + Hkv) * D * D + U + Hkv
    router = E * R + 1 + R + 2 * R * R + R * N + N
    rows = -(-V // 128) * 128               # the head's rows as the program pads them
    # the final norm's gain, and its shift: a zero leaf the program's tree
    # holds for every family and the source does not have
    return {"dense": L * (mixer + 2 * E + router) + 2 * E + rows * E, "gathered": 0,
            "bank": {"layers": L, "experts": N, "held": N, "top_k": kw["top_k"],
                     "hidden": E, "width": kw["intermediate_size"]}}
