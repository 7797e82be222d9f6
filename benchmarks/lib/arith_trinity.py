"""What one serve step of a Trinity (``afmoe``) configuration MUST read and
compute, from shapes alone: this family's function for ``step_mfu_pct``
(``lib/arith_step.py`` says what the three parts are and how
``arith_step.step_work`` spends them).

The stack is ``dense_layers`` layers with a dense SwiGLU and ``n_layer -
dense_layers`` layers with a bank, so two things differ from the families of
``arith_step.py``: the dense part counts the lead's MLP a leading layer and
the router, its bias and the shared expert an EXPERT layer, and the bank's
``layers`` are the expert layers alone.  ``experts`` is the width the router
chooses among (256) and ``held`` the experts here (16): ``step_work`` gives a
bank that holds a share that share of the expected reach and of the
assignments.

The cache's reads of this cell are every resident kind's
(``arith_window.attention``, which came from this file: a chunk's pages once
a chunk, a row without a request nothing).
"""

from benchmarks.lib import arith_moe


def attention_params(kw):
    """A layer's attention: q, k, v and the output gate from the hidden
    size, the output projection, ONE gain of ``head_dim`` each for q and k,
    and the four norms of the block."""
    E, D = kw["n_embd"], kw["head_dim"]
    H, Hkv = kw["n_head"], kw["n_kv_head"]
    return E * (H + 2 * Hkv) * D + 2 * E * H * D + 2 * D + 4 * E


def trinity_weights(kw):
    """``model.kwargs`` of the configuration file -> ``{"dense", "gathered",
    "bank"}`` (``arith_step.py``)."""
    E, L, V = kw["n_embd"], kw["n_layer"], kw["vocab_size"]
    lead = kw["dense_layers"]
    first, held = kw["experts_held"] or (0, kw["num_experts"])
    expert = arith_moe.expert_params(E, kw["moe_intermediate_size"])
    per_expert_layer = (E * kw["num_experts"] + kw["num_experts"]
                        + kw["shared_experts"] * expert)
    dense = (L * attention_params(kw)
             + lead * arith_moe.expert_params(E, kw["intermediate_size"])
             + (L - lead) * per_expert_layer + E + V * E)
    return {"dense": dense, "gathered": V * E,
            "bank": {"layers": L - lead, "experts": kw["num_experts"], "held": held,
                     "top_k": kw["top_k"], "hidden": E,
                     "width": kw["moe_intermediate_size"]}}
