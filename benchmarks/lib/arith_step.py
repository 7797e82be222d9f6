"""What one serve step MUST read and compute, from shapes alone: the
benchmark's own arithmetic for ``step_mfu_pct``, beside ``arith.py``.

A step runs every live row (a decode slot's one token, a prompt chunk's
tokens) through the whole stack once.  What the ALGORITHM needs of it,
whatever kernels and copies an implementation makes:

* every weight that every row goes through is read ONCE a step (the
  attention projections, the norms, the router, a dense MLP or a shared
  expert, the head's rows) and costs ``2 x parameters`` operations a row;
* of each layer's expert bank, the experts the step's rows reach are read
  once, and each assignment costs ``2 x expert parameters`` operations
  (``arith_moe.expert_bank_call``); a bank that holds a share of the experts
  its router chooses among gets that share of both;
* the tables a row takes ONE row of (the embedding where the head is untied,
  learned positions) cost a row's bytes and are left out;
* attention's reads of the cache are counted by the traffic kind from the
  lengths (``Serving.paged_model``, ``attention_counters``) and added by the
  reader.

A copy of a layer out of a stacked array is in none of these counts.

A family is one function here: the builder's keyword arguments of the
configuration file (``model.kwargs``, which the file's tests hold to the
source's keys) -> ``{"dense": parameters every row goes through, "gathered":
parameters of the tables read a row a token, "bank": None or {"layers",
"experts" (the router chooses among), "held", "top_k", "hidden", "width"}}``.
The three together are every parameter of the published model as cut; the
program's arrays hold the same and zero biases the sources do not have
(``tests/benchmarks/test_step_share.py`` holds both to the engine's arrays).
A new family brings its function in a file of its own and names it in its
configuration's ``step_work`` block.
"""

from benchmarks.lib import arith, arith_moe


def _bank(kw, held=None):
    n = kw["num_experts"]
    return {"layers": kw["n_layer"], "experts": n, "held": n if held is None else held,
            "top_k": kw["top_k"], "hidden": kw["n_embd"], "width": kw["intermediate_size"]}


def bank_params(bank):
    """Parameters of the experts a bank holds, all layers."""
    return bank["layers"] * bank["held"] * arith_moe.expert_params(
        bank["hidden"], bank["width"])


def gpt2_weights(kw):
    """GPT-2: biased projections and a GELU MLP of 4E, LayerNorms, the head
    tied to the embedding, whose rows the program pads to a multiple of 128
    (all of them are multiplied); learned positions are gathered."""
    E, rows = kw["n_embd"], -(-kw["vocab_size"] // 128) * 128
    gathered = kw["n_positions"] * E
    return {"dense": arith.gpt2_param_count(E, kw["n_layer"], rows, kw["n_positions"])
            - gathered, "gathered": gathered, "bank": None}


def olmoe_weights(kw):
    """OLMoE: q, k, v, o of E x E without bias, an RMSNorm over all lanes of
    q and of k, two RMSNorms a layer, a router of E x experts; the final
    norm and an untied head."""
    E, L, V = kw["n_embd"], kw["n_layer"], kw["vocab_size"]
    per_layer = 4 * E * E + 2 * E + 2 * E + E * kw["num_experts"]
    return {"dense": L * per_layer + E + V * E, "gathered": V * E, "bank": _bank(kw)}


def smallthinker_weights(kw):
    """SmallThinker: ``n_head`` query heads on ``n_kv_head`` K/V heads of
    ``head_dim`` without bias, two RMSNorms a layer, a router of E x experts;
    the final norm and an untied head."""
    E, L, V, D = kw["n_embd"], kw["n_layer"], kw["vocab_size"], kw["head_dim"]
    attn = E * (kw["n_head"] + 2 * kw["n_kv_head"]) * D + kw["n_head"] * D * E
    per_layer = attn + 2 * E + E * kw["num_experts"]
    return {"dense": L * per_layer + E + V * E, "gathered": V * E, "bank": _bank(kw)}


def mistral4_weights(kw):
    """Mistral-Small-4 (latent attention): the query down to ``q_lora_rank``
    (normed) and up to ``n_head x head_dim``; keys and values down to
    ``kv_lora_rank + qk_rope_dim`` (the latent normed) and the latent up to
    ``n_head x (head_dim - qk_rope_dim + v_head_dim)``; the output projection;
    two RMSNorms a layer; a router as wide as the experts it chooses among,
    with a bias each; ``shared_experts`` experts every row goes through; of
    the routed experts the ``experts_held`` here."""
    E, L, V, H = kw["n_embd"], kw["n_layer"], kw["vocab_size"], kw["n_head"]
    q, kv, rope = kw["q_lora_rank"], kw["kv_lora_rank"], kw["qk_rope_dim"]
    nope, v = kw["head_dim"] - rope, kw["v_head_dim"]
    attn = (E * q + q + q * H * (nope + rope) + E * (kv + rope) + kv
            + kv * H * (nope + v) + H * v * E)
    shared = kw["shared_experts"] * arith_moe.expert_params(E, kw["intermediate_size"])
    per_layer = attn + 2 * E + E * kw["num_experts"] + kw["num_experts"] + shared
    lo, hi = kw["experts_held"]
    return {"dense": L * per_layer + E + V * E, "gathered": V * E,
            "bank": _bank(kw, held=hi - lo)}


def step_work(weights, rows, itemsize=2):
    """(operations, bytes) of one step over ``rows`` live rows, attention's
    reads of the cache apart.  A step without a live row runs nothing."""
    if rows <= 0:
        return 0, 0
    flops = 2 * weights["dense"] * rows
    nbytes = weights["dense"] * itemsize
    bank = weights["bank"]
    if bank:
        f, b = arith_moe.expert_bank_call(rows, bank["experts"], bank["top_k"],
                                          bank["hidden"], bank["width"], itemsize)
        share = bank["layers"] * bank["held"] / bank["experts"]
        flops, nbytes = flops + share * f, nbytes + share * b
    return flops, nbytes
