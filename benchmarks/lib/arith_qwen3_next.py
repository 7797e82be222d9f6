"""Operations and bytes of a Qwen3-Next serve step, from shapes and the rows'
lengths alone: the benchmark's own arithmetic for ``step_mfu_pct``'s weights,
for the delta layers' states and convolution states and for the full layers'
pages, beside ``arith.py`` and ``arith_step.py``.  Nothing here looks at an
op's name, so the count is the same work whatever implements it.

A row is one token at position ``t``.  What the ALGORITHM needs of it:

* a FULL layer: the pages that hold the keys ``0 .. t``, of K and of V, each
  K/V head's keys once; the rows of a prompt chunk are ONE sequence's and
  need each page once for all of them (``arith_window.full_rows``), a row
  that carries no request nothing;
* a DELTA layer: the state ``[value heads, key_dim, value_dim]`` float32 read
  and written once a decode row, and once a prompt CHUNK (its tokens share
  the read and the write): a MOVE; beside it the convolution state, the last
  ``taps - 1`` packed ``[q | k | v]`` rows, read and written the same.  A
  token costs ``6 x key_dim x value_dim`` operations a VALUE head and ``2 x
  taps`` a packed lane (q and k a KEY head's, v a value head's);
* the bank: ``arith_step.step_work`` over ``bank``: of the 512 experts the
  router chooses among the 256 here, so half the expected reach and half the
  assignments.
"""

from benchmarks.lib import arith_moe
from benchmarks.lib.arith_window import full_rows  # noqa: F401  the full layer's pages
from benchmarks.lib.arith_olmo_hybrid import state_bytes  # a VALUE head's [dk, dv] float32, all heads


def packed_lanes(kw):
    """Lanes of a delta layer's packed ``[q | k | v]``."""
    return (2 * kw["linear_key_heads"] * kw["linear_key_head_dim"]
            + kw["linear_heads"] * kw["linear_value_head_dim"])


def qwen3_next_weights(kw):
    """``lib/arith_step.py``'s family function for ``model.kwargs`` of a
    Qwen3-Next configuration: a delta layer's packed q|k|v of ``E x lanes``,
    W_z and W_o of ``E x Hv dv``, W_b | W_a of ``E x 2 Hv``, the depthwise
    taps, ``A_log``, ``dt_bias`` and ONE output gain; a full layer's q, k, v
    of ``E x (H + 2 Hkv) D``, the gate and o of ``E x H D``, a gain a head's
    lanes for q and for k; beside either a router of ``E x experts``, the
    shared expert of ``3 E I_s`` with its gate's ``E`` and two RMSNorms; the
    final norm and an untied head; the embedding's rows are gathered.  The
    bank: ``held`` of ``num_experts`` SwiGLU experts of ``3 E I`` a layer,
    ``top_k`` a token."""
    E, V = kw["n_embd"], kw["vocab_size"]
    Hv, dv = kw["linear_heads"], kw["linear_value_head_dim"]
    H, Hkv, D = kw["n_head"], kw["n_kv_head"], kw["head_dim"]
    lanes = packed_lanes(kw)
    beside = (E * kw["num_experts"]
              + arith_moe.expert_params(E, kw["shared_expert_intermediate_size"]) + E
              + 2 * E)
    per = {"linear_attention": (E * lanes + 2 * E * Hv * dv + 2 * E * Hv
                                + kw["linear_conv_kernel_dim"] * lanes + 2 * Hv + dv),
           "full_attention": E * (H + 2 * Hkv) * D + 2 * E * H * D + 2 * D}
    first, held = kw["experts_held"] or (0, kw["num_experts"])
    rows = -(-V // kw.get("vocab_multiple", 128)) * kw.get("vocab_multiple", 128)
    return {"dense": sum(per[t] + beside for t in kw["layer_types"]) + E + rows * E,
            "gathered": rows * E,
            "bank": {"layers": len(kw["layer_types"]), "experts": kw["num_experts"],
                     "held": held, "top_k": kw["top_k"], "hidden": E,
                     "width": kw["moe_intermediate_size"]}}


def delta_rows(tokens, state_moves, layers, kw, itemsize=2):
    """(operations, bytes of state, bytes of convolution state) of ``layers``
    delta layers over ``tokens`` live tokens whose states were read and
    written ``state_moves`` times (once a decode row, once a prompt chunk)."""
    Hv, dk, dv = kw["linear_heads"], kw["linear_key_head_dim"], kw["linear_value_head_dim"]
    taps, lanes = kw["linear_conv_kernel_dim"], packed_lanes(kw)
    return (layers * tokens * (6 * Hv * dk * dv + 2 * taps * lanes),
            layers * 2 * state_moves * state_bytes(kw),
            layers * 2 * state_moves * (taps - 1) * lanes * itemsize)
