"""Operations and bytes of an Olmo-Hybrid serve step, from the rows' lengths
alone: the benchmark's own arithmetic for the delta layers' states and
convolution states, for the full layers' pages and for ``step_mfu_pct``'s
weights, beside ``arith.py`` and ``arith_step.py``.  Nothing here looks at an
op's name, so the count is the same work whatever implements it.

A row is one token at position ``t``.  What the ALGORITHM needs of it:

* a FULL layer: the pages that hold the keys ``0 .. t``, of K and of V, each
  K/V head's keys once a decode row and once a prompt chunk
  (``arith_window.full_rows``: what ``paged_gqa_attention``'s roofline divides
  in the other resident cells);
* a DELTA layer: the state ``[heads, key_dim, value_dim]`` float32 read and
  written once a decode row, and once a prompt CHUNK (its tokens share the
  read and the write): a MOVE; beside it the convolution state, the last
  ``taps - 1`` packed ``[q | k | v]`` rows, read and written the same.  A
  token costs ``6 x key_dim x value_dim`` operations a head (what the state
  returns for its key, the write, what it returns for its query) and ``2 x
  taps`` a packed lane.

A row that carries no request needs nothing.
"""

from benchmarks.lib.arith_window import full_rows  # noqa: F401  the full layers' pages


def olmo_hybrid_weights(kw):
    """``lib/arith_step.py``'s family function for ``model.kwargs`` of an
    Olmo-Hybrid configuration: a delta layer's W_q, W_k of ``E x H dk``,
    W_v, W_z, W_o of ``E x H dv``, W_b, W_a of ``E x H``, the depthwise taps,
    ``A_log``, ``dt_bias`` and ONE output gain; a full layer's q, k, v, o of
    ``E x H D`` and the gains of its q and k norms over all lanes; both a
    SwiGLU MLP of ``3 E I`` and two RMSNorms; the final norm and an untied
    head.  No bank."""
    E, I, V = kw["n_embd"], kw["intermediate_size"], kw["vocab_size"]
    H, dk, dv = kw["linear_heads"], kw["linear_key_head_dim"], kw["linear_value_head_dim"]
    lanes = H * (2 * dk + dv)
    shared = 3 * E * I + 2 * E
    per = {"linear_attention": (E * lanes + 2 * E * H * dv + 2 * E * H
                                + kw["linear_conv_kernel_dim"] * lanes + 2 * H + dv + shared),
           "full_attention": (E * (kw["n_head"] + 2 * kw["n_kv_head"]) * kw["head_dim"]
                              + kw["n_head"] * kw["head_dim"] * E
                              + (kw["n_head"] + kw["n_kv_head"]) * kw["head_dim"] + shared)}
    rows = -(-V // 128) * 128               # the head's rows as the program pads them
    return {"dense": sum(per[t] for t in kw["layer_types"]) + E + rows * E,
            "gathered": rows * E, "bank": None}


def state_bytes(kw):
    """Bytes of ONE delta layer's state a slot: float32."""
    return (kw["linear_heads"] * kw["linear_key_head_dim"]
            * kw["linear_value_head_dim"] * 4)


def delta_rows(tokens, state_moves, layers, kw, itemsize=2):
    """(operations, bytes of state, bytes of convolution state) of ``layers``
    delta layers over ``tokens`` live tokens whose states were read and
    written ``state_moves`` times (once a decode row, once a prompt chunk)."""
    H, dk, dv = kw["linear_heads"], kw["linear_key_head_dim"], kw["linear_value_head_dim"]
    taps, lanes = kw["linear_conv_kernel_dim"], H * (2 * dk + dv)
    return (layers * tokens * (6 * H * dk * dv + 2 * taps * lanes),
            layers * 2 * state_moves * state_bytes(kw),
            layers * 2 * state_moves * (taps - 1) * lanes * itemsize)
