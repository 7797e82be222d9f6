"""Operations and bytes of a Jamba serve step, from the rows' lengths alone:
the benchmark's own arithmetic for the mamba layers' states and convolution
states, for the full layers' pages, for ``step_mfu_pct``'s weights and for the
two kernels of ``ops/pallas/selective_scan.py``, beside ``arith.py`` and
``arith_step.py``.  Nothing here looks at an op's name, so the count is the
same work whatever implements it.

A row is one token at position ``t``.  What the ALGORITHM needs of it:

* a FULL layer: the pages that hold the keys ``0 .. t``, of K and of V, the
  ONE K/V head's keys once for all 20 query heads, a decode row's once a row
  and a prompt chunk's once a chunk (``arith_window.full_rows``);
* a MAMBA layer: the state ``[channels, states]`` float32 read and written
  once a decode row, and once a prompt CHUNK (its tokens share the read and
  the write): a MOVE; beside it the convolution state, the last ``taps - 1``
  input rows, read and written the same.  A token costs ``7`` operations a
  (channel, state) pair (the decay's product, its ``exp``, the decay, the
  write's product and sum, the read's product and sum), ``4`` a channel (the
  step times the input, ``D``'s product and sum) and ``2 x taps`` a channel
  for the convolution.

A row that carries no request needs nothing.
"""

from benchmarks.lib.arith_window import full_rows  # noqa: F401  the full layers' pages


def _sizes(kw):
    """(channels, states, step lanes, taps) of a mamba layer."""
    return (kw["mamba_expand"] * kw["n_embd"], kw["mamba_d_state"],
            kw["mamba_dt_rank"], kw["mamba_d_conv"])


def layer_kinds(kw):
    """``"full"`` or ``"mamba"`` a layer, by the family's rule."""
    return ["full" if i % kw["attn_layer_period"] == kw["attn_layer_offset"] else "mamba"
            for i in range(kw["n_layer"])]


def mixer_params(kw):
    """Parameters of one mamba mixer: in_proj ``E x 2N``, the depthwise taps
    and their bias, x_proj ``N x (R + 2S)``, the three inner norms' gains,
    dt_proj ``R x N`` and its bias, ``A_log`` ``N x S``, ``D``, out_proj ``N
    x E``."""
    E = kw["n_embd"]
    N, S, R, taps = _sizes(kw)
    return (E * 2 * N + taps * N + N + N * (R + 2 * S) + (R + 2 * S)
            + R * N + N + N * S + N + N * E)


def jamba_weights(kw):
    """``lib/arith_step.py``'s family function for ``model.kwargs`` of a
    Jamba configuration: a mamba layer's mixer (:func:`mixer_params`); a full
    layer's q, o of ``E x H D`` and k, v of ``E x Hkv D``, no norm, no bias;
    both a SwiGLU MLP of ``3 E I`` and two RMSNorms; the final norm and the
    embedding, which IS the head (tied: every row multiplies all its rows,
    so it is among the dense weights and nothing is only gathered).  No
    bank."""
    E, I, V = kw["n_embd"], kw["intermediate_size"], kw["vocab_size"]
    D = kw["head_dim"]
    shared = 3 * E * I + 2 * E
    per = {"mamba": mixer_params(kw) + shared,
           "full": E * (kw["n_head"] + 2 * kw["n_kv_head"]) * D + kw["n_head"] * D * E + shared}
    rows = -(-V // 128) * 128               # the head's rows as the program pads them
    # the final norm's gain, and its shift: a zero leaf the program's tree
    # holds for every family and the source does not have
    return {"dense": sum(per[k] for k in layer_kinds(kw)) + 2 * E + rows * E,
            "gathered": 0, "bank": None}


def state_bytes(kw):
    """Bytes of ONE mamba layer's state a slot: float32."""
    N, S, _, _ = _sizes(kw)
    return N * S * 4


def conv_state_bytes(kw, itemsize=2):
    """Bytes of ONE mamba layer's convolution state a slot."""
    N, _, _, taps = _sizes(kw)
    return (taps - 1) * N * itemsize


def token_flops(kw):
    """Operations of one token in one mamba layer's recurrence and
    convolution (the module's docstring)."""
    N, S, _, taps = _sizes(kw)
    return 7 * N * S + 4 * N + 2 * taps * N


def mamba_rows(tokens, state_moves, layers, kw, itemsize=2):
    """(operations, bytes of state, bytes of convolution state) of ``layers``
    mamba layers over ``tokens`` live tokens whose states were read and
    written ``state_moves`` times (once a decode row, once a prompt chunk)."""
    return (layers * tokens * token_flops(kw),
            layers * 2 * state_moves * state_bytes(kw),
            layers * 2 * state_moves * conv_state_bytes(kw, itemsize))


def _token_rows_bytes(tokens, kw):
    """What either kernel reads and writes of ``tokens`` rows beside the
    state: ``c`` and ``dt`` in, ``y`` out (float32 a channel), ``B`` and ``C``
    in."""
    N, S, _, _ = _sizes(kw)
    return tokens * (3 * N + 2 * S) * 4


def state_update_call(rows, kw):
    """(operations, bytes) of the kernel ``mamba_state_update`` over ``rows``
    live decode rows of ONE layer: each row's state read once and written
    once, its rows beside it."""
    N, S, _, _ = _sizes(kw)
    return rows * (7 * N * S + 4 * N), 2 * rows * state_bytes(kw) + _token_rows_bytes(rows, kw)


def chunk_scan_call(tokens, kw):
    """(operations, bytes) of the kernel ``mamba_chunk_scan`` over one chunk
    of ``tokens`` tokens of ONE layer: the slot's state in and out once, the
    tokens' rows.  ``exp`` is counted as ONE operation of 7 a (channel,
    state) pair; the chip has no published peak for it or for the vector
    unit, so a share of this call's roofline reads low by construction."""
    N, S, _, _ = _sizes(kw)
    return tokens * (7 * N * S + 4 * N), 2 * state_bytes(kw) + _token_rows_bytes(tokens, kw)
