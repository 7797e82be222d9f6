"""Operations and bytes, from shapes alone.  The benchmark's own copy of the
arithmetic: no PR of the program can move a utilization by changing how the
program counts itself.

Every function returns what the ALGORITHM needs for the call, not what an
implementation happens to do: recomputation under remat is not counted in
``train_flops_per_token``; a kernel's bytes are the operands it must read and
the results it must write once.
"""


def gpt2_param_count(n_embd, n_layer, vocab_rows, n_positions):
    """Parameters of a GPT-2 as the program holds it: ``vocab_rows`` is the
    embedding's row count (the vocabulary padded to the MXU's multiple; the
    tied head multiplies by all of them)."""
    E = n_embd
    per_block = (E * 3 * E + 3 * E) + (E * E + E) + (E * 4 * E + 4 * E) \
        + (4 * E * E + E) + 4 * E
    return vocab_rows * E + n_positions * E + n_layer * per_block + 2 * E


def train_flops_per_token(n_params, n_layer, n_embd, seq):
    """6 N for the matrix multiplications of forward and backward, plus
    12 L E S for attention's scores and weighted sum over a context of
    ``seq`` (PaLM, appendix B; after ``GPT.flops_per_token``)."""
    return 6 * n_params + 12 * n_layer * n_embd * seq


# matrix multiplications of [S, D] x [D, S] size in each flash kernel:
# forward QK^T, PV; dq recomputes QK^T, then dO V^T and dS K; dkv recomputes
# QK^T, then P^T dO, dO V^T and dS^T Q.
FLASH_MATMULS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
# [B, S, H, D] operands read and written once (lse and delta rows left out:
# 1/D of an operand each): fwd q k v -> o; dq q k v do -> dq; dkv q k v do ->
# dk dv.
FLASH_OPERANDS = {"flash_fwd": 4, "flash_bwd_dq": 5, "flash_bwd_dkv": 6}


def flash_call(kernel, batch, heads, seq, head_dim, itemsize=2, causal=True):
    """(operations, bytes) of one call of a flash kernel."""
    flops = FLASH_MATMULS[kernel] * 2 * batch * heads * seq * seq * head_dim
    if causal:
        flops //= 2
    nbytes = FLASH_OPERANDS[kernel] * batch * seq * heads * head_dim * itemsize
    return flops, nbytes


def blocks_for(tokens, block_size):
    return -(-int(tokens) // block_size)


def paged_attention_row(resident, sq, block_size, lanes, heads, head_dim,
                        itemsize=2):
    """(operations, bytes) of one row of one paged-attention call: ``sq``
    queries at positions ``resident..resident+sq-1`` over the
    ``ceil((resident + sq) / block_size)`` live blocks of K and of V, each
    ``block_size x lanes``; plus q read and o written."""
    t = blocks_for(resident + sq, block_size) * block_size
    nbytes = 2 * t * lanes * itemsize + 2 * sq * heads * head_dim * itemsize
    flops = 2 * 2 * sq * t * heads * head_dim
    return flops, nbytes


def roofline_seconds(flops, nbytes, peak):
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
