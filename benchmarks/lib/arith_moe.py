"""Operations and bytes of a mixture-of-experts bank, from shapes alone: the
benchmark's own arithmetic, beside ``arith.py``.

The bank of one layer is ``n_experts`` gated MLPs: per expert a
``hidden x 2*width`` matrix (gate and up) and a ``width x hidden`` one.  A
call computes ``tokens * top_k`` assignments, each one row through one
expert.  What the ALGORITHM needs: each expert that a row reaches is read
once, however many rows it gets; each assignment reads its row and writes
its result once; the rows that carry no request need nothing.
"""


def expert_params(hidden, width, gated=True):
    """Parameters of one expert."""
    return hidden * width * (3 if gated else 2)


def experts_reached(tokens, n_experts, top_k):
    """Expected number of experts that ``tokens`` rows reach when every row
    picks ``top_k`` distinct experts evenly: ``N (1 - (1 - k/N)^T)``.  128
    rows of top 8 of 64 reach 64.0; 16 rows reach 56.4."""
    return n_experts * (1.0 - (1.0 - top_k / n_experts) ** tokens)


def expert_bank_call(tokens, n_experts, top_k, hidden, width, itemsize=2,
                     gated=True):
    """(operations, bytes) of one layer's bank over ``tokens`` live rows."""
    per_expert = expert_params(hidden, width, gated)
    assignments = tokens * top_k
    flops = 2 * assignments * per_expert
    nbytes = (experts_reached(tokens, n_experts, top_k) * per_expert
              + 2 * assignments * hidden) * itemsize
    return flops, nbytes
