"""Traffic kind ``serve-backlog-resident-hybrid``: ``serve-backlog-resident``
as it stands (its plan, its fill, its window and its check of the sample
against one full pass of the plain reference are that module's, called, not
copied) for a stack of sparse and linear layers (MiniCPM-SALA), with

* the cache's work counted for THAT stack (:func:`attention_counters` over
  ``lib/arith_sala.py``, in the place of the resident kind's, which counts
  pages under a window): the pages a query chose (a prompt chunk's once a
  chunk), the compressed keys it scored, the states its linear layers moved;
* the two LIMITS of the comparison that decides ``correct`` found on this
  model, as ``serve_backlog_resident_routed4`` found its own.

Why this model needs limits of its own (PERF.md § 6, PR 39).  Two things
set its logits apart from the other resident cells'.  They are SMALL: the head
reads the final norm's output over 16 (``dim_model_base``) and every residual
branch is scaled by 0.25, so a logit's deviation over the vocabulary is about
0.08 where SmallThinker's is 1, the best of 73,448 leads a random one by
0.34, and the best two lie 0.001-0.01 apart.  And a sparse layer attends the
64 blocks its query scores highest: the scores around the 64th lie close, and
a block that swaps on rounding takes 64 keys out of the softmax and puts 64
others in, as an expert swaps in a routed model.  bf16 against the float32
reference then serves the reference's second best at 5-9% of the positions,
each time by under 0.015 of logit.  The program in float32 serves the
reference's every token at these widths to 0.001 (``tools/serve_parity.py``
at 4 layers, 8,440 positions), so the gaps are rounding, not a fault.

* ``LOGIT_MARGIN``: the GROSS limit on every served token's gap.
* ``NOISE_LIMIT``: the limit on precision, on the MEDIAN over the run's
  checked requests of the noise scale, as in the resident kind.

Both readings a limit lies between are in PERF.md § 6.
"""

import functools

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.lib import arith_sala, resident_stack

END_TO_END = resident.END_TO_END
# 10 times the largest a bf16 run has read (0.0141 over 60 requests of 15 runs
# of the cell; 0.0075 over five short contexts), 0.44 of what a token
# unrelated to the reference loses by (0.34).  The weights through float8 read
# 0.067 and pass it.
LOGIT_MARGIN = 0.15
# bf16 runs read medians of 0.0029-0.0043 (a request 0.0023-0.0057); the same
# model with every matrix through float8_e4m3fn reads 0.121 at its best and
# past every scale (999.99: more tokens flipped than any noise explains) on
# four requests of five: 3.5 times the one, a sixth of the other.
NOISE_LIMIT = 0.02


judge = functools.partial(resident_stack.judge, logit_margin=LOGIT_MARGIN,
                          noise_limit=NOISE_LIMIT)      # tools/serve_parity.py's


def attention_counters(srv, snaps, steps):
    """What the caches cost between two snapshots, from the lengths alone
    (``resident.rows_between``): each request's decode steps in between a
    single-query row at its own position in every sparse layer, its prompt
    tokens the chunks they ran as (a chunk's chosen pages once a chunk); a
    linear layer's state moved once a decode row and once a prompt chunk.
    ``paged_sparse_*`` is the kernel's part (the chosen pages:
    ``readers/sala.py:roofline``); ``paged_gqa_*``, the names under which the
    resident kind leaves "the cache's reads" for ``step_mfu_pct``
    (``readers/paged_gqa.py:work``), is ALL of it here: the chosen pages, the
    compressed keys scored and the states moved."""
    mcfg = srv.model.cfg
    kw = srv.cell.config["model"]["kwargs"]
    sp = dict(arith_sala.SPARSE, **dict(zip(arith_sala.SPARSE, kw.get("sparse", ()))))
    decode, chunks = resident.rows_between(srv, snaps)
    positions = resident.live_positions(decode, chunks)
    moves = len(decode) + len(chunks)
    n_sparse = sum(m == "minicpm4" for m in kw["mixer_types"])
    n_linear = len(kw["mixer_types"]) - n_sparse
    itemsize = srv.params["wte"].dtype.itemsize
    flops, nbytes, compressed = arith_sala.sparse_rows(
        decode, chunks, n_sparse, mcfg.n_head, mcfg.kv_heads, mcfg.head_dim, sp, itemsize)
    lin_flops, lin_bytes = arith_sala.linear_rows(
        len(positions), moves, n_linear, mcfg.n_head, mcfg.head_dim)
    per = n_sparse * mcfg.kv_heads
    return dict(resident.row_counters(srv, steps, decode, chunks),
                paged_sparse_flops=flops, paged_sparse_bytes=nbytes,
                paged_gqa_flops=flops + lin_flops,
                paged_gqa_bytes=nbytes + compressed + lin_bytes,
                sparse_keys_attended=int(arith_sala.keys_attended(positions, sp).sum()) * per,
                sparse_keys_resident=int((positions + 1).sum()) * per,
                state_bytes_moved=lin_bytes)


def run(cell, args, ctx):
    """``resident.run`` with this stack's count of the cache's work, its
    sample judged again by this module's limits."""
    return resident_stack.run(cell, args, ctx, logit_margin=LOGIT_MARGIN,
                              noise_limit=NOISE_LIMIT, attention_counters=attention_counters)
