"""Traffic kind ``serve-backlog-resident-delta``: ``serve-backlog-resident``
as it stands (its plan, its fill, its window and its check of the sample
against one full pass of the plain reference are that module's, called, not
copied) for a stack of gated delta-rule layers among full-attention layers
(Olmo-Hybrid), with

* the caches' work counted for THAT stack (:func:`attention_counters` over
  ``lib/arith_olmo_hybrid.py``, in the place of the resident kind's, which
  counts pages in every layer): the pages a decode row's full layers read
  and a prompt chunk's once for all its queries, the
  states its delta layers read and write, the convolution states beside them;
* the two LIMITS of the comparison that decides ``correct`` found on this
  model, as ``serve_backlog_resident_hybrid`` found its own.

Why this model needs limits of its own (PERF.md § 6, PR 47).  Every sublayer's
output goes through a norm before it is added (``x + norm(f(x))``), so at
seeded weights a perturbation of the residual is not damped by the branch's
small weights as under a norm on the input: it is carried at full size from
sublayer to sublayer, 32 of them.  A nudge of 1e-6 of the embedding moves the
REFERENCE's own logits by 1e-4 at a tiny size on the CPU; on the chip the
program in float32 with XLA's default matmul precision (one bf16 pass) reads
a noise scale of 0.06 at FOUR layers, and with the highest precision serves
the reference's every token with a gap of 0.0 at these widths
(``tools/serve_parity.py``, one period: 240 served tokens of five sequences): the gaps are
rounding, carried far, and not a fault.  bf16 at 16 layers then serves the
reference's second best at 23-36% of the positions, by 0.04-0.06 of logit
on average, where SmallThinker's limits (0.75 / 0.06) would refuse every run.
The same at TWO periods (8 layers, 9.7 GB in float32): a gap of 0.0 again.

* ``LOGIT_MARGIN``: the GROSS limit on every served token's gap.
* ``NOISE_LIMIT``: the limit on precision, on the MEDIAN over the run's
  checked requests of the noise scale, as in the resident kind.

Both readings a limit lies between are in PERF.md § 6 (PR 47).
"""

import functools

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.lib import arith_olmo_hybrid, resident_stack

END_TO_END = resident.END_TO_END
# Twice the largest a bf16 run has read (1.001 over 240 requests of thirty
# runs of the cell, about 140,000 served tokens; a run's largest 0.61-1.00; the
# tool's five short sequences 0.540), 0.44 of the least the same model with
# every matrix through float8_e4m3fn reads (4.53-6.41 a sequence; its MEAN gap
# is 2.7-3.1 where bf16's is 0.04-0.06).  A float32 state kept in bf16 reads
# 0.75-0.98 and passes it: the gross limit is for a cache of garbage, not for
# precision.
LOGIT_MARGIN = 2.0
# bf16 runs read medians of 0.353-0.438 over thirty runs of the cell, a seed
# each (a request 0.290-0.613).  The state in bf16 in the place of float32,
# everything else as served, reads 0.628 and 0.765 THROUGH THIS COMPARISON on
# the cell's own eight requests (two runs: 4 and 8 of 8 wrong; the tool's five
# short sequences 0.835), and every matrix through float8 is past every scale
# (999.99: more tokens flipped than any noise explains).  The limit is the
# geometric middle of 0.438 and 0.628: 1.19 times the one, 0.83 of the other
# (0.6, this PR's first, left the state's lower reading 5% of room).
NOISE_LIMIT = 0.52


judge = functools.partial(resident_stack.judge, logit_margin=LOGIT_MARGIN,
                          noise_limit=NOISE_LIMIT)      # tools/serve_parity.py's


def attention_counters(srv, snaps, steps):
    """What the caches cost between two snapshots, from the lengths alone
    (``resident.rows_between``): each request's decode steps in between a
    single-query row at its own position in every full layer, its prompt
    tokens the chunks they ran as (a chunk's pages once a chunk); a delta
    layer's state and convolution state moved once a decode row and once a
    prompt chunk.  ``paged_gqa_*``, the names under which the resident kind
    leaves "the cache's reads" for ``step_mfu_pct``
    (``readers/paged_gqa.py:work``), is ALL of it here: the pages, the states
    and the convolution states.  ``traced_step_state_moves`` and
    ``traced_step_decode_moves`` are the moves a step that ran a program
    (``readers/olmo_hybrid.py``)."""
    kw = srv.cell.config["model"]["kwargs"]
    decode, chunks = resident.rows_between(srv, snaps)
    rows = resident.row_counters(srv, steps, decode, chunks)
    moves = len(decode) + len(chunks)
    ran = [st for st in steps if st[2] > 0 or st[3] > 0]
    n_full = kw["layer_types"].count("full_attention")
    n_delta = len(kw["layer_types"]) - n_full
    itemsize = srv.params["wte"].dtype.itemsize
    flops, nbytes = arith_olmo_hybrid.full_rows(decode, chunks, n_full, srv.block, kw, itemsize)
    d_flops, state, conv = arith_olmo_hybrid.delta_rows(
        rows["attention_rows_live"], moves, n_delta, kw, itemsize)
    return dict(rows, paged_gqa_flops=flops + d_flops, paged_gqa_bytes=nbytes + state + conv,
                full_pages_bytes=nbytes, delta_state_bytes_moved=state,
                delta_conv_bytes_moved=conv, delta_state_moves=moves * n_delta,
                traced_step_state_moves=[int(st[2] + (st[3] > 0)) * n_delta for st in ran],
                traced_step_decode_moves=[int(st[2]) * n_delta for st in ran])


def run(cell, args, ctx):
    """``resident.run`` with this stack's count of the caches' work, its
    sample judged again by this module's limits."""
    return resident_stack.run(cell, args, ctx, logit_margin=LOGIT_MARGIN,
                              noise_limit=NOISE_LIMIT, attention_counters=attention_counters)
