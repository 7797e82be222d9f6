"""Traffic kind ``serve-backlog-resident-indexed``: ``serve-backlog-resident``
as it stands (its plan, its fill, its window and its check of the sample
against one full pass of the plain reference are that module's, called, not
copied) for a stack of indexed layers (Keye-VL-2.0: a lightning indexer
scores every cached token and the query attends the 2,048 it scores highest),
with

* the cache's work counted for THAT stack (:func:`attention_counters` over
  ``lib/arith_keye_vl2.py``, in the place of the resident kind's, which
  counts pages under a window): the index keys a row scored and the tokens it
  chose, whatever implements either;
* the two LIMITS of the comparison that decides ``correct`` found on this
  model, as ``serve_backlog_resident_hybrid`` found its own.

Why this model needs limits of its own (PERF.md § 6, PR 51).  A query attends
the 2,048 tokens its indexer scores highest of 25,000-46,000: the scores
around the 2,048th place lie close, and a token that swaps there on rounding
leaves the softmax and another enters, as an expert swaps in the bank beside
it (the eighth and the ninth of 128 logits).  The swaps come from bf16's own
rounding of the activations the indexer reads, not from the cached keys'
type: a sequence of 30,000 reads a noise scale of 0.094 where contexts under
2,048 (no selection) read 0.009-0.036, and the same cell with its index keys
cached in float8_e4m3fn reads what bf16 reads (0.095-0.39 a request, median
0.152, against 0.012-0.25, medians 0.068-0.135), so no limit that admits
bf16 refuses float8 index keys ALONE; what the limits do refuse is every
matrix through float8.  The logits are plain (deviation 0.9 over the
vocabulary; a token unrelated to the reference loses by 4.1), the seeded
model's outputs run close to ties, and bf16 serves the reference's second
best at 0-40% of a request's positions.  The program in float32 serves the
reference's every token, at contexts on both sides of ``topk`` and at 30,000
positions (``tools/serve_parity.py --long 30000`` at two layers: every gap
0.0), so the gaps are rounding, not a fault.

* ``LOGIT_MARGIN``: the GROSS limit on every served token's gap.
* ``NOISE_LIMIT``: the limit on precision, on the MEDIAN over the run's
  checked requests of the noise scale, as in the resident kind.

Both readings a limit lies between are in PERF.md § 6.
"""

import functools

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.lib import arith_keye_vl2 as arith_keye
from benchmarks.lib import resident_stack

END_TO_END = resident.END_TO_END
# 2.3 times the largest a bf16 run has read (0.882 over 36 requests of 9 runs
# of the cell; 0.139 on ``serve_parity``'s sequence of 30,000), half of what a
# token unrelated to the reference loses by (4.1).  Every matrix through
# float8 reads 1.32 and passes it.
LOGIT_MARGIN = 2.0
# bf16 runs read medians of 0.068-0.135 (a request 0.012-0.246; a run of 20 s
# with two requests 0.145 and 0.154); the same cell with every matrix through
# float8_e4m3fn reads 0.254 and 0.294 on two requests and past every scale
# (999.99: more tokens flipped than any noise explains) on the other two, a
# median of 500: 1.5 times the one, 0.79 of the other's least request.
NOISE_LIMIT = 0.2


judge = functools.partial(resident_stack.judge, logit_margin=LOGIT_MARGIN,
                          noise_limit=NOISE_LIMIT)      # tools/serve_parity.py's


def attention_counters(srv, snaps, steps):
    """What the caches cost between two snapshots, from the lengths alone
    (``resident.rows_between``): each request's decode steps in between a
    single-query row at its own position in every indexed layer, its prompt
    tokens chunks of one sequence.  ``index_*`` is the scores' part, ``indexed_attend_*`` the
    chosen tokens' (``readers/keye_vl2.py:scope_roofline``); ``paged_gqa_*``,
    the names under which the resident kind leaves "the cache's reads" for
    ``step_mfu_pct`` (``readers/paged_gqa.py:work``), is ALL of it here."""
    mcfg = srv.model.cfg
    ix = arith_keye.indexer_of(srv.cell.config["model"]["kwargs"])
    decode, chunks = resident.rows_between(srv, snaps)
    positions = resident.live_positions(decode, chunks)
    itemsize = srv.params["wte"].dtype.itemsize
    s_flops, s_bytes = arith_keye.score_rows(decode, chunks, mcfg.n_layer, ix, itemsize)
    a_flops, a_bytes = arith_keye.attend_rows(
        decode, chunks, mcfg.n_layer, mcfg.n_head, mcfg.kv_heads, mcfg.head_dim, ix, itemsize)
    resident_keys = int((positions + 1).sum()) * mcfg.n_layer
    return dict(resident.row_counters(srv, steps, decode, chunks),
                index_flops=s_flops, index_bytes=s_bytes,
                indexed_attend_flops=a_flops, indexed_attend_bytes=a_bytes,
                paged_gqa_flops=s_flops + a_flops, paged_gqa_bytes=s_bytes + a_bytes,
                index_keys_scored=resident_keys, indexed_keys_resident=resident_keys,
                indexed_keys_attended=int(arith_keye.keys_attended(positions, ix).sum())
                * mcfg.n_layer)


def run(cell, args, ctx):
    """``resident.run`` with this stack's count of the cache's work, its
    sample judged again by this module's limits."""
    return resident_stack.run(cell, args, ctx, logit_margin=LOGIT_MARGIN,
                              noise_limit=NOISE_LIMIT, attention_counters=attention_counters)
