"""Traffic kind ``serve-backlog-resident-afmoe``: ``serve-backlog-resident``
as it stands (its plan, its fill, its window and its check of the sample
against one full pass of the plain reference are that module's, called, not
copied), with

* the cache's reads counted for THIS cell's lengths (:func:`attention_counters`
  over ``lib/arith_trinity.py``, in the place of the resident kind's, as
  ``serve_backlog_resident_indexed`` puts its own there): a prompt chunk the
  consecutive queries of ONE sequence, whose pages are needed once a chunk and
  not once a token, and a row without a request nothing.  The resident kind
  counts every row a single query, which at SmallThinker's cohort of one age
  reads ``paged_gqa_attention_roofline`` 101.9%; at a chunk of 512 inside
  prompts of up to 32,768 it would ask for hundreds of times what any program
  needs;
* the two LIMITS of the comparison that decides ``correct`` found on the
  model this cell serves, by ``serve_backlog_resident_routed4.py``'s method.

Why that model needs limits of its own (PERF.md § 6, PR 55).  A Trinity block
norms every sublayer's OUTPUT before it is added: a rounding in the bank is
not damped by the residual it joins but scaled up to unit size with the rest;
its router's four weights are 2.448 / 4 = 0.61 each, so an expert that
rounding swaps in or out, or one that another chip holds, moves the routed
sum by a quarter; and the logits are plain (deviation 1.1 over 25,024 words),
so a served token loses by up to 2.3 of logit where SmallThinker's loses 0.31
and Mistral's 3.0.  The program in float32 serves the reference's every token
at four layers of these widths (``tools/serve_parity.py``: largest gap 0.0),
so the gaps are rounding, not a fault.

* ``LOGIT_MARGIN``: the GROSS limit on every served token's gap.
* ``NOISE_LIMIT``: the limit on precision, on the MEDIAN over the run's
  checked requests of the noise scale, as in the resident kind: a bf16 run
  and a run with the bank through float8 fall on opposite sides of it.

Both readings a limit lies between are in PERF.md § 6.
"""

import numpy as np

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.lib import arith_trinity
from benchmarks.lib.serving import Serving

END_TO_END = resident.END_TO_END
# 1.55 times the largest a bf16 run has read (2.26 over 80 requests of 20
# seeds; a request's largest 0.42-2.26), four fifths of what a token that has
# nothing to do with the reference loses by in the mean (the best of 25,024
# logits of deviation 1.1 less a random one: 4.4).  The bank through float8
# reads 1.70-2.39 and passes it.
LOGIT_MARGIN = 3.5
# bf16 runs read medians of 0.029-0.041 (a request 0.022-0.048; 20 seeds),
# the same cell with its bank through float8_e4m3fn 0.108-0.123 (a request
# 0.101-0.134; four seeds): 1.7 times the one, 0.65 of the other.
NOISE_LIMIT = 0.07


def judge(largest, noise_scales, median):
    """Samples over the gross limit, and those over the noise limit when
    their median is (``resident.check_sample``'s rule, these limits)."""
    return sum(w > LOGIT_MARGIN or (median > NOISE_LIMIT and s > NOISE_LIMIT)
               for w, s in zip(largest, noise_scales))


def attention_counters(srv, snaps, steps):
    """Operations and bytes the cache's reads needed between two snapshots,
    from the lengths alone, under the names ``readers/paged_gqa.py`` reads:
    each request's decode steps in between a single-query row at its own
    position, its prompt tokens the chunks they ran as, in every layer at
    the pages that layer's kind can see."""
    mcfg = srv.model.cfg
    decode, chunks = [], []
    for rid, (plen, res1, gen1) in snaps["after"].items():
        _, res0, gen0 = snaps["before"].get(rid, (plen, 0, 0))
        if gen0 == 0 and res0 < plen:                 # prompt chunks run
            end = min(res1, plen)
            chunks += [(first, min(srv.chunk, end - first))
                       for first in range(res0, end, srv.chunk)]
        d = max((gen1 - gen0) - (1 if gen0 == 0 and gen1 > 0 else 0), 0)
        decode.append(np.arange(res1 - d, res1))
    decode = np.concatenate(decode) if decode else np.zeros(0, np.int64)
    layers = {}
    for kind in mcfg.pattern:
        layers[kind.window] = layers.get(kind.window, 0) + mcfg.n_layer // len(mcfg.pattern)
    flops, nbytes = arith_trinity.attention(
        decode, chunks, layers, srv.block, srv.lanes, mcfg.n_head, mcfg.head_dim,
        srv.params["wte"].dtype.itemsize)
    live = len(decode) + sum(n for _, n in chunks)
    programs = sum(1 for st in steps if st[2] > 0 or st[3] > 0)
    return {"paged_gqa_flops": flops, "paged_gqa_bytes": nbytes,
            "attention_rows_live": live, "attention_chunks": len(chunks),
            "attention_rows_idle": max(programs * (srv.slots + srv.chunk) - live, 0),
            "traced_step_rows": Serving.step_rows(steps)}


def run(cell, args, ctx):
    """``resident.run`` with this cell's count of the cache's reads, its
    sample judged again by this module's limits."""
    theirs, resident.attention_counters = resident.attention_counters, attention_counters
    try:
        out = resident.run(cell, args, ctx)
    finally:
        resident.attention_counters = theirs
    notes = out["notes"]
    if not notes["checked"]:
        return out
    other = out["failed"] - notes["wrong"]            # short or refused requests
    wrong = judge(notes["logit_gaps"], notes["noise_scales"],
                  notes["noise_scale_median"])
    notes.update(wrong=wrong, tie_tolerance=LOGIT_MARGIN, noise_limit=NOISE_LIMIT)
    out.setdefault("compared", {}).update(
        largest_logit_gap=[max(notes["logit_gaps"]), LOGIT_MARGIN],
        noise_scale_median=[notes["noise_scale_median"], NOISE_LIMIT],
        requests_wrong=[wrong, 0])
    out.update(failed=wrong + other,
               correct=(wrong == 0 and other == 0 and not notes["backlog_ran_dry"]
                        and notes["cohort_filled"]))
    return out
