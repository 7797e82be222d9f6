"""Traffic kind ``serve-backlog-resident-routed4``: ``serve-backlog-resident``
as it stands (its plan, its fill, its window, its counters and its check of
the sample against one full pass of the plain reference are that module's,
called, not copied), with the two LIMITS of the comparison that decides
``correct`` found on the model this cell serves.

Why that model needs limits of its own (PERF.md § 6, PR 33).  The resident
kind's limits were found on a router that weighs its six experts by the
softmax over their logits, so the expert that rounding swaps in or out is the
one that weighs least.  Here the router's scores are sigmoids of logits of
2.4–3.2, 0.92–0.96 each, renormalised over the chosen four: the four weigh
0.245–0.256, the fourth and the fifth logit lie 0.14 apart in the mean, a
swap of the two changes a quarter of the routed sum, and where one of the two
is held by another chip that quarter appears or disappears.  With seeded
weights an expert's output (0.29 a lane) is fifteen times the embedding it is
added to (0.02), so a swap turns the hidden state, and a served token then
loses by up to 3 logits where the other model's lost 0.31.  The program in
float32 serves the reference's every token at these widths
(``tools/serve_parity.py`` at 2 layers, 8,492 positions: largest gap 0.0), so
the gaps are rounding, not a fault.

* ``LOGIT_MARGIN``: the GROSS limit on every served token's gap.  A token
  that has nothing to do with the reference loses by the best of 32,768
  logits of deviation 1.28 less a random one, 5.4 in the mean and 9 at the
  largest of a request.
* ``NOISE_LIMIT``: the limit on precision, on the MEDIAN over the run's
  checked requests of the noise scale, as in the resident kind.  The largest
  gap does not tell bf16 from the precision below it here either (3.03
  against 2.88); the noise scale does, four times over.

Both readings a limit lies between are in PERF.md § 6.
"""

import functools

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.lib import resident_stack

END_TO_END = resident.END_TO_END
# 2.0 times the largest a bf16 run has read (3.03 over 24 requests of three
# seeds), two thirds of what a token unrelated to the reference loses by.
# The bank through float8 reads 2.88 and passes it.
LOGIT_MARGIN = 6.0
# bf16 runs read medians of 0.050-0.055 (a request 0.045-0.063), the same
# cell with its bank through float8_e4m3fn 0.208 (a request 0.167-0.218):
# 2.2 times the one, 0.58 of the other.  ``tools/serve_parity.py``'s short
# contexts read 0.090 and 0.245.
NOISE_LIMIT = 0.12


judge = functools.partial(resident_stack.judge, logit_margin=LOGIT_MARGIN,
                          noise_limit=NOISE_LIMIT)      # tools/serve_parity.py's


def run(cell, args, ctx):
    """``resident.run``, its sample judged again by this module's limits."""
    return resident_stack.run(cell, args, ctx, logit_margin=LOGIT_MARGIN,
                              noise_limit=NOISE_LIMIT)
