"""Traffic kind ``serve-backlog-resident-afmoe``: ``serve-backlog-resident``
as it stands (its plan, its fill, its window, its count of the cache's reads,
which came from this kind, and its check of the sample against one full pass
of the plain reference are that module's, called, not copied), with the two
LIMITS of the comparison that decides ``correct`` found on the model this
cell serves, by ``serve_backlog_resident_routed4.py``'s method.

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

import functools

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.lib import resident_stack

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


judge = functools.partial(resident_stack.judge, logit_margin=LOGIT_MARGIN,
                          noise_limit=NOISE_LIMIT)      # tools/serve_parity.py's


def run(cell, args, ctx):
    """``resident.run``, its sample judged again by this module's limits."""
    return resident_stack.run(cell, args, ctx, logit_margin=LOGIT_MARGIN,
                              noise_limit=NOISE_LIMIT)
