"""Reader for a routed stack (first ZAYA1's cell): the share of the stack's experts a step's live
rows reach, from what the engine says of its routing on the span of a step's
commit (``serve.decode.commit``'s stat ``moe_experts_touched``: the experts
with an assignment, counted a LAYER, which is how ``ServingEngine`` counts
them for a hybrid stack; the other routed models' stat is of the sum over
layers, so this reader is theirs to leave alone).  With one expert a token
that share is what the bank's bytes a step follow: 48 live rows reach ``16 (1
- (15/16)^48)`` = 15.3 of a layer's 16.  A run without a trace, or a program
whose spans carry no such stat (a parent commit), gives None and the metric
is left out of the line."""

import numpy as np

from benchmarks.readers import afmoe, moe

STAT = "moe_experts_touched"


def experts_reached_pct(run):
    """Mean over the traced stretch's decode steps of the (layer, expert)
    pairs with a live assignment, over ``num_experts x num_hidden_layers`` of
    the configuration file.  A configuration whose engine counts the SUM over
    layers (a periodic stack: its file says ``"experts_touched":
    "summed_over_layers"``) is read by ``readers/afmoe.py``, which inverts
    the sum: the same share, of an expert layer's experts a step."""
    if run["cell"].config.get("experts_touched") == "summed_over_layers":
        return afmoe.experts_reached_pct(run)
    stats = moe.span_stats(run) or {}
    values = [s[STAT] for s in stats.get(moe.LOAD_SPAN, []) if STAT in s]
    if not values:
        return None
    c = run["cell"].config
    return 100.0 * float(np.mean(values)) / (c["num_experts"] * c["num_hidden_layers"])
