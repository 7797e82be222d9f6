"""Readers for an Olmo-Hybrid cell: how close the delta layers' state update
runs to the time its bytes need, and how many states a step moves.

The bytes are the kind's count from the rows' lengths
(``kinds/serve_backlog_resident_delta.py:attention_counters``: a MOVE is one
layer's state of one slot read and written, once a live decode row and once
a step's prompt chunk; ``lib/arith_olmo_hybrid.py:state_bytes`` each way), a
list a step of the traced stretch.  The device's part of a trace can start
some programs after the host's, so the steps read are the LAST
``Trace.program_runs()`` of them, as ``readers/step_share.py`` reads them:
work whose time the window does not hold is never counted.  No op's name
decides the count, so it is the same work whether XLA or a kernel does it.

A run without a trace, a program without the scope, the kernel or the stat
(a parent commit) gives every reader here nothing to read: it returns None
and the metric is left out of the line.
"""

from benchmarks.lib import arith, arith_olmo_hybrid
from benchmarks.readers import turnaround
from benchmarks.readers.program_spans import _stats_of

SCOPE, KERNEL = "delta_update", "delta_state_update"
STAT = "delta_state_moves"


def _least_seconds(run, counter):
    """The least time for the moves of the traced stretch's steps that the
    device line holds, at the chip's memory bandwidth."""
    t, c = run["trace"], run["counters"]
    if t is None or counter not in c:
        return None
    held = t.program_runs()
    moves = c[counter][-held:] if held else c[counter]
    nbytes = 2 * sum(moves) * arith_olmo_hybrid.state_bytes(
        run["cell"].config["model"]["kwargs"])
    return arith.roofline_seconds(0, nbytes, run["peaks"]) if nbytes else None


def delta_state_roofline(run):
    """The states' bytes of every move (decode rows and chunks) over the self
    time of the device ops under the scope ``delta_update``: the state's
    read, correction and write for the decode rows, the chunked form with
    its solve, the read for the query."""
    least = _least_seconds(run, "traced_step_state_moves")
    st = _stats_of(run) if least else None
    if not st or not st["chips"]:
        return None
    took = sum(sum(s for scopes, s in ops if SCOPE in scopes)
               for _, ops in st["chips"]) / len(st["chips"])
    if not took:
        return None
    run["notes"].setdefault("roofline_bound", {})[SCOPE] = least[1]
    return 100.0 * least[0] / took


def delta_state_update_roofline(run):
    """The states' bytes of the decode rows' moves over the self time of the
    kernel ``delta_state_update``, which moves those and no chunk's."""
    least = _least_seconds(run, "traced_step_decode_moves")
    took = run["trace"].op_seconds().get(KERNEL) if least else None
    if not took:
        return None
    run["notes"].setdefault("roofline_bound", {})[KERNEL] = least[1]
    return 100.0 * least[0] / took


def state_moves_per_step(run, stat=STAT):
    """Mean over the stretch's steps of the stat ``delta_state_moves`` of
    ``serve.stats`` (``stat``: a mamba stack's ``mamba_state_moves``): what
    the program says it moved, a live decode row a layer that keeps a state
    and one a layer for the step's chunk."""
    return turnaround.stat_mean_ms(run, stat)
