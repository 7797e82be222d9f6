"""Reader for the two kernels of ``ops/pallas/selective_scan.py``
(``mamba_state_update`` over a step's live decode rows, ``mamba_chunk_scan``
over its prompt chunk): each one's share of its roofline.  The kind leaves,
a step of the traced stretch that ran a program, what each kernel worked on
(``kinds/serve_backlog_resident_mamba.py:attention_counters``:
``traced_step_decode_rows``, ``traced_step_chunk_tokens``); the metric's file
names the counter and the function of ``lib/arith_jamba.py`` that counts one
call's operations and bytes a mamba layer.  The device's part of a trace can
start some programs after the host's, so the steps read are the LAST
``Trace.program_runs()`` of them, as ``readers/step_share.py`` reads them.  A
run without a trace, the counter or the kernel gives None and the metric is
left out of the line."""

from benchmarks.lib import arith, arith_jamba
from benchmarks.lib.cells import resolve


def kernel_roofline(run, kernel, rows, call):
    """The least time for ``call`` (``"module:function"``: (what a step
    worked on, ``model.kwargs``) -> (operations, bytes) of ONE layer's call)
    over the steps' counts under the counter ``rows``, every mamba layer,
    over the self time of ``kernel`` in the traced stretch."""
    trace, counters = run["trace"], run["counters"]
    if trace is None or rows not in counters:
        return None
    took = trace.op_seconds().get(kernel)
    held = trace.program_runs()
    worked = counters[rows][-held:] if held else counters[rows]
    kw = run["cell"].config["model"]["kwargs"]
    calls = [resolve(call)(n, kw) for n in worked if n]
    if not took or not calls:
        return None
    layers = arith_jamba.layer_kinds(kw).count("mamba")
    flops, nbytes = (layers * sum(c[i] for c in calls) for i in (0, 1))
    least, bound = arith.roofline_seconds(flops, nbytes, run["peaks"])
    run["notes"].setdefault("roofline_bound", {})[kernel] = bound
    return 100.0 * least / took
