"""Reader for the paged kernel over grouped K/V heads with a window
(``paged_gqa_attention`` of ``ops/pallas/decode_attention.py``): its share of
its roofline.  The operations and bytes are the kind's count of the LEAST
the algorithm needs for what the program ran
(``kinds/serve_backlog_resident.py:attention_counters`` over
``lib/arith_window.py``: a decode row a single query at its position, a
prompt chunk's pages ONCE for all its queries, a window layer at the pages it
can see, a row without a request nothing; the same count in every kind that
leaves pages under these names, so one entry lists SmallThinker's, Trinity's
and Qwen3-Next's cells), left in the run's counters.  A program without the kernel (a parent commit,
a multi-head model) gives nothing to read: None, and the metric is left out
of the line."""

from benchmarks.lib import arith

KERNEL = "paged_gqa_attention"


def work(run):
    """(operations, bytes) the kernel needed over the traced stretch, the
    kind's count; None without it."""
    c = run["counters"]
    return (c["paged_gqa_flops"], c["paged_gqa_bytes"]) if "paged_gqa_bytes" in c else None


def roofline(run):
    """The least time for the operations and bytes the kernel needed over
    the traced stretch over its time there."""
    t = run["trace"]
    needed = work(run) if t is not None else None
    if needed is None:
        return None
    took = t.op_seconds().get(KERNEL)
    if not took:
        return None
    bound_s, which = arith.roofline_seconds(*needed, run["peaks"])
    run["notes"].setdefault("roofline_bound", {})[KERNEL] = which
    return 100.0 * bound_s / took
