"""Reader for a Qwen3-Next cell: the caches' work of the traced stretch for
``step_mfu_pct`` (the configuration's ``step_work.attention``).  The kind
(``kinds/serve_backlog_resident_delta_moe.py:attention_counters``) leaves the
full layer's pages under the names the resident kinds use (``paged_gqa_flops``,
``paged_gqa_bytes``: what the paged kernel's roofline divides, pages ALONE)
and the delta layers' part beside them (``delta_flops``,
``delta_state_bytes_moved``, ``delta_conv_bytes_moved``); the step's share of
the peak takes ALL of it.  A run without a count of the pages (no trace, a
parent commit) gives nothing to read: None."""

from benchmarks.readers import paged_gqa


def work(run):
    """(operations, bytes) of the pages, and of the states and the
    convolution states where the kind counted them, over the traced stretch;
    None without the kind's count."""
    pages = paged_gqa.work(run)
    if pages is None:
        return None
    c = run["counters"]
    return (pages[0] + c.get("delta_flops", 0),
            pages[1] + c.get("delta_state_bytes_moved", 0) + c.get("delta_conv_bytes_moved", 0))
