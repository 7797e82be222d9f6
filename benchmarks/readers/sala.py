"""Readers for a MiniCPM-SALA cell: what its kind counted from the rows'
lengths (``kinds/serve_backlog_resident_hybrid.py:attention_counters`` over
``lib/arith_sala.py``).  A run without those counts, or a program without the
kernel (a parent commit), gives None and the metric is left out of the line."""

from benchmarks.lib import arith

KERNEL = "paged_sparse_attention"


def roofline(run):
    """The least time for the operations and bytes the kernel needed over
    the traced stretch (the pages the decode rows chose, and a prompt chunk's
    chosen pages ONCE a chunk, no more than its sequence's pages up to its
    end: what one masked pass over the chunk's context reads; of K and of V)
    over its time there."""
    t, c = run["trace"], run["counters"]
    if t is None or "paged_sparse_bytes" not in c:
        return None
    took = t.op_seconds().get(KERNEL)
    if not took:
        return None
    bound_s, which = arith.roofline_seconds(c["paged_sparse_flops"],
                                            c["paged_sparse_bytes"], run["peaks"])
    run["notes"].setdefault("roofline_bound", {})[KERNEL] = which
    return 100.0 * bound_s / took


def keys_read_pct(run):
    """Keys the live rows' sparse layers attended over the keys resident
    before them: what a dense layer would have read."""
    c = run["counters"]
    if not c.get("sparse_keys_resident"):
        return None
    return 100.0 * c["sparse_keys_attended"] / c["sparse_keys_resident"]
