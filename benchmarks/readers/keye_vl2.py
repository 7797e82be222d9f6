"""Readers for a Keye-VL-2.0 cell: what its kind counted from the rows'
lengths (``kinds/serve_backlog_resident_indexed.py:attention_counters`` over
``lib/arith_keye_vl2.py``) against the time of the device ops under one of the
mixer's SCOPES (``index_score``, ``index_attend``: whatever op or kernel does
the work there, so a later kernel swap reads the same work).  A run without
those counts, or a program that opens no such scope (a parent commit), gives
None and the metric is left out of the line."""

import numpy as np

from benchmarks.lib import arith
from benchmarks.readers.program_spans import _stats_of


def scope_roofline(run, scope, flops, nbytes):
    """The least time for the operations and bytes counted under the
    counters ``flops`` and ``nbytes`` over the traced stretch, over the self
    time there of the device ops traced under ``scope`` (the mean over the
    chips that ran an op)."""
    c = run["counters"]
    st = _stats_of(run) if run["trace"] is not None else None
    if not st or not st["chips"] or nbytes not in c:
        return None
    took = float(np.mean([sum(s for scopes, s in ops if scope in scopes)
                          for _, ops in st["chips"]]))
    if not took:
        return None
    bound_s, which = arith.roofline_seconds(c[flops], c[nbytes], run["peaks"])
    run["notes"].setdefault("roofline_bound", {})[scope] = which
    return 100.0 * bound_s / took


def keys_read_pct(run):
    """Keys the live rows' indexed layers attended over the keys resident
    before them: what dense attention would have read."""
    c = run["counters"]
    if not c.get("indexed_keys_resident"):
        return None
    return 100.0 * c["indexed_keys_attended"] / c["indexed_keys_resident"]
