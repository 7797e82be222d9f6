"""Readers for a mixture-of-experts model's serve cell: what the program
says of its expert bank in the profiler's trace.

* The device ops of the bank carry the scope ``moe_experts``
  (``deepspeed_tpu/moe/dropless.py``), and its matmuls are the kernel
  ``grouped_matmul``; their self times come from the reductions in
  ``program_spans`` and ``lib/trace``.
* A ``serve.decode.dispatch`` span (stat ``batch``: live rows) is one call of
  the bank a layer with that many rows, a ``serve.prefill.dispatch`` span
  (stat ``tokens``) one with a prompt chunk's; ``serve.decode.commit``
  carries ``moe_load_max_over_mean`` of its step.

The share metrics of the MoE scopes (``moe``, ``moe_router``, ...) and of
``attn`` in a serve program are ``program_spans.scope_share_pct`` with their
scopes as arguments; their files name it through this module, because
``tests/benchmarks/test_program_spans.py`` holds every file that names
``program_spans`` to the scopes and the synthetic trace of PR 24.

A program without these (a dense model, a parent commit) gives every reader
here nothing to read: it returns None and the metric is left out of the line.
"""

import numpy as np

from benchmarks.lib import arith, arith_moe
from benchmarks.lib.trace import newest_xplane
from benchmarks.readers.program_spans import (TRACE_DIR, _stats_of,
                                              scope_share_pct)  # noqa: F401

# span -> the stat that holds the live rows of its program
BANK_CALLS = {"serve.decode.dispatch": "batch", "serve.prefill.dispatch": "tokens"}
LOAD_SPAN, LOAD_STAT = "serve.decode.commit", "moe_load_max_over_mean"
BANK_SCOPE, BANK_KERNEL = "moe_experts", "grouped_matmul"


START = "_start_s"          # where ``read_span_stats`` puts an event's start


def read_span_stats(path):
    """{span name: [the stats of each of its events, and its start under
    ``START``]} of one ``.xplane.pb``, for the spans named above."""
    from jax.profiler import ProfileData
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    out = {}
    for line in (host.lines if host else []):
        for e in line.events:
            if e.name in BANK_CALLS or e.name == LOAD_SPAN:
                out.setdefault(e.name, []).append(
                    dict(e.stats, **{START: e.start_ns * 1e-9}))
    return out


def span_stats(run):
    """``read_span_stats`` of the run's trace, read once and kept on the run."""
    if run["trace"] is None:
        return None
    if "_moe_span_stats" not in run:
        path = newest_xplane(TRACE_DIR)
        run["_moe_span_stats"] = read_span_stats(path) if path else None
    return run["_moe_span_stats"]


def load_max_over_mean(run):
    """Mean over the decode steps of the traced stretch of the fullest
    expert's assignments over the mean expert's (live rows, summed over
    layers): 1.0 is even routing."""
    stats = span_stats(run) or {}
    values = [s[LOAD_STAT] for s in stats.get(LOAD_SPAN, []) if LOAD_STAT in s]
    return float(np.mean(values)) if values else None


def bank_least_seconds(run):
    """The least time the chip could take for the bank's calls of the traced
    stretch: ``arith_moe.expert_bank_call`` a layer for the live rows of each
    program dispatched there.  None where the trace holds no such span.

    A program counts only if it was dispatched between the first chip's
    first and last op: the device's part of a trace starts a little after
    the host's (seen on the v5e, PR 27: two or three programs' spans and no
    op of theirs), and a call that was never timed may not be counted as
    done.  A program cut by the trace's start adds its ops' time and no
    call: the reading errs low, never high."""
    import jax.numpy as jnp
    calls = span_stats(run)
    if not calls:
        return None
    first = run["trace"].devices[0]
    c = run["cell"].config
    layers, itemsize = c["num_hidden_layers"], jnp.dtype(c["dtype"]).itemsize
    least, n_calls, bound = 0.0, 0, set()
    for span, stat in BANK_CALLS.items():
        for s in calls.get(span, []):
            if stat not in s or not first.start <= s.get(START, first.start) <= first.end:
                continue
            flops, nbytes = arith_moe.expert_bank_call(
                int(s[stat]), c["num_experts"], c["num_experts_per_tok"],
                c["hidden_size"], c["intermediate_size"], itemsize=itemsize)
            seconds, which = arith.roofline_seconds(flops, nbytes, run["peaks"])
            least, n_calls = least + layers * seconds, n_calls + layers
            bound.add(which)
    if not n_calls:
        return None
    run["notes"]["moe_bank_calls"] = n_calls
    return least, "/".join(sorted(bound))


def experts_roofline(run):
    """``bank_least_seconds`` over the self time of the device ops under
    the scope ``moe_experts`` (the grouped matmuls and the SwiGLU between)."""
    st = _stats_of(run)
    if not st or not st["chips"]:
        return None
    took = float(np.mean([sum(s for scopes, s in ops if BANK_SCOPE in scopes)
                          for _, ops in st["chips"]]))
    least = bank_least_seconds(run) if took else None
    if least is None:
        return None
    run["notes"].setdefault("roofline_bound", {})[BANK_SCOPE] = least[1]
    return 100.0 * least[0] / took


def grouped_matmul_roofline(run):
    """``bank_least_seconds`` over the self time of the kernel
    ``grouped_matmul`` (two calls a layer: gate|up, then down)."""
    t = run["trace"]
    took = t.op_seconds().get(BANK_KERNEL) if t is not None else None
    least = bank_least_seconds(run) if took else None
    if least is None:
        return None
    run["notes"].setdefault("roofline_bound", {})[BANK_KERNEL] = least[1]
    return 100.0 * least[0] / took
