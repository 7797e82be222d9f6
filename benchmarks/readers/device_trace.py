"""Readers of the reduced device trace (``benchmarks/lib/trace.py``).  Every
one returns None where the run was not traced, or the trace holds nothing of
the kind."""

from benchmarks.lib import arith


def device_idle_pct(run):
    t = run["trace"]
    return None if t is None else 100.0 * t.idle_share()


def op_share_pct(run, ops):
    """Self time of the named ops over the time the device was busy."""
    t = run["trace"]
    if t is None:
        return None
    secs = t.op_seconds()
    found = [secs[o] for o in ops if o in secs]
    return 100.0 * sum(found) / t.busy_s() if found else None


def collective_share_pct(run):
    t = run["trace"]
    shares = t.collective_shares() if t is not None else None
    return None if shares is None else 100.0 * shares[0]


def collective_exposed_pct(run):
    t = run["trace"]
    shares = t.collective_shares() if t is not None else None
    return None if shares is None else 100.0 * shares[1]


def flash_roofline(run, kernels):
    """The least time the chip could take for the calls of these kernels
    seen in the trace, over the time they took.  One call's operations and
    bytes come from ``arith.flash_call`` and the cell's shapes."""
    t = run["trace"]
    shape = run["counters"].get("flash_shape")
    if t is None or shape is None:
        return None
    secs, counts = t.op_seconds(), t.op_counts()
    least = took = 0.0
    for k in kernels:
        if not counts.get(k):
            continue
        flops, nbytes = arith.flash_call(k, **shape)
        bound_s, which = arith.roofline_seconds(flops, nbytes, run["peaks"])
        run["notes"].setdefault("roofline_bound", {})[k] = which
        least += counts[k] * bound_s
        took += secs[k]
    return 100.0 * least / took if took else None


def paged_attention_roofline(run):
    """The least time for the operations and bytes the paged kernel needed
    over the traced window (``Serving.paged_model``) over its time there."""
    t = run["trace"]
    c = run["counters"]
    if t is None or "paged_bytes" not in c:
        return None
    took = t.op_seconds().get("paged_attention")
    if not took:
        return None
    bound_s, which = arith.roofline_seconds(c["paged_flops"], c["paged_bytes"],
                                            run["peaks"])
    run["notes"].setdefault("roofline_bound", {})["paged_attention"] = which
    return 100.0 * bound_s / took
