"""Readers of the reduced device trace (``benchmarks/lib/trace.py``).  Every
one returns None where the run was not traced.  In a traced run a SHARE of
the device's time is a number whatever the trace holds: an op that a change
took out of the program reads 0.0, so the change shows as a gain and the line
keeps the metric.  A quotient by a time (a roofline) has no value where its
kernel took none, and stays None there."""

from benchmarks.lib import arith


def device_idle_pct(run):
    t = run["trace"]
    return None if t is None else 100.0 * t.idle_share()


def op_share_pct(run, ops):
    """Self time of the named ops over the time the device was busy: 0.0
    where the traced run holds no op of the names, None without a trace."""
    t = run["trace"]
    if t is None:
        return None
    secs = t.op_seconds()
    return 100.0 * sum(secs[o] for o in ops if o in secs) / t.busy_s()


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


def paged_work(run):
    """(operations, bytes) the paged kernel needed over the traced stretch,
    whichever kernel did it (``Serving.paged_model``); None without them."""
    c = run["counters"]
    return (c["paged_flops"], c["paged_bytes"]) if "paged_bytes" in c else None


def paged_attention_roofline(run, kernels=("paged_attention",)):
    """The least time for the operations and bytes paged attention needed
    over the traced window (``paged_work``) over the time there of the
    ``kernels`` that do it, summed: the count is of the work, so the metric
    outlives a kernel that another of the names replaces."""
    t = run["trace"]
    work = paged_work(run)
    if t is None or work is None:
        return None
    secs = t.op_seconds()
    took = sum(secs[k] for k in kernels if k in secs)
    if not took:
        return None
    bound_s, which = arith.roofline_seconds(*work, run["peaks"])
    run["notes"].setdefault("roofline_bound", {})[kernels[0]] = which
    return 100.0 * bound_s / took
