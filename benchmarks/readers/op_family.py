"""Reader for an op that XLA emits under several names: a fusion appears in
one program as ``dynamic-slice_bitcast_fusion``,
``dynamic-slice_bitcast_fusion.12.remat3`` and
``dynamic-slice_bitcast_fusion.13.remat3`` (the numbers and the ``remat``
suffix are the compiler's and change with the program), and
``device_trace:op_share_pct`` takes a name whole."""


def share_pct(run, ops):
    """Self time of the ops named ``ops`` or ``<one of ops>.<suffix>`` over
    the time the device was busy: 0.0 where the traced run holds none of
    them (a change took the op out of the program), None without a trace."""
    t = run["trace"]
    if t is None:
        return None
    found = [s for name, s in t.op_seconds().items()
             if any(name == o or name.startswith(o + ".") for o in ops)]
    return 100.0 * sum(found) / t.busy_s()
