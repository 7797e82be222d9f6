"""Reader of the whole serve step's share of the chip's peak: what
``mfu_pct.train`` is to a train cell.  Over the traced stretch, the least
time the chip could take for the work its steps HAD to do, over the stretch's
time on the device's clock (``Trace.window_s``: the first op to the last,
idle included, so a host that holds the chip back lowers it).

The work is counted from shapes and lengths by the benchmark's own
arithmetic, never from op names and never from what the program says of
itself: ``lib/arith_step.py`` for the weights every row goes through and the
expert bank (the configuration file's ``step_work.weights`` names its
family's function), and the attention reader the file names under
``step_work.attention`` for the cache (the traffic kind's count between the
trace's two snapshots, as the kernels' rooflines take it).  A copy of a layer
out of a stacked array is in none of it, so a program that drops the copy
reads higher by the time it saved; a kernel swapped for another reads the
same work.

The kinds leave ``traced_step_rows`` in the run's counters: the live rows
(decode rows + prompt tokens) of each step of the stretch that ran a program.
The device's part of a trace can start some programs after the host's, so the
steps read are the LAST ``Trace.program_runs()`` of them (a step runs one
program) and attention's count is cut in proportion to their rows: work
whose time the window does not hold is never counted, and the reading errs
low.  None without a trace or without the counts.
"""

from benchmarks.lib import arith, arith_step
from benchmarks.lib.cells import resolve


def mfu_pct(run):
    """100 x (sum over the stretch's steps of a step's least time) over the
    stretch's time on the device."""
    import jax.numpy as jnp
    t, c = run["trace"], run["counters"]
    if t is None or "traced_step_rows" not in c:
        return None
    cfg = run["cell"].config
    spec = cfg.get("step_work")
    attention = resolve(spec["attention"])(run) if spec else None
    if attention is None:
        return None
    weights = resolve(spec["weights"])(cfg["model"]["kwargs"])
    itemsize = jnp.dtype(cfg["dtype"]).itemsize
    rows = [r for r in c["traced_step_rows"] if r > 0]
    held = t.program_runs()
    kept = rows[-held:] if held else rows
    if not kept:
        return None
    total = sum(rows)
    least, weight_bytes, bounds = 0.0, 0.0, set()
    for r in kept:
        flops, nbytes = arith_step.step_work(weights, r, itemsize)
        weight_bytes += nbytes
        # attention's count is the stretch's: a step gets its rows' share
        seconds, which = arith.roofline_seconds(
            flops + attention[0] * r / total, nbytes + attention[1] * r / total,
            run["peaks"])
        least += seconds
        bounds.add(which)
    notes = run["notes"]
    notes.setdefault("roofline_bound", {})["step"] = "/".join(sorted(bounds))
    gb_a_step = lambda nbytes: round(nbytes / len(kept) / 1e9, 4)
    notes["step_work"] = {"steps": len(kept), "steps_counted_by_the_host": len(rows),
                          "least_ms_a_step": round(1e3 * least / len(kept), 4),
                          "weight_and_bank_gb_a_step": gb_a_step(weight_bytes),
                          "attention_gb_a_step": gb_a_step(attention[1] * sum(kept) / total)}
    return 100.0 * least / t.window_s()
