"""Readers of the serve step's own turn-round: DURATIONS the program stamped
on its one clock and wrote as stats of its ``serve.stats`` span, a record a
step (``serving/engine.py:TURNAROUND_STATS``), against the device's own gaps
between two program runs, durations on the device's clock.  The two are
subtracted, mean from mean over the same steps; no event of the host's line is
ever placed against an event of the device's, so the offset at which the
profiler lays the two lines (a millisecond and more, either way, from run to
run: PERF.md § 6, PR 36) moves nothing here.  That offset itself is read into
the notes, ``host_device_skew_ms``: it is the least error of anything that
does overlay the lines (the three ``idle_*_pct`` metrics did, and went with
PR 68).

What a TPU trace holds of it: the plane ``/device:TPU:<n>`` has a line ``XLA
Modules`` with one event a program run; a serve step runs one program, so the
line's gaps are the chip waiting between two steps.  The last run of a traced
stretch is the last step's (``step()`` is synchronous and the trace stops
after it), while the device's part of a trace starts a few programs after the
host's, so steps and runs are matched by COUNT from the end, and the stretch
read is the steps whose program and the one before it the device line holds.
The first ``serve.stats`` of a trace tells of a turn-round that began before
the trace did and holds the profiler's start; it is never in that stretch.

A program without the stats (a parent commit) gives every reader here nothing
to read: it returns None and the metric is left out of the line.
``moe.read_span_stats`` reads three span names only, so this module opens the
newest ``.xplane.pb`` itself, once a run.
"""

import bisect

import numpy as np

from benchmarks.lib.trace import newest_xplane
from benchmarks.readers.program_spans import TRACE_DIR

STATS_SPAN = "serve.stats"           # one a step, on the thread that steps
FETCH_SPANS = ("serve.prefill.fetch", "serve.decode.fetch")    # on any thread
MODULES_LINE = "XLA Modules"         # one event a program run
TURNAROUND = "turnaround_ms"
PARTS = ("commit_ms", "outside_ms", "prepare_ms", "result_wait_ms")
RAN = "attention_rows"               # of ``serve.stats``: 0 in a step with no program


def read_steps(path):
    """{"steps": [the stats of each ``serve.stats`` event, in order of start],
    "fetch_ends": [end s of each fetch span, whatever thread opened it],
    "runs": [(start s, end s) of each program run of the first chip] or None
    where the trace has no ``XLA Modules`` line} of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    steps, fetch_ends, runs = [], [], None
    chips = sorted((p for p in data.planes if p.name.startswith("/device:TPU:")),
                   key=lambda p: p.name)
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == STATS_SPAN:
                        steps.append((e.start_ns, dict(e.stats)))
                    elif e.name in FETCH_SPANS:
                        fetch_ends.append((e.start_ns + e.duration_ns) * 1e-9)
    for line in (chips[0].lines if chips else []):
        if line.name == MODULES_LINE:
            runs = sorted((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                          for e in line.events)
    return {"steps": [st for _, st in sorted(steps, key=lambda x: x[0])],
            "fetch_ends": sorted(fetch_ends), "runs": runs}


def stretch(read, device):
    """([the stats of each step read], [the chip's idle before that step's
    program, ms]) or None where no step carries the stats.

    ``read``: what ``read_steps`` gives; ``device``: the first chip's
    ``DeviceTrace``, whose gaps stand in where the trace has no ``XLA
    Modules`` line: a gap holds a program boundary if it is no shorter than
    the shortest turn-round stamped, since the chip has no program of this
    engine during one, and the gaps between a program's ops are microseconds."""
    stamped = [st[TURNAROUND] for st in read["steps"] if TURNAROUND in st]
    if not stamped:
        return None
    if read["runs"] is not None:
        runs = read["runs"]
        gaps = [1e3 * (b[0] - a[1]) for a, b in zip(runs, runs[1:])]
    else:
        gaps = [1e3 * (e - s) for s, e in device.gaps() if 1e3 * (e - s) >= min(stamped)]
    ran = [st for st in read["steps"] if st.get(RAN, 1)]
    k = min(len(gaps), len(ran))
    pairs = [(st, gap) for st, gap in zip(ran[len(ran) - k:], gaps[len(gaps) - k:])
             if TURNAROUND in st]
    return ([st for st, _ in pairs], [gap for _, gap in pairs]) if pairs else None


def skew_ms(read):
    """Median, as the trace LAYS them, of (end of a fetch span - end of the
    program run it fetched: the run that ends nearest to it).  Causality holds
    it to [0, wire]; outside, it is the profiler's offset between the lines."""
    ends = [e for _, e in read["runs"] or []]
    if not ends or not read["fetch_ends"]:
        return None
    near = []
    for f in read["fetch_ends"]:
        i = bisect.bisect_left(ends, f)
        near.append(min((f - e for e in ends[max(i - 1, 0):i + 1]), key=abs))
    return 1e3 * float(np.median(near))


def _of(run):
    """The run's stretch, read once and kept on the run, with its notes."""
    if run["trace"] is None:
        return None
    if "_turnaround" not in run:
        path = newest_xplane(TRACE_DIR)
        run["_turnaround"] = _with_notes(run, read_steps(path)) if path else None
    return run["_turnaround"]


def _with_notes(run, read):
    found = stretch(read, run["trace"].devices[0])
    if found is None:
        return None
    steps, gaps = found
    spread = lambda stat: [round(float(np.percentile([st[stat] for st in steps], q)), 4)
                           for q in (50, 99, 100)]
    notes = run["notes"]
    notes["turnaround_steps"] = len(steps)
    # a machine's pause is ONE step's wait or turn-round: which says whose
    notes["turnaround_ms_p50_p99_max"] = spread(TURNAROUND)
    notes["result_wait_ms_p50_p99_max"] = spread("result_wait_ms")
    notes["turnaround_parts_ms"] = {
        stat: round(float(np.mean([st[stat] for st in steps])), 4) for stat in PARTS}
    notes["idle_between_programs_ms"] = round(float(np.mean(gaps)), 4)
    slowest = max(steps, key=lambda st: st[TURNAROUND])
    notes["slowest_turnaround_parts_ms"] = {stat: round(slowest[stat], 4) for stat in PARTS}
    skew = skew_ms(read)
    if skew is not None:
        notes["host_device_skew_ms"] = round(skew, 4)
    return found


def stat_mean_ms(run, stat):
    """Mean of one of the step's stamped durations over the stretch."""
    found = _of(run)
    if found is None:
        return None
    values = [st[stat] for st in found[0] if stat in st]
    return float(np.mean(values)) if values else None


def wire_ms(run):
    """Mean idle of the first chip between two consecutive program runs LESS
    the mean ``turnaround_ms`` of the same steps: the token row's way back
    plus the launch's way out, which no host code shortens."""
    found = _of(run)
    if found is None:
        return None
    steps, gaps = found
    return float(np.mean(gaps) - np.mean([st[TURNAROUND] for st in steps]))
