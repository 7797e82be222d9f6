"""Readers of what a serve PROGRAM says of itself: the engine numbers the
programs it launches, and when a program's row lands the ``fetch`` span that
landed it carries the program's own durations on the engine's one clock
(``serving/engine.py:PROGRAM_STATS``: ``ahead``, ``device_ms``, ``host_ms``,
beside the ``program``, ``chunk_tokens`` and ``batch`` it opened with).  One such
event a landed program, on whichever thread fetched.  A step is not the unit:
since PR 48 it launches program k and lands k-1, or both, or none.

The four metrics read those events alone.  The stretch is the landed programs
launched after the first traced step's: that step's ``outside_ms`` holds the
profiler's start, and so does the wait for the row it lands.

The notes JOIN the programs to the first chip's ``XLA Modules`` runs BY
NUMBER.  A fetch span ends when its program's row is on the host, a wire after
the run ends, and the profiler lays the host's line 1.5-2.4 ms off the
device's: both are small beside a run, so the run whose end lies nearest a
fetch's end is that program's, and every landed program votes for where number
k lies on the line; from that anchor, number k+1 is the next run (of the
module that ran most on the line: the step program's).  The join HOLDS when
every residual (fetch end - run end) lies within half the shortest joined run
of their median: one run too many or too few moves the residual of every
program behind it by a whole run.  One exception, seen on the chip in 6 of 30
traced stretches (PERF.md section 6, PR 53;
``benchmarks/testdata/programs_chip_stretches.json`` keeps those whose traces
were read again): the host, paused for 19-110 ms, fetched rows LATE.  The
engine is one program ahead at most, so a pause holds back one row, or, where
it outlasts the next program's run, two: the second late by that run less.
That is one or two residuals ABOVE the band between two neighbours inside it
(a pair's second smaller than its first by its own run, to within the band),
and the note counts them (``late_fetches``); a residual below the band, a pair
late by the same, three in a row, or none inside the band behind them is a run
too few or too many.  A late row's ``device_ms`` holds the host's pause and
the ``device_ms`` of the program behind it, whose period starts at the late
row, is short by as much (or by less, where the chip ran dry meanwhile): the
note gives ``program_ms`` beside ``run_ms_mean`` over the joined programs
and, ``_less_late``, both without the late rows' programs and the one behind
them, so that a pause is not read as device time.  A
program in flight at either end of the trace has a landing without a run or a
run without a landing (the run the trace's stop cuts short), and is left out
like any other the line does not hold.  On the joined programs durations are
subtracted pair by pair; no event of one line is ever placed against an event
of the other.

A program without the stats (a parent commit) gives every reader here nothing
to read: it returns None and the metric is left out of the line.
"""

import bisect
import collections

import numpy as np

from benchmarks.lib.trace import MODULES_LINE, newest_xplane
from benchmarks.readers.program_spans import TRACE_DIR

FETCH_SPANS = ("serve.prefill.fetch", "serve.decode.fetch")    # on any thread
STATS_SPAN = "serve.stats"           # one a step, on the thread that steps
LANDED = "device_ms"                 # a fetch span that carries it landed a row
HOST_PARTS = ("commit_ms", "outside_ms", "prepare_ms")
END = "_end_s"                       # where ``read`` puts a fetch span's end
TOP_OPS = 10


def read(path):
    """{"landings": [the stats of each landing event, by program number, its
    span's end under ``END``], "launched": {program: the stats of the
    ``serve.stats`` of the step that launched it}, "first": the program the
    first traced step launched (None: the program numbers none), "runs":
    [(start s, end s) of each run of the first chip's most run module] or None
    where the trace has no ``XLA Modules`` line} of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    landings, steps, runs = [], [], None
    chips = sorted((p for p in data.planes if p.name.startswith("/device:TPU:")),
                   key=lambda p: p.name)
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in FETCH_SPANS:
                        stats = dict(e.stats)
                        if LANDED in stats and "program" in stats:
                            stats[END] = (e.start_ns + e.duration_ns) * 1e-9
                            landings.append(stats)
                    elif e.name == STATS_SPAN:
                        steps.append((e.start_ns, dict(e.stats)))
    for line in (chips[0].lines if chips else []):
        if line.name == MODULES_LINE:
            events = [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                      for e in line.events]
            step = collections.Counter(n for n, _, _ in events).most_common(1)
            runs = sorted((s, e) for n, s, e in events if n == step[0][0])
    launched = [st for _, st in sorted(steps, key=lambda x: x[0]) if "program" in st]
    return {"landings": sorted(landings, key=lambda st: st["program"]),
            "launched": {st["program"]: st for st in launched},
            "first": launched[0]["program"] if launched else None, "runs": runs}


def stretch(found):
    """The landed programs the metrics read: those launched after the first
    traced step's."""
    if found["first"] is None:
        return []
    return [st for st in found["landings"] if st["program"] > found["first"]]


def join(landed, runs):
    """({program: index of its run in ``runs``}, the note) for the landed
    programs of the stretch: an anchor from time, then by number."""
    if not runs:
        return {}, {"programs": 0, "why": f"no {MODULES_LINE} line"}
    ends = [e for _, e in runs]

    def nearest(t):
        i = bisect.bisect_left(ends, t)
        return min(range(max(i - 1, 0), min(i + 1, len(ends))), key=lambda j: abs(t - ends[j]))
    votes = collections.Counter(nearest(st[END]) - st["program"] for st in landed)
    offset = votes.most_common(1)[0][0]
    at = {st["program"]: st["program"] + offset for st in landed
          if 0 <= st["program"] + offset < len(runs)}
    note = {"landed": len(landed), "runs": len(runs)}
    if not at:
        return {}, dict(note, programs=0, why="no run of a landed program on the line")
    numbers = sorted(at)
    residual = np.asarray([1e3 * (st[END] - ends[at[st["program"]]])
                           for st in landed if st["program"] in at])
    off = residual - np.median(residual)
    took = [1e3 * (runs[at[k]][1] - runs[at[k]][0]) for k in numbers]
    half = 0.5 * min(took)
    inside = np.abs(off) <= half
    # rows the host fetched late: one above the band, or two, the second
    # late by its own run less, between two neighbours inside it
    late = []
    for j in np.flatnonzero(off > half):
        for last in (j, j + 1):
            if (0 < j and last < len(off) - 1 and inside[j - 1] and inside[last + 1]
                    and numbers[last + 1] - numbers[j - 1] == last - j + 2
                    and (last == j or abs(off[j] - off[last] - took[last]) <= half < off[last])):
                late += range(j, last + 1)
    late = sorted(set(late))
    note.update(residual_ms_p50=round(float(np.median(residual)), 4),
                residual_ms_max_off_p50=round(float(
                    np.abs(off[inside] if inside.any() else off).max()), 4))
    if late:
        note.update(late_fetches=len(late), late_fetch_ms_max=round(float(off[late].max()), 4))
    if inside.sum() + len(late) < len(off):
        worst = float(off[~inside][np.abs(off[~inside]).argmax()])
        return {}, dict(note, programs=0, why=(
            f"a residual lies {worst:+.3f} ms off their median, past half the shortest joined "
            f"run ({half:.3f} ms), and is no row or pair of rows fetched late: the line holds a "
            f"run too many or too few"))
    device = {st["program"]: st[LANDED] for st in landed}
    note.update(programs=len(at), run_ms_mean=round(float(np.mean(took)), 4),
                program_ms=round(float(np.mean([device[k] for k in numbers])), 4))
    if late:
        on_time = [j for j in range(len(numbers)) if not {j, j - 1} & set(late)]
        note.update(
            program_ms_less_late=round(float(np.mean([device[numbers[j]] for j in on_time])), 4),
            run_ms_mean_less_late=round(float(np.mean([took[j] for j in on_time])), 4))
    return at, note


def _spread(values, *qs):
    return [round(float(np.mean(values)), 4)] + [
        round(float(np.percentile(values, q)), 4) for q in qs]


def _ops_a_program(ops, intervals):
    """[[op name, ms of self time a program]] of the ``TOP_OPS`` ops with most
    self time inside the runs ``intervals``; ``ops`` a ``DeviceTrace``'s."""
    starts = [o[1] for o in ops]
    took = collections.Counter()
    for s, e in intervals:
        for name, _, _, self_s in ops[bisect.bisect_left(starts, s):bisect.bisect_left(starts, e)]:
            took[name] += self_s
    return [[name, round(1e3 * v / len(intervals), 4)] for name, v in took.most_common(TOP_OPS)]


def notes_of(found, landed, ops):
    """What the notes say of the stretch ``landed``: the join, and on the
    joined programs the clock check, the two kinds and the gaps' parts."""
    runs = found["runs"]
    at, out = join(landed, runs)
    ms = lambda i: 1e3 * (runs[i][1] - runs[i][0])
    out = {"program_join": out}
    check, gaps = {}, {}
    for key, ahead in (("ahead", 1), ("not_ahead", 0)):
        of = [(st, at[st["program"]]) for st in landed
              if st["program"] in at and st["ahead"] == ahead]
        if not of:
            continue
        diff = [st[LANDED] - ms(i) for st, i in of]
        check[key] = {"programs": len(of), "p50": round(float(np.median(diff)), 4),
                      "max": round(float(max(diff, key=abs)), 4)}
        # the chip's wait before the program's run beside the host's parts for
        # it (hidden where the program was launched ahead) and what they leave
        beside = [(1e3 * (runs[i][0] - runs[i - 1][1]), found["launched"][st["program"]])
                  for st, i in of if i > 0 and "host_ms" in st]
        if beside:
            wire = [gap - (0.0 if ahead else sum(step[p] for p in HOST_PARTS))
                    for gap, step in beside]
            gaps[key] = dict(
                {"programs": len(beside), "gap_ms": _spread([g for g, _ in beside], 99),
                 "wire_ms": _spread(wire, 99)},
                **{p: _spread([step[p] for _, step in beside], 99) for p in HOST_PARTS})
    if check:
        out["program_clock_check_ms"] = check
    if gaps:
        out["idle_gap_parts_ms"] = gaps
    out["programs_by_kind"] = kinds = {}
    for kind, of in (("decode", [st for st in landed if not st["chunk_tokens"]]),
                     ("chunk", [st for st in landed if st["chunk_tokens"]])):
        if not of:
            continue
        kinds[kind] = {"programs": len(of),
                       "device_ms_mean_p50_max": _spread([st[LANDED] for st in of], 50, 100)}
        on_line = [at[st["program"]] for st in of if st["program"] in at]
        if on_line:
            kinds[kind].update(
                joined=len(on_line),
                run_ms_mean_p50_max=_spread([ms(i) for i in on_line], 50, 100),
                top_ops_ms_a_program=_ops_a_program(ops, [runs[i] for i in on_line]))
    return out


def _of(run):
    """The stretch's landed programs, read once and kept on the run, with its
    notes; None where the trace holds none."""
    if run["trace"] is None:
        return None
    if "_programs" not in run:
        path = newest_xplane(TRACE_DIR)
        run["_programs"] = _with_notes(run, read(path)) if path else None
    return run["_programs"]


def _with_notes(run, found):
    landed = stretch(found)
    if not landed:
        return None
    ops = sorted(run["trace"].devices[0].ops, key=lambda o: o[1])
    run["notes"].update(notes_of(found, landed, ops))
    return landed


def program_ms(run):
    """Mean ``device_ms`` over the stretch's landed programs."""
    landed = _of(run)
    return None if landed is None else float(np.mean([st[LANDED] for st in landed]))


def chunk_program_time_pct(run):
    """``device_ms`` of the programs with a prompt chunk over that of all."""
    landed = _of(run)
    if landed is None:
        return None
    return 100.0 * sum(st[LANDED] for st in landed if st["chunk_tokens"]) / sum(
        st[LANDED] for st in landed)


def dispatched_ahead_pct(run):
    """Landed programs launched before the row of the program before them."""
    landed = _of(run)
    return None if landed is None else 100.0 * float(np.mean([st["ahead"] for st in landed]))


def host_occupancy_pct(run):
    """``sum(host_ms) / sum(device_ms)`` over the programs that carry both:
    at 100 the host sets the pace.  0.0 where none carries a host part."""
    landed = _of(run)
    if landed is None:
        return None
    both = [st for st in landed if "host_ms" in st]
    return 100.0 * sum(st["host_ms"] for st in both) / sum(
        st[LANDED] for st in both) if both else 0.0
