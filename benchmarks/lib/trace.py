"""From the profiler's ``.xplane.pb`` to numbers: the one reducer, kept with
the benchmark so that every PR computes a share the same way.

What a TPU trace holds (looked at by hand, PR 23): a plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
program run), ``XLA Ops`` (every HLO op, NESTED: a ``while`` event spans the
ops of its body) and ``Async XLA Ops`` (copies and collectives in flight,
start to done); and a plane ``/host:CPU`` with a line per thread, where
``jax.profiler.TraceAnnotation`` spans sit on the line ``python``.  An op
event's name is its HLO text, ``%paged_attention.7 = bf16[...] custom-call(``;
a Pallas kernel is named by its ``name=``.

* busy time is the UNION of the ``XLA Ops`` intervals (never a sum: nested);
* an op's time is its SELF time, its duration less its children's, so a
  ``while`` counts nothing twice;
* collective time is the union of the collectives' intervals on both lines,
  and its exposed part is what no other op's self interval covers.
"""

import collections
import glob
import os
import re

import numpy as np

OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|ragged-all-to-all)")
_NAME = re.compile(r"^%([\w\-.]+) = ")


def op_name(hlo_text):
    """``%paged_attention.7 = ...`` -> ``paged_attention``."""
    m = _NAME.match(hlo_text)
    name = m.group(1) if m else hlo_text.split("(")[0].strip()[:48]
    return re.sub(r"(\.\d+)+$", "", name)


def newest_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def _events(line):
    """[(name, start_s, duration_s)] of a line."""
    return [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events]


def union(intervals):
    """Merged, sorted ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged):
    return float(sum(e - s for s, e in merged))


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(merged, cover):
    """The parts of ``merged`` that ``cover`` (merged too) leaves bare."""
    out, j = [], 0
    for s, e in merged:
        cur = s
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events):
    """[(name, start, end, self_seconds)]: each event's duration less that
    of the events nested directly inside it (one line, properly nested)."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    out, stack = [], []        # stack of [index_in_out, end]
    for i in order:
        name, start, dur = events[i]
        end = start + dur
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack and end <= stack[-1][1] + 1e-12:
            out[stack[-1][0]][3] -= dur
        out.append([name, start, end, dur])
        stack.append([len(out) - 1, end])
    return out


class DeviceTrace:
    """One chip's part of a trace, cut to the window between its first and
    last op."""

    def __init__(self, plane_name, ops, asyncs, program_runs=None):
        self.name = plane_name
        self.program_runs = program_runs    # events of ``XLA Modules``; None: no such line
        self.ops = self_times([(op_name(n), s, d) for n, s, d in ops])
        self.asyncs = [(op_name(n), s, s + d) for n, s, d in asyncs]
        self.start = min(o[1] for o in self.ops)
        self.end = max(o[2] for o in self.ops)
        self.busy = union([(o[1], o[2]) for o in self.ops])

    @property
    def window_s(self):
        return self.end - self.start

    @property
    def busy_s(self):
        return length(self.busy)

    def gaps(self):
        return subtract([[self.start, self.end]], self.busy)

    def op_seconds(self):
        tot = collections.Counter()
        for name, _, _, self_s in self.ops:
            tot[name] += self_s
        return tot

    def op_counts(self):
        return collections.Counter(o[0] for o in self.ops)

    def collective_intervals(self):
        sync = [(o[1], o[2]) for o in self.ops if COLLECTIVE.match(o[0])]
        inflight = [(s, e) for n, s, e in self.asyncs if COLLECTIVE.match(n)]
        return union(clip(sync + inflight, self.start, self.end))

    def compute_intervals(self):
        """Where an op that is no collective and no container ran: leaves of
        the nesting, by self time > 0 and no children."""
        leaves = [(o[1], o[2]) for o in self.ops
                  if not COLLECTIVE.match(o[0]) and abs(o[3] - (o[2] - o[1])) < 1e-12]
        return union(leaves)


class Trace:
    def __init__(self, devices, host_spans, host_events):
        self.devices = devices            # [DeviceTrace]
        self.host_spans = host_spans      # [(name, start, end)] bench.* annotations
        self.host_events = host_events    # [(name, start, end)] other python-line events

    @classmethod
    def from_file(cls, path, span_prefix="bench."):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        devices, spans, others = [], [], []
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                lines = {l.name: l for l in plane.lines}
                if OPS_LINE not in lines:
                    continue
                ops = _events(lines[OPS_LINE])
                if not ops:
                    continue
                asyncs = _events(lines[ASYNC_LINE]) if ASYNC_LINE in lines else []
                runs = sum(1 for _ in lines[MODULES_LINE].events) if MODULES_LINE in lines else None
                devices.append(DeviceTrace(plane.name, ops, asyncs, runs))
            elif plane.name == "/host:CPU":
                # the thread that carries the benchmark's spans is the one
                # that drives the program: its other events say what the
                # host did inside a span
                for line in plane.lines:
                    events = _events(line)
                    if not any(n.startswith(span_prefix) for n, _, _ in events):
                        continue
                    for name, s, d in events:
                        (spans if name.startswith(span_prefix) else others).append(
                            (name, s, s + d))
        devices.sort(key=lambda d: d.name)
        return cls(devices, sorted(spans, key=lambda x: x[1]),
                   sorted(others, key=lambda x: x[1]))

    # ---- averaged over the chips used ---------------------------------- #
    def busy_s(self):
        return float(np.mean([d.busy_s for d in self.devices]))

    def window_s(self):
        return float(np.mean([d.window_s for d in self.devices]))

    def idle_share(self):
        return 1.0 - self.busy_s() / self.window_s()

    def program_runs(self):
        """Program runs the first chip's part of the trace holds (one event
        of ``XLA Modules`` each), or None where it has no such line."""
        return self.devices[0].program_runs

    def op_seconds(self):
        """name -> seconds of self time, averaged over the chips."""
        tot = collections.Counter()
        for d in self.devices:
            for k, v in d.op_seconds().items():
                tot[k] += v / len(self.devices)
        return tot

    def op_counts(self):
        """name -> events on the busiest-in-that-op chip (calls per chip)."""
        tot = collections.Counter()
        for d in self.devices:
            for k, v in d.op_counts().items():
                tot[k] = max(tot[k], v)
        return tot

    def collective_shares(self):
        """(share of the window with a collective in flight, share with one
        in flight and no other op running), averaged over the chips; None
        where the trace holds no collective."""
        share, exposed = [], []
        for d in self.devices:
            coll = d.collective_intervals()
            if not coll:
                return None
            bare = subtract(coll, d.compute_intervals())
            share.append(length(coll) / d.window_s)
            exposed.append(length(bare) / d.window_s)
        return float(np.mean(share)), float(np.mean(exposed))

    def host_name_at(self, start, end):
        """What the host was doing in ``[start, end]``: the innermost
        ``bench.*`` span over its middle, and the python-line event that
        overlaps it most."""
        mid = 0.5 * (start + end)
        over = [s for s in self.host_spans if s[1] <= mid <= s[2]]
        span = max(over, key=lambda s: s[1])[0] if over else "outside_bench_spans"
        best, best_len = None, 0.0
        for name, s, e in self.host_events:
            if s >= end:
                break
            ov = min(e, end) - max(s, start)
            if ov > best_len:
                best, best_len = name, ov
        return f"{span}:{best}" if best else span

    def breakdown(self, top=10):
        """The device ops that took most self time, and the idle time by what
        the host was doing, both on the first chip, in seconds."""
        d = self.devices[0]
        ops = [[k, v] for k, v in d.op_seconds().most_common(top)]
        idle = collections.Counter()
        for s, e in d.gaps():
            idle[self.host_name_at(s, e)] += e - s
        return {"device_ops": ops,
                "idle_gaps": [[k, v] for k, v in idle.most_common(top)]}
