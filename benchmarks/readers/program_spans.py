"""Readers of the names the PROGRAM gives its own time: the ``serve.*`` phase
spans it writes into the profiler's trace (``telemetry/tracing.py``; the list is
``serving/engine.py:SERVE_STEP_SPANS``), the stats of ``serve.first_token``,
and the ``jax.named_scope`` path of every device op.  A program without them
(a parent commit) gives every reader here nothing to read: it returns None
and the metric is left out of the line.

What a TPU trace holds of them (looked at by hand on the v5e, PR 24):

* a ``TraceAnnotation("serve.admit", admitted=3)`` is an event named
  ``serve.admit`` on the ``/host:CPU`` line of the thread that opened it, its
  keyword arguments the event's stats; ``Trace.host_events`` holds name and
  interval of those on the benchmark's own thread, and drops the stats;
* an ``XLA Ops`` event's own stats are its device offset and duration; the
  HLO ``op_name`` is the stat ``tf_op`` (``SCOPE_STAT``) of the event's
  METADATA, with a colon at its end:
  ``jit(fused)/transpose(jvp(blocks))/while/body/closed_call/attn/dot_general:``.
  Its components are the scopes the program opened, each perhaps wrapped by
  the transformation that made the op (``jvp``, ``transpose``, ``checkpoint``,
  ``rematted_computation``).  A fusion carries the stack of its root; a
  ``while``, a copy the compiler put in, a parameter's ``reshape`` may carry
  none.  ``jax.profiler.ProfileData`` does not give metadata stats, so
  ``device_ops_with_scope`` reads them from the file's bytes.

Stats are not in ``Trace``, so the two readers that need them open the
newest ``.xplane.pb`` under ``<ROOT>/.bench_trace`` themselves, once a run.
"""

import collections
import os

import numpy as np

from benchmarks.lib.cells import ROOT
from benchmarks.lib.trace import OPS_LINE, length, newest_xplane, self_times, union

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
STEP_SPAN = "bench.engine_step"      # the benchmark's span round engine.step()
FIRST_TOKEN = "serve.first_token"    # zero length, one a request
SCOPE_STAT = "tf_op"                 # the XLA Ops stat that holds the name stack
# the scopes the program opens (models/gpt.py, runtime/engine.py), most
# specific first: the notes' table gives an op to the first it is under
PROGRAM_SCOPES = ("optimizer", "cross_entropy", "head", "attn", "mlp", "embed",
                  "blocks")


# ---- host spans -------------------------------------------------------------- #
def span_ms_per_step(run, spans):
    """Summed duration of the named program spans over the number of
    ``engine.step()`` calls the benchmark made in the traced stretch."""
    t = run["trace"]
    if t is None:
        return None
    steps = sum(1 for n, _, _ in t.host_spans if n == STEP_SPAN)
    found = [e - s for n, s, e in t.host_events if n in spans]
    return 1e3 * sum(found) / steps if steps and found else None


# ---- what needs the events' stats -------------------------------------------- #
def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: a varint as an int, a
    length-delimited field as a ``memoryview`` of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:                           # fixed64, fixed32
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def device_ops_with_scope(path):
    """{plane name: [(name stack or None, start s, duration s)]} of the
    ``XLA Ops`` line of every ``/device:TPU:<n>`` plane of an ``.xplane.pb``.

    Read from the file's bytes, with the field numbers of ``xplane.proto``
    (XSpace.planes 1; XPlane.name 2, .lines 3, .event_metadata 4,
    .stat_metadata 5; XLine.name 2, .events 4; XEvent.metadata_id 1,
    .offset_ps 2, .duration_ps 3; XEventMetadata.stats 5; XStat.metadata_id
    1, .str_value 5, .ref_value 7; XStatMetadata.name 2; a map entry is key 1,
    value 2): ``SCOPE_STAT`` is a stat of the op's METADATA, and
    ``jax.profiler.ProfileData`` (JAX 0.9.0) gives an event's own stats only."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, lines, event_md, stat_names = "", [], {}, {}
        for number, value in _fields(plane):
            if number == 2:
                name = _text(value)
            elif number == 3:
                lines.append(value)
            elif number in (4, 5):
                entry = dict(_fields(value))
                (event_md if number == 4 else stat_names)[entry[1]] = entry[2]
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {k: _text(dict(_fields(v)).get(2, b"")) for k, v in stat_names.items()}

        def scope_of(metadata):
            for number, stat in _fields(metadata):
                if number == 5:
                    st = dict(_fields(stat))
                    if stat_names.get(st.get(1)) == SCOPE_STAT:
                        return _text(st[5]) if 5 in st else stat_names.get(st.get(7))
            return None

        for line in lines:
            parts = [(n, v) for n, v in _fields(line) if n in (2, 4)]
            if _text(dict(parts).get(2, b"")) != OPS_LINE:
                continue
            scopes = {k: scope_of(v) for k, v in event_md.items()}
            events = [dict(_fields(v)) for n, v in parts if n == 4]
            out[name] = [(scopes.get(e.get(1)), e.get(2, 0) * 1e-12, e.get(3, 0) * 1e-12)
                         for e in events]
    return out


def _components(path):
    """The scopes of a name stack (or of several, ``;`` between them):
    ``jit(f)/transpose(jvp(attn))/mul:`` -> {"f", "attn", "mul"}."""
    return frozenset(c[c.rfind("(") + 1:].split(")")[0]
                     for stack in (path or "").rstrip(":").split(";")
                     for c in stack.split("/") if c)


def read_stats(path):
    """{"first_tokens": [the stats of each FIRST_TOKEN event], "chips":
    [(busy seconds, [(scopes of the op, self seconds)])], a chip a device
    plane that ran an op} of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    first = [dict(e.stats) for line in (host.lines if host else [])
             for e in line.events if e.name == FIRST_TOKEN]
    chips = []
    for _, ops in sorted(device_ops_with_scope(path).items()):
        if not ops:
            continue
        timed = self_times(ops)
        scopes = {p: _components(p) for p in {p for p, _, _ in ops}}
        chips.append((length(union([(s, e) for _, s, e, _ in timed])),
                      [(scopes[p], self_s) for p, _, _, self_s in timed]))
    return {"first_tokens": first, "chips": chips}


def _stats_of(run):
    """``read_stats`` of the run's trace, read once and kept on the run."""
    if run["trace"] is None:
        return None
    if "_program_stats" not in run:
        path = newest_xplane(TRACE_DIR)
        run["_program_stats"] = read_stats(path) if path else None
    return run["_program_stats"]


def first_token_mean_ms(run, stat):
    """Mean of one stat of the ``serve.first_token`` events of the traced
    stretch: the program's own split of its time to first token."""
    st = _stats_of(run)
    values = [f[stat] for f in (st or {}).get("first_tokens", []) if stat in f]
    if not values:
        return None
    run["notes"]["first_token_events"] = len(values)
    return float(np.mean(values))


def scope_share_pct(run, scopes, invert=False):
    """Self time of the device ops whose name stack holds one of ``scopes``
    as a whole component (``invert``: none of them), over the time the chip
    was busy, averaged over the chips.  None where no op is such a one: the
    program opens no such scope."""
    st = _stats_of(run)
    if not st or not any(c for _, ops in st["chips"] for c, _ in ops):
        return None                     # no op says where it came from
    wanted = frozenset(scopes)
    found = [[s for c, s in ops if bool(c & wanted) != invert]
             for _, ops in st["chips"]]
    if not any(found):
        return None
    if "scope_share_pct" not in run["notes"]:
        table = collections.Counter()
        for busy, ops in st["chips"]:
            for c, s in ops:
                owner = next((p for p in PROGRAM_SCOPES if p in c), "none")
                table[owner] += 100.0 * s / busy / len(st["chips"])
        run["notes"]["scope_share_pct"] = {k: round(v, 3) for k, v in table.most_common()}
    return 100.0 * float(np.mean([sum(f) / busy for f, (busy, _) in zip(found, st["chips"])]))
