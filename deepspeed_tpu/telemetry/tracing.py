"""Span-based tracing — the "where inside a step" layer.

The :class:`~deepspeed_tpu.telemetry.hub.TelemetryHub` answers *how fast*
a step was; spans answer *where inside the step the time went*.  Engines
and the comm facade open spans around phases (``fwd``/``bwd``/``step``,
the ``serve.*`` phases of a serving step), collectives
(``comm.all_reduce``), pipeline schedule slots, inference prefill/decode,
and checkpoint save/load.

One clock, one switch.  Every span is a ``jax.profiler.TraceAnnotation``:
whenever a profiler session is active (a ``ProfilerWindow`` capture, the
benchmark's ``--trace 1``) it lies in the same ``.xplane.pb`` as the
device ops, on the device's clock, with its scalar attributes as the
event's stats; without a session it is an inactive TraceMe (well under a
microsecond).  Nothing configures that.  Where a :class:`Tracer` is
configured (``telemetry.tracing`` / the watchdog) the same span is ALSO
recorded into its ring, with parent ids, for the operator's Chrome export,
the watchdog's heartbeat and the flight recorder.

Design constraints (shared with the hub):

* **Zero-sync.**  Opening/closing a span is an annotation, and with a
  ring two ``time.monotonic_ns`` reads and a list append.  Ring attribute
  values are stored by reference — a still-in-flight ``jax.Array`` attr is
  never forced until export (and the flight recorder deliberately never
  forces it at all: forcing blocks during the very hangs it exists to
  diagnose).  Only ``bool``/``int``/``float``/``str`` attributes go to the
  profiler; anything else stays in the ring alone.
* **Spans never touch the program.**  A span is host-side only: it enters
  no ``jax.named_scope``, so a step traced under ``fwd`` or
  ``train_batch`` lowers to the same HLO, metadata included, with tracing
  on or off.  Op ownership comes from the scopes the model and the engine
  open themselves (``attn``, ``mlp``, ``optimizer`` ...).
* **Monotonic clock only for ring durations.**  Wall-clock time appears in
  exactly one place — the per-tracer clock anchor used by
  ``tools/trace_merge.py`` to align rank timelines — and is statically
  policed by ``tools/check_monotonic.py``.
* **Bounded memory.**  Completed spans live in a ring (``capacity``);
  overflow increments ``dropped`` instead of growing without bound.

Ring export is Chrome-trace / Perfetto JSON (``traceEvents`` with complete
``X`` duration events), one file per rank; ``tools/trace_merge.py`` folds
N rank files onto one clock-aligned timeline.
"""

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

from deepspeed_tpu.utils.logging import logger

#: the only clock spans are timed with (see tools/check_monotonic.py)
_mono_ns = time.monotonic_ns

#: attribute types that go to the profiler as an event's stats; anything
#: else (a ``jax.Array`` above all) is never looked at, let alone forced
_SCALARS = (bool, int, float, str)


class Span:
    """One program span: a ``TraceAnnotation`` always, and a record in
    ``tracer``'s ring where there is one.  ``set()`` adds attributes known
    only once the work is done (a count of what was admitted)."""

    __slots__ = ("_ann", "_tracer", "_name", "_args", "_rec")

    def __init__(self, tracer: Optional["Tracer"], name: str,
                 args: Dict[str, Any]):
        self._ann = TraceAnnotation(name, **{
            k: v for k, v in args.items() if isinstance(v, _SCALARS)})
        self._tracer = tracer if tracer is not None and not tracer.closed else None
        self._name, self._args, self._rec = name, args, None

    def __enter__(self):
        self._ann.__enter__()
        if self._tracer is not None:
            self._rec = self._tracer._open_span(self._name, self._args)
        return self

    def __exit__(self, *exc):
        if self._rec is not None:
            self._tracer._close_span(self._rec)
        self._ann.__exit__(*exc)
        return False

    def set(self, **args):
        self._ann.set_metadata(**args)
        if self._rec is not None:
            self._rec["args"] = dict(self._rec["args"] or {}, **args)


class Tracer:
    """Nested context-manager span recorder with Chrome-trace export.

    ``clock`` is injectable for tests and must be a nanosecond monotonic
    clock.  ``heartbeat`` (optional) is invoked on every span open — the
    hang watchdog registers its ``pet`` here so each phase/collective
    span doubles as a liveness beat.
    """

    def __init__(self, rank: int = 0, capacity: int = 65536,
                 clock: Optional[Callable[[], int]] = None,
                 heartbeat: Optional[Callable[[], None]] = None):
        self.rank = int(rank)
        self.capacity = max(1, int(capacity))
        self._clock = clock or _mono_ns
        self.heartbeat = heartbeat
        self.completed = deque(maxlen=self.capacity)
        self.dropped = 0
        self._ids = itertools.count(1)
        self._open: Dict[int, List[Dict[str, Any]]] = {}  # tid -> span stack
        self.epoch_ns = self._clock()
        # The single sanctioned wall-clock read: trace_merge aligns rank
        # timelines by mapping each tracer's monotonic epoch to wall time.
        self.epoch_wall_ns = time.time_ns()  # wall-clock anchor: ok
        self.closed = False

    # -- recording (zero-sync hot path) -------------------------------- #
    def _stack(self) -> List[Dict[str, Any]]:
        tid = threading.get_ident()
        stack = self._open.get(tid)
        if stack is None:
            stack = self._open[tid] = []
        return stack

    def _append(self, rec: Dict[str, Any]):
        if len(self.completed) == self.capacity:
            self.dropped += 1
        self.completed.append(rec)

    def span(self, name: str, **args) -> Span:
        """Open a nested span (a context manager); attributes are stored
        by reference (never forced here)."""
        return Span(self, name, args)

    def _open_span(self, name: str, args: Dict[str, Any]) -> Dict[str, Any]:
        if self.heartbeat is not None:
            self.heartbeat()
        stack = self._stack()
        rec = {
            "sid": next(self._ids),
            "name": name,
            "t0": self._clock(),
            "t1": None,
            "tid": threading.get_ident(),
            "depth": len(stack),
            "parent": stack[-1]["sid"] if stack else 0,
            "args": args or None,
        }
        stack.append(rec)
        return rec

    def _close_span(self, rec: Dict[str, Any]):
        rec["t1"] = self._clock()
        stack = self._stack()
        if stack and stack[-1] is rec:
            stack.pop()
        else:  # defensive: unbalanced exit from another thread/path
            try:
                stack.remove(rec)
            except ValueError:
                pass
        self._append(rec)

    def add_span(self, name: str, t0_ns: int, t1_ns: int,
                 track: Optional[str] = None, **args):
        """Record a retrospective span with explicit timestamps (used for
        synthetic tracks, e.g. the pipeline schedule-slot timeline).
        ``track`` names a virtual thread lane in the exported trace."""
        if self.closed:
            return
        self._append({
            "sid": next(self._ids), "name": name, "t0": int(t0_ns),
            "t1": int(t1_ns), "tid": track or threading.get_ident(),
            "depth": 0, "parent": 0, "args": args or None,
        })

    # -- introspection (flight recorder / tests) ------------------------ #
    def open_spans(self) -> List[Dict[str, Any]]:
        """Snapshot of every currently-open span across all threads (the
        flight recorder dumps these on a stall).  Values are copied
        shallowly; attrs stay unforced."""
        out = []
        for tid, stack in list(self._open.items()):
            for rec in list(stack):
                out.append(dict(rec))
        return out

    def snapshot(self, limit: Optional[int] = None) -> List[Dict[str, Any]]:
        """Most-recent completed spans, newest last."""
        recs = list(self.completed)
        return recs if limit is None else recs[-int(limit):]

    # -- export ---------------------------------------------------------- #
    def _args_host(self, args):
        if not args:
            return None
        from deepspeed_tpu.telemetry.hub import _to_host
        try:
            return {k: _to_host(v) for k, v in args.items()}
        except Exception:
            return {k: str(type(v).__name__) for k, v in args.items()}

    def _tid_index(self, tids) -> Dict[Any, int]:
        """Stable small integers per lane: real thread ids first (main
        thread = 0), then named synthetic tracks."""
        ints = sorted(t for t in tids if isinstance(t, int))
        names = sorted(str(t) for t in tids if not isinstance(t, int))
        main = threading.main_thread().ident
        if main in ints:
            ints.remove(main)
            ints.insert(0, main)
        index = {t: i for i, t in enumerate(ints)}
        index.update({n: len(ints) + i for i, n in enumerate(names)})
        return index

    def to_chrome_events(self) -> List[Dict[str, Any]]:
        """Completed spans as Chrome-trace ``traceEvents`` (ts/dur in µs,
        relative to this tracer's monotonic epoch)."""
        recs = self.snapshot()
        tid_of = self._tid_index({r["tid"] for r in recs})
        events: List[Dict[str, Any]] = [
            {"ph": "M", "name": "process_name", "pid": self.rank, "tid": 0,
             "ts": 0, "args": {"name": f"rank {self.rank}"}},
            {"ph": "M", "name": "process_sort_index", "pid": self.rank,
             "tid": 0, "ts": 0, "args": {"sort_index": self.rank}},
        ]
        for tid, i in tid_of.items():
            name = tid if isinstance(tid, str) else (
                "main" if tid == threading.main_thread().ident
                else f"thread-{i}")
            events.append({"ph": "M", "name": "thread_name", "pid": self.rank,
                           "tid": i, "ts": 0, "args": {"name": name}})
        for r in recs:
            ev = {
                "name": r["name"],
                "cat": str(r["name"]).split(".", 1)[0],
                "ph": "X",
                "pid": self.rank,
                "tid": tid_of[r["tid"]],
                "ts": (r["t0"] - self.epoch_ns) / 1e3,
                "dur": max((r["t1"] - r["t0"]) / 1e3, 0.0),
            }
            args = self._args_host(r.get("args"))
            if args:
                ev["args"] = args
            events.append(ev)
        return events

    def export_chrome_trace(self, path: str) -> str:
        """Write this rank's timeline as a Perfetto-loadable JSON object.
        ``metadata.clock_sync`` carries the monotonic→wall anchor that
        ``tools/trace_merge.py`` uses for cross-rank alignment."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        doc = {
            "traceEvents": self.to_chrome_events(),
            "displayTimeUnit": "ms",
            "metadata": {
                "rank": self.rank,
                "dropped_spans": self.dropped,
                "clock_sync": {"mono_ns": self.epoch_ns,
                               "wall_ns": self.epoch_wall_ns},
            },
        }
        with open(path, "w") as f:
            json.dump(doc, f, default=str)
        logger.info(f"tracer: wrote {len(doc['traceEvents'])} events -> {path}")
        return path

    def close(self):
        self.closed = True


# --------------------------------------------------------------------------- #
# Global tracer registry — the instrumentation points (comm facade,
# checkpointing, engines built without an explicit tracer) look here.
# --------------------------------------------------------------------------- #
_GLOBAL_TRACER: Optional[Tracer] = None


def set_global_tracer(tracer: Optional[Tracer]):
    global _GLOBAL_TRACER
    _GLOBAL_TRACER = tracer


def get_global_tracer() -> Optional[Tracer]:
    return _GLOBAL_TRACER


def maybe_span(name: str, tracer: Optional[Tracer] = None, **args) -> Span:
    """The one entry instrumentation points use: a span on the profiler's
    clock, recorded too in ``tracer``'s ring (or the global tracer's)
    where one is configured."""
    return Span(tracer if tracer is not None else _GLOBAL_TRACER, name, args)
