#!/usr/bin/env python
"""Merge per-rank Chrome-trace JSON files onto one shared timeline.

Usage:
    python tools/trace_merge.py trace_rank0.json trace_rank1.json ... \
        [-o merged_trace.json] [--flops telemetry.jsonl]

Each input is a ``Tracer.export_chrome_trace`` document: a Chrome-trace
object whose ``metadata.clock_sync`` records the rank's monotonic epoch
against a wall-clock anchor.  Monotonic clocks on different hosts share
no epoch, so raw per-rank timestamps are mutually meaningless; the merge
aligns them by shifting every rank onto the earliest rank's anchor:

    shift_us(rank) = (wall_ns(rank) - min_rank_wall_ns) / 1000

After alignment a collective that straggles on one rank shows up as a
visibly late ``comm.*`` span on that rank's row in Perfetto — the
straggler diagnosis The Big Send-off (arXiv:2504.18658) motivates.

``--flops`` optionally folds the ``flops_breakdown`` record out of a
telemetry JSONL into the merged metadata, so the timeline carries the
per-module FLOPs attribution next to the spans.

``--collectives`` folds the per-rank ``collective_window`` records of
one or more telemetry JSONL files into the merged metadata, keyed
``"rank:seq"`` — the same ``seq`` every ``comm.*`` span carries in its
args, so a span on the timeline joins to its collective record (enter/
exit stamps, fingerprint, bytes) by (pid, args.seq).

Pure host-side JSON transform: runs anywhere, imports no accelerator.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class TraceFormatError(ValueError):
    pass


def load_rank_trace(path: str) -> dict:
    """Read + validate one per-rank trace document."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or not isinstance(doc.get("traceEvents"), list):
        raise TraceFormatError(f"{path}: not a Chrome-trace object "
                               "(missing traceEvents list)")
    meta = doc.get("metadata") or {}
    sync = meta.get("clock_sync") or {}
    if "wall_ns" not in sync:
        raise TraceFormatError(f"{path}: metadata.clock_sync.wall_ns missing "
                               "(was this written by Tracer.export_chrome_trace?)")
    return doc


def merge_traces(docs, flops=None) -> dict:
    """Fold rank documents onto one timeline (earliest anchor = t0)."""
    if not docs:
        raise TraceFormatError("no input traces")
    anchor_ns = min(d["metadata"]["clock_sync"]["wall_ns"] for d in docs)
    events = []
    ranks = []
    for doc in docs:
        meta = doc["metadata"]
        rank = meta.get("rank", len(ranks))
        shift_us = (meta["clock_sync"]["wall_ns"] - anchor_ns) / 1e3
        ranks.append({"rank": rank, "shift_us": shift_us,
                      "dropped_spans": meta.get("dropped_spans", 0)})
        for ev in doc["traceEvents"]:
            ev = dict(ev)
            ev["pid"] = rank
            if ev.get("ph") != "M":      # metadata events stay at ts 0
                ev["ts"] = float(ev.get("ts", 0.0)) + shift_us
            events.append(ev)
    events.sort(key=lambda e: (e.get("ph") != "M", e.get("ts", 0.0)))
    merged = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "metadata": {"ranks": ranks, "anchor_wall_ns": anchor_ns},
    }
    if flops is not None:
        merged["metadata"]["flops_breakdown"] = flops
    return merged


def load_collective_records(jsonl_paths):
    """Merge the ``collective_window`` records of telemetry JSONL files
    into a ``{"rank:seq": record}`` join table (later windows win per
    key — the windows overlap by design).  Returns None when no window
    records exist."""
    table = {}
    for path in jsonl_paths:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("kind") != "collective_window":
                    continue
                rank = rec.get("rank", 0)
                for r in rec.get("records", []):
                    table[f"{rank}:{r.get('seq')}"] = r
    return table or None


def load_flops_breakdown(jsonl_path: str):
    """Last ``flops_breakdown`` record in a telemetry JSONL, or None."""
    found = None
    with open(jsonl_path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("kind") == "flops_breakdown":
                found = {k: v for k, v in rec.items()
                         if k not in ("kind", "schema")}
    return found


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="trace_merge",
        description="merge per-rank Chrome traces onto one aligned timeline")
    parser.add_argument("traces", nargs="+",
                        help="per-rank trace JSON files (>=1)")
    parser.add_argument("-o", "--output", default="merged_trace.json",
                        help="merged Chrome-trace output path")
    parser.add_argument("--flops", default="",
                        help="telemetry JSONL to pull a flops_breakdown from")
    parser.add_argument("--collectives", action="append", default=[],
                        help="telemetry JSONL to pull collective_window "
                             "records from (repeatable, one per rank); "
                             "embeds a rank:seq join table in metadata")
    args = parser.parse_args(argv)

    try:
        docs = [load_rank_trace(p) for p in args.traces]
    except (TraceFormatError, OSError, json.JSONDecodeError) as e:
        print(f"trace_merge: {e}", file=sys.stderr)
        return 1
    flops = None
    if args.flops:
        try:
            flops = load_flops_breakdown(args.flops)
        except OSError as e:
            print(f"trace_merge: --flops: {e}", file=sys.stderr)
            return 1
    merged = merge_traces(docs, flops=flops)
    if args.collectives:
        try:
            table = load_collective_records(args.collectives)
        except OSError as e:
            print(f"trace_merge: --collectives: {e}", file=sys.stderr)
            return 1
        if table is not None:
            merged["metadata"]["collectives"] = table
            print(f"joined {len(table)} collective record(s) by (rank, seq)")
        else:
            print("trace_merge: --collectives: no collective_window "
                  "records found", file=sys.stderr)
    with open(args.output, "w") as f:
        json.dump(merged, f)
    n = len(merged["traceEvents"])
    print(f"wrote {args.output}: {n} events from {len(docs)} rank(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
