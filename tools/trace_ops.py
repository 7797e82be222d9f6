"""Where a traced step's device time went: by op family, by the program's
scopes, and by name stack.

    python3 tools/trace_ops.py <trace.xplane.pb | directory> [--top 25]
                               [--steps N] [--under blocks] [--json]

Reads one profiler trace (the newest ``.xplane.pb`` under a directory such as
``.bench_trace``, what ``benchmarks/run.py --trace 1`` leaves) with the
benchmark's own reducers, so a number here is the number a per-layer metric
reads: an op's time is its SELF time (``benchmarks/lib/trace.py``), its name
stack the ``tf_op`` stat of its metadata
(``benchmarks/readers/program_spans.py``).  Three tables, all of one chip's
busy time (the first chip's; ``--steps N`` also gives milliseconds a step):

* ``family``: the HLO op's name less its numbering
  (``bitcast_dynamic-update-slice_fusion.12.remat3`` counts under
  ``bitcast_dynamic-update-slice_fusion``);
* ``scope``: the first of the program's scopes (``PROGRAM_SCOPES``:
  optimizer, cross_entropy, head, attn, mlp, embed, blocks) the op's stack
  holds, ``none`` for an op under none of them: ``blocks`` there is what the
  walk of the layers costs beside the layers themselves;
* ``stack``: the name stack as written, its ``jit(...)`` head dropped and a
  loop's ``while/body/closed_call`` kept, so the forward scan's stash reads
  ``jvp(blocks)/while/body/dynamic_update_slice``; ``--under blocks`` keeps
  the stacks that hold that scope.

Needs no chip: a trace is a file.  This is how ROADMAP S8 was found (the
layer scan's stash, PR 50) and how PR 54 checked that it left the program.
"""

import argparse
import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_FAMILY = re.compile(r"(\.\d+|\.remat\d*|\.clone)+$")


def family(op: str) -> str:
    """``fusion.12.remat3`` -> ``fusion``."""
    return _FAMILY.sub("", op)


def tables(path: str):
    """{"busy_s", "window_s", "family", "scope", "stack"} of the first chip
    of the trace at ``path``: each table a Counter of self seconds."""
    from benchmarks.lib import trace as tr
    from benchmarks.readers import program_spans as ps
    by_plane = ps.device_ops_with_scope(path)
    plane = next((p for p in sorted(by_plane) if by_plane[p]), None)
    if plane is None:
        raise SystemExit(f"{path}: no device op in it")
    stacks = tr.self_times(by_plane[plane])           # named by their stack
    device = next(d for d in tr.Trace.from_file(path).devices if d.name == plane)
    out = {"busy_s": device.busy_s, "window_s": device.window_s,
           "family": collections.Counter(), "scope": collections.Counter(),
           "stack": collections.Counter()}
    for name, self_s in device.op_seconds().items():
        out["family"][family(name)] += self_s
    for stack, _, _, self_s in stacks:
        scopes = ps._components(stack)
        owner = next((s for s in ps.PROGRAM_SCOPES if s in scopes), "none")
        out["scope"][owner] += self_s
        shown = (stack or "(no stack)").rstrip(":")
        out["stack"][re.sub(r"^jit\([^)]*\)/(jit\(main\)/)?", "", shown)] += self_s
    return out


def _rows(counter, busy_s, steps, top):
    rows = []
    for name, s in counter.most_common(top):
        row = {"name": name, "s": s, "share_pct": 100.0 * s / busy_s}
        if steps:
            row["ms_per_step"] = 1e3 * s / steps
        rows.append(row)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--steps", type=int, default=0,
                    help="whole steps in the trace, for ms a step")
    ap.add_argument("--under", default=None, metavar="SCOPE")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    path = args.trace
    if os.path.isdir(path):
        from benchmarks.lib.trace import newest_xplane
        path = newest_xplane(path) or sys.exit(f"{args.trace}: no .xplane.pb under it")
    found = tables(path)
    if args.under:
        from benchmarks.readers.program_spans import _components
        found["stack"] = collections.Counter({
            k: v for k, v in found["stack"].items() if args.under in _components(k)})
    report = {"trace": path, "busy_s": found["busy_s"], "window_s": found["window_s"],
              **{t: _rows(found[t], found["busy_s"], args.steps, args.top)
                 for t in ("family", "scope", "stack")}}
    if args.json:
        print(json.dumps(report))
        return 0
    print(f"{path}: busy {found['busy_s']:.4f} s of {found['window_s']:.4f} s")
    for t in ("family", "scope", "stack"):
        print(f"\nby {t}")
        for row in report[t]:
            per = f"{row['ms_per_step']:9.3f} ms/step" if args.steps else ""
            print(f"  {row['s']:9.5f} s {row['share_pct']:6.2f}% {per}  {row['name']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
