"""The benchmark's one command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on, which
must hold the TPU chips the cell asks for, and prints as the LAST line of its
standard output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``) and ``device``, and last in it ``compared``: each
number the comparison that decides ``correct`` held to a limit, as ``[number,
limit]``, which are also the last lines on standard error.  The line before
it splits ``setup_s`` into its phases.  ``--rehearse`` runs the cell's control flow at a
tiny size on the CPU and prints no device metric; ``--set key=value``
overrides one number of the traffic file for a sweep by hand.  The driver
passes neither.
"""

import time
T_START = time.perf_counter()

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# libtpu otherwise logs under /tmp/tpu_logs: nothing is written outside the
# checkout and the directories the driver gives
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE")
    return ap.parse_args(argv)


def emit(line, result):
    """The result's line, ``compared`` last in it; then each number compared
    beside its limit as the last lines of standard error."""
    compared = result.get("compared", {})
    print(json.dumps(dict(line, compared=compared)), flush=True)
    for name, (value, limit) in compared.items():
        print(f"compared {name} {value} limit {limit}", file=sys.stderr, flush=True)
    return 0


def main(argv=None):
    args = parse(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmarks.lib import cells
    from benchmarks.lib import device as dev
    try:
        import deepspeed_tpu  # noqa: F401  the system under test
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout: {e}", file=sys.stderr)
        return 2
    from deepspeed_tpu.utils.logging import logger
    for handler in logger.handlers:      # stdout carries the result only
        handler.setStream(sys.stderr)

    cell = cells.Cell(args.workload)
    if args.seconds is None:
        args.seconds = float(cell.run_seconds)
    device = dev.device_or_exit(cell.chips, args.rehearse)
    import jax
    phases = {"import_and_device": time.perf_counter() - T_START}
    setup = {}

    @contextlib.contextmanager
    def phase(name):
        t = time.perf_counter()
        yield
        phases[name] = phases.get(name, 0.0) + time.perf_counter() - t

    ctx = {"device": device, "phase": phase, "phases": phases,
           "compiles": dev.CompileCounter(),
           "setup_done": lambda t: setup.setdefault("s", t - T_START),
           "tracer": None, "trace_seconds": min(3.0, args.seconds / 2)}
    if args.rehearse:
        cells.merge(cell.config, cell.config.get("rehearse", {}))
        cells.merge(cell.traffic, cell.traffic.get("rehearse", {}))
        peaks = None
    else:
        dev.use_compile_cache()
        peaks = dev.peaks(device["kind"])
        if args.trace:
            ctx["tracer"] = dev.Tracer(os.path.join(ROOT, ".bench_trace"))
    for item in args.set:
        key, _, value = item.partition("=")
        cell.traffic[key] = json.loads(value)

    result = cell.kind.run(cell, args, ctx)

    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    run = dict(result, peaks=peaks, cell=cell,
               device=dict(device, memory_limit_bytes=limit))
    phases["setup_s"] = setup["s"]
    print(json.dumps({"phases": phases, "notes": result["notes"], "counters": {
        k: v for k, v in result["counters"].items() if isinstance(v, (int, float))}}),
          flush=True)
    line = {"correct": bool(result["correct"]), "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if args.rehearse:
        # counts only: a CPU run gives no time, rate or share of a device
        line.update(metrics={}, device=device, rehearsal=True,
                    counts={k: v for k, v in result["counters"].items()
                            if k in ("steps", "compiles_in_window",
                                     "requests_measured", "finished_in_window")},
                    would_report=sorted(m["name"] for m in
                                        cell.end_to_end + cell.per_layer))
        return emit(line, result)
    device = dict(device, memory_peak_bytes=result["counters"]["memory_peak_bytes"])
    if args.trace:
        trace = result["trace"]
        if trace is None or not trace.devices:
            print("benchmark: the traced run holds no device operation", file=sys.stderr)
            return 3
        metrics = cells.per_layer_values(cell, run)
        device.update(busy_s=trace.busy_s(), window_s=trace.window_s())
        line.update(metrics=metrics, device=device, breakdown=trace.breakdown(),
                    notes=run["notes"])
    else:
        values = dict(result["end_to_end"], setup_s=setup["s"])
        line.update(metrics={m["name"]: {"value": float(values[m["name"]]),
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}, device=device)
    return emit(line, result)


if __name__ == "__main__":
    sys.exit(main())
