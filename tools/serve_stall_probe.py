"""Where a serve step that takes seconds spends them.

    python3 tools/serve_stall_probe.py OUT.json --workload <serve cell> --seed <n> --seconds 600

Runs one cell of the benchmark (``benchmarks/run.py`` of the checkout it is
started in, with the arguments after ``OUT.json``) and watches it from four
sides, none of which touches the traced program:

* a global ``Tracer`` that keeps every ``serve.*`` span over ``LONG_S`` and
  every stretch between two of them that long: which leaf the time lies under;
* a thread that sleeps ``BEAT_S`` and notes each wake-up over ``LATE_S`` late,
  with the main thread's stack at that moment: a thread that keeps time while
  the main thread sits in one line names the line; one that is late as well
  was held off the interpreter, or the whole process was;
* a child process that does the same and nothing else: late with the thread,
  the machine paused; on time, only this process did;
* ``gc.callbacks`` (collections over ``GC_S``), and the machine's own counts
  before and after: stolen ticks (``/proc/stat``), pressure stall totals
  (``/proc/pressure``), the process's involuntary context switches.

Writes all of it to ``OUT.json``; the cell's own lines (``slow_steps`` among
its notes) go to standard output as ever.
"""

import gc
import json
import os
import resource
import runpy
import subprocess
import sys
import threading
import time
import traceback

LONG_S, BEAT_S, LATE_S, GC_S = 0.5, 0.005, 0.1, 0.02

_CHILD = """
import sys, time
beat, late = float(sys.argv[1]), float(sys.argv[2])
last = time.monotonic()
while True:
    time.sleep(beat)
    now = time.monotonic()
    if now - last - beat > late:
        print(now, now - last - beat, flush=True)
    last = now
"""


def machine():
    out = {}
    with open("/proc/stat") as f:
        out["steal_ticks"] = int(f.readline().split()[8])
    for kind in ("cpu", "memory", "io"):
        try:
            with open(f"/proc/pressure/{kind}") as f:
                out[f"pressure_{kind}"] = f.read().split("\n")[0]
        except OSError:
            pass
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out["involuntary_switches"], out["major_faults"] = ru.ru_nivcsw, ru.ru_majflt
    return out


def main():
    out_path, argv = sys.argv[1], sys.argv[2:]
    sys.path.insert(0, os.getcwd())
    from deepspeed_tpu.telemetry import Tracer, set_global_tracer

    found = {"long_spans": [], "late_beats": [], "child_late_beats": [],
             "collections": [], "argv": argv}

    class Keeper(Tracer):
        """Keeps what is long, and nothing else of a run of minutes."""
        last = None

        def _append(self, rec):
            if rec.get("t1") is None or not rec["name"].startswith("serve."):
                return
            if rec["name"] != "serve.submit":       # the step's leaves
                if self.last is not None and rec["t0"] - self.last > LONG_S * 1e9:
                    found["long_spans"].append({
                        "name": "(between spans)", "at": self.last / 1e9,
                        "s": (rec["t0"] - self.last) / 1e9})
                self.last = rec["t1"]
            if rec["t1"] - rec["t0"] > LONG_S * 1e9:
                found["long_spans"].append({
                    "name": rec["name"], "at": rec["t0"] / 1e9,
                    "s": (rec["t1"] - rec["t0"]) / 1e9, "args": rec["args"]})

    set_global_tracer(Keeper())
    main_tid = threading.get_ident()

    def beat():
        last = time.monotonic()
        while True:
            time.sleep(BEAT_S)
            now = time.monotonic()
            if now - last - BEAT_S > LATE_S:
                frame = sys._current_frames().get(main_tid)
                found["late_beats"].append({
                    "at": now, "late_s": now - last - BEAT_S,
                    "main_thread": traceback.format_stack(frame)[-4:]})
            last = now
    threading.Thread(target=beat, daemon=True).start()

    # and a watcher that sees the main thread sit still while the beat is on
    # time: the line it sits in, every LONG_S while it sits there
    def watch():
        seen = None
        while True:
            time.sleep(LONG_S)
            frame = sys._current_frames().get(main_tid)
            at = (id(frame), frame.f_lasti) if frame is not None else None
            if at is not None and at == seen:
                found.setdefault("main_thread_still", []).append({
                    "at": time.monotonic(),
                    "stack": traceback.format_stack(frame)[-4:]})
            seen = at
    threading.Thread(target=watch, daemon=True).start()

    gc_t0 = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_t0[0] = time.monotonic()
        elif time.monotonic() - gc_t0[0] > GC_S:
            found["collections"].append({
                "at": gc_t0[0], "s": time.monotonic() - gc_t0[0],
                "generation": info["generation"]})
    gc.callbacks.append(on_gc)

    child = subprocess.Popen(
        [sys.executable, "-c", _CHILD, str(BEAT_S), str(LATE_S)],
        stdout=subprocess.PIPE, text=True, env={"PATH": os.environ.get("PATH", "")})
    found["machine_before"], t0 = machine(), time.monotonic()
    sys.argv = ["benchmarks/run.py"] + argv
    try:
        runpy.run_path("benchmarks/run.py", run_name="__main__")
    except SystemExit as e:
        found["exit"] = e.code
    finally:
        found["machine_after"], found["seconds"] = machine(), time.monotonic() - t0
        child.terminate()
        found["child_late_beats"] = [
            dict(zip(("at", "late_s"), map(float, line.split())))
            for line in child.stdout]
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(found, f, indent=1)
        print(json.dumps({k: (len(v) if isinstance(v, list) else v)
                          for k, v in found.items() if k != "argv"}),
              file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
