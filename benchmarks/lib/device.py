"""The device as JAX reports it, its peaks, its memory, and the compile cache.
A run that finds no TPU, or another number of chips than the cell asks for,
exits non-zero and prints no result; ``--rehearse`` is the one way onto the
CPU, and a rehearsal's line carries no device metric."""

import contextlib
import os
import sys

from benchmarks.lib.cells import BENCH_DIR, ROOT, BenchmarkError, load_json

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def device_or_exit(chips, rehearse):
    import jax
    devices = jax.devices()
    plat = devices[0].platform
    if rehearse:
        if plat != "cpu":
            print("benchmark: --rehearse is for the CPU", file=sys.stderr)
            sys.exit(2)
    elif plat != "tpu" or len(devices) != chips:
        print(f"benchmark: the cell needs {chips} TPU chip(s), JAX found "
              f"{len(devices)} x {plat}; refusing to run", file=sys.stderr)
        sys.exit(2)
    return {"platform": plat, "kind": devices[0].device_kind, "count": len(devices)}


def peaks(device_kind):
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table:
        raise BenchmarkError(
            f"device kind {device_kind!r} is not in benchmarks/peaks.json; "
            f"add its published peaks with their source")
    return table[device_kind]


def use_compile_cache():
    """JAX's persistent compile cache at a FIXED path inside the checkout
    (the path is part of the key), unless ``JAX_COMPILATION_CACHE_DIR`` names
    one.  Every program is cached, however quick its compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR


def memory_peak_bytes():
    """The peak on the fullest chip: what arrays held at their most
    (``peak_bytes_in_use``) plus what loaded programs hold for their
    temporaries (``peak_bytes_reserved``; on the v5e the runtime counts the
    two apart: a GPT-2 124M train step read 1.5 GB in use and 7.9 GB
    reserved where the compiler's ``memory_analysis`` says 1.4 + 7.8)."""
    import jax
    peak = 0
    for d in jax.devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0))
                   + int(st.get("peak_bytes_reserved", 0)))
    return peak


class CompileCounter:
    """Compile requests (a new program compiled, or loaded from the
    persistent cache), from ``jax.monitoring``.  ``mark()`` at the window's
    start; what comes after is in the window, and a steady window has none."""

    def __init__(self):
        import jax
        self.requests, self._at_mark = 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def mark(self):
        self._at_mark = self.requests

    def in_window(self):
        return self.requests - self._at_mark


@contextlib.contextmanager
def span(name):
    """A host span on the profiler's clock."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


class Tracer:
    """The profiler round the last ``seconds`` of a window: started between
    two steps, stopped after the window's end, so that starting and stopping
    cost the window nothing that an end-to-end run does not also pay."""

    def __init__(self, directory):
        import shutil
        shutil.rmtree(directory, ignore_errors=True)     # last run's trace
        self.directory, self.on = directory, False

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self.on = True

    def stop(self):
        import jax
        from benchmarks.lib.trace import Trace, newest_xplane
        jax.profiler.stop_trace()
        self.on = False
        path = newest_xplane(self.directory)
        return Trace.from_file(path) if path else None
