"""Wall-clock and throughput timers.

TPU-native analogue of the reference's ``deepspeed/utils/timer.py``:
``SynchronizedWallClockTimer`` (reference ``utils/timer.py:33``) and
``ThroughputTimer`` (reference ``utils/timer.py:137``).  Device
synchronization is a ``jax.block_until_ready`` on a trivial computation (or a
caller-supplied array) instead of CUDA events — on TPU all dispatch is async
through the same stream, so draining it is an exact fence.
"""

import time

from deepspeed_tpu.utils.logging import log_dist

FORWARD_MICRO_TIMER = "fwd_microstep"
FORWARD_GLOBAL_TIMER = "fwd"
BACKWARD_MICRO_TIMER = "bwd_microstep"
BACKWARD_GLOBAL_TIMER = "bwd"
BACKWARD_INNER_MICRO_TIMER = "bwd_inner_microstep"
BACKWARD_INNER_GLOBAL_TIMER = "bwd_inner"
BACKWARD_REDUCE_MICRO_TIMER = "bwd_allreduce_microstep"
BACKWARD_REDUCE_GLOBAL_TIMER = "bwd_allreduce"
STEP_MICRO_TIMER = "step_microstep"
STEP_GLOBAL_TIMER = "step"

try:
    import psutil
    PSUTIL_AVAILABLE = True
except ImportError:
    PSUTIL_AVAILABLE = False


def _sync_device():
    import jax
    # Draining dispatch: put a token op and block.  jax has no global
    # "synchronize" API; blocking on a trivial device computation after all
    # enqueued work is an effective fence on TPU's in-order stream.
    jax.block_until_ready(jax.device_put(0))


class _IntervalTimer:
    """One named timer: accumulates start→stop intervals.

    Total/count accumulators (not a list of records) — the engine reads
    these every ``steps_per_print`` and a record list would grow without
    bound over a long run.
    """

    __slots__ = ("name", "_begin", "_running", "_total_s", "_count")

    def __init__(self, name: str):
        self.name = name
        self._begin = 0.0
        self._running = False
        self._total_s = 0.0
        self._count = 0

    def start(self, sync: bool = False):
        if self._running:
            raise RuntimeError(
                f"timer {self.name!r} is running; stop() it before start()")
        if sync:
            _sync_device()
        self._begin = time.time()
        self._running = True

    def stop(self, reset: bool = False, record: bool = True, sync: bool = True):
        if not self._running:
            raise RuntimeError(f"timer {self.name!r} stopped while not running")
        if sync:
            _sync_device()
        self._running = False
        if record:
            self._total_s += time.time() - self._begin
            self._count += 1
        if reset:
            self.reset()

    def reset(self):
        self._running = False
        self._total_s = 0.0
        self._count = 0

    def elapsed(self, reset: bool = True) -> float:
        """Accumulated milliseconds; a running interval is folded in and the
        timer keeps running."""
        was_running = self._running
        if was_running:
            self.stop(sync=False)
        ms = self._total_s * 1000.0
        if reset:
            self.reset()
        if was_running:
            self.start()
        return ms

    def mean(self) -> float:
        """Mean interval in milliseconds."""
        return (self._total_s / self._count) * 1000.0 if self._count else 0.0


class SynchronizedWallClockTimer:
    """Registry of named interval timers, optionally fencing the device."""

    # engine code does `timers.Timer` in a couple of spots; keep the alias
    Timer = _IntervalTimer

    def __init__(self):
        self.timers = {}

    def get_timers(self):
        return self.timers

    def __call__(self, name):
        if name not in self.timers:
            self.timers[name] = _IntervalTimer(name)
        return self.timers[name]

    @staticmethod
    def memory_usage():
        import jax
        try:
            stats = jax.local_devices()[0].memory_stats() or {}
        except Exception:
            stats = {}
        alloc = stats.get("bytes_in_use", 0) / (1024**3)
        peak = stats.get("peak_bytes_in_use", 0) / (1024**3)
        return f"DeviceMem Allocated {round(alloc, 2)} GB Max {round(peak, 2)} GB"

    def log(self, names, normalizer=1.0, reset=True, memory_breakdown=False, ranks=None):
        assert normalizer > 0.0
        string = "time (ms)"
        for name in names:
            if name in self.timers:
                elapsed_time = self.timers[name].elapsed(reset=reset) / normalizer
                string += f" | {name}: {elapsed_time:.2f}"
        log_dist(string, ranks=ranks or [0])

    def get_mean(self, names, normalizer=1.0, reset=True):
        assert normalizer > 0.0
        means = {}
        for name in names:
            if name in self.timers:
                means[name] = self.timers[name].mean() / normalizer
                if reset:
                    self.timers[name].reset()
        return means


class NoopTimer:
    """Placeholder with the SynchronizedWallClockTimer interface."""

    class Timer:

        def start(self, **kwargs):
            ...

        def reset(self):
            ...

        def stop(self, **kwargs):
            ...

        def elapsed(self, **kwargs):
            return 0

        def mean(self):
            return 0

    def __call__(self, name):
        return self.Timer()

    def get_timers(self):
        return {}

    def log(self, names, normalizer=1.0, reset=True, memory_breakdown=False, ranks=None):
        ...

    def get_mean(self, names, normalizer=1.0, reset=True):
        ...


class ThroughputTimer:
    """Samples/sec tracker (the role of reference ``utils/timer.py:137``).

    TPU-native design point: never fence the device on a per-step basis.
    Dispatch is fully asynchronous, so a per-step start/stop sync — the
    reference's CUDA-event pattern — serializes the pipeline and *is itself*
    the bottleneck.  Instead, steps are only counted between report
    boundaries; the device is drained once per ``steps_per_output`` window
    and throughput is window_samples / window_time.

    ``batch_size`` is the *global* train batch per step.
    """

    def __init__(self, batch_size, start_step=2, steps_per_output=50,
                 monitor_memory=False, logging_fn=None):
        self.batch_size = max(1, batch_size)
        self.start_step = start_step
        self.steps_per_output = max(1, steps_per_output)
        self.monitor_memory = monitor_memory
        self.logging = logging_fn or log_dist
        self.started = False
        self.epoch_count = 0
        self.micro_step_count = 0
        self.global_step_count = 0
        # measurement window (between device drains)
        self._window_start: float = 0.0
        self._window_step0 = 0
        self._last_stop: float = 0.0
        self._excluded = 0.0   # host time between stop() and the next start()
        # lifetime accumulation over *measured* windows only
        self.total_elapsed_time = 0.0
        self._measured_steps = 0

    def update_epoch_count(self):
        self.epoch_count += 1
        self.micro_step_count = 0

    def start(self):
        self.started = True
        if self._window_start == 0.0 and self.global_step_count >= self.start_step:
            _sync_device()
            self._window_start = time.time()
            self._window_step0 = self.global_step_count
            self._excluded = 0.0
        elif self._last_stop > 0.0:
            # host-side time spent outside train steps (eval, data loading,
            # checkpointing) is not training throughput; device-async work
            # from those calls may still bleed in, but host stalls dominate
            self._excluded += time.time() - self._last_stop
            self._last_stop = 0.0

    def _close_window(self, report_speed):
        _sync_device()
        now = time.time()
        window = self.global_step_count - self._window_step0
        duration = max(now - self._window_start - self._excluded, 1e-9)
        self.total_elapsed_time += duration
        self._measured_steps += window
        if report_speed and window > 0:
            self.logging(
                f"epoch={self.epoch_count}/micro_step={self.micro_step_count}/"
                f"global_step={self.global_step_count}, RunningAvgSamplesPerSec="
                f"{self.avg_samples_per_sec():.3f}, CurrSamplesPerSec="
                f"{self.batch_size * window / duration:.3f}")
        self._window_start = now
        self._window_step0 = self.global_step_count
        self._excluded = 0.0
        # the drain above is window compute time, not an out-of-step gap;
        # clearing _last_stop keeps the next start() from excluding it
        self._last_stop = 0.0

    def stop(self, global_step=False, report_speed=True):
        if not self.started:
            return
        self.started = False
        self.micro_step_count += 1
        if not global_step:
            return
        self.global_step_count += 1
        self._last_stop = time.time()
        if (self._window_start > 0.0
                and self.global_step_count - self._window_step0 >= self.steps_per_output):
            self._close_window(report_speed)

    def avg_samples_per_sec(self):
        if (self._measured_steps == 0 and self._window_start > 0.0
                and self.global_step_count > self._window_step0):
            # run shorter than one report window: close it now so short
            # trainings still report a measured value
            self._close_window(report_speed=False)
        if self._measured_steps > 0 and self.total_elapsed_time > 0:
            return self.batch_size * self._measured_steps / self.total_elapsed_time
        return 0.0


def trim_mean(data, trim_percent):
    """Mean with the tails trimmed (used by comms logging summaries)."""
    assert 0.0 <= trim_percent <= 1.0
    n = len(data)
    if n == 0:
        return 0
    data = sorted(data)
    k = int(round(n * trim_percent))
    return sum(data[k:n - k]) / max(1, n - 2 * k)
