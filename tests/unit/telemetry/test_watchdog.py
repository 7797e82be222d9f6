"""Hang watchdog + flight recorder: simulated stalls with a fake clock
(no real multi-minute waits), dump schema, hang-safety, signal chaining."""

import json
import os
import signal
import threading
import time

import jax.numpy as jnp
import pytest

from deepspeed_tpu.telemetry import (FlightRecorder, HangWatchdog, JsonlSink,
                                     RingBufferSink, TelemetryHub, Tracer,
                                     read_dump)
from deepspeed_tpu.telemetry.flight_recorder import _hang_safe, thread_stacks


class FakeClock:
    def __init__(self, start=1_000_000_000):
        self.now = start

    def __call__(self):
        return self.now

    def advance_s(self, s):
        self.now += int(s * 1e9)


class TestWatchdog:

    def test_fires_once_on_stall(self):
        clock = FakeClock()
        fired = []
        wd = HangWatchdog(timeout_s=10.0, clock=clock,
                          on_stall=lambda w, s, what: fired.append((s, what)))
        wd.arm("step=3")
        clock.advance_s(5)
        assert wd.check() is False            # below threshold
        clock.advance_s(6)
        assert wd.check() is True             # 11s > 10s
        assert wd.check() is False            # once per stall
        assert fired == [(11.0, "step=3")]
        assert wd.stall_count == 1

    def test_pet_resets_the_clock(self):
        clock = FakeClock()
        wd = HangWatchdog(timeout_s=10.0, clock=clock)
        wd.arm("x")
        clock.advance_s(9)
        wd.pet()
        clock.advance_s(9)
        assert wd.check() is False            # 9s since last beat
        clock.advance_s(2)
        assert wd.check() is True

    def test_disarmed_never_fires(self):
        clock = FakeClock()
        wd = HangWatchdog(timeout_s=1.0, clock=clock)
        wd.arm("x")
        wd.disarm()
        clock.advance_s(100)
        assert wd.check() is False

    def test_rearm_after_fire_re_enables(self):
        clock = FakeClock()
        wd = HangWatchdog(timeout_s=1.0, clock=clock)
        wd.arm("a")
        clock.advance_s(2)
        assert wd.check() is True
        wd.arm("b")
        clock.advance_s(2)
        assert wd.check() is True
        assert wd.stall_count == 2

    def test_callback_errors_are_contained(self):
        clock = FakeClock()

        def broken(w, s, what):
            raise OSError("disk full")

        wd = HangWatchdog(timeout_s=1.0, clock=clock, on_stall=broken)
        wd.arm("x")
        clock.advance_s(2)
        assert wd.check() is True             # no raise

    def test_tracer_spans_pet_the_watchdog(self):
        clock = FakeClock()
        wd = HangWatchdog(timeout_s=10.0, clock=clock)
        tr = Tracer(clock=clock, heartbeat=wd.pet)
        wd.arm("step")
        clock.advance_s(9)
        with tr.span("comm.all_reduce"):      # collective beats
            pass
        clock.advance_s(9)
        assert wd.check() is False

    def test_poll_thread_fires_on_real_stall(self):
        fired = threading.Event()
        wd = HangWatchdog(timeout_s=0.2, poll_s=0.05,
                          on_stall=lambda w, s, what: fired.set())
        wd.arm("real")
        wd.start()
        try:
            assert fired.wait(timeout=5.0)
        finally:
            wd.stop()


class TestFlightRecorder:

    def _make_state(self, tmp_path):
        """A hub with ring+jsonl sinks, some records, and an open span."""
        ring = RingBufferSink(capacity=16)
        hub = TelemetryHub(sinks=[ring, JsonlSink(str(tmp_path / "t.jsonl"))],
                           flush_every=0, batch_size=8,
                           sync_fn=lambda: None,
                           memory_stats_fn=lambda: {"peak_bytes_in_use": 1})
        for s in (1, 2):
            hub.record_step(s, loss=0.5 / s, lr=1e-3)
        hub.flush()
        hub.record_step(3, loss=jnp.float32(0.1), lr=1e-3)  # stays pending
        tracer = Tracer()
        return hub, tracer

    def test_stall_dump_contains_everything(self, tmp_path):
        hub, tracer = self._make_state(tmp_path)
        fr = FlightRecorder(str(tmp_path / "dumps"), rank=0, hub=hub,
                            tracer=tracer)
        wd = HangWatchdog(timeout_s=1.0, clock=FakeClock(),
                          on_stall=fr.on_stall)
        with tracer.span("train_batch", step=3):
            with tracer.span("comm.all_reduce", bytes=1024):
                wd.arm("step=3")
                wd._clock.advance_s(2)
                assert wd.check() is True     # simulated stall -> dump

        dumps = os.listdir(tmp_path / "dumps")
        assert len(dumps) == 1
        sections = read_dump(str(tmp_path / "dumps" / dumps[0]))
        header = sections["header"][0]
        assert header["reason"] == "stall:step=3"
        assert header["stalled_for_s"] == pytest.approx(2.0)
        # ring-buffer records (flushed steps 1..2)
        ring = sections["ring_buffer"][0]
        assert {r["step"] for r in ring if r.get("kind") == "step"} == {1, 2}
        # pending records survive unforced
        assert len(sections["pending_records"][0]) == 1
        # open spans at stall time, innermost last
        open_names = [s["name"] for s in sections["open_spans"][0]]
        assert open_names == ["train_batch", "comm.all_reduce"]
        # per-thread python stacks include this test frame
        stacks = sections["thread_stacks"][0]
        assert any("test_watchdog" in "".join(t["stack"]) for t in stacks)
        assert sections["end"][0]["complete"] is True

    def test_dump_never_forces_device_arrays(self, tmp_path):
        """A pending jax.Array (potentially in-flight during a hang) must
        be summarized from its aval, not converted to host."""
        hub, tracer = self._make_state(tmp_path)
        forced = []
        x = jnp.ones((8,), jnp.float32)

        class Exploding:
            """Stands in for an in-flight array: any host conversion
            (forcing) is an error."""
            aval = x.aval

            def __array__(self):
                forced.append(1)
                raise AssertionError("dump forced a device value")

            def __float__(self):
                forced.append(1)
                raise AssertionError("dump forced a device value")

        hub._pending.append({"kind": "step", "step": 9,
                             "loss": Exploding()})
        with tracer.span("fwd", loss=Exploding()):
            fr = FlightRecorder(str(tmp_path / "d2"), hub=hub, tracer=tracer)
            path = fr.dump(reason="manual")
        assert not forced
        sections = read_dump(path)
        pend = sections["pending_records"][0]
        assert any("unforced" in str(r.get("loss")) for r in pend)
        span = sections["open_spans"][0][0]
        assert "unforced" in span["args"]["loss"]

    def test_dump_lines_are_individually_parseable(self, tmp_path):
        """Crash-safety: every line of the dump is standalone JSON, so a
        truncated file (SIGKILL mid-dump) still parses line by line."""
        hub, tracer = self._make_state(tmp_path)
        fr = FlightRecorder(str(tmp_path / "d3"), hub=hub, tracer=tracer)
        path = fr.dump(reason="manual")
        with open(path) as f:
            lines = f.read().splitlines()
        assert len(lines) >= 6
        for line in lines:
            rec = json.loads(line)
            assert "section" in rec

    def test_sequential_dumps_get_distinct_files(self, tmp_path):
        fr = FlightRecorder(str(tmp_path / "d4"))
        p1, p2 = fr.dump("a"), fr.dump("b")
        assert p1 != p2 and os.path.exists(p1) and os.path.exists(p2)

    def test_hang_safe_scalars_pass_through(self):
        assert _hang_safe({"a": 1, "b": [1.5, "x", None, True]}) == {
            "a": 1, "b": [1.5, "x", None, True]}

    def test_thread_stacks_cover_all_threads(self):
        evt = threading.Event()
        t = threading.Thread(target=evt.wait, name="parked", daemon=True)
        t.start()
        try:
            stacks = thread_stacks()
            names = {s["name"] for s in stacks}
            assert "parked" in names
            parked = [s for s in stacks if s["name"] == "parked"][0]
            assert any("wait" in ln for ln in parked["stack"])
        finally:
            evt.set()
            t.join()


class TestSignals:

    def test_sigterm_dumps_then_chains(self, tmp_path):
        """SIGTERM triggers a dump, then the previously-installed handler
        runs (chaining) — the process is not silently kept alive."""
        chained = []
        prev = signal.signal(signal.SIGTERM,
                             lambda s, f: chained.append(s))
        fr = FlightRecorder(str(tmp_path / "sig"))
        wd = HangWatchdog(timeout_s=60.0, on_stall=fr.on_stall)
        try:
            wd.install_signal_handlers(signals=(signal.SIGTERM,))
            os.kill(os.getpid(), signal.SIGTERM)
            # signal delivery is synchronous in the main thread on CPython
            deadline = time.monotonic() + 5.0
            while not chained and time.monotonic() < deadline:
                time.sleep(0.01)
            assert chained == [signal.SIGTERM]
            dumps = os.listdir(tmp_path / "sig")
            assert len(dumps) == 1
            header = read_dump(str(tmp_path / "sig" / dumps[0]))["header"][0]
            assert header["reason"] == f"signal:{int(signal.SIGTERM)}"
        finally:
            wd.restore_signal_handlers()
            signal.signal(signal.SIGTERM, prev)
