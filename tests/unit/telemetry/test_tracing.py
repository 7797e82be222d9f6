"""Tracer span nesting, zero-sync contract, and Chrome-trace export
schema — all host-side, fast, no toy training runs."""

import json
import threading

import jax.numpy as jnp
import pytest

from deepspeed_tpu.telemetry import (Tracer, get_global_tracer, maybe_span,
                                     set_global_tracer)


class FakeClock:
    """Deterministic nanosecond monotonic clock."""

    def __init__(self, start=1_000_000_000):
        self.now = start

    def __call__(self):
        return self.now

    def advance_ms(self, ms):
        self.now += int(ms * 1e6)


def make_tracer(**kw):
    clock = FakeClock()
    return Tracer(rank=kw.pop("rank", 0), clock=clock, **kw), clock


class TestSpans:

    def test_nesting_depth_and_parent(self):
        tr, clock = make_tracer()
        with tr.span("train_batch") as outer:
            clock.advance_ms(1)
            with tr.span("fwd") as inner:
                clock.advance_ms(2)
            clock.advance_ms(1)
        recs = tr.snapshot()
        assert [r["name"] for r in recs] == ["fwd", "train_batch"]  # close order
        fwd, tb = recs
        assert tb["depth"] == 0 and tb["parent"] == 0
        assert fwd["depth"] == 1 and fwd["parent"] == tb["sid"]
        assert fwd["t1"] - fwd["t0"] == 2_000_000
        assert tb["t1"] - tb["t0"] == 4_000_000
        assert tr.open_spans() == []          # everything closed

    def test_open_spans_visible_inside(self):
        tr, _ = make_tracer()
        with tr.span("fwd"):
            with tr.span("comm.all_reduce"):
                open_names = [s["name"] for s in tr.open_spans()]
                assert open_names == ["fwd", "comm.all_reduce"]
                assert all(s["t1"] is None for s in tr.open_spans())

    def test_span_closes_on_exception(self):
        tr, _ = make_tracer()
        with pytest.raises(RuntimeError):
            with tr.span("fwd"):
                raise RuntimeError("boom")
        assert tr.open_spans() == []
        assert tr.snapshot()[0]["t1"] is not None

    def test_ring_capacity_counts_drops(self):
        tr, _ = make_tracer(capacity=4)
        for i in range(10):
            with tr.span(f"s{i}"):
                pass
        assert len(tr.snapshot()) == 4
        assert tr.dropped == 6

    def test_heartbeat_fires_on_every_span_open(self):
        beats = []
        tr, _ = make_tracer(heartbeat=lambda: beats.append(1))
        with tr.span("a"):
            with tr.span("b"):
                pass
        assert len(beats) == 2

    def test_zero_sync_contract(self):
        """Opening/closing spans with a device-array attribute must not
        force it: the value is stored by reference until export."""
        tr, _ = make_tracer()
        x = jnp.ones((4,))
        with tr.span("fwd", loss=x):
            pass
        rec = tr.snapshot()[-1]
        assert rec["args"]["loss"] is x       # by reference, unconverted

    def test_threads_get_independent_stacks(self):
        tr, _ = make_tracer()
        seen = {}

        def worker():
            with tr.span("worker_span"):
                seen["depth"] = tr.open_spans()[-1]["depth"]

        with tr.span("main_span"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        # the worker's span is a root on its own thread, not a child of
        # the main thread's open span
        assert seen["depth"] == 0
        w = [r for r in tr.snapshot() if r["name"] == "worker_span"][0]
        assert w["parent"] == 0


class TestGlobalRegistry:

    def test_maybe_span_inert_without_tracer(self):
        set_global_tracer(None)
        with maybe_span("anything"):
            pass   # must not raise, records nothing

    def test_maybe_span_records_on_global(self):
        tr, _ = make_tracer()
        set_global_tracer(tr)
        try:
            with maybe_span("checkpoint.save", tag="t1"):
                pass
            assert get_global_tracer() is tr
            assert tr.snapshot()[-1]["name"] == "checkpoint.save"
        finally:
            set_global_tracer(None)


class TestChromeExport:

    def test_export_schema(self, tmp_path):
        tr, clock = make_tracer()
        with tr.span("fwd", step=3):
            clock.advance_ms(5)
        tr.add_span("pipe.fwd.m0", clock.now, clock.now + 1_000_000,
                    track="pipe.stage0", micro=0, synthetic=True)
        path = tr.export_chrome_trace(str(tmp_path / "t.json"))
        doc = json.load(open(path))
        assert isinstance(doc["traceEvents"], list)
        assert doc["metadata"]["rank"] == 0
        assert {"mono_ns", "wall_ns"} <= set(doc["metadata"]["clock_sync"])
        evs = {e["name"]: e for e in doc["traceEvents"]}
        fwd = evs["fwd"]
        assert fwd["ph"] == "X" and fwd["dur"] == pytest.approx(5000.0)
        assert fwd["args"]["step"] == 3
        slot = evs["pipe.fwd.m0"]
        assert slot["ph"] == "X" and slot["args"]["synthetic"] is True
        # synthetic track got its own named lane
        lanes = [e for e in doc["traceEvents"] if e.get("ph") == "M"
                 and e["name"] == "thread_name"]
        assert any(e["args"]["name"] == "pipe.stage0" for e in lanes)
        # required metadata events for Perfetto grouping
        meta_names = {e["name"] for e in doc["traceEvents"]
                      if e.get("ph") == "M"}
        assert {"process_name", "process_sort_index"} <= meta_names

    def test_device_array_attrs_converted_at_export(self):
        tr, _ = make_tracer()
        with tr.span("fwd", loss=jnp.float32(1.5)):
            pass
        evs = [e for e in tr.to_chrome_events() if e["name"] == "fwd"]
        assert evs[0]["args"]["loss"] == pytest.approx(1.5)
        assert isinstance(evs[0]["args"]["loss"], float)

    def test_closed_tracer_records_nothing(self):
        tr, _ = make_tracer()
        tr.close()
        with tr.span("late"):
            pass
        assert tr.snapshot() == []
