"""Collective health plane: per-collective seq/fingerprint records on the
comm facade, the cross-rank skew/straggler/desync fold (three provably
equal paths — host views, device gather on the 8-virtual-device mesh,
offline JSONL records), the DS_FAULT_PLAN-delayed straggler e2e (named
by the fold, by ``/collectives``, and by ``tools/collective_report.py``),
desync detection at the exact first divergent seq, the wedged-collective
flight-recorder dump, and the ``/healthz`` desync latch."""

import json
import subprocess
import sys
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm import comm as C
from deepspeed_tpu.telemetry import collective_monitor as cm
from deepspeed_tpu.telemetry import events
from deepspeed_tpu.telemetry import (RingBufferSink, TelemetryHub, Tracer)
from deepspeed_tpu.telemetry.flight_recorder import FlightRecorder, read_dump
from deepspeed_tpu.telemetry.ledger import GoodputLedger
from deepspeed_tpu.telemetry.metrics import MetricsRegistry, MetricsSink
from deepspeed_tpu.telemetry.obs_server import (
    ObsServer, collective_desync_health_check)
from deepspeed_tpu.telemetry.tracing import set_global_tracer
from deepspeed_tpu.testing.fault_injection import clear_plan, install_plan

ANCHOR_US = 1_700_000_000_000_000


class FakeClock:
    """monotonic_ns stand-in the tests drive by hand."""

    def __init__(self, start_ns=0):
        self.ns = start_ns

    def __call__(self):
        return self.ns

    def advance_us(self, us):
        self.ns += int(us) * 1000


def make_monitor(rank, clock=None, capacity=64):
    """Monitor with a deterministic epoch anchor: stamps become exactly
    ANCHOR_US + fake-clock microseconds, comparable across 'ranks'."""
    mon = cm.CollectiveMonitor(rank=rank, capacity=capacity,
                               clock_ns=clock or time.monotonic_ns)
    mon._anchor_unix_us = ANCHOR_US
    mon._anchor_mono_ns = 0
    return mon


def stage(mon, clock, op="all_reduce", axis="dp", dtype="float32",
          shape=(4, 4), nbytes=64, at_us=None, dur_us=10):
    if at_us is not None:
        clock.ns = int(at_us) * 1000
    rec = mon.begin(op, axis, dtype, shape, nbytes)
    clock.advance_us(dur_us)
    mon.end(rec)
    return rec


def _get(url, timeout=5):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def make_hub(**kw):
    kw.setdefault("sinks", [RingBufferSink(128)])
    kw.setdefault("flush_every", 0)
    kw.setdefault("sync_fn", lambda: None)
    return TelemetryHub(**kw)


class TestFingerprint:

    def test_deterministic_across_processes(self):
        """Python hash() is salted per process; the fingerprint must not
        be — compute the same fingerprint in a subprocess and compare."""
        fp = cm.fingerprint_of("all_reduce", "dp", "float32", (4, 4))
        code = ("import importlib.util; "
                "spec = importlib.util.spec_from_file_location('m', %r); "
                "m = importlib.util.module_from_spec(spec); "
                "spec.loader.exec_module(m); "
                "print(m.fingerprint_of('all_reduce', 'dp', 'float32', "
                "(4, 4)))" % cm.__file__)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, check=True)
        assert int(out.stdout.strip()) == fp

    def test_sensitive_to_every_structural_field(self):
        base = cm.fingerprint_of("all_reduce", "dp", "float32", (4, 4))
        assert cm.fingerprint_of("all_gather", "dp", "float32", (4, 4)) != base
        assert cm.fingerprint_of("all_reduce", "mp", "float32", (4, 4)) != base
        assert cm.fingerprint_of("all_reduce", "dp", "bfloat16", (4, 4)) != base
        assert cm.fingerprint_of("all_reduce", "dp", "float32", (4, 8)) != base
        # list vs tuple shape spellings agree (facade passes tuples,
        # JSONL round-trips lists)
        assert cm.fingerprint_of("all_reduce", "dp", "float32", [4, 4]) == base


class TestMonitorRing:

    def test_seq_monotonic_and_ring_bounded(self):
        clock = FakeClock()
        mon = make_monitor(0, clock, capacity=4)
        for i in range(10):
            stage(mon, clock, at_us=i * 1000)
        assert mon.seq == 10
        recs = mon.last_records()
        assert [r["seq"] for r in recs] == [7, 8, 9, 10]
        assert mon.last_records(2)[-1]["seq"] == 10
        # JSON-ready: shapes are plain int lists, stamps are ints
        rec = recs[-1]
        assert rec["shape"] == [4, 4]
        assert rec["t_enter_us"] == ANCHOR_US + 9 * 1000
        json.dumps(recs)

    def test_window_view_and_wedged_summary(self):
        clock = FakeClock()
        mon = make_monitor(3, clock)
        stage(mon, clock)
        view = mon.window_view()
        assert view["rank"] == 3 and view["seq"] == 1
        assert "(closed)" in mon.wedged_summary()
        mon.begin("all_gather", "fsdp", "float32", (8,), 32)  # never ends
        assert "op=all_gather" in mon.wedged_summary()
        assert "(open)" in mon.wedged_summary()

    def test_health_check_latches_on_desync(self):
        mon = make_monitor(0, FakeClock())
        assert mon.health_check()["ok"]
        mon.note_desync({"first_seq": 7})
        out = mon.health_check()
        assert not out["ok"]
        assert out["desync_count"] == 1 and out["first_seq"] == 7


class TestDesyncFold:

    def _views(self, divergent_dtype):
        ops = [("all_reduce", "float32"), ("all_reduce", "float32"),
               ("reduce_scatter", "float32"), ("all_reduce", "float32")]
        views = []
        for rank in range(2):
            clock = FakeClock()
            mon = make_monitor(rank, clock)
            for i, (op, dtype) in enumerate(ops):
                if rank == 1 and i == 2:
                    dtype = divergent_dtype
                stage(mon, clock, op=op, dtype=dtype, at_us=i * 1000)
            views.append(mon.window_view())
        return views

    def test_detected_at_exact_first_divergent_seq(self):
        health = cm.fold_windows(self._views("bfloat16"))
        d = health["desync"]
        assert d["detected"] and d["first_seq"] == 3
        assert d["ranks"] == [0, 1]
        fps = d["fingerprints"]
        assert fps["0"]["dtype"] == "float32"
        assert fps["1"]["dtype"] == "bfloat16"
        assert fps["0"]["fp"] != fps["1"]["fp"]
        assert fps["0"]["op"] == fps["1"]["op"] == "reduce_scatter"

    def test_identical_sequences_are_clean(self):
        health = cm.fold_windows(self._views("float32"))
        assert health["desync"] == {"detected": False}
        assert health["common_seqs"] == 4

    def test_missing_seq_is_not_desync(self):
        """Ring eviction / window-tail mismatch: a rank that merely lacks
        a seq is not desynced with the ranks that have it."""
        views = self._views("float32")
        views[1]["records"] = [r for r in views[1]["records"]
                               if r["seq"] != 2]
        health = cm.fold_windows(views)
        assert not health["desync"]["detected"]
        assert health["common_seqs"] == 3   # seq 2 excluded from skew too


class TestSkewAndStraggler:

    def _views(self, n_ranks=3, n_collectives=6, late_rank=2, late_us=50_000):
        views = []
        for rank in range(n_ranks):
            clock = FakeClock()
            mon = make_monitor(rank, clock)
            for i in range(n_collectives):
                at = i * 1_000_000 + (late_us if rank == late_rank else 0)
                op = "all_reduce" if i % 2 == 0 else "all_gather"
                stage(mon, clock, op=op, at_us=at // 1)
            views.append(mon.window_view())
        return views

    def test_straggler_named_with_ew_score(self):
        health = cm.fold_windows(self._views())
        strag = health["straggler"]
        assert strag["rank"] == 2
        # every collective exactly 50ms late: EW from 0 over 6 samples
        assert strag["score_ms"] == pytest.approx(
            50.0 * (1.0 - 0.8 ** 6), rel=1e-6)
        assert strag["scores_ms"]["0"] == 0.0
        skew = health["skew"]
        assert skew["count"] == 6
        assert skew["max_ms"] == pytest.approx(50.0)
        assert skew["p99_ms"] <= 100.0      # inside the 50..100ms bucket
        assert skew["last_seq"] == 6
        assert set(health["per_op_skew"]) == {"all_reduce", "all_gather"}
        assert health["per_op_skew"]["all_reduce"]["count"] == 3

    def test_new_after_gates_samples_not_histograms(self):
        health = cm.fold_windows(self._views(), new_after=4)
        assert health["skew"]["count"] == 6            # histogram: all seqs
        assert [s["seq"] for s in health["skew_samples"]] == [5, 6]

    def test_single_rank_has_no_skew(self):
        health = cm.fold_windows(self._views(n_ranks=1))
        assert health["n_ranks"] == 1
        assert health["skew"]["count"] == 0
        assert health["straggler"]["rank"] is None


class TestFoldParity:
    """The acceptance proof: host fold == device-gather fold == offline
    JSONL fold, on the 8-virtual-device CPU mesh."""

    def _views(self):
        views = []
        for rank in range(3):
            clock = FakeClock()
            mon = make_monitor(rank, clock)
            for i in range(5):
                stage(mon, clock, op="all_reduce" if i % 2 else "all_gather",
                      dtype="float32", shape=(8, 2 + i),
                      nbytes=64 * (i + 1), at_us=i * 10_000 + rank * 700)
            # one open record per rank: exit stamps must survive packing
            mon.begin("reduce_scatter", "dp", "float32", (4,), 16)
            views.append(mon.window_view())
        return views

    @staticmethod
    def _comparable(health):
        return {k: health[k] for k in
                ("n_ranks", "ranks", "seq_lo", "seq_hi", "common_seqs",
                 "skew", "per_op_skew", "straggler", "desync")}

    def test_three_way_fold_parity(self):
        assert jax.device_count() == 8
        views = self._views()
        host = cm.fold_windows(views)

        device_views = cm.gather_windows_over_mesh(views)
        device = cm.fold_windows(device_views)

        jsonl = [json.loads(json.dumps(
            {"kind": "collective_window", "rank": v["rank"],
             "records": v["records"]})) for v in views]
        offline = cm.fold_window_records(jsonl)

        assert self._comparable(device) == self._comparable(host)
        assert self._comparable(offline) == self._comparable(host)
        assert host["straggler"]["rank"] == 2    # +700us per rank seeded
        assert host["common_seqs"] == 6          # open seq-6 records common too

    def test_pack_unpack_round_trip(self):
        view = self._views()[1]
        base = min(r["t_enter_us"] for r in view["records"])
        meta, vec = cm.pack_window(view, base, width=8)
        back = cm.unpack_window(vec, meta, view["rank"], base)
        assert back["rank"] == view["rank"]
        assert len(back["records"]) == len(view["records"])
        for a, b in zip(view["records"], back["records"]):
            assert b["seq"] == a["seq"] and b["fp"] == a["fp"]
            assert b["t_enter_us"] == a["t_enter_us"]
            assert b["bytes"] == a["bytes"]
            assert b["op"] == a["op"] and b["shape"] == list(a["shape"])
            assert (b["t_exit_us"] is None) == (a["t_exit_us"] is None)

    def test_fold_window_records_merges_overlapping_windows(self):
        views = self._views()
        recs = []
        for v in views:
            # two overlapping windows per rank: early half, then full ring
            recs.append({"kind": "collective_window", "rank": v["rank"],
                         "records": v["records"][:3]})
            recs.append({"kind": "collective_window", "rank": v["rank"],
                         "records": v["records"]})
        health = cm.fold_window_records(recs)
        assert self._comparable(health) == self._comparable(
            cm.fold_windows(views))
        assert cm.fold_window_records([{"kind": "step", "step": 1}]) is None


class TestFacadeInstrumentation:

    def setup_method(self):
        clear_plan()
        set_global_tracer(None)
        C.configure_collective_monitor(None)

    teardown_method = setup_method

    def test_staged_collectives_get_seq_fp_and_span_args(self):
        from jax.sharding import Mesh, PartitionSpec as P

        mon = cm.CollectiveMonitor(rank=0)
        tracer = Tracer(rank=0)
        C.configure_collective_monitor(mon)
        set_global_tracer(tracer)
        try:
            mesh = Mesh(np.array(jax.devices()), ("dp",))

            def prog(x):
                y = C.all_reduce(x, group="dp")
                return C.all_gather(y, group="dp", axis=0, tiled=True)

            fn = jax.jit(jax.shard_map(prog, mesh=mesh, in_specs=P("dp"),
                                       out_specs=P(None), check_vma=False))
            x = jnp.arange(8.0)
            fn(x).block_until_ready()
        finally:
            C.configure_collective_monitor(None)
            set_global_tracer(None)

        assert mon.seq == 2
        recs = mon.last_records()
        assert [r["op"] for r in recs] == ["all_reduce", "all_gather"]
        assert [r["seq"] for r in recs] == [1, 2]
        for r in recs:
            assert r["axis"] == "dp" and r["fp"] != 0
            assert r["t_exit_us"] is not None
        # S1: the comm spans carry the seq, joining timelines to records
        spans = [s for s in tracer.snapshot()
                 if s["name"].startswith("comm.")]
        assert {(s["name"], s["args"]["seq"]) for s in spans} == {
            ("comm.all_reduce", 1), ("comm.all_gather", 2)}

        # trace-time semantics: a cache hit stages nothing new
        fn(x).block_until_ready()
        assert mon.seq == 2

    def test_facade_works_with_no_monitor_installed(self):
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()), ("dp",))
        fn = jax.jit(jax.shard_map(lambda x: C.all_reduce(x, group="dp"),
                                   mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))
        out = fn(jnp.ones(8))
        assert float(out[0]) == 8.0


class TestStragglerE2E:
    """A DS_FAULT_PLAN-delayed virtual rank on the 8-virtual-device mesh
    is named straggler by the fold, by ``/collectives``, and by
    ``tools/collective_report.py``."""

    LATE_RANK = 5
    DELAY_S = 0.05

    def setup_method(self):
        clear_plan()
        C.configure_collective_monitor(None)

    teardown_method = setup_method

    def _replay_views(self):
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()), ("dp",))
        views = []
        for rank in range(8):
            mon = cm.CollectiveMonitor(rank=rank)
            C.configure_collective_monitor(mon)
            if rank == self.LATE_RANK:
                # delay the 2nd collective this rank stages
                install_plan([{"site": "comm.collective", "action": "delay",
                               "delay_s": self.DELAY_S, "on_hit": 2}])
            try:
                def prog(x):
                    y = C.all_gather(x, group="dp", axis=0, tiled=True)
                    return C.all_reduce(y, group="dp")

                jax.jit(jax.shard_map(prog, mesh=mesh, in_specs=P("dp"),
                                      out_specs=P(None), check_vma=False))(
                    jnp.ones(8)).block_until_ready()
            finally:
                C.configure_collective_monitor(None)
                clear_plan()
            view = mon.window_view()
            # the virtual ranks replayed sequentially on one host: align
            # each rank's first staging stamp on a common base so only
            # *intra-sequence* lateness (the injected delay) remains
            base = view["records"][0]["t_enter_us"]
            for r in view["records"]:
                r["t_enter_us"] -= base
            views.append(view)
        return views

    def test_delayed_rank_named_everywhere(self, tmp_path):
        views = self._replay_views()

        # 1. the fold names the straggler
        health = cm.fold_windows(views)
        assert health["n_ranks"] == 8 and health["common_seqs"] == 2
        assert not health["desync"]["detected"]
        assert health["straggler"]["rank"] == self.LATE_RANK
        assert health["skew"]["max_ms"] >= self.DELAY_S * 1e3 * 0.6

        # 2. /collectives serves the same verdict
        hub = make_hub()
        hub.collective_monitor = cm.CollectiveMonitor(rank=0)
        hub.collective_fold(per_rank_views=views, step=1)
        reg = MetricsRegistry()
        srv = ObsServer(reg, port=0).start()
        try:
            srv.collectives_fn = hub.collective_status
            code, body = _get(f"{srv.url}/collectives")
        finally:
            srv.stop()
        assert code == 200
        out = json.loads(body)
        assert out["health"]["straggler"]["rank"] == self.LATE_RANK
        assert out["desync_count"] == 0

        # 3. the offline report over per-rank JSONL names it too
        from tools import collective_report
        paths = []
        for v in views:
            p = tmp_path / f"telemetry_rank{v['rank']}.jsonl"
            p.write_text(json.dumps(
                {"kind": "collective_window", "rank": v["rank"],
                 "records": v["records"]}) + "\n")
            paths.append(str(p))
        rc = collective_report.main(
            paths + ["--forbid-desync",
                     "--json", str(tmp_path / "report.json")])
        assert rc == 0
        report = json.loads((tmp_path / "report.json").read_text())
        assert report["ok"] and report["tool"] == "collective_report"
        assert report["straggler"]["rank"] == self.LATE_RANK
        assert report["gates"]["forbid_desync"]["ok"]

        # gate flips: a tight skew bound fails the same artifact set
        assert collective_report.main(
            paths + ["--max-skew-ms", "0.001"]) == 1
        # usage error: a JSONL with no window records
        bare = tmp_path / "bare.jsonl"
        bare.write_text(json.dumps({"kind": "step", "step": 1}) + "\n")
        assert collective_report.main([str(bare)]) == 2

    def test_report_fails_desynced_run(self, tmp_path, capsys):
        from tools import collective_report
        paths = []
        for rank in range(2):
            clock = FakeClock()
            mon = make_monitor(rank, clock)
            stage(mon, clock, op="all_reduce")
            stage(mon, clock,
                  dtype="float32" if rank == 0 else "bfloat16")
            p = tmp_path / f"r{rank}.jsonl"
            p.write_text(json.dumps(
                {"kind": "collective_window", "rank": rank,
                 "records": mon.window_view()["records"]}) + "\n")
            paths.append(str(p))
        assert collective_report.main(paths + ["--forbid-desync"]) == 1
        report = json.loads(capsys.readouterr().out)
        assert report["desync"]["detected"]
        assert report["desync"]["first_seq"] == 2


class TestWedgeAndHealthz:

    def test_wedged_collective_survives_into_watchdog_dump(self, tmp_path):
        """A collective that never exits: the watchdog fires, the flight
        recorder dump's ``collectives`` section ends with the open record
        naming the stuck op."""
        from deepspeed_tpu.telemetry.watchdog import HangWatchdog

        clock = FakeClock()
        mon = make_monitor(0, clock)
        stage(mon, clock, op="all_gather")          # a healthy one first
        mon.begin("all_reduce", "dp", "float32", (1024,), 4096)  # wedge

        fr = FlightRecorder(str(tmp_path), collective_monitor=mon)
        paths = []
        wd = HangWatchdog(timeout_s=10.0, clock=clock,
                          on_stall=lambda w, s, what: paths.append(
                              fr.on_stall(w, s, what)))
        wd.context_fn = mon.wedged_summary
        wd.arm("train_step")
        clock.advance_us(11_000_000)
        assert wd.check() is True
        assert len(paths) == 1

        dump = read_dump(paths[0])
        sec = dump["collectives"][0]
        assert sec["seq"] == 2 and sec["desync_count"] == 0
        stuck = sec["records"][-1]
        assert stuck["op"] == "all_reduce" and stuck["t_exit_us"] is None
        assert "op=all_reduce" in mon.wedged_summary()
        assert "(open)" in mon.wedged_summary()

    def test_dump_without_monitor_has_empty_section(self, tmp_path):
        fr = FlightRecorder(str(tmp_path))
        dump = read_dump(fr.dump(reason="manual"))
        assert dump["collectives"][0] == {"records": [], "seq": 0,
                                          "desync_count": 0}

    def test_healthz_503_after_desync(self):
        mon = make_monitor(0, FakeClock())
        reg = MetricsRegistry()
        srv = ObsServer(reg, port=0).start()
        try:
            srv.add_health_check("collective_desync",
                                 collective_desync_health_check(mon))
            code, body = _get(f"{srv.url}/healthz")
            assert code == 200 and json.loads(body)["healthy"]

            mon.note_desync({"first_seq": 9, "ranks": [0, 1]})
            code, body = _get(f"{srv.url}/healthz")
            out = json.loads(body)
            assert code == 503 and not out["healthy"]
            check = out["checks"]["collective_desync"]
            assert check["ok"] is False and check["first_seq"] == 9

            # latched: no later event can flip it back within the run
            code, _ = _get(f"{srv.url}/healthz")
            assert code == 503
        finally:
            srv.stop()


class TestHubIntegration:

    def _fold_views(self, divergent=False, late_us=40_000):
        views = []
        for rank in range(2):
            clock = FakeClock()
            mon = make_monitor(rank, clock)
            for i in range(4):
                dtype = ("bfloat16" if divergent and rank == 1 and i == 3
                         else "float32")
                stage(mon, clock, dtype=dtype,
                      at_us=i * 100_000 + (late_us if rank == 1 else 0))
            views.append(mon.window_view())
        return views

    def test_from_config_builds_and_wires_monitor(self):
        from types import SimpleNamespace
        tcfg = SimpleNamespace(jsonl_path="", ring_buffer_size=32,
                               flush_every=0, metrics=True, snapshot_every=1,
                               slo_rules=None, goodput=False,
                               collective_monitor=True, collective_ring=8,
                               ops_server=False)
        hub = TelemetryHub.from_config(tcfg)
        try:
            assert hub.collective_monitor is not None
            assert hub.collective_monitor.capacity == 8
        finally:
            hub.close()

        tcfg.collective_monitor = False
        hub = TelemetryHub.from_config(tcfg)
        try:
            assert hub.collective_monitor is None
        finally:
            hub.close()

    def test_fold_emits_window_health_and_feeds_registry_once(self):
        reg = MetricsRegistry()
        ring = RingBufferSink(128)
        hub = make_hub(sinks=[ring, MetricsSink(reg)])
        hub.collective_monitor = cm.CollectiveMonitor(rank=0)

        views = self._fold_views()
        hub.collective_fold(per_rank_views=views, step=1)
        hub.flush()
        assert ring.last(events.COLLECTIVE_WINDOW) is not None
        health_rec = ring.last(events.COLLECTIVE_HEALTH)
        assert health_rec["straggler"]["rank"] == 1
        snap = reg.snapshot()
        hist = snap["histograms"]["collective_skew_ms"]
        assert hist["count"] == 4
        assert 'collective_skew_ms{op="all_reduce"}' in snap["histograms"]
        assert snap["gauges"]["collective_straggler_rank"]["value"] == 1.0
        assert snap["gauges"][
            'collective_straggler_score_ms{rank="1"}']["value"] > 0.0

        # incremental feed: refolding the same window re-observes nothing
        hub.collective_fold(per_rank_views=views, step=2)
        hub.flush()
        assert reg.snapshot()["histograms"][
            "collective_skew_ms"]["count"] == 4

    def test_desync_event_emitted_once_and_latches(self):
        reg = MetricsRegistry()
        ring = RingBufferSink(128)
        hub = make_hub(sinks=[ring, MetricsSink(reg)])
        hub.collective_monitor = cm.CollectiveMonitor(rank=0)

        views = self._fold_views(divergent=True)
        hub.collective_fold(per_rank_views=views, step=1)
        hub.collective_fold(per_rank_views=views, step=2)
        hub.flush()
        desyncs = ring.of_kind(events.COLLECTIVE_DESYNC)
        assert len(desyncs) == 1
        assert desyncs[0]["first_seq"] == 4
        assert hub.collective_monitor.desync_count == 1
        assert not hub.collective_monitor.health_check()["ok"]
        snap = reg.snapshot()
        assert snap["counters"]["collective_desync_total"]["value"] == 1.0
        assert snap["gauges"]["collective_desync_first_seq"]["value"] == 4.0

    def test_fold_feeds_ledger_straggler_share(self):
        hub = make_hub()
        hub.collective_monitor = cm.CollectiveMonitor(rank=0)
        hub.ledger = GoodputLedger()
        hub.collective_fold(per_rank_views=self._fold_views(late_us=40_000))
        # 4 common seqs x 40ms skew = 0.16s booked as straggler share
        assert hub.ledger.exposed_comm_straggler_s == pytest.approx(
            0.16, rel=1e-3)
        snap = hub.ledger.snapshot()
        assert snap["exposed_comm_straggler_s"] == pytest.approx(
            0.16, rel=1e-3)
        assert "exposed_comm_straggler_frac" in snap

    def test_close_runs_final_fold_into_jsonl(self, tmp_path):
        from types import SimpleNamespace
        path = str(tmp_path / "telemetry.jsonl")
        tcfg = SimpleNamespace(jsonl_path=path, ring_buffer_size=0,
                               flush_every=0, metrics=True, snapshot_every=0,
                               slo_rules=None, goodput=False,
                               collective_monitor=True, collective_ring=16,
                               ops_server=False)
        hub = TelemetryHub.from_config(tcfg)
        rec = hub.collective_monitor.begin("all_reduce", "dp", "float32",
                                           (4,), 16)
        hub.collective_monitor.end(rec)
        hub.close()
        kinds = [json.loads(l).get("kind")
                 for l in open(path) if l.strip()]
        assert events.COLLECTIVE_WINDOW in kinds
        assert events.COLLECTIVE_HEALTH in kinds

        # the short run's artifact satisfies the offline report
        from tools import collective_report
        assert collective_report.main([path, "--forbid-desync"]) == 0
