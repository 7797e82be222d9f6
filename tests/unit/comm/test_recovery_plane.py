"""Out-of-band recovery control-plane tests (comm/recovery.py): policy
ladder decisions, file rendezvous wire format, coordinator liveness +
abort protocol, manager incident bookkeeping, and the agent-side exit
markers.  All host-side — no jax, no devices, no subprocesses except a
dead-pid probe."""

import json
import os
import threading

import pytest

from deepspeed_tpu.comm.recovery import (MESH_SHRINK_EXIT_CODE,
                                         RECOVERY_EXIT_CODES,
                                         RECOVERY_RESTART_EXIT_CODE,
                                         FileRendezvous, RecoveryCoordinator,
                                         RecoveryManager, RecoveryPolicy,
                                         _write_json_atomic,
                                         consume_recovery_marker,
                                         resolve_rank_world,
                                         write_recovery_marker)


# --------------------------------------------------------------------------- #
# Policy
# --------------------------------------------------------------------------- #

class TestRecoveryPolicy:
    def test_disabled_by_default(self):
        assert not RecoveryPolicy.from_config({}).enabled
        assert not RecoveryPolicy.from_config(None).enabled
        assert not RecoveryPolicy.from_config(
            {"elasticity": {"enabled": True}}).enabled   # solver key only

    def test_from_config_reads_elasticity_block(self):
        pol = RecoveryPolicy.from_config({"elasticity": {
            "recovery_enabled": True, "collective_timeout_s": 7.5,
            "max_step_retries": 1, "min_world_size": 2,
            "allow_restart": False}})
        assert pol.enabled
        assert pol.collective_timeout_s == 7.5
        assert pol.max_step_retries == 1
        assert pol.min_world_size == 2
        assert not pol.allow_restart

    def test_from_config_object_form(self):
        class Cfg:
            elasticity_config = {"recovery_enabled": True}
        assert RecoveryPolicy.from_config(Cfg()).enabled

    def test_shrink_target_power_of_two(self):
        pol = RecoveryPolicy(enabled=True)
        assert pol.shrink_target(7) == 4
        assert pol.shrink_target(4) == 4
        assert pol.shrink_target(3) == 2
        assert pol.shrink_target(1) == 1

    def test_shrink_target_respects_min_world(self):
        pol = RecoveryPolicy(enabled=True, min_world_size=4)
        assert pol.shrink_target(7) == 4
        assert pol.shrink_target(3) is None

    def test_ladder_all_alive_retries_then_restarts(self):
        """A wedge with every rank still alive must retry, never shrink
        (no rank to exclude), and escalate to restart when retries run
        out — the acceptance shape for the wedged-rank incident."""
        pol = RecoveryPolicy(enabled=True, max_step_retries=2)
        assert pol.next_rung(0, 8, 8) == "retry"
        assert pol.next_rung(1, 8, 8) == "retry"
        assert pol.next_rung(2, 8, 8) == "restart"

    def test_ladder_dead_rank_goes_straight_to_shrink(self):
        """A dead rank cannot be retried back to life: the first rung for
        a reduced survivor set is the shrink."""
        pol = RecoveryPolicy(enabled=True, max_step_retries=2)
        assert pol.next_rung(0, 7, 8) == "shrink"

    def test_ladder_shrink_disabled_falls_to_restart(self):
        pol = RecoveryPolicy(enabled=True, allow_shrink=False)
        assert pol.next_rung(0, 7, 8) == "restart"

    def test_ladder_everything_disabled_fails(self):
        pol = RecoveryPolicy(enabled=True, allow_shrink=False,
                             allow_restart=False, max_step_retries=0)
        assert pol.next_rung(0, 8, 8) == "fail"

    def test_retry_backoff_doubles(self):
        pol = RecoveryPolicy(enabled=True, retry_backoff_s=0.5)
        assert pol.retry_delay_s(0) == 0.5
        assert pol.retry_delay_s(1) == 1.0
        assert pol.retry_delay_s(2) == 2.0

    def test_resolve_rank_world_env(self, monkeypatch):
        monkeypatch.setenv("DS_RECOVERY_RANK", "3")
        monkeypatch.setenv("DS_RECOVERY_WORLD", "8")
        assert resolve_rank_world() == (3, 8)
        monkeypatch.delenv("DS_RECOVERY_RANK")
        monkeypatch.delenv("DS_RECOVERY_WORLD")
        monkeypatch.delenv("RANK", raising=False)
        monkeypatch.delenv("WORLD_SIZE", raising=False)
        assert resolve_rank_world() == (0, 1)


# --------------------------------------------------------------------------- #
# Rendezvous
# --------------------------------------------------------------------------- #

class TestFileRendezvous:
    def test_announce_and_members(self, tmp_path):
        a = FileRendezvous(str(tmp_path), rank=0, world_size=2)
        b = FileRendezvous(str(tmp_path), rank=1, world_size=2)
        a.announce()
        b.announce()
        assert sorted(a.members()) == [0, 1]
        assert a.members()[1]["pid"] == os.getpid()

    def test_heartbeats_carry_step(self, tmp_path):
        a = FileRendezvous(str(tmp_path), rank=0, world_size=1)
        a.heartbeat(step=17, epoch=2)
        hb = a.heartbeats()[0]
        assert hb["step"] == 17 and hb["epoch"] == 2
        assert hb["pid"] == os.getpid()

    def test_two_threads_beat_one_path(self, tmp_path):
        """The heartbeat thread and the main thread (``advance_epoch``)
        write one rank's heartbeat file: neither ``os.replace`` may find
        its temporary file taken by the other."""
        rdv = FileRendezvous(tmp_path, rank=1, world_size=2)
        failed = []

        def beat():
            try:
                for step in range(300):
                    rdv.heartbeat(step=step)
            except OSError as e:
                failed.append(e)

        threads = [threading.Thread(target=beat) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not failed, failed
        assert rdv.heartbeats()[1]["step"] == 299
        assert os.listdir(tmp_path / "hb") == ["rank_1.json"]

    def test_abort_first_writer_wins(self, tmp_path):
        a = FileRendezvous(str(tmp_path), rank=0, world_size=2)
        b = FileRendezvous(str(tmp_path), rank=1, world_size=2)
        doc_a, won_a = a.signal_abort(0, {"cause": "timeout_a"})
        doc_b, won_b = b.signal_abort(0, {"cause": "timeout_b"})
        assert won_a and not won_b
        # both converge on the winner's doc
        assert doc_b["cause"] == "timeout_a"
        assert a.read_abort(0)["cause"] == "timeout_a"
        # a different epoch is a fresh abort slot
        assert b.read_abort(1) is None

    def test_acks_accumulate(self, tmp_path):
        a = FileRendezvous(str(tmp_path), rank=0, world_size=2)
        b = FileRendezvous(str(tmp_path), rank=1, world_size=2)
        a.ack_abort(0)
        assert a.acks(0) == {0}
        b.ack_abort(0)
        assert a.acks(0) == {0, 1}
        assert a.acks(1) == set()

    def test_plan_roundtrip(self, tmp_path):
        a = FileRendezvous(str(tmp_path), rank=0, world_size=2)
        assert a.read_plan(0) is None
        a.publish_plan(0, {"rung": "shrink", "new_world": 4})
        assert a.read_plan(0)["new_world"] == 4

    def test_quarantine_merges(self, tmp_path):
        a = FileRendezvous(str(tmp_path), rank=0, world_size=8)
        a.write_quarantine([4], detail={"cause": "dead"})
        a.write_quarantine([6, 5])
        assert a.read_quarantine()["ranks"] == [4, 5, 6]


# --------------------------------------------------------------------------- #
# Coordinator
# --------------------------------------------------------------------------- #

def _coord(tmp_path, rank, world, **pol_kw):
    pol_kw.setdefault("heartbeat_timeout_s", 0.5)
    pol_kw.setdefault("recovery_deadline_s", 4.0)
    pol = RecoveryPolicy(enabled=True, **pol_kw)
    rdv = FileRendezvous(str(tmp_path), rank=rank, world_size=world)
    return RecoveryCoordinator(rdv, pol)


class TestRecoveryCoordinator:
    def test_live_ranks_same_host_pid_probe(self, tmp_path):
        c0 = _coord(tmp_path, 0, 2)
        c0.rdv.announce()
        c0.heartbeat_now()
        # fabricate a same-host rank whose pid is dead: detection must
        # not wait for the heartbeat to age out
        import socket
        _write_json_atomic(
            os.path.join(str(tmp_path), "hb", "rank_1.json"),
            {"rank": 1, "pid": 2 ** 22 + 12345, "host": socket.gethostname(),
             "t": __import__("time").time(), "step": 0, "epoch": 0})
        assert c0.live_ranks() == [0]
        assert c0.dead_ranks() == [1]

    @pytest.mark.skipif(not os.path.isdir("/proc"),
                        reason="needs /proc for zombie state")
    def test_pid_probe_counts_unreaped_zombie_as_dead(self, tmp_path):
        import subprocess
        from deepspeed_tpu.comm.recovery import RecoveryCoordinator
        # a SIGKILLed rank whose parent has not reaped it yet: signal-0
        # still succeeds, so the probe must read the /proc state
        child = subprocess.Popen(["true"])
        deadline = __import__("time").monotonic() + 10.0
        while __import__("time").monotonic() < deadline:
            with open(f"/proc/{child.pid}/stat") as f:
                if f.read().rpartition(")")[2].split()[0] == "Z":
                    break
            __import__("time").sleep(0.05)
        try:
            assert not RecoveryCoordinator._pid_alive(child.pid)
        finally:
            child.wait()
        assert not RecoveryCoordinator._pid_alive(child.pid)

    def test_live_ranks_remote_host_uses_heartbeat_age(self, tmp_path):
        c0 = _coord(tmp_path, 0, 2)
        c0.heartbeat_now()
        import time as _t
        # a remote rank with a fresh heartbeat is live regardless of pid
        _write_json_atomic(
            os.path.join(str(tmp_path), "hb", "rank_1.json"),
            {"rank": 1, "pid": 1, "host": "other-host", "t": _t.time(),
             "step": 0, "epoch": 0})
        assert 1 in c0.live_ranks()
        # ...and dead once the heartbeat is stale
        _write_json_atomic(
            os.path.join(str(tmp_path), "hb", "rank_1.json"),
            {"rank": 1, "pid": 1, "host": "other-host", "t": _t.time() - 60,
             "step": 0, "epoch": 0})
        assert 1 not in c0.live_ranks()

    def test_abort_barrier_converges(self, tmp_path):
        c0 = _coord(tmp_path, 0, 2)
        c1 = _coord(tmp_path, 1, 2)
        for c in (c0, c1):
            c.rdv.announce()
            c.heartbeat_now()
        c0.request_abort("collective_timeout")
        assert c1.poll_abort()["cause"] == "collective_timeout"
        out = {}
        t = threading.Thread(
            target=lambda: out.setdefault("s1", c1.abort_barrier()))
        t.start()
        s0 = c0.abort_barrier()
        t.join(timeout=10)
        assert s0 == [0, 1]
        assert out["s1"] == [0, 1]

    def test_leader_is_lowest_survivor(self, tmp_path):
        c1 = _coord(tmp_path, 1, 8)
        assert c1.is_leader([1, 2, 3])
        assert not c1.is_leader([0, 1, 2])

    def test_plan_publish_and_await(self, tmp_path):
        c0 = _coord(tmp_path, 0, 2)
        c1 = _coord(tmp_path, 1, 2)
        plan = c0.publish_plan({"rung": "shrink", "new_world": 1})
        assert plan["leader"] == 0 and plan["epoch"] == 0
        got = c1.await_plan(deadline_s=2.0)
        assert got["new_world"] == 1

    def test_advance_epoch_clears_abort_scope(self, tmp_path):
        c0 = _coord(tmp_path, 0, 1)
        c0.rdv.announce()
        c0.request_abort("x")
        assert c0.poll_abort() is not None
        c0.advance_epoch(new_world_size=1)
        assert c0.epoch == 1
        assert c0.poll_abort() is None

    def test_heartbeat_thread_lifecycle(self, tmp_path):
        c0 = _coord(tmp_path, 0, 1, heartbeat_interval_s=0.05)
        c0.start()
        import time as _t
        _t.sleep(0.2)
        c0.note_step(5)
        _t.sleep(0.2)
        c0.stop()
        assert c0.rdv.heartbeats()[0]["step"] == 5


# --------------------------------------------------------------------------- #
# Manager
# --------------------------------------------------------------------------- #

class FakeLedger:
    def __init__(self):
        self.booked = []

    def note_comm_recovery(self, s):
        self.booked.append(s)


class FakeHub:
    def __init__(self):
        self.events = []

    def emit(self, kind, payload, **kw):
        self.events.append((kind, payload))

    def flush(self):
        ...


class TestRecoveryManager:
    def _mgr(self, clock=None, **pol_kw):
        pol = RecoveryPolicy(enabled=True, **pol_kw)
        hub, ledger = FakeHub(), FakeLedger()
        kw = {"telemetry": hub, "ledger": ledger}
        if clock is not None:
            kw["clock"] = clock
        return RecoveryManager(pol, **kw), hub, ledger

    def test_incident_lifecycle_and_booking(self):
        t = [100.0]
        mgr, hub, ledger = self._mgr(clock=lambda: t[0])
        mgr.begin_incident("collective_timeout", step=7, backdate_s=2.0)
        assert mgr.status()["ladder_state"] == "aborting"
        assert not mgr.health_check()["ok"]
        mgr.note_rung("retry", attempt=0)
        t[0] += 1.0                       # ladder work
        booked = mgr.book_rung_complete()
        assert booked == pytest.approx(3.0)     # 2.0 backdated + 1.0 ladder
        t[0] += 5.0                       # the retried step itself: NOT booked
        dt = mgr.note_recovered("retry")
        assert dt == pytest.approx(8.0)   # end-to-end incident duration
        assert ledger.booked == [pytest.approx(3.0)]   # only the ladder time
        st = mgr.status()
        assert st["incidents"] == 1 and st["recoveries"] == 1
        assert st["ladder_state"] == "recovered"
        assert mgr.health_check()["ok"]    # recovered run is healthy again
        kinds = [k for k, _ in hub.events]
        assert kinds == ["collective_abort", "recovery_retry",
                         "recovery_resume"]

    def test_note_recovered_books_fallback_when_unbooked(self):
        t = [0.0]
        mgr, _, ledger = self._mgr(clock=lambda: t[0])
        mgr.begin_incident("x")
        t[0] += 2.5
        mgr.note_recovered("retry")
        assert ledger.booked == [pytest.approx(2.5)]

    def test_failed_latches_health(self):
        mgr, hub, _ = self._mgr()
        mgr.begin_incident("x")
        mgr.note_failed("ladder_exhausted")
        assert not mgr.health_check()["ok"]
        assert mgr.status()["ladder_state"] == "failed"
        assert hub.events[-1][0] == "recovery_failed"

    def test_rung_telemetry_kinds(self):
        mgr, hub, _ = self._mgr()
        mgr.begin_incident("x")
        mgr.note_rung("shrink", attempt=0, detail={"new_world": 4})
        mgr.note_rung("restart", attempt=1)
        kinds = [k for k, _ in hub.events]
        assert "mesh_shrink" in kinds and "recovery_restart" in kinds

    def test_quarantine_and_world_size_in_status(self):
        mgr, _, _ = self._mgr()
        mgr.note_quarantined([4, 7])
        mgr.note_world_size(4)
        st = mgr.status()
        assert st["quarantined_ranks"] == [4, 7]
        assert st["world_size"] == 4


# --------------------------------------------------------------------------- #
# Exit markers (elastic-agent handshake)
# --------------------------------------------------------------------------- #

class TestRecoveryMarkers:
    def test_exit_codes_are_distinct_and_reserved(self):
        assert MESH_SHRINK_EXIT_CODE != RECOVERY_RESTART_EXIT_CODE
        assert set(RECOVERY_EXIT_CODES) == {MESH_SHRINK_EXIT_CODE,
                                            RECOVERY_RESTART_EXIT_CODE}
        for code in RECOVERY_EXIT_CODES:
            assert 0 < code < 128        # not a signal-death rc

    def test_marker_roundtrip(self, tmp_path):
        write_recovery_marker(str(tmp_path), "mesh_shrink", epoch=3,
                              extra={"new_world": 4})
        doc = consume_recovery_marker(str(tmp_path))
        assert doc["cause"] == "mesh_shrink"
        assert doc["epoch"] == 3
        # one-shot: consumed markers do not classify a second exit
        assert consume_recovery_marker(str(tmp_path)) is None

    def test_stale_marker_ignored(self, tmp_path):
        write_recovery_marker(str(tmp_path), "restart")
        p = os.path.join(str(tmp_path), "recovery_exit.json")
        doc = json.load(open(p))
        doc["t"] -= 10_000
        with open(p, "w") as f:
            json.dump(doc, f)
        assert consume_recovery_marker(str(tmp_path), max_age_s=600) is None

    def test_missing_marker(self, tmp_path):
        assert consume_recovery_marker(str(tmp_path / "nope")) is None
