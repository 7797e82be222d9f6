"""CLI-level tests for the goodput tooling: ``tools/goodput_report.py``
(JSONL fold + EFFICIENCY.json artifact input, gates, 0/1/2 exits),
and the uniform ``--json`` envelope (``tool`` +
``report_schema`` keys from ``telemetry/stats.py:finalize_report``)
shared by every report CLI."""

import importlib.util
import json
import os

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO_ROOT, "tools", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ledger_mod():
    spec = importlib.util.spec_from_file_location(
        "_ledger_for_tools", os.path.join(
            REPO_ROOT, "deepspeed_tpu", "telemetry", "ledger.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_jsonl(path, records):
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")


def _goodput_rec(ledmod, run_id, wall, productive, downcat=0.0, lost=0,
                 steps=1):
    cats = {c: 0.0 for c in ledmod.CATEGORIES}
    cats["productive"] = productive
    cats["downtime"] = downcat
    cats["idle_other"] = wall - productive - downcat
    return {"kind": "goodput", "schema": 1, "mode": "train",
            "run_id": run_id, "wall_s": wall, "categories": cats,
            "steps": steps, "productive_steps": steps,
            "lost_work_steps": lost, "rollbacks": 1 if lost else 0,
            "quarantine_skips": 0,
            "goodput_frac": productive / wall, "mfu": None}


class TestGoodputReport:
    def test_clean_run_gates_exit_0(self, tmp_path):
        led = _ledger_mod()
        path = tmp_path / "t.jsonl"
        _write_jsonl(path, [_goodput_rec(led, "a1", 10.0, 9.5)])
        tool = _tool("goodput_report")
        out = tmp_path / "rep.json"
        assert tool.main([str(path), "--min-goodput-frac", "0.9",
                          "--max-lost-steps", "0",
                          "--json", str(out)]) == 0
        rep = json.loads(out.read_text())
        assert rep["tool"] == "goodput_report"
        assert rep["report_schema"] == 1
        assert rep["source"] == "jsonl"
        assert rep["ok"] is True
        assert rep["gates"]["max_conservation_err"]["ok"] is True

    def test_lossy_run_fails_goodput_and_lost_step_gates(self, tmp_path):
        led = _ledger_mod()
        path = tmp_path / "t.jsonl"
        _write_jsonl(path, [
            _goodput_rec(led, "a1", 10.0, 5.0, lost=3),
            {"kind": "downtime", "schema": 1, "downtime_s": 5.0},
        ])
        tool = _tool("goodput_report")
        assert tool.main([str(path), "--min-goodput-frac", "0.9"]) == 1
        assert tool.main([str(path), "--max-lost-steps", "2"]) == 1
        assert tool.main([str(path), "--min-goodput-frac", "0.2",
                          "--max-lost-steps", "3"]) == 0

    def test_conservation_always_gated(self, tmp_path):
        led = _ledger_mod()
        rec = _goodput_rec(led, "a1", 10.0, 9.0)
        rec["categories"]["idle_other"] = 5.0     # over-claims the wall
        path = tmp_path / "t.jsonl"
        _write_jsonl(path, [rec])
        tool = _tool("goodput_report")
        assert tool.main([str(path)]) == 1
        # a loose epsilon lets the same file through
        assert tool.main([str(path), "--max-conservation-err", "0.5"]) == 0

    def test_artifact_input_agrees_with_fold(self, tmp_path):
        led = _ledger_mod()
        clockbox = {"t": 0.0}
        ledger = led.GoodputLedger(clock=lambda: clockbox["t"])
        clockbox["t"] = 2.0
        ledger.on_step(1)
        snap = ledger.snapshot(now=2.0)
        eff = tmp_path / "EFFICIENCY.json"
        ledger.write_efficiency_json(str(eff), snap=snap)
        jsonl = tmp_path / "t.jsonl"
        _write_jsonl(jsonl, [dict(snap, kind="goodput")])
        tool = _tool("goodput_report")
        out_a, out_j = tmp_path / "a.json", tmp_path / "j.json"
        assert tool.main([str(eff), "--json", str(out_a)]) == 0
        assert tool.main([str(jsonl), "--json", str(out_j)]) == 0
        rep_a = json.loads(out_a.read_text())
        rep_j = json.loads(out_j.read_text())
        assert rep_a["source"] == "artifact"
        assert rep_a["categories"] == pytest.approx(rep_j["categories"])
        assert rep_a["wall_s"] == pytest.approx(rep_j["wall_s"])
        assert rep_a["goodput_frac"] == pytest.approx(rep_j["goodput_frac"])

    def test_no_goodput_data_exits_2(self, tmp_path):
        path = tmp_path / "t.jsonl"
        _write_jsonl(path, [{"kind": "step", "step": 1, "schema": 1}])
        tool = _tool("goodput_report")
        assert tool.main([str(path)]) == 2
        assert tool.main([str(tmp_path / "missing.jsonl")]) == 2


class TestUniformJsonEnvelope:
    """Every report CLI stamps the same envelope keys into its --json
    output while keeping its historical top-level payload fields."""

    def _check(self, out_path, tool_name):
        rep = json.loads(out_path.read_text())
        assert rep["tool"] == tool_name
        assert rep["report_schema"] == 1
        assert "ok" in rep
        return rep

    def test_serve_report(self, tmp_path):
        path = tmp_path / "t.jsonl"
        _write_jsonl(path, [
            {"kind": "serve_request", "schema": 1, "event": "finished",
             "rid": 1, "slo": "standard", "new_tokens": 4,
             "ttft_ms": 10.0, "latency_ms": 20.0, "tokens_per_sec": 10.0},
        ])
        out = tmp_path / "r.json"
        assert _tool("serve_report").main([str(path), "--json",
                                           str(out)]) == 0
        rep = self._check(out, "serve_report")
        assert rep["finished"] == 1          # payload stays top-level

    def test_offload_audit(self, tmp_path):
        path = tmp_path / "t.jsonl"
        _write_jsonl(path, [
            {"kind": "offload_staged", "schema": 1, "step": 1,
             "wait_ms": 1.0, "ring_hits": 3, "ring_misses": 1,
             "nvme_bytes_written": 64, "nvme_bytes_read": 64},
            {"kind": "step", "schema": 1, "step": 1, "step_time_ms": 100.0},
        ])
        out = tmp_path / "r.json"
        assert _tool("offload_audit").main([str(path), "--json",
                                            str(out)]) == 0
        rep = self._check(out, "offload_audit")
        assert rep["ok"] is True
        assert rep["gates"]["max_stall_frac"]["ok"] is True
        assert rep["gates"]["min_hit_rate"]["value"] == 0.75
        # the inline gate semantics survived the gates-dict conversion
        assert _tool("offload_audit").main(
            [str(path), "--min-hit-rate", "0.9"]) == 1

    def test_stability_report(self, tmp_path):
        path = tmp_path / "t.jsonl"
        _write_jsonl(path, [
            {"kind": "anomaly", "schema": 1, "step": 3, "cause":
             "nonfinite_loss", "detected_at": 3},
            {"kind": "step", "schema": 1, "step": 3, "step_time_ms": 5.0},
        ])
        out = tmp_path / "r.json"
        assert _tool("stability_report").main([str(path), "--json",
                                               str(out)]) == 0
        rep = self._check(out, "stability_report")
        assert rep["anomalies"] == 1

    def test_obs_report(self, tmp_path):
        path = tmp_path / "t.jsonl"
        _write_jsonl(path, [
            {"kind": "step", "schema": 1, "step": 1, "loss": 1.0,
             "step_time_ms": 5.0},
        ])
        out = tmp_path / "r.json"
        assert _tool("obs_report").main([str(path), "--json",
                                         str(out)]) == 0
        rep = self._check(out, "obs_report")
        assert rep["records"] == 1           # payload stays top-level
