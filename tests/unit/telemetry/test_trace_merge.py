"""tools/trace_merge.py end to end on two synthetic rank traces, plus the
clock-discipline static check (tools/check_monotonic.py) as a suite gate."""

import importlib.util
import json
import os

import pytest

from deepspeed_tpu.telemetry import Tracer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO_ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


trace_merge = _load_tool("trace_merge")
check_monotonic = _load_tool("check_monotonic")


_WALL_BASE_NS = 1_700_000_000_000_000_000   # pinned anchor for exact skew


def write_rank_trace(tmp_path, rank, wall_offset_ns=0):
    """A synthetic rank trace through the real Tracer export path."""
    tr = Tracer(rank=rank)
    tr.epoch_wall_ns = _WALL_BASE_NS + wall_offset_ns  # skewed host clock
    with tr.span("train_batch", step=1):
        with tr.span("comm.all_reduce", op="all_reduce", bytes=4096):
            pass
    path = str(tmp_path / f"trace_rank{rank}.json")
    return tr.export_chrome_trace(path)


class TestTraceMerge:

    def test_merge_two_ranks_valid_schema(self, tmp_path):
        p0 = write_rank_trace(tmp_path, 0)
        p1 = write_rank_trace(tmp_path, 1, wall_offset_ns=2_000_000)  # +2ms
        out = str(tmp_path / "merged.json")
        rc = trace_merge.main([p0, p1, "-o", out])
        assert rc == 0
        doc = json.load(open(out))

        # valid Chrome-trace object: traceEvents list, every event carries
        # the required keys for its phase
        assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
        for ev in doc["traceEvents"]:
            assert {"name", "ph", "pid", "tid"} <= set(ev)
            if ev["ph"] == "X":
                assert "ts" in ev and "dur" in ev and ev["dur"] >= 0
        json.dumps(doc)      # round-trips as JSON

        # both ranks present as distinct pids
        pids = {ev["pid"] for ev in doc["traceEvents"]}
        assert pids == {0, 1}

        # clock alignment: rank1's anchor is 2ms later, so its spans are
        # shifted +2000us relative to rank0's
        meta = {r["rank"]: r for r in doc["metadata"]["ranks"]}
        assert meta[0]["shift_us"] == pytest.approx(0.0)
        assert meta[1]["shift_us"] == pytest.approx(2000.0)
        tb = {ev["pid"]: ev for ev in doc["traceEvents"]
              if ev["name"] == "train_batch"}
        # each rank's span opens a few us after its (pinned) anchor, so
        # the merged gap is the injected skew up to scheduling jitter
        assert tb[1]["ts"] - tb[0]["ts"] == pytest.approx(2000.0, abs=1000.0)

    def test_merge_preserves_span_args_and_names(self, tmp_path):
        p0 = write_rank_trace(tmp_path, 0)
        p1 = write_rank_trace(tmp_path, 1)
        merged = trace_merge.merge_traces([trace_merge.load_rank_trace(p0),
                                           trace_merge.load_rank_trace(p1)])
        comms = [e for e in merged["traceEvents"]
                 if e["name"] == "comm.all_reduce"]
        assert len(comms) == 2
        assert all(e["args"]["bytes"] == 4096 for e in comms)
        assert all(e["cat"] == "comm" for e in comms)

    def test_flops_breakdown_folds_into_metadata(self, tmp_path):
        p0 = write_rank_trace(tmp_path, 0)
        jsonl = tmp_path / "telemetry.jsonl"
        jsonl.write_text(json.dumps({
            "kind": "flops_breakdown", "schema": 1, "step": 4,
            "flops_per_step": 1.0e12, "latency_s": 0.5,
            "modules": [{"scope": "blocks.0", "op": "dot_general",
                         "flops": 500, "calls": 2}]}) + "\n")
        out = str(tmp_path / "merged.json")
        rc = trace_merge.main([p0, "-o", out, "--flops", str(jsonl)])
        assert rc == 0
        doc = json.load(open(out))
        fb = doc["metadata"]["flops_breakdown"]
        assert fb["flops_per_step"] == 1.0e12
        assert fb["modules"][0]["scope"] == "blocks.0"

    def test_rejects_non_trace_input(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"hello": 1}))
        rc = trace_merge.main([str(bad), "-o", str(tmp_path / "o.json")])
        assert rc == 1

    def test_rejects_trace_without_clock_sync(self, tmp_path):
        bad = tmp_path / "noanchor.json"
        bad.write_text(json.dumps({"traceEvents": [], "metadata": {}}))
        with pytest.raises(trace_merge.TraceFormatError):
            trace_merge.load_rank_trace(str(bad))


class TestCheckMonotonic:

    def test_repo_tracing_paths_are_clean(self):
        """The suite gate: watchdog/tracing/flight-recorder must never use
        a wall clock for durations."""
        assert check_monotonic.check_files() == []

    def test_cli_exit_zero_on_clean_tree(self):
        assert check_monotonic.main([]) == 0

    def test_detects_time_time(self, tmp_path):
        p = tmp_path / "bad.py"
        p.write_text("import time\n\nt0 = time.time()\n")
        vs = check_monotonic.check_files([str(p)])
        assert len(vs) == 1 and "time.time()" in vs[0]

    def test_detects_time_ns_and_datetime(self, tmp_path):
        p = tmp_path / "bad.py"
        p.write_text("import time\nfrom datetime import datetime\n"
                     "a = time.time_ns()\nb = datetime.now()\n")
        vs = check_monotonic.check_files([str(p)])
        assert len(vs) == 2

    def test_detects_from_time_import(self, tmp_path):
        p = tmp_path / "bad.py"
        p.write_text("from time import time as now\nt = now()\n")
        vs = check_monotonic.check_files([str(p)])
        assert len(vs) == 2   # the import and the aliased call

    def test_pragma_sanctions_the_anchor_line(self, tmp_path):
        p = tmp_path / "ok.py"
        p.write_text("import time\n"
                     "anchor = time.time_ns()  # wall-clock anchor: ok\n"
                     "mono = time.monotonic_ns()\n")
        assert check_monotonic.check_files([str(p)]) == []
