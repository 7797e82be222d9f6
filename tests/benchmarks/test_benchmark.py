"""Tests of the benchmark itself (``benchmarks/``): CPU only, quick, and none
loads libtpu.  What a number means on the chip is not tested here; that the
arithmetic, the generators, the trace reducer and the files fit together is.
"""

import collections
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.lib import arith, cells, draws
from benchmarks.lib.trace import Trace, op_name, self_times, subtract, union

ROOT = cells.ROOT
BENCH = cells.load_benchmark()
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
SUFFIX = {"train": "train_tokens_per_s", "ttft": "ttft_p90_ms",
          "tpot": "tpot_p90_ms", "gen": "serve_tokens_per_s"}


def _cells_of(metric):
    return metric.get("workloads", [w["name"] for w in BENCH["workloads"]])


# ---- the trace reducer on a trace with known answers ---------------------- #
@pytest.fixture(scope="module")
def synthetic(tmp_path_factory):
    from jax.profiler import ProfileData
    text = open(os.path.join(ROOT, "benchmarks/testdata/synthetic.xspace.txt")).read()
    text = "\n".join(l for l in text.splitlines() if not l.startswith("#"))
    path = tmp_path_factory.mktemp("trace") / "synthetic.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return Trace.from_file(str(path))


US = 1e-6


def test_trace_busy_is_the_union_and_idle_what_is_left(synthetic):
    assert synthetic.window_s() == pytest.approx(20 * US)
    assert synthetic.busy_s() == pytest.approx(15 * US)
    assert synthetic.idle_share() == pytest.approx(0.25)


def test_trace_op_time_is_self_time_by_kernel_name(synthetic):
    secs = synthetic.op_seconds()
    assert secs["paged_attention"] == pytest.approx(9 * US)
    assert secs["while"] == pytest.approx(3 * US)
    assert secs["fusion"] == pytest.approx(3 * US)
    assert sum(secs.values()) == pytest.approx(synthetic.busy_s())
    assert synthetic.op_counts()["paged_attention"] == 2


def test_trace_collectives_in_flight_and_exposed(synthetic):
    share, exposed = synthetic.collective_shares()
    assert share == pytest.approx(0.60)
    assert exposed == pytest.approx(0.35)


def test_trace_breakdown_names_what_the_host_did_in_a_gap(synthetic):
    b = synthetic.breakdown()
    assert b["device_ops"][0][0] == "paged_attention"
    assert b["idle_gaps"] == [["bench.engine_step:np.asarray(jax.Array)",
                               pytest.approx(5 * US)]]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_trace_readers_return_nothing_where_there_is_nothing(synthetic):
    from benchmarks.readers import device_trace as rd
    run = {"trace": None, "counters": {}, "notes": {}, "peaks": None}
    assert rd.device_idle_pct(run) is None
    assert rd.op_share_pct(run, ops=["paged_attention"]) is None
    run["trace"] = synthetic
    assert rd.device_idle_pct(run) == pytest.approx(25.0)
    assert rd.op_share_pct(run, ops=["paged_attention"]) == pytest.approx(60.0)
    assert rd.op_share_pct(run, ops=["flash_fwd"]) == 0.0    # a traced run without the op
    assert rd.collective_exposed_pct(run) == pytest.approx(35.0)


@pytest.mark.parametrize("text,name", [
    ("%paged_attention.7 = bf16[1,64,768]{2,1,0} custom-call(", "paged_attention"),
    ("%dynamic-slice_bitcast_fusion.4 = bf16[8192,16,768] fusion(", "dynamic-slice_bitcast_fusion"),
    ("%all-gather-start.12.1 = (bf16[8]) all-gather-start(", "all-gather-start"),
    ("%while = (s32[]) while(", "while"),
])
def test_op_name(text, name):
    assert op_name(text) == name


def test_interval_arithmetic():
    assert union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert subtract([[0, 10]], [[1, 2], [4, 6]]) == [(0, 1), (2, 4), (6, 10)]
    nested = self_times([("a", 0.0, 10.0), ("b", 1.0, 2.0), ("c", 1.5, 1.0), ("d", 12.0, 1.0)])
    assert {n: s for n, _, _, s in nested} == {"a": 8.0, "b": 1.0, "c": 1.0, "d": 1.0}


# ---- the traffic generators ---------------------------------------------- #
CHAT = cells.load_json(os.path.join(ROOT, "benchmarks/traffic/chat-steady.json"))
HEAVY = cells.load_json(os.path.join(ROOT, "benchmarks/traffic/decode-heavy.json"))


@pytest.mark.parametrize("spec", [CHAT["prompt_tokens"], CHAT["output_tokens"],
                                  HEAVY["prompt_tokens"], HEAVY["output_tokens"]])
def test_lengths_same_multiset_other_order(spec):
    a = draws.lengths(spec, 115, np.random.default_rng(1))
    b = draws.lengths(spec, 115, np.random.default_rng(2 ** 31 + 7))
    assert sorted(a) == sorted(b) == sorted(draws.quantiles(spec, 115))
    assert list(a) != list(b)
    assert min(a) >= spec["min"] and max(a) <= spec["max"]


def test_lengths_every_block_spans_the_range():
    spec = HEAVY["output_tokens"]
    a = draws.lengths(spec, 384, np.random.default_rng(3))
    means = a.reshape(-1, 16).mean(1)
    assert means.max() - means.min() < 0.05 * a.mean()


def test_lognormal_quantiles_median_and_clip():
    q = draws.quantiles(CHAT["prompt_tokens"], 1001)
    assert q[500] == 96 and q[0] == 16 and q[-1] == 512


def test_arrivals_same_count_every_second():
    for seed in (0, 2 ** 31 + 5):
        t = draws.arrivals(3.7, 30, np.random.default_rng(seed))
        per_second = np.bincount(t.astype(int), minlength=30)
        assert set(per_second) <= {3, 4} and len(t) == 111
        assert (np.diff(t) >= 0).all()
    a = draws.arrivals(3.7, 30, np.random.default_rng(0))
    b = draws.arrivals(3.7, 30, np.random.default_rng(1))
    assert not np.allclose(a, b)


def test_residual_life_of_a_fixed_length_is_uniform():
    r = draws.residual_quantiles({"dist": "fixed", "value": 100}, 100)
    assert list(r) == list(range(1, 101))


def test_residual_life_first_cohort():
    from benchmarks.kinds import serve_backlog
    spec = HEAVY["output_tokens"]
    r = draws.residual_quantiles(spec, 256)
    # E[R] = E[L^2] / (2 E[L]) for L uniform on 256..768: 277.3
    assert r.mean() == pytest.approx(277.3, abs=2.0)
    assert r.min() >= 1 and r.max() <= spec["max"]
    cohort, backlog = serve_backlog.plan(HEAVY, 256, 1024, 50257, seed=11)
    again, _ = serve_backlog.plan(HEAVY, 256, 1024, 50257, seed=12)
    assert len(cohort) == 256 and len(backlog) == HEAVY["backlog_requests"]
    # member i is asked for its residual plus the 256 - i steps of the fill
    left = [new - (256 - i) for i, (_, new) in enumerate(cohort)]
    left2 = [new - (256 - i) for i, (_, new) in enumerate(again)]
    assert sorted(left) != left and left != left2
    clipped = sum(a != b for a, b in zip(sorted(left), sorted(r)))
    assert clipped <= 4                       # prompt + new <= 1024 cuts a few
    assert all(len(p) + new <= 1024 for p, new in cohort + backlog)
    assert sorted(n for _, n in backlog) == sorted(
        draws.quantiles(spec, len(backlog)))


def test_open_loop_plan_replays_one_trace_with_other_token_ids():
    from benchmarks.kinds import serve_open_loop
    seconds, B = BENCH["run_seconds"], CHAT["block_seconds"]
    assert seconds % B == 0, "the window holds whole blocks"
    (a, lead), (b, _) = (serve_open_loop.plan(CHAT, seconds, 1024, 50257, seed)
                         for seed in (1, 2 ** 31 + 2))
    assert lead == B * -(-CHAT["lead_seconds"] // B) and a[0][0] >= -lead
    # the same dues and lengths whatever the seed; other token ids
    assert [(d, len(p), n) for d, p, n in a] == [(d, len(p), n) for d, p, n in b]
    assert any((p != q).any() for (_, p, _), (_, q, _) in zip(a, b))
    assert [d for d, _, _ in a] == sorted(d for d, _, _ in a)
    window = [(d, len(p), n) for d, p, n in a if d >= 0]
    assert len(window) == int(CHAT["rate_per_s"] * seconds) >= 100, \
        "a p90 wants ten requests beyond it"
    per_second = np.bincount([int(d) for d, _, _ in window], minlength=seconds)
    assert set(per_second) == {int(CHAT["rate_per_s"])}
    # lengths are the mix's evenly spaced quantiles, lead-in and window together
    assert sorted(len(p) for _, p, _ in a) == sorted(
        draws.quantiles(CHAT["prompt_tokens"], len(a)))
    assert sorted(n for _, _, n in a) == sorted(
        draws.quantiles(CHAT["output_tokens"], len(a)))
    # and every block spans the range: its mean prompt is near the mix's mean
    per_block = int(CHAT["rate_per_s"] * B)
    means = np.asarray([p for _, p, _ in window]).reshape(-1, per_block).mean(1)
    assert means.max() - means.min() < 0.25 * means.mean()
    # another canonical_seed is another trace
    other, _ = serve_open_loop.plan(dict(CHAT, canonical_seed=1), seconds, 1024, 50257, 1)
    assert [d for d, _, _ in other] != [d for d, _, _ in a]


def test_zipf_batches_shift_by_one_and_repeat_from_the_seed():
    a, b = (draws.ZipfBatches(2 ** 31 + 9, 50257, 2, 64) for _ in range(2))
    x, y = a()
    assert x.shape == (1, 2, 64) and x.dtype == np.int32
    assert (x[..., 1:] == y[..., :-1]).all()
    assert (b()[0] == x).all() and (a()[0] != x).any()
    assert 0 <= x.min() and x.max() < 50257


# ---- operations and bytes against hand-worked shapes ----------------------- #
def test_gpt2_parameter_count_is_the_published_one():
    # 124,439,808 with the 50,257-row embedding; the program pads to 50,304
    assert arith.gpt2_param_count(768, 12, 50257, 1024) == 124_439_808
    assert arith.gpt2_param_count(768, 12, 50304, 1024) == 124_475_904
    assert arith.gpt2_param_count(1600, 48, 50257, 1024) == 1_557_611_200


def test_train_flops_per_token():
    n = 124_475_904
    assert arith.train_flops_per_token(n, 12, 768, 1024) == 6 * n + 12 * 12 * 768 * 1024


def test_flash_call_by_hand():
    # B=8, H=12, S=1024, D=64, causal: one matmul is 2*8*12*1024*1024*64 / 2
    one = 8 * 12 * 1024 * 1024 * 64
    operand = 8 * 1024 * 12 * 64 * 2
    assert arith.flash_call("flash_fwd", 8, 12, 1024, 64) == (2 * one, 4 * operand)
    assert arith.flash_call("flash_bwd_dq", 8, 12, 1024, 64) == (3 * one, 5 * operand)
    assert arith.flash_call("flash_bwd_dkv", 8, 12, 1024, 64) == (4 * one, 6 * operand)


def test_paged_attention_row_by_hand():
    # 100 tokens resident, one query, blocks of 16 x 768 lanes in bf16:
    # 7 blocks = 112 rows of K and of V, plus q and o of 768 lanes
    flops, nbytes = arith.paged_attention_row(100, 1, 16, 768, 12, 64)
    assert nbytes == 2 * 112 * 768 * 2 + 2 * 768 * 2
    assert flops == 4 * 112 * 768
    # an idle slot reads its one trash block
    assert arith.paged_attention_row(0, 1, 16, 768, 12, 64)[1] == 2 * 16 * 768 * 2 + 2 * 768 * 2


def test_roofline_says_which_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert arith.roofline_seconds(197e12, 1, peak) == (1.0, "compute")
    assert arith.roofline_seconds(1, 819e9, peak) == (1.0, "memory")


def test_unknown_device_kind_is_an_error():
    from benchmarks.lib import device
    assert device.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(cells.BenchmarkError):
        device.peaks("TPU v9 imaginary")


# ---- the files fit together ------------------------------------------------ #
@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_workload_resolves_to_files_that_exist(workload):
    cell = cells.Cell(workload)
    assert callable(cell.kind.run)
    reported = set(cell.kind.END_TO_END) | {"setup_s"}
    assert {m["name"] for m in cell.end_to_end} == reported
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        fn, args = cell.reader(m["name"])
        assert callable(fn) and isinstance(args, dict)
    assert cell.config["source"] == next(
        c["source"] for c in BENCH["configs"] if c["name"] == cell.config_name)


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_moves_a_metric_its_cells_report(metric):
    moved = E2E[metric["moves"]]
    assert set(_cells_of(metric)) <= set(_cells_of(moved))
    base, _, suffix = metric["name"].rpartition(".")
    if base:                                   # the suffix rule
        assert SUFFIX[suffix] == metric["moves"]
    else:                                      # <kernel>_roofline, unit %
        assert metric["name"].endswith("_roofline") and metric["unit"] == "%"
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks/metrics", metric["name"] + ".json"))


def test_every_metric_file_is_listed_and_every_config_used():
    listed = {m["name"] + ".json" for m in BENCH["per_layer"]}
    assert set(os.listdir(os.path.join(ROOT, "benchmarks/metrics"))) == listed
    assert {c["name"] for c in BENCH["configs"]} == {
        w["config"] for w in BENCH["workloads"]}
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


# ---- one entry a scope and a kernel (PR 68) ------------------------------------ #
TRINITY, ZAYA, KEYE, QWEN, OLMO, MISTRAL = (
    "trinity-large-preview.serve-mixed-lengths", "zaya1-8b.serve-reasoning-resident",
    "keye-vl-2.0-30b-a3b.serve-long-indexed", "qwen3-next-80b-a3b.serve-long-delta-moe",
    "olmo-hybrid-7b.serve-chat-resident", "mistral-small-4-119b.serve-reasoning-batch")
# the entry PR 68 folded or renamed -> (the entry that reads the same thing, the
# cells the old one listed): the ledger shows ``null`` under each old name
FOLDED = {
    "afmoe_attn_window_share_pct.gen": ("attn_window_share_pct.gen", [TRINITY]),
    "afmoe_attn_full_share_pct.gen": ("attn_full_share_pct.gen", [TRINITY]),
    "afmoe_attn_gate_share_pct.gen": ("attn_gate_share_pct.gen", [TRINITY]),
    "afmoe_lead_mlp_share_pct.gen": ("lead_mlp_share_pct.gen", [TRINITY]),
    "afmoe_bank_share_pct.gen": ("moe_experts_share_pct.gen", [TRINITY]),
    "afmoe_shared_expert_share_pct.gen": ("moe_shared_expert_share_pct.gen", [TRINITY]),
    "afmoe_head_share_pct.gen": ("lm_head_share_pct.gen", [TRINITY]),
    "afmoe_assignments_held_pct.gen": ("moe_assignments_held_pct.gen", [TRINITY]),
    "afmoe_experts_reached_pct.gen": ("experts_reached_pct.gen", [TRINITY]),
    "afmoe_kv_window_freed_pct.gen": ("kv_window_freed_pct.gen", [TRINITY]),
    "afmoe_paged_gqa_roofline": ("paged_gqa_attention_roofline", [TRINITY]),
    "afmoe_grouped_matmul_roofline": ("grouped_matmul_roofline", [TRINITY]),
    "expert_bank_share_pct.gen": ("moe_experts_share_pct.gen", [ZAYA]),
    "softmax_bank_experts_share_pct.gen": ("moe_experts_share_pct.gen", [KEYE, QWEN]),
    "moe_held_share_pct.gen": ("moe_share_pct.gen", [MISTRAL]),
    "softmax_bank_share_pct.gen": ("moe_share_pct.gen", [KEYE, QWEN]),
    "router_mlp_share_pct.gen": ("moe_router_share_pct.gen", [ZAYA]),
    "softmax_router_share_pct.gen": ("moe_router_share_pct.gen", [KEYE, QWEN]),
    "dense_mlp_share_pct.gen": ("mlp_share_pct.gen", [OLMO]),
    "attn_mha_share_pct.gen": ("attn_full_share_pct.gen", [OLMO]),
    "mla_attn_share_pct.gen": ("attn_share_pct.gen", [MISTRAL]),
    "held_bank_copy_share_pct.gen": ("moe_bank_copy_share_pct.gen", [MISTRAL]),
    "softmax_bank_reached_pct.gen": ("experts_reached_pct.gen", [KEYE, QWEN]),
}
RETIRED = [f"idle_{what}_pct.{suffix}" for what in ("host_work", "fetch", "unnamed")
           for suffix in ("gen", "tpot")]
LISTED = {m["name"]: m for m in BENCH["per_layer"]}


@pytest.mark.parametrize("old", sorted(FOLDED))
def test_a_folded_entry_is_read_by_the_entry_that_reads_the_same_thing(old):
    """The old name is gone, list and file; every cell it listed is listed by
    the entry it was folded into, whose reader resolves for that cell."""
    new, listed_it = FOLDED[old]
    assert old not in LISTED
    assert not os.path.exists(os.path.join(ROOT, "benchmarks/metrics", old + ".json"))
    assert set(listed_it) <= set(LISTED[new]["workloads"])
    for cell in listed_it:
        fn, args = cells.Cell(cell).reader(new)
        assert callable(fn) and isinstance(args, dict)


@pytest.mark.parametrize("old", RETIRED)
def test_the_overlays_three_are_retired_and_their_sum_stays(old):
    """``idle_host_work_pct``, ``idle_fetch_pct`` and ``idle_unnamed_pct``
    were good to the profiler's offset only (PERF.md § 7 since PR 36); their
    sum is ``device_idle_pct``, which every cell that listed them lists."""
    assert old not in LISTED
    assert not os.path.exists(os.path.join(ROOT, "benchmarks/metrics", old + ".json"))
    idle = LISTED["device_idle_pct." + old.rsplit(".", 1)[1]]
    assert idle["source"] == "device_trace" and len(idle["workloads"]) >= 1


def test_one_entry_a_reader_its_arguments_and_the_metric_it_moves():
    """No two entries that move the same end-to-end metric name the same
    reader with the same arguments: a new cell APPENDS itself to the entry
    that reads it.  No name carries a model family's prefix, the list holds
    what the contract lets it, and the traced line's notes hold no layer's
    metrics (``grep`` over the kinds)."""
    seen = {}
    for m in BENCH["per_layer"]:
        spec = cells.load_json(os.path.join(ROOT, "benchmarks/metrics", m["name"] + ".json"))
        key = (m["moves"], spec["reader"], json.dumps(spec.get("args", {}), sort_keys=True))
        assert key not in seen, (m["name"], seen.get(key))
        seen[key] = m["name"]
        assert not m["name"].startswith(("afmoe_", "softmax_", "mamba_attn", "mamba_mlp",
                                         "mamba_head"))
    assert len(BENCH["per_layer"]) <= 128
    kinds = os.path.join(ROOT, "benchmarks/kinds")
    for name in os.listdir(kinds):
        if name.endswith(".py"):
            text = open(os.path.join(kinds, name)).read()
            assert "_layers\"]" not in text and "read_right_and_not_listed" not in text, name
            assert "def layer_notes" not in text and "PINNED_ELSEWHERE" not in text, name


BACKLOG = [w["name"] for w in BENCH["workloads"] if ".serve-" in w["name"]
           and w["name"] != "gpt2-124m.serve-chat-steady"]


@pytest.mark.parametrize("name", ["compiles_in_window.gen", "serve_step_ms.gen",
                                  "decode_batch_mean.gen", "kv_blocks_peak_pct.gen",
                                  "preemptions.gen", "device_idle_pct.gen", "sched_host_ms.gen",
                                  "table_build_ms.gen", "host_turnaround_ms.gen",
                                  "step_outside_ms.gen", "idle_wire_ms.gen", "step_mfu_pct.gen",
                                  "program_ms.gen", "chunk_program_time_pct.gen",
                                  "dispatched_ahead_pct.gen", "host_occupancy_pct.gen"])
def test_an_entry_meant_for_every_backlog_serve_cell_lists_them_all(name):
    assert set(LISTED[name]["workloads"]) == set(BACKLOG)


# every kernel a serve cell's program runs that has arithmetic under
# ``benchmarks/lib/``: kernel -> its ONE listed roofline
KERNEL_ROOFLINES = {"grouped_matmul": "grouped_matmul_roofline",
                    "paged_mla_attention": "paged_mla_attention_roofline",
                    "paged_sparse_attention": "paged_sparse_attention_roofline",
                    "mamba_state_update": "mamba_state_update_roofline",
                    "mamba_chunk_scan": "mamba_chunk_scan_roofline",
                    "delta_state_update": "delta_state_update_roofline"}
# the kernels each cell's traced run held (ledger, PR 67: ``breakdown.device_ops``)
RUNS = {
    "olmoe-1b-7b.serve-decode-heavy": ["grouped_matmul"],
    "smallthinker-21b-a3b.serve-long-context": ["grouped_matmul"],
    MISTRAL: ["grouped_matmul", "paged_mla_attention"],
    "minicpm-sala-9b.serve-long-mixed": ["paged_sparse_attention"],
    ZAYA: ["grouped_matmul"], OLMO: ["delta_state_update"], KEYE: ["grouped_matmul"],
    TRINITY: ["grouped_matmul"],
    "jamba2-3b.serve-chat-packed": ["mamba_state_update", "mamba_chunk_scan"],
    "deepseek-v3.2-exp.serve-long-latent-indexed": ["grouped_matmul"],
    QWEN: ["grouped_matmul", "delta_state_update"],
    "xing4.0-29b-a4b.serve-prompt-heavy": ["grouped_matmul", "paged_mla_attention"],
}


@pytest.mark.parametrize("cell", sorted(RUNS))
def test_every_kernel_a_cell_runs_has_one_roofline_that_lists_the_cell(cell):
    for kernel in RUNS[cell]:
        entry = LISTED[KERNEL_ROOFLINES[kernel]]
        assert cell in entry["workloads"], (kernel, cell)
        assert (entry["unit"], entry["better"], entry["source"]) == ("%", "higher", "device_trace")
    # and the paged K/V kernel's, where the kind leaves its pages ALONE under
    # ``paged_gqa_*`` (Olmo-Hybrid's and Jamba2's leave all the caches' work
    # there, for ``step_mfu_pct``: PERF.md § 7)
    for name in ("smallthinker-21b-a3b.serve-long-context", TRINITY, QWEN, ZAYA):
        assert name in LISTED["paged_gqa_attention_roofline"]["workloads"]


def test_a_later_pr_adds_one_of_each_without_editing_a_file(tmp_path):
    """A configuration, a traffic mix, a cell and a per-layer metric, each as
    a new file or a new entry, in a copy of the benchmark."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*") if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    cfg = cells.load_json(root / "benchmarks/configs/gpt2-124m.json")
    cfg["n_layer"] = cfg["model"]["kwargs"]["n_layer"] = 24
    (root / "benchmarks/configs/gpt2-deeper.json").write_text(json.dumps(cfg))
    mix = dict(CHAT, rate_per_s=1.5)
    (root / "benchmarks/traffic/chat-slow.json").write_text(json.dumps(mix))
    (root / "benchmarks/metrics/steps_with_prefill.tpot.json").write_text(json.dumps(
        {"reader": "benchmarks.readers.counters:counter",
         "args": {"key": "steps_with_prefill"}}))
    bench["configs"].append({"name": "gpt2-deeper", "source": cfg["source"],
                             "file": "benchmarks/configs/gpt2-deeper.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "gpt2-deeper.chat-slow", "config": "gpt2-deeper",
                               "traffic": "chat-slow", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p90_ms", "tpot_p90_ms"):
            m["workloads"].append("gpt2-deeper.chat-slow")
    bench["per_layer"].append({
        "name": "steps_with_prefill.tpot", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "scheduler (serving/scheduler.py)",
        "moves": "tpot_p90_ms", "workloads": ["gpt2-deeper.chat-slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.Cell("gpt2-deeper.chat-slow", root=str(root))
    assert cell.config["n_layer"] == 24 and cell.traffic["rate_per_s"] == 1.5
    assert cell.kind.__name__ == "benchmarks.kinds.serve_open_loop"
    assert [m["name"] for m in cell.per_layer] == ["steps_with_prefill.tpot"]
    fn, args = cell.reader("steps_with_prefill.tpot", root=str(root))
    assert fn({"counters": {"steps_with_prefill": 7}}, **args) == 7.0
    assert all(p.read_bytes() == data for p, data in before.items())


# ---- the command, rehearsed on the CPU -------------------------------------- #
def _run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(ROOT, "benchmarks/run.py"), *argv],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_the_command_exits_non_zero_and_prints_no_result():
    done = _run("--workload", "gpt2-124m.train-seq1024", "--seed", "1", "--seconds", "1",
                "--trace", "0")
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "refusing to run" in done.stderr


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_rehearsal_prints_a_last_line_of_the_contracts_shape(workload):
    done = _run("--workload", workload, "--seed", str(2 ** 31 + 11), "--seconds", "1",
                "--trace", "0", "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    # each number compared beside its limit: last in the line, and the last
    # lines of standard error
    assert list(line)[-1] == "compared" and line["compared"]
    assert all(len(pair) == 2 for pair in line["compared"].values())
    last = done.stderr.strip().splitlines()[-len(line["compared"]):]
    assert [l.split()[1] for l in last] == list(line["compared"])
    assert all(l.startswith("compared ") and " limit " in l for l in last)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    assert line["metrics"] == {}, "a CPU run carries no device metric"
    assert line["counts"]["compiles_in_window"] == 0
    cell = cells.Cell(workload)
    assert line["would_report"] == sorted(
        m["name"] for m in cell.end_to_end + cell.per_layer)
