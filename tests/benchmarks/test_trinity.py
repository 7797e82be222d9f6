"""Tests of what the benchmark adds for Trinity-Large-Preview's cell: the
configuration against the catalog's row, its parameter count against the
program's arrays, the arena against the engine's, the step's arithmetic and the
held bank's by hand, the new readers on runs with nothing to read, and the
cell's control flow at the tiny size; CPU only.

The cell, its configuration and its metrics are held to be IN the lists of
``BENCHMARK.json`` with at least these metrics: never to a place in a list and
never to a count of entries, so a later cell appended behind them breaks
nothing here."""

import json
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.kinds import serve_backlog_resident_afmoe as kind
from benchmarks.lib import arith_moe, arith_step, arith_trinity, cells, draws
from benchmarks.readers import afmoe, moe

CELL = "trinity-large-preview.serve-mixed-lengths"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["layer_types", "num_dense_layers", "num_experts", "num_hidden_layers",
           "vocab_size"]
# the entries this cell came with (PR 55), under the names PR 68 folded them into
NEW = ("attn_window_share_pct.gen", "attn_full_share_pct.gen",
       "attn_gate_share_pct.gen", "lead_mlp_share_pct.gen",
       "moe_experts_share_pct.gen", "moe_shared_expert_share_pct.gen",
       "lm_head_share_pct.gen", "moe_assignments_held_pct.gen",
       "experts_reached_pct.gen", "kv_window_freed_pct.gen",
       "paged_gqa_attention_roofline", "grouped_matmul_roofline")
COMMON = ("compiles_in_window.gen", "serve_step_ms.gen", "decode_batch_mean.gen",
          "kv_blocks_peak_pct.gen", "preemptions.gen", "device_idle_pct.gen",
          "step_mfu_pct.gen", "program_ms.gen", "dispatched_ahead_pct.gen")
PEAKS = {"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12}


def test_the_configuration_is_the_catalogs_but_for_the_five_reduced_keys():
    cfg = cells.Cell(CELL).config
    try:        # the catalog beside the guide, where it is installed
        rows = [json.loads(l) for l in open(CATALOG)]
    except FileNotFoundError:
        pytest.skip("no catalog beside the guide here")
    row = next(r for r in rows if r["name"] == "Trinity-Large-Preview")
    source = row["config"]
    assert row["source_url"] == cfg["source"]
    differs = sorted(k for k, v in source.items() if cfg.get(k, "missing") != v)
    assert differs == sorted(cfg["reduced"]) == REDUCED
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (8, 1, 16, 25024)
    assert cfg["layer_types"] == source["layer_types"][:8] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 2
    # no width is cut
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["sliding_window"],
            cfg["max_position_embeddings"]) == (3072, 48, 8, 128, 12288, 3072, 4, 4096,
                                                262144)
    # the router stays 256 wide, and the file says which 16 are held
    assert cfg["num_experts_the_router_chooses_among"] == source["num_experts"] == 256
    assert cfg["experts_held"] == [0, 16] and cfg["num_dense_layers_published"] == 6
    assert cfg["vocab_size"] * 8 == source["vocab_size"]
    assert set(cfg["assumed"]) >= {"gate", "qk_norm", "rope", "window", "norms", "router",
                                   "embedding", "weights", "deployment"}


def test_the_builder_and_the_reference_are_given_the_same_layer():
    cfg = cells.Cell(CELL).config
    mk, rk = cfg["model"]["kwargs"], cfg["reference"]["kwargs"]
    assert (mk["n_embd"], mk["n_layer"], mk["n_head"], mk["n_kv_head"], mk["head_dim"],
            mk["vocab_size"]) == (cfg["hidden_size"], cfg["num_hidden_layers"],
                                  cfg["num_attention_heads"], cfg["num_key_value_heads"],
                                  cfg["head_dim"], cfg["vocab_size"])
    assert (mk["intermediate_size"], mk["moe_intermediate_size"], mk["num_experts"],
            mk["top_k"], mk["shared_experts"], mk["dense_layers"], mk["route_scale"],
            mk["window"], mk["n_positions"]) == (
        cfg["intermediate_size"], cfg["moe_intermediate_size"], 256,
        cfg["num_experts_per_tok"], cfg["num_shared_experts"], cfg["num_dense_layers"],
        cfg["route_scale"], cfg["sliding_window"], cfg["max_position_embeddings"])
    assert mk["experts_held"] == rk["experts_held"] == cfg["experts_held"]
    assert (rk["n_head"], rk["n_kv_head"], rk["head_dim"], rk["top_k"], rk["num_experts"],
            rk["window"], rk["num_dense_layers"], rk["route_scale"], rk["vocab_size"]) == (
        48, 8, 128, 4, 256, 4096, 1, 2.448, 25024)
    assert rk["layer_types"] == cfg["layer_types"]
    assert rk["eps"] == cfg["rms_norm_eps"] and rk["rope_theta"] == cfg["rope_theta"]


def test_the_weights_are_what_the_file_says():
    import jax
    from benchmarks.lib.build import model_from
    cfg = cells.Cell(CELL).config
    model = model_from(cfg)
    assert model.num_params() == 4_144_995_072
    assert "4,144,995,072 parameters = 8,289,990,144 B" in cfg["reduced_why"]
    # the arrays: those, and the zero shifts and biases a bias-free RMSNorm
    # model never reads
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    unread = sum(a.size for path, a in jax.tree_util.tree_leaves_with_path(shapes)
                 if path[-1].key.endswith("_b"))
    assert sum(a.size for a in jax.tree.leaves(shapes)) - unread == 4_144_995_072
    assert unread == 8 * (2 * 3072 + 8192 + 3072) + 3072
    # the step's arithmetic counts the same parameters: what every row goes
    # through, the rows of the embedding a token takes one of, the bank held
    w = arith_trinity.trinity_weights(cfg["model"]["kwargs"])
    assert w["dense"] + w["gathered"] + arith_step.bank_params(w["bank"]) == 4_144_995_072
    assert arith_trinity.attention_params(cfg["model"]["kwargs"]) == 62_927_104
    assert w["bank"] == {"layers": 7, "experts": 256, "held": 16, "top_k": 4,
                         "hidden": 3072, "width": 3072}
    assert arith_step.bank_params(w["bank"]) == 7 * 16 * 3 * 3072 ** 2


def test_the_arena_is_the_engines():
    """``serve.arena_bytes`` over ``lib/serving.py``'s divisor is 12,288
    blocks of all eight layers, 49,152 pages of a layer group each; held to
    an engine's arrays at the rehearse size."""
    import jax
    import deepspeed_tpu
    from benchmarks.lib.build import model_from
    from deepspeed_tpu.serving.kv_cache import arena_bytes
    cfg = cells.Cell(CELL).config
    serve, mcfg = cfg["serve"], model_from(cfg).cfg
    per_block = 2 * mcfg.n_layer * 16 * mcfg.kv_heads * mcfg.head_dim * 2
    assert per_block == 524_288 and serve["arena_bytes"] == 12_288 * per_block
    assert arena_bytes(mcfg, 12_288, 16) == serve["arena_bytes"]
    assert mcfg.arena_layout == (2, 4, (1024, 1024))
    assert serve["serving"] == {"max_batch_size": 32, "prefill_chunk": 512,
                                "max_blocks_per_seq": 2400, "dtype": "bfloat16"}
    # 32 + 512 rows of top 4: whole 128-row tiles of the bank's kernel
    assert (32 + 512) * 4 % 128 == 0
    # the longest request of the mix fits a full layer's table
    mix = cells.Cell(CELL).traffic
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] <= 2400 * 16
    cells.merge(cfg, cfg["rehearse"])
    model = model_from(cfg)
    lanes = model.cfg.kv_heads * model.cfg.head_dim
    blocks = cfg["serve"]["arena_bytes"] // (2 * model.cfg.n_layer * 16 * lanes * 4)
    eng = deepspeed_tpu.init_serving(
        model=model, params=model.init_params(jax.random.PRNGKey(0)),
        config={"serving": dict(cfg["serve"]["serving"], num_blocks=blocks)})
    try:
        assert eng._k_pages.shape == eng._v_pages.shape == (2, 4 * blocks, 16, lanes)
        assert 2 * eng._k_pages.nbytes == arena_bytes(model.cfg, blocks, 16, 4)
    finally:
        eng.close()


def test_the_mix_is_the_issues():
    """Prompts lognormal, median 4,096, sigma 1, clipped to 512-32,768: the
    mean about 6,425, half inside the window, 8% over 16,384; the plan's
    cohort holds about 8,200 keys a slot, rows of 1,500 and of 37,000 in one
    batch, whatever the seed."""
    mix = cells.Cell(CELL).traffic
    assert mix["kind"] == "serve-backlog-resident-afmoe"
    assert (mix["backlog_requests"], mix["check_requests"]) == (96, 4)
    q = draws.quantiles(mix["prompt_tokens"], 4096)
    assert q.min() == 512 and q.max() == 32768
    assert 6300 < q.mean() < 6550
    assert 0.49 < (q <= 4096).mean() < 0.51 and 0.07 < (q > 16384).mean() < 0.09
    cohort, backlog, members = resident.plan(mix, 32, 512, 262144, 25024, seed=1)
    again, _, _ = resident.plan(mix, 32, 512, 262144, 25024, seed=2 ** 31 + 5)
    assert [len(p) for p, _ in cohort] == [len(p) for p, _ in again]
    held = np.asarray([p + a for p, a, _ in members])
    assert 7500 < held.mean() < 9000 and held.min() < 2500 and held.max() > 33000
    assert len(backlog) == 96 and max(len(p) + n for p, n in backlog) <= 2400 * 16
    assert all(int(p.max()) < 25024 for p, _ in cohort)


def test_the_held_banks_least_time_by_hand():
    """A program of 32 live rows: of 256 experts top 4 the rows reach ``256
    (1 - (1 - 4/256)^32)`` = 101.3, a sixteenth of them here: 6.33 experts of
    28.3 M parameters read once, and 32 x 4 / 16 = 8 assignments computed, in
    each of the seven expert layers."""
    reached = arith_moe.experts_reached(32, 256, 4)
    assert reached == pytest.approx(101.3, abs=0.1) and reached / 16 == pytest.approx(6.33, abs=0.01)
    expert = arith_moe.expert_params(3072, 3072)
    flops, nbytes = arith_moe.expert_bank_call(32, 256, 4, 3072, 3072)
    assert nbytes / 16 == pytest.approx((6.33 * expert + 2 * 8 * 3072) * 2, rel=1e-3)
    assert flops / 16 == 2 * 8 * expert
    first = types.SimpleNamespace(start=10.0, end=20.0)
    trace = types.SimpleNamespace(devices=[first], op_seconds=lambda: {"grouped_matmul": 0.002})
    calls = {"serve.decode.dispatch": [{"batch": 32, moe.START: 11.0},
                                       {"batch": 32, moe.START: 25.0}],     # never timed
             "serve.prefill.dispatch": [{"tokens": 544, moe.START: 12.0}]}
    run = {"trace": trace, "cell": cells.Cell(CELL), "peaks": PEAKS, "notes": {},
           "counters": {}, "_moe_span_stats": calls}
    least, bound = afmoe.bank_least_seconds(run)
    f2, b2 = arith_moe.expert_bank_call(544, 256, 4, 3072, 3072)
    want = 7 * (max(flops / 16 / 1e14, nbytes / 16 / 1e12)
                + max(f2 / 16 / 1e14, b2 / 16 / 1e12))
    assert least == pytest.approx(want, rel=1e-9) and run["notes"]["moe_bank_calls"] == 14
    assert afmoe.grouped_matmul_roofline(run) == pytest.approx(100 * want / 0.002)
    # what a step must move at 32 rows, the issue's arithmetic: the dense
    # weights and the head 1.8 GB, the reached experts 2.5 GB
    w = arith_trinity.trinity_weights(cells.Cell(CELL).config["model"]["kwargs"])
    assert 2 * w["dense"] / 1e9 == pytest.approx(1.79, abs=0.02)
    assert 7 * nbytes / 16 / 1e9 == pytest.approx(2.51, abs=0.02)
    _, step_bytes = arith_step.step_work(w, 32)
    assert step_bytes / 1e9 == pytest.approx(1.79 + 2.51, abs=0.03)


def test_experts_reached_is_inverted_from_the_sum_over_layers():
    """A step whose 7 layers each reach a share ``p`` of the 256 experts
    leaves ``256 (1 - (1 - p)^7)`` with an assignment in some layer: the
    reader gives ``p`` back; every expert touched is the whole bank."""
    p = 1 - (1 - 4 / 256) ** 32
    touched = 256 * (1 - (1 - p) ** 7)
    run = {"trace": object(), "cell": cells.Cell(CELL), "notes": {}, "counters": {},
           "_moe_span_stats": {moe.LOAD_SPAN: [{"moe_experts_touched": touched}] * 3}}
    assert afmoe.experts_reached_pct(run) == pytest.approx(100 * p, rel=1e-9)
    assert 16 * p == pytest.approx(6.33, abs=0.01)
    run["_moe_span_stats"] = {moe.LOAD_SPAN: [{"moe_experts_touched": 256}]}
    assert afmoe.experts_reached_pct(run) == 100.0


def test_the_new_readers_find_nothing_on_a_run_without_their_spans():
    """A parent commit, a dense model, an untraced run: None, never a raise,
    and the metric is left out of the line."""
    cell = cells.Cell(CELL)
    untraced = {"trace": None, "cell": cell, "notes": {}, "counters": {}, "peaks": PEAKS}
    bare = types.SimpleNamespace(devices=[types.SimpleNamespace(start=0.0, end=1.0)],
                                 op_seconds=lambda: {})
    no_spans = dict(untraced, trace=bare, _moe_span_stats={})
    for run in (untraced, no_spans):
        assert afmoe.experts_reached_pct(run) is None
        assert afmoe.bank_least_seconds(run) is None
        assert afmoe.grouped_matmul_roofline(run) is None


def test_the_cell_its_traffic_and_its_metrics_resolve():
    bench = cells.load_benchmark()
    cell = cells.Cell(CELL)
    entry = next(c for c in bench["configs"] if c["name"] == "trinity-large-preview")
    assert entry["file"] == "benchmarks/configs/trinity-large-preview.json"
    assert sorted(entry["reduced"]) == sorted(cell.config["reduced"]) == REDUCED
    assert entry["source"] == cell.config["source"]
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (work["config"], work["traffic"], work["chips"]) == (
        "trinity-large-preview", "mixed-lengths", 1)
    assert len(work["why"]) <= 200 and len(entry["why"]) <= 200
    assert cell.kind is kind and kind.END_TO_END == ("serve_tokens_per_s",)
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s", "setup_s"]
    listed = {m["name"]: m for m in cell.per_layer}
    assert set(NEW) <= set(listed) and set(COMMON) <= set(listed)     # at least these
    for name in NEW:
        m = listed[name]
        assert CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s"
        fn, args = cell.reader(name)
        assert callable(fn) and isinstance(args, dict)
    for name in ("paged_gqa_attention_roofline", "grouped_matmul_roofline"):
        assert listed[name]["unit"] == "%" and listed[name]["source"] == "device_trace"
    # the limits of the comparison are this kind's own, found by routed4's method
    assert kind.LOGIT_MARGIN > kind.NOISE_LIMIT > 0
    assert kind.judge([0.1, kind.LOGIT_MARGIN + 1], [0.0, 0.0], 0.0) == 1
    assert kind.judge([0.1, 0.1], [2 * kind.NOISE_LIMIT] * 2, 2 * kind.NOISE_LIMIT) == 2
    assert kind.judge([0.1, 0.1], [2 * kind.NOISE_LIMIT, 0.0], 0.0) == 0


def test_rehearse_runs_the_cells_control_flow():
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed", "3000000019",
         "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=cells.ROOT, capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [l for l in out.stdout.splitlines() if l.startswith("{")]
    last = json.loads(lines[-1])
    assert last["rehearsal"] and last["correct"] and last["failed"] == 0
    assert set(NEW) <= set(last["would_report"])
    counters = json.loads(lines[-2])["counters"]
    assert counters["compiles_in_window"] == 0 and counters["preemptions"] == 0
    assert counters["kv_window_freed_pct"] > 0


def test_a_chunks_pages_are_needed_once_a_chunk_by_hand():
    """A chunk of 512 queries at 8,192 .. 8,703, blocks of 16, 1,024 lanes,
    48 heads of 128.  A full layer: the pages 0 .. 543, K and V, once (35.7
    MB where a row a token asks 512 x 17.3 MB), and the queries and outputs;
    a window layer: from the page of key 8,192 - 4,095 to page 543.  The
    operations are the rows': each query over the pages it sees."""
    from benchmarks.lib import arith_window
    f, b = arith_window.chunk_rows(8192, 512, 16, 1024, 48, 128)
    assert b == (2 * 544 * 16 * 1024 + 2 * 512 * 48 * 128) * 2
    rf, rb = arith_window.rows(8192 + np.arange(512), 16, 1024, 48, 128)
    assert f == rf and rb > 250 * b
    fw, bw = arith_window.chunk_rows(8192, 512, 16, 1024, 48, 128, window=4096)
    assert bw == (2 * (544 - 4097 // 16) * 16 * 1024 + 2 * 512 * 48 * 128) * 2
    assert fw == arith_window.rows(8192 + np.arange(512), 16, 1024, 48, 128, 4096)[0]
    # the stack: six window layers and two full; decode rows as the rows'
    decode = np.asarray([1500, 37000])
    flops, nbytes = arith_window.attention(decode, [(8192, 512)], {4096: 6, None: 2},
                                            16, 1024, 48, 128)
    d_full, d_win = (arith_window.rows(decode, 16, 1024, 48, 128, w) for w in (None, 4096))
    assert nbytes == 6 * (d_win[1] + bw) + 2 * (d_full[1] + b)
    assert flops == 6 * (d_win[0] + fw) + 2 * (d_full[0] + f)
    # a short last chunk and no chunk at all
    assert arith_window.chunk_rows(0, 7, 16, 1024, 48, 128)[1] == (2 * 16 * 1024 + 2 * 7 * 6144) * 2
    assert arith_window.attention(np.zeros(0, np.int64), [], {None: 2}, 16, 1024, 48, 128) == (0, 0)
