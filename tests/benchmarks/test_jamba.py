"""Tests of what the benchmark adds for AI21-Jamba2-3B's chat-packed cell: the
configuration against the catalog's row, its arithmetic held to the arrays
the engine builds, the cell, its traffic, its kind and the metrics it
reports, the kind's counts from the lengths, the layers' notes on a synthetic
trace, and the cell's control flow at the rehearse size; CPU only.

Nothing here holds a COUNT of a list or a place in one: a later PR appends
behind this cell."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.kinds import serve_backlog_resident_mamba as kind
from benchmarks.lib import arith_jamba, arith_step, cells
from benchmarks.readers import jamba

CELL = "jamba2-3b.serve-chat-packed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
STATE, CONV = 5120 * 16 * 4, 3 * 5120 * 2
PARAMS = 3_029_337_472
# what every backlog serve cell reports
SHARED = {"compiles_in_window.gen", "serve_step_ms.gen", "decode_batch_mean.gen",
          "kv_blocks_peak_pct.gen", "preemptions.gen", "device_idle_pct.gen",
          "sched_host_ms.gen", "table_build_ms.gen", "host_turnaround_ms.gen",
          "step_outside_ms.gen", "idle_wire_ms.gen", "step_mfu_pct.gen", "program_ms.gen",
          "chunk_program_time_pct.gen", "dispatched_ahead_pct.gen", "host_occupancy_pct.gen"}
# the cell's own, listed since PR 68 (PR 57 left them in ``notes.mamba_layers``):
# entry -> the scope it reads
SCOPES = {"attn_mamba_share_pct.gen": "attn_mamba", "mamba_conv_share_pct.gen": "mamba_conv",
          "mamba_params_share_pct.gen": "mamba_params", "mamba_scan_share_pct.gen": "mamba_scan",
          "attn_full_share_pct.gen": "attn_full", "mlp_share_pct.gen": "mlp",
          "lm_head_share_pct.gen": "head"}
KERNELS = {"mamba_state_update_roofline": "mamba_state_update",
           "mamba_chunk_scan_roofline": "mamba_chunk_scan"}
OWN = set(SCOPES) | set(KERNELS) | {"mamba_state_moves_per_step.gen"}


def test_the_configuration_is_the_catalogs_uncut():
    cfg = cells.Cell(CELL).config
    try:        # the catalog beside the guide, where it is installed
        rows = [json.loads(l) for l in open(CATALOG)]
        source = next(r for r in rows if r["name"] == "AI21-Jamba2-3B")
        assert cfg["source"] == source["source_url"]
        assert [k for k, v in source["config"].items() if cfg.get(k, "missing") != v] == []
    except FileNotFoundError:
        pass
    assert cfg["reduced"] == [] and cfg["num_hidden_layers"] == 28
    kw, ref = cfg["model"]["kwargs"], cfg["reference"]["kwargs"]
    assert (kw["n_embd"], kw["n_layer"], kw["n_head"], kw["n_kv_head"], kw["intermediate_size"],
            kw["vocab_size"], kw["n_positions"], kw["attn_layer_period"],
            kw["attn_layer_offset"], kw["mamba_expand"], kw["mamba_d_state"],
            kw["mamba_d_conv"], kw["mamba_dt_rank"]) == (
                cfg["hidden_size"], cfg["num_hidden_layers"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["intermediate_size"], cfg["vocab_size"],
                cfg["max_position_embeddings"], cfg["attn_layer_period"],
                cfg["attn_layer_offset"], cfg["mamba_expand"], cfg["mamba_d_state"],
                cfg["mamba_d_conv"], cfg["mamba_dt_rank"]) == (
                    2560, 28, 20, 1, 8192, 65536, 262144, 14, 7, 2, 16, 4, 160)
    assert kw["head_dim"] == cfg["hidden_size"] // cfg["num_attention_heads"] == 128
    assert {k: ref[k] for k in ("n_layer", "attn_layer_period", "attn_layer_offset", "n_head",
                                "n_kv_head", "head_dim", "mamba_inner", "mamba_d_state",
                                "mamba_dt_rank", "vocab_size")} == {
        "n_layer": 28, "attn_layer_period": 14, "attn_layer_offset": 7, "n_head": 20,
        "n_kv_head": 1, "head_dim": 128, "mamba_inner": 5120, "mamba_d_state": 16,
        "mamba_dt_rank": 160, "vocab_size": 65536}
    assert ref["eps"] == cfg["rms_norm_eps"] == 1e-6 and cfg["tie_word_embeddings"] is True
    assert cfg["num_experts"] == 1 and cfg["mamba_conv_bias"] and not cfg["mamba_proj_bias"]
    assert [i for i, k in enumerate(arith_jamba.layer_kinds(kw)) if k == "full"] == [7, 21]
    # what the config does not fix, and what differs in form
    assert {"layer_order", "feed_forward", "block", "mamba", "attention", "positions",
            "dtype", "weights", "deployment"} <= set(cfg["assumed"])
    assert {"none_from_the_equations", "state_layout"} <= set(cfg["departures"])
    assert "log(n + 1)" in cfg["assumed"]["weights"] and "[1e-3, 1e-1]" in cfg["assumed"]["weights"]
    assert "3,029,337,472 parameters = 6.06 GB" in cfg["reduced_why"]


def test_the_program_builds_the_whole_model_from_the_file():
    import jax
    import jax.numpy as jnp
    from benchmarks.lib.build import model_from
    from deepspeed_tpu.models import hybrid
    cfg = cells.Cell(CELL).config
    model = model_from(cfg)
    mcfg = model.cfg
    assert mcfg.mixers.count("mamba") == 26 and mcfg.mixers.count("full") == 2
    assert [i for i, m in enumerate(mcfg.mixers) if m == "full"] == [7, 21]
    assert mcfg.ffns == ("mlp",) * 28
    # what the harness and the resident kind read of a model's configuration
    assert (mcfg.n_layer, mcfg.kv_heads, mcfg.head_dim, mcfg.n_head) == (28, 1, 128, 20)
    assert all(k.window is None and not k.rope for k in mcfg.pattern) and not mcfg.untied_head
    assert not mcfg.norm_after and not mcfg.qk_norm
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) - 2560     # lnf_b
    assert held == model.num_params() == PARAMS
    kw = cfg["model"]["kwargs"]
    assert arith_jamba.mixer_params(kw) == 41_241_792
    w = arith_jamba.jamba_weights(kw)
    assert w["dense"] == PARAMS + 2560 and w["gathered"] == 0 and w["bank"] is None
    # a slot's state whatever its length, the CHANNELS on the lanes
    assert (arith_jamba.state_bytes(kw), arith_jamba.conv_state_bytes(kw)) == (STATE, CONV)
    assert 26 * (STATE + CONV) == 9_318_400
    aux = jax.eval_shape(lambda: hybrid.init_aux(mcfg, 16, 64, 384, jnp.bfloat16))
    assert aux["mamba_state"].shape == (26, 384, 16, 5120)
    assert aux["mamba_state"].dtype == jnp.float32 and aux["mamba_state"].shape[-1] % 128 == 0
    assert aux["mamba_conv"].shape == (26, 384, 3, 5120)
    assert (aux["mamba_state"].size * 4 + aux["mamba_conv"].size * 2) // 384 == 9_318_400


def test_a_steps_least_work_is_the_issues_arithmetic():
    """384 live rows at 1,600 keys: the matrices 6.06 GB read once and 2.3
    TFLOP (11.8 ms at the bf16 peak, 7.4 ms for their bytes: the chip's
    ridge); the mamba layers' states 6.54 GB (8.0 ms); the two full layers'
    pages 0.6 GB (0.8 ms)."""
    kw = cells.Cell(CELL).config["model"]["kwargs"]
    w = arith_jamba.jamba_weights(kw)
    flops, weights = arith_step.step_work(w, 384)
    assert weights == 2 * w["dense"] == pytest.approx(6.06e9, rel=1e-3)
    assert flops == pytest.approx(2.33e12, rel=5e-3)
    assert flops / 197e12 == pytest.approx(11.8e-3, rel=5e-3)
    assert weights / 819e9 == pytest.approx(7.4e-3, rel=5e-3)
    ops, state, conv = arith_jamba.mamba_rows(384, 384, 26, kw)
    assert state == 384 * 26 * 2 * STATE == pytest.approx(6.54e9, rel=1e-3)
    assert state / 819e9 == pytest.approx(8.0e-3, rel=5e-3)
    assert conv == 384 * 26 * 2 * CONV
    # 81,920 exponentials a token a layer: 818 M a decode step
    assert 384 * 26 * 5120 * 16 == pytest.approx(818e6, rel=1e-3)
    assert ops == 384 * 26 * (7 * 81_920 + 4 * 5120 + 8 * 5120)
    _, pages = arith_jamba.full_rows(np.full(384, 1599), [], 2, 64, kw)
    # 1,600 keys are 25 pages of 64; K and V of 128 lanes, two layers; q and o beside
    assert pages == 2 * (2 * 384 * 25 * 64 * 128 * 2 + 2 * 384 * 2560 * 2)
    assert pages == pytest.approx(0.63e9, rel=0.02)
    # the two kernels' calls, a layer: the state in and out, the rows beside it
    f, b = arith_jamba.state_update_call(384, kw)
    assert b == 384 * (2 * STATE + (3 * 5120 + 32) * 4) and f == 384 * (7 * 81_920 + 4 * 5120)
    f, b = arith_jamba.chunk_scan_call(512, kw)
    assert b == 2 * STATE + 512 * (3 * 5120 + 32) * 4 and f == 512 * (7 * 81_920 + 4 * 5120)


def test_the_arena_and_the_states_are_the_engines():
    import jax
    import deepspeed_tpu
    from benchmarks.lib.build import model_from
    from deepspeed_tpu.serving.kv_cache import arena_bytes
    cfg = cells.Cell(CELL).config
    serve, mcfg = cfg["serve"], model_from(cfg).cfg
    block = serve["serving"]["block_size"]
    # lib/serving.py's divisor counts 28 layers of K and V where 2 own pages
    per_block = 2 * mcfg.n_layer * block * mcfg.kv_heads * mcfg.head_dim * 2
    blocks = serve["arena_bytes"] // per_block
    assert blocks * block == 800_000 and serve["arena_bytes"] == blocks * per_block
    assert serve["arena_bytes_really_held"] == arena_bytes(mcfg, blocks, block) == 800_000 * 1024
    assert serve["serving"]["max_blocks_per_seq"] * block == 4_096
    assert (serve["serving"]["max_batch_size"], serve["serving"]["prefill_chunk"],
            serve["serving"]["dtype"]) == (384, 512, "bfloat16")
    assert serve["mamba_state_bytes"] == 26 * 384 * STATE
    assert serve["mamba_conv_bytes"] == 26 * 384 * CONV
    # the rehearse size, through the harness's own arithmetic to an engine
    cells.merge(cfg, cfg["rehearse"])
    model = model_from(cfg)
    lanes = model.cfg.kv_heads * model.cfg.head_dim
    blocks = cfg["serve"]["arena_bytes"] // (2 * model.cfg.n_layer * 16 * lanes * 4)
    assert blocks == 100
    eng = deepspeed_tpu.init_serving(
        model=model, params=model.init_params(jax.random.PRNGKey(0)),
        config={"serving": dict(cfg["serve"]["serving"], num_blocks=blocks)})
    try:
        assert eng._k_pages.shape == eng._v_pages.shape == (1, 100, 16, 16)
        assert eng._k_pages.nbytes + eng._v_pages.nbytes == arena_bytes(model.cfg, 100, 16, 4)
        assert eng._aux["mamba_state"].shape == (4, 4, 16, 128)
        assert eng._aux["mamba_conv"].shape == (4, 4, 3, 128)
        assert eng.cache_bytes_per_token == 2 * 16 * 4
        assert eng.alloc.num_blocks == 100
    finally:
        eng.close()


# ---- the files ------------------------------------------------------------------ #
def test_the_cell_its_traffic_and_its_metrics_resolve():
    cell = cells.Cell(CELL)
    listed = {m["name"]: m for m in cell.per_layer}
    assert SHARED | OWN <= set(listed)            # at least these
    for name in SHARED | OWN:
        fn, args = cell.reader(name)
        assert callable(fn) and isinstance(args, dict)
        assert CELL in listed[name]["workloads"] and listed[name]["moves"] == "serve_tokens_per_s"
    assert cell.chips == 1 and cell.kind is kind
    assert {"serve_tokens_per_s", "setup_s"} <= {m["name"] for m in cell.end_to_end}
    assert cell.config["step_work"] == {
        "_about": cell.config["step_work"]["_about"],
        "weights": "benchmarks.lib.arith_jamba:jamba_weights",
        "attention": "benchmarks.readers.paged_gqa:work"}
    assert cells.resolve(cell.config["step_work"]["attention"])({"counters": {
        "paged_gqa_flops": 1, "paged_gqa_bytes": 2}}) == (1, 2)
    for name in ("logits", "hidden", "head", "states"):
        assert callable(cells.resolve(cell.config["reference"][name]))
    bench = cells.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "jamba2-3b")
    assert entry["reduced"] == cell.config["reduced"] == []
    assert entry["source"] == cell.config["source"]
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (workload["config"], workload["traffic"]) == ("jamba2-3b", "chat-packed")
    assert all(0 < len(e["why"]) <= 200 for e in bench["configs"] + bench["workloads"])


def test_the_traffic_is_chat_lengths_at_384_slots():
    cell = cells.Cell(CELL)
    mix = cell.traffic
    assert cell.kind is kind and kind.END_TO_END == resident.END_TO_END
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 768, "max": 3072}
    assert (mix["backlog_requests"], mix["check_requests"]) == (512, 4)
    cohort, backlog, planned = resident.plan(mix, 384, 512, 262_144, 65_536, 5)
    # about 1,700 tokens a slot when the window opens (prompt + age; the age
    # of a member met at a random moment leans to the long outputs), 656,000
    # in all: 82% of the 800,000 the pages hold
    at_its_age = [p + a for p, a, _ in planned]
    assert 1_650 < np.mean(at_its_age) < 1_760 and 0.78 < sum(at_its_age) / 800_000 < 0.86
    assert len(cohort) == 384 and len(backlog) == 512
    # every request fits a table of 4,096 positions
    assert max(len(p) + n for p, n in cohort + backlog) <= 4_096
    assert all(256 <= len(p) <= 1024 for p, _ in backlog)
    # a member finishes every 5 steps and brings 1.7 chunk steps: a third
    chunks = np.mean([-(-len(p) // 512) for p, _ in backlog])
    assert 1.6 < chunks < 1.8 and 0.31 < chunks / 5 < 0.36
    # the mix's ceiling is twice what the cell reads
    assert 512 * 1_920 / 48 == pytest.approx(20_480)


def test_the_scopes_and_the_kernels_the_metrics_name_are_the_programs():
    import inspect
    from deepspeed_tpu.models import gpt, hybrid
    from deepspeed_tpu.ops.pallas import selective_scan
    cell = cells.Cell(CELL)
    source = inspect.getsource(hybrid) + inspect.getsource(gpt)
    for name, scope in SCOPES.items():
        assert cell.reader(name)[1] == {"scopes": [scope]}
        assert f'named_scope("{scope}")' in source, scope
    for name, kernel in KERNELS.items():
        fn, args = cell.reader(name)
        assert fn is jamba.kernel_roofline and args["kernel"] == kernel
        assert f'name="{kernel}"' in inspect.getsource(selective_scan)
        assert callable(cells.resolve(args["call"]))
    from benchmarks.readers import olmo_hybrid
    assert cell.reader("mamba_state_moves_per_step.gen") == (
        olmo_hybrid.state_moves_per_step, {"stat": "mamba_state_moves"})
    assert '"mamba_state_moves"' in inspect.getsource(
        __import__("deepspeed_tpu.serving.engine", fromlist=["x"]))


# ---- the counters and the notes ------------------------------------------------------- #
class _Srv:
    """What ``attention_counters`` reads of a ``Serving``."""
    slots, chunk, block = 384, 512, 64

    def __init__(self):
        self.cell = cells.Cell(CELL)
        self.params = {"wte": np.zeros(1, np.dtype("float16"))}       # two bytes a number


def test_the_kind_counts_pages_states_and_moves_from_the_lengths():
    """Two steps: 384 decode rows each, the second with a chunk of 300 prompt
    tokens of a request that starts: 2 x 384 + 1 moves a mamba layer."""
    srv = _Srv()
    kw = srv.cell.config["model"]["kwargs"]
    before = {r: (600, 1500 + r, 900 + r) for r in range(384)}
    after = {r: (600, 1502 + r, 902 + r) for r in range(384)}
    after[999] = (300, 300, 1)                      # its one chunk yields its first token
    steps = [(0.0, 0.03, 384, 0, 0, 0, 0), (0.03, 0.09, 384, 300, 0, 0, 0)]
    c = kind.attention_counters(srv, {"before": before, "after": after}, steps)
    assert c["traced_step_decode_rows"] == [384, 384]
    assert c["traced_step_chunk_tokens"] == [0, 300]
    assert c["mamba_state_moves"] == 769 * 26 and c["traced_step_rows"] == [384, 684]
    assert c["mamba_state_bytes_moved"] == 769 * 26 * 2 * STATE
    assert c["mamba_conv_bytes_moved"] == 769 * 26 * 2 * CONV
    assert c["attention_rows_live"] == 1068 and c["attention_rows_idle"] == 2 * 896 - 1068
    decode = np.concatenate([np.arange(1500 + r, 1502 + r) for r in range(384)])
    flops, pages = arith_jamba.full_rows(decode, [(0, 300)], 2, 64, kw)
    # the chunk's pages 0..4 once, K and V of the one K/V head's 128 lanes
    assert pages == 2 * (2 * (int((decode // 64 + 1).sum()) + 5) * 64 * 128 * 2
                         + 2 * 1068 * 2560 * 2)
    assert c["full_pages_bytes"] == pages
    assert c["paged_gqa_bytes"] == pages + c["mamba_state_bytes_moved"] + c["mamba_conv_bytes_moved"]
    assert c["paged_gqa_flops"] == flops + arith_jamba.mamba_rows(1068, 769, 26, kw)[0]


class _Trace:
    def __init__(self, runs, seconds):
        self.runs, self.seconds = runs, seconds

    def program_runs(self):
        return self.runs

    def op_seconds(self):
        return self.seconds


def _run(ops, runs=3, seconds=None, counters=None):
    stats = {"first_tokens": [], "chips": [
        (sum(s for _, s in ops), [(frozenset(c), s) for c, s in ops])]}
    rows = {"traced_step_decode_rows": [384, 384, 383, 384],
            "traced_step_chunk_tokens": [512, 0, 512, 300]}
    seconds = {"mamba_state_update": 0.030, "mamba_chunk_scan": 0.012} if seconds is None else seconds
    return {"trace": _Trace(runs, seconds), "notes": {}, "cell": cells.Cell(CELL),
            "peaks": PEAKS, "counters": rows if counters is None else counters,
            "_program_stats": stats}


def test_the_cells_own_entries_read_a_synthetic_trace():
    """Three steps of 10 ms busy whose ops lie under the program's nested
    scopes: each share is the self time under its scope over the busy time;
    each kernel's roofline divides the least time of the LAST three steps'
    calls (the device line holds three of the host's four), 26 layers each,
    by the kernel's self time.  Through the entries' own files."""
    ops = [(("attn", "attn_mamba"), 6.0e-3),
           (("attn", "attn_mamba", "mamba_conv"), 3.0e-3),
           (("attn", "attn_mamba", "mamba_params"), 0.6e-3),
           (("attn", "attn_mamba", "mamba_scan"), 8.4e-3),            # both kernels among it
           (("attn", "attn_full"), 2.4e-3),
           (("mlp",), 7.5e-3),
           (("head",), 2.1e-3)]
    run = _run(ops)
    cell = run["cell"]
    read = lambda name, r: cell.reader(name)[0](r, **cell.reader(name)[1])
    want = {"attn_mamba": 60.0, "mamba_conv": 10.0, "mamba_params": 2.0, "mamba_scan": 28.0,
            "attn_full": 8.0, "mlp": 25.0, "head": 7.0}
    for name, scope in SCOPES.items():
        assert read(name, run) == pytest.approx(want[scope]), scope
    kw = cell.config["model"]["kwargs"]
    update = 26 * sum(arith_jamba.state_update_call(n, kw)[1] for n in (384, 383, 384))
    assert read("mamba_state_update_roofline", run) == pytest.approx(100 * update / 819e9 / 0.030)
    scan = 26 * sum(arith_jamba.chunk_scan_call(n, kw)[1] for n in (512, 300))
    assert read("mamba_chunk_scan_roofline", run) == pytest.approx(100 * scan / 819e9 / 0.012)
    assert run["notes"]["roofline_bound"] == {"mamba_state_update": "memory",
                                              "mamba_chunk_scan": "memory"}
    assert 0 < read("mamba_chunk_scan_roofline", run) < read("mamba_state_update_roofline", run) < 100
    # a program without the kernels, a kind that left no count, a run
    # without a trace: nothing to read, and nothing raised
    gone = _run([(("attn",), 1e-3)], seconds={})
    bare = {"trace": None, "counters": {}, "notes": {}, "cell": cell}
    for name in KERNELS:
        assert read(name, gone) is None and read(name, bare) is None
        assert read(name, _run(ops, counters={})) is None
    assert read("mamba_scan_share_pct.gen", gone) is None


def test_the_kinds_limits_judge_a_sample():
    assert kind.judge([0.1, 0.2], [0.01, 0.02], 0.015) == 0
    assert kind.judge([kind.LOGIT_MARGIN + 0.01, 0.2], [0.0, 0.0], 0.0) == 1
    over = kind.NOISE_LIMIT * 1.5
    assert kind.judge([0.1, 0.1, 0.1], [over, over, 0.0], over) == 2
    assert kind.judge([0.1, 0.1, 0.1], [over, 0.0, 0.0], 0.0) == 0     # the median holds
    # each limit between its two chip readings (PERF.md § 6, PR 57): the
    # largest a bf16 run of the cell read as served, and the least of the
    # control that limit must refuse
    assert BF16_NOISE * 1.3 < kind.NOISE_LIMIT < FORGETS_NOISE / 1.3 < FLOAT8_NOISE
    assert BF16_GAP * 1.5 < kind.LOGIT_MARGIN < FORGETS_GAP / 1.3 < FLOAT8_GAP
    assert BF16_STATE * 1.5 < kind.STATE_LIMIT < STATE16_SLOT / 1.2 < STATE16_STATE / 1.5


# the readings of my chip runs (PR 57).  As served: the largest noise-scale
# median, the largest gap and the largest first-layer state gap of a kept slot
# over the bf16 runs of the cell.  The controls: a chunk that forgets its
# carried state (its noise-scale median; the least of its requests' gaps),
# every matrix through float8 (its noise scale; the least gap), the state
# kept in bf16 (the least median of a run and the least kept slot)
BF16_NOISE, BF16_GAP, BF16_STATE = 0.082, 0.240, 0.0043
FORGETS_NOISE, FORGETS_GAP = 0.175, 0.63
FLOAT8_NOISE, FLOAT8_GAP = 999.99, 2.11
STATE16_STATE, STATE16_SLOT = 0.0143, 0.0080


# ---- the check of the state ----------------------------------------------------------- #
def _tiny():
    """The rehearse preset's model and reference, float32."""
    import jax
    from benchmarks.lib.build import model_from
    cell = cells.Cell(CELL)
    cells.merge(cell.config, cell.config["rehearse"])
    model = model_from(cell.config)
    return model, model.init_params(jax.random.PRNGKey(3)), cell.config["reference"]


def test_the_references_states_are_its_recurrences_after_the_tokens_named():
    """``jamba_states`` of a padded sequence after its first ``n`` tokens is
    what the pass over those ``n`` alone ends with, a state a mamba layer
    ``[channels, states]``; the hidden rows are not touched by the change."""
    from benchmarks.lib import reference_jamba as ref
    _, params, reference = _tiny()
    kw = reference["kwargs"]
    ids = np.random.default_rng(0).integers(0, 512, 64).astype(np.int32)
    whole = np.asarray(ref.jamba_states(params, ids[:32], 32, **kw))
    assert whole.shape == (4, 128, 16) and np.abs(whole).max() > 1e-4
    padded = np.asarray(ref.jamba_states(params, ids, 32, **kw))
    assert np.abs(padded - whole).max() < 1e-6 * np.abs(whole).max()
    assert np.abs(np.asarray(ref.jamba_states(params, ids, 33, **kw)) - whole).max() > 1e-5
    assert np.asarray(ref.jamba_hidden(params, ids, **kw)).shape == (64, 64)


@pytest.mark.parametrize("fault, least", [
    (None, 0.0), ("scaled", 0.0099), ("rounded", 1e-3), ("a_token_short", 1e-3)])
def test_the_state_gap_sees_a_state_that_is_not_the_references(fault, least):
    """A slot that holds the reference's own states (kept ``[states,
    channels]`` as the program keeps them) reads a gap of rounding's size in
    every layer; one whose states are 1% off, were rounded through bf16, or
    are a token behind does not."""
    from benchmarks.lib import reference_jamba as ref
    _, params, reference = _tiny()
    ids = np.random.default_rng(1).integers(0, 512, 32).astype(np.int32)
    n = len(ids) - (fault == "a_token_short")
    import jax.numpy as jnp
    held = np.asarray(ref.jamba_states(params, ids[:n], n, **reference["kwargs"]))
    held = held.transpose(0, 2, 1)
    if fault == "scaled":
        held = held * 1.01
    if fault == "rounded":
        held = np.asarray(jnp.asarray(held).astype(jnp.bfloat16).astype(jnp.float32))
    gaps = kind.state_gaps(params, reference, [(ids, held), (ids[:24], held)])
    assert gaps.shape == (2, 4)
    assert (gaps[0] < 1e-5).all() if fault is None else (gaps[0] > least).all()
    assert (gaps[1] > 0.1).all()                    # another sequence's states


def test_the_slots_kept_are_decoding_slots_and_what_their_states_took_in():
    class Req:
        def __init__(self, prompt, generated, prefilled):
            self.prompt, self.generated, self.prefilled = prompt, generated, prefilled
            self.context = prompt + generated

    class Engine:
        _aux = {"mamba_state": np.arange(2 * 4 * 3 * 5, dtype=np.float32).reshape(2, 4, 3, 5)}

        class sched:
            active = {0: Req([1, 2, 3], [], 2),              # still in its prompt
                      2: Req([1, 2, 3], [7, 8, 9], 5),       # 9 is made, not yet taken in
                      3: Req([4, 5], [6], 2)}
    kept = kind.slots_kept(Engine, 4, seed=3000000019)
    assert [ids.tolist() for ids, _ in kept] == [[1, 2, 3, 7, 8], [4, 5]]
    assert all((state == Engine._aux["mamba_state"][:, slot]).all()
               for (_, state), slot in zip(kept, (2, 3)))
    assert len(kind.slots_kept(Engine, 1, seed=5)) == 1


def test_a_kinds_names_are_the_residents_again_after_its_run():
    from benchmarks.lib import resident_stack
    theirs = resident.attention_counters
    with pytest.raises(RuntimeError):
        with resident_stack.replaced(resident, attention_counters=len, Resident=dict):
            assert resident.attention_counters is len and resident.Resident is dict
            raise RuntimeError
    assert resident.attention_counters is theirs and resident.Resident is not dict
    assert set(kind.PLANTED) == {None, "state-bfloat16", "chunk-forgets-state", "weights-float8"}


# ---- the cell's control flow, at the rehearse size ---------------------------------- #
def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(cells.ROOT, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "2", "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=cells.ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["requests_wrong"] == [0, 0]
    assert line["compared"]["cohort_not_filled"] == [0, 0]
    gap, limit = line["compared"]["mamba_state_gap_median"]
    assert gap < 1e-5 and limit == kind.STATE_LIMIT       # float32 against float32
    assert line["compared"]["slots_whose_state_is_wrong"] == [0, 0]
    assert SHARED | OWN | {"serve_tokens_per_s", "setup_s"} <= set(line["would_report"])


def test_a_chunk_that_forgets_its_state_is_not_correct():
    """The planted fault through the cell's own command: every kept slot
    whose prompt was more than one chunk holds another state."""
    out = subprocess.run(
        [sys.executable, os.path.join(cells.ROOT, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "2", "--rehearse",
         "--set", 'planted="chunk-forgets-state"'],
        capture_output=True, text=True, timeout=600, cwd=cells.ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] > 0
    assert line["compared"]["mamba_state_gap_median"][0] > 10 * kind.STATE_LIMIT
