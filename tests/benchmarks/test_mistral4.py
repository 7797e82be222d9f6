"""Tests of what the benchmark adds for Mistral-Small-4's cell: the
configuration against the catalog's row, the two numbers of its arena against
the engine's arrays, the latent kernel's operations and bytes by hand, and the
new readers on runs with nothing to read; CPU only."""

import json
import types

import numpy as np
import pytest

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.kinds import serve_backlog_resident_routed4 as kind
from benchmarks.lib import arith, arith_mla, arith_window, cells
from benchmarks.readers import held_experts, paged_mla

CELL = "mistral-small-4-119b.serve-reasoning-batch"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the entries this cell came with (PR 33), under the names PR 68 folded them into
NEW = ("paged_mla_attention_share_pct.gen", "paged_mla_attention_roofline",
       "attn_share_pct.gen", "moe_share_pct.gen",
       "moe_shared_expert_share_pct.gen", "moe_bank_copy_share_pct.gen",
       "moe_assignments_held_pct.gen")
PEAKS = {"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12}


def test_the_configuration_is_the_catalogs_but_for_the_three_reduced_keys():
    source = {
        "attention_bias": False, "first_k_dense_replace": 0, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 12288,
        "kv_lora_rank": 256, "max_position_embeddings": 1048576, "mlp_bias": False,
        "model_type": "mistral4", "moe_intermediate_size": 2048, "n_group": 1,
        "n_routed_experts": 128, "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4, "num_hidden_layers": 36,
        "num_key_value_heads": 32, "q_lora_rank": 1024, "qk_head_dim": 128,
        "qk_nope_head_dim": 64, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True,
        "rope_parameters": {"beta_fast": 32, "beta_slow": 1, "factor": 128,
                            "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
                            "original_max_position_embeddings": 8192, "rope_theta": 10000,
                            "rope_type": "yarn", "type": "yarn"},
        "routed_scaling_factor": 1, "sliding_window": None, "tie_word_embeddings": False,
        "topk_group": 1, "v_head_dim": 128, "vocab_size": 131072}
    try:        # the catalog beside the guide, where it is installed
        rows = [json.loads(l) for l in open(CATALOG)]
        row = next(r for r in rows if r["name"] == "Mistral-Small-4-119B-2603")
        assert row["config"] == source
        assert row["source_url"] == cells.Cell(CELL).config["source"]
    except FileNotFoundError:
        pass
    cfg = cells.Cell(CELL).config
    differs = sorted(k for k, v in source.items() if cfg.get(k, "missing") != v)
    assert differs == sorted(cfg["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (
        5, 32, 32768)
    # the router stays 128 wide, and the file says which 32 are held
    assert cfg["n_routed_experts_the_router_chooses_among"] == 128
    assert cfg["experts_held"] == [0, 32]
    assert set(cfg["assumed"]) >= {"scoring", "softmax_scale", "query_scale",
                                   "score_correction_bias", "weights", "deployment"}
    # the program's builder and the reference are given the same layer
    mk, rk = cfg["model"]["kwargs"], cfg["reference"]["kwargs"]
    assert (mk["n_embd"], mk["n_layer"], mk["n_head"], mk["vocab_size"]) == (
        cfg["hidden_size"], 5, cfg["num_attention_heads"], 32768)
    assert (mk["q_lora_rank"], mk["kv_lora_rank"], mk["qk_rope_dim"], mk["v_head_dim"]) == (
        cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    assert mk["head_dim"] == cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] == 128
    assert (mk["num_experts"], mk["top_k"], mk["intermediate_size"], mk["shared_experts"]) == (
        128, cfg["num_experts_per_tok"], cfg["moe_intermediate_size"], cfg["n_shared_experts"])
    assert mk["experts_held"] == rk["experts_held"] == cfg["experts_held"]
    rp = cfg["rope_parameters"]
    assert mk["rope_yarn"] == [rp["factor"], rp["original_max_position_embeddings"],
                               rp["beta_fast"], rp["beta_slow"], rp["mscale"],
                               rp["mscale_all_dim"], rp["llama_4_scaling_beta"]]
    assert rk["rope_parameters"] == rp and rk["n_routed_experts"] == 128
    assert mk["n_positions"] == cfg["max_position_embeddings"]


def test_the_weights_are_what_the_file_says():
    from benchmarks.lib.build import model_from
    cfg = cells.Cell(CELL).config
    model = model_from(cfg)
    assert model.num_params() == 4_563_716_992
    assert "4,563,716,992 parameters = 9,127,433,984 B" in cfg["reduced_why"]


def test_both_numbers_of_the_arena_are_the_engines():
    """``serve.arena_bytes`` is the number ``lib/serving.py``'s divisor (K and
    V of 32 heads of 128) turns into 50,000 blocks; what the arena really
    holds is ``arena_bytes_really_held``, the program's own count: both held
    to the engine's arrays, at the rehearse size."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from benchmarks.lib.build import model_from
    from deepspeed_tpu.serving.kv_cache import arena_bytes
    cfg = cells.Cell(CELL).config
    serve, mcfg = cfg["serve"], model_from(cfg).cfg
    per_block = 2 * mcfg.n_layer * 16 * mcfg.kv_heads * mcfg.head_dim * 2
    assert per_block == 1_310_720 and serve["arena_bytes"] // per_block == 50_000
    assert serve["arena_bytes"] % per_block == 0
    assert serve["arena_bytes_really_held"] == arena_bytes(mcfg, 50_000, 16) == (
        800_000 * 5 * 768) == 3_072_000_000
    assert serve["serving"] == {"max_batch_size": 128, "prefill_chunk": 384,
                                "block_size": 16, "max_blocks_per_seq": 1024,
                                "dtype": "bfloat16"}
    # the rehearse size, through the harness's own arithmetic to an engine
    cells.merge(cfg, cfg["rehearse"])
    model = model_from(cfg)
    lanes = model.cfg.kv_heads * model.cfg.head_dim
    blocks = cfg["serve"]["arena_bytes"] // (2 * model.cfg.n_layer * 16 * lanes * 2)
    assert blocks == 200
    eng = deepspeed_tpu.init_serving(
        model=model, params=model.init_params(jax.random.PRNGKey(0)),
        config={"serving": dict(cfg["serve"]["serving"], num_blocks=blocks,
                                dtype="bfloat16")})
    try:
        assert eng._v_pages is None and eng._k_pages.dtype == jnp.bfloat16
        assert eng._k_pages.shape == (2, 200, 16, 256)       # 128 + 16 in 256 lanes
        assert eng._k_pages.nbytes == arena_bytes(model.cfg, 200, 16)
        assert eng.cache_bytes_per_token == 512
    finally:
        eng.close()


# ---- the kernel's arithmetic by hand -------------------------------------------- #
def test_latent_attention_by_hand():
    """Three decode rows over two layers read 100 keys between them and
    multiply as many pairs: each key's 320 cached numbers once, 2 x 32 x (320
    + 256) operations a pair; a row reads 32 queries of 320 and writes 32
    outputs of 256.  A chunk multiplies more pairs than it reads keys."""
    flops, nbytes = arith_mla.latent_attention(100, 100, 3, 32, 256, 64)
    assert flops == 2 * 32 * (320 + 256) * 100 == 3_686_400
    assert nbytes == 100 * 640 + 3 * 32 * (320 + 256) * 2 == 174_592
    assert arith_mla.latent_attention(100, 5000, 3, 32, 256, 64) == (50 * flops, nbytes)


def test_the_kind_leaves_the_reads_and_the_pairs_in_keys():
    """Two decode rows at positions 5 and 40, pages of 16, three layers: (1 +
    3) pages x 16 keys x 3 layers read and multiplied, the idle row nothing;
    a chunk of 20 from 30 reads the pages 0..3 once and multiplies each
    query's own pages."""
    read, pairs = arith_window.keys(np.asarray([5, 40]), [], {None: 3}, 16)
    assert read == pairs == (1 + 3) * 16 * 3 == 192
    read, pairs = arith_window.keys(np.zeros(0, np.int64), [(30, 20)], {None: 3}, 16)
    assert read == 4 * 16 * 3
    assert pairs == sum(t // 16 + 1 for t in range(30, 50)) * 16 * 3


def _run(op_seconds, counters, config=None):
    trace = types.SimpleNamespace(op_seconds=lambda: op_seconds)
    cfg = config or cells.Cell(CELL).config
    return {"trace": trace, "counters": counters, "peaks": PEAKS, "notes": {},
            "cell": types.SimpleNamespace(config=cfg)}


def test_roofline_is_least_time_over_the_kernels_time():
    read, pairs = arith_window.keys(np.asarray([5, 40]), [], {None: 5}, 16)
    counters = {"attention_keys_read": read, "attention_key_products": pairs,
                "attention_rows_live": 2, "attention_rows_idle": 1}
    run = _run({"paged_mla_attention": 2e-6}, counters)
    keys, rows = 4 * 16 * 5, 2 * 5                      # the idle row reads nothing
    want_bytes = keys * 640 + rows * 32 * 576 * 2
    want_flops = 2 * 32 * 576 * keys
    least, which = arith.roofline_seconds(want_flops, want_bytes, PEAKS)
    assert which == "memory"
    assert paged_mla.roofline(run) == pytest.approx(100 * least / 2e-6)
    assert run["notes"]["roofline_bound"] == {"paged_mla_attention": "memory"}


def test_readers_give_none_where_there_is_nothing_to_read():
    counters = {"attention_keys_read": 10, "attention_key_products": 10,
                "attention_rows_live": 1, "attention_rows_idle": 0}
    assert paged_mla.roofline({"trace": None, "counters": counters, "notes": {}}) is None
    # a parent's program (no such kernel), a run without the kind's counters,
    # a model with K and V
    assert paged_mla.roofline(_run({"paged_gqa_attention": 1e-3}, counters)) is None
    assert paged_mla.roofline(_run({"paged_mla_attention": 1e-3}, {})) is None
    assert paged_mla.roofline(_run({"paged_mla_attention": 1e-3}, counters,
                                   {"num_hidden_layers": 8})) is None
    assert held_experts.assignments_held_pct({"trace": None, "notes": {}}) is None
    parent = {"trace": object(), "notes": {}, "_moe_span_stats": {
        "serve.decode.commit": [{"batch": 128, "moe_load_max_over_mean": 1.2}]}}
    assert held_experts.assignments_held_pct(parent) is None
    assert held_experts.assignments_held_pct(dict(parent, _moe_span_stats={})) is None


def test_assignments_held_are_summed_over_the_steps():
    run = {"trace": object(), "notes": {}, "_moe_span_stats": {"serve.decode.commit": [
        {"moe_assignments": 2560, "moe_assignments_held": 600},
        {"moe_assignments": 2560, "moe_assignments_held": 680},
        {"batch": 3}]}}
    assert held_experts.assignments_held_pct(run) == pytest.approx(100 * 1280 / 5120)


# ---- the files ------------------------------------------------------------------ #
def test_the_new_metrics_list_the_cell():
    cell = cells.Cell(CELL)
    listed = {m["name"]: m for m in cell.per_layer}
    for name in NEW:
        fn, args = cell.reader(name)
        assert callable(fn) and isinstance(args, dict)
        m = listed[name]
        assert CELL in m["workloads"] and m["unit"] == "%"
        assert m["moves"] == "serve_tokens_per_s"
    assert cell.chips == 1 and cell.kind is kind
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s", "setup_s"]
    # the K/V kernels' metrics are no part of this cell
    assert not {"paged_gqa_attention_roofline", "paged_attention_roofline",
                "moe_experts_roofline"} & set(listed)


def test_the_traffic_is_a_file_of_the_resident_kind_under_its_own_limits():
    mix = cells.Cell(CELL).traffic
    assert mix["kind"] == "serve-backlog-resident-routed4"
    assert kind.END_TO_END == resident.END_TO_END
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 512, "max": 2048}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 2048, "max": 10240}
    assert (mix["backlog_requests"], mix["check_requests"]) == (128, 8)
    cohort, backlog, planned = resident.plan(mix, 128, 384, 16384, 32768, 5)
    # about 4,800 tokens a slot when the window opens (prompt + age), 615,000
    # in all; prefilled short by what a member generates while the fill runs
    at_its_age = [p + a for p, a, _ in planned]
    assert 4600 < np.mean(at_its_age) < 5000 and 590_000 < sum(at_its_age) < 640_000
    assert 4000 < np.mean([len(p) for p, _ in cohort]) < np.mean(at_its_age)
    assert max(len(p) + n for p, n in cohort) <= 2048 + 10240 + 4
    assert len(backlog) == 128


# ---- the limits of the comparison that decides ``correct`` ------------------------ #
def _judged(monkeypatch, largest, scales, failed_besides=0, **notes):
    """``kind.run`` over a result the resident kind would have returned."""
    import statistics
    median = statistics.median(scales)
    theirs = sum(w > resident.LOGIT_MARGIN or (median > resident.NOISE_LIMIT
                                               and s > resident.NOISE_LIMIT)
                 for w, s in zip(largest, scales))
    out = {"correct": False, "attempted": 17, "failed": theirs + failed_besides,
           "notes": dict({"checked": len(largest), "wrong": theirs, "logit_gaps": largest,
                          "noise_scales": scales, "noise_scale_median": median,
                          "backlog_ran_dry": False, "cohort_filled": True}, **notes)}
    monkeypatch.setattr(resident, "run", lambda cell, args, ctx: out)
    return kind.run(None, None, None)


# the readings of PERF.md section 6 (my chip runs, PR 33): a bf16 run, and
# the same cell with its bank through float8_e4m3fn
BF16 = ([2.68, 2.81, 1.83, 1.98, 1.11, 1.61, 1.39, 1.32],
        [0.0487, 0.0492, 0.0508, 0.0507, 0.0523, 0.0557, 0.0555, 0.0468])
FLOAT8 = ([1.76, 2.18, 1.34, 1.38, 2.88, 1.35, 1.76, 1.68],
          [0.2182, 0.2175, 0.1830, 0.2159, 0.2051, 0.1665, 0.1906, 0.2113])


def test_a_sound_bf16_run_is_correct_by_these_limits_and_not_by_the_resident_kinds(
        monkeypatch):
    out = _judged(monkeypatch, *BF16)
    assert out["notes"]["wrong"] == 0 and out["failed"] == 0 and out["correct"] is True
    assert out["notes"]["tie_tolerance"] == kind.LOGIT_MARGIN > resident.LOGIT_MARGIN
    assert out["notes"]["noise_limit"] == kind.NOISE_LIMIT > resident.NOISE_LIMIT
    assert max(BF16[0]) > resident.LOGIT_MARGIN


def test_the_bank_through_float8_is_refused_by_the_noise_limit_alone(monkeypatch):
    out = _judged(monkeypatch, *FLOAT8)
    assert out["notes"]["wrong"] == 8 and out["correct"] is False
    assert max(FLOAT8[0]) < kind.LOGIT_MARGIN              # not by each limit
    # each number beside the limit it was held to, this kind's
    assert out["compared"]["largest_logit_gap"] == [2.88, kind.LOGIT_MARGIN]
    assert out["compared"]["noise_scale_median"][1] == kind.NOISE_LIMIT
    assert out["compared"]["requests_wrong"] == [8, 0]
    assert 2 * max(BF16[1]) < kind.NOISE_LIMIT < min(FLOAT8[1]) / 1.3
    # one noisy request does not fail a run whose median is sound
    scales = list(BF16[1])
    scales[0] = 0.2
    assert _judged(monkeypatch, BF16[0], scales)["correct"] is True


def test_the_gross_limit_refuses_a_token_unrelated_to_the_reference(monkeypatch):
    largest = list(BF16[0])
    largest[3] = 7.5
    out = _judged(monkeypatch, largest, BF16[1])
    assert out["notes"]["wrong"] == 1 and out["failed"] == 1 and out["correct"] is False


def test_what_else_fails_a_run_still_fails_it(monkeypatch):
    assert _judged(monkeypatch, *BF16, failed_besides=2)["failed"] == 2
    assert _judged(monkeypatch, *BF16, failed_besides=2)["correct"] is False
    assert _judged(monkeypatch, *BF16, backlog_ran_dry=True)["correct"] is False
    assert _judged(monkeypatch, *BF16, cohort_filled=False)["correct"] is False
