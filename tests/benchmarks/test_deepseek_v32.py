"""Tests of what the benchmark adds for DeepSeek-V3.2-Exp's long-latent-indexed
cell: the configuration against the catalog's row, its bytes held to the
arrays the engine builds, the count of the caches' work by hand, the cell, its
traffic's plan and the metrics it joins, the kind's two limits and its
controls, and the rehearsal; CPU only."""

import json
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.kinds import serve_backlog_resident_latent_indexed as kind
from benchmarks.lib import arith_deepseek_v32 as arith_ds
from benchmarks.lib import arith_step, cells
from benchmarks.readers import keye_vl2

CELL = "deepseek-v3.2-exp.serve-long-latent-indexed"
KEYE = "keye-vl-2.0-30b-a3b.serve-long-indexed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"]
# Keye's metrics that read the scopes and counters this stack opens under the
# same names, and the head's share
JOINED = ("attn_indexed_share_pct.gen", "index_score_share_pct.gen",
          "index_topk_share_pct.gen", "index_attend_share_pct.gen",
          "indexed_keys_read_pct.gen", "index_score_roofline",
          "indexed_attention_roofline", "lm_head_share_pct.gen")
# the cell's own, listed since PR 68 (PR 61 left them in
# ``notes.deepseek_layers``): entry -> the scope it reads
SCOPES = {"latent_project_share_pct.gen": "latent_project",
          "route_groups_share_pct.gen": "route_groups", "lead_mlp_share_pct.gen": "lead_mlp",
          "moe_share_pct.gen": "moe", "moe_router_share_pct.gen": "moe_router",
          "moe_experts_share_pct.gen": "moe_experts",
          "moe_shared_expert_share_pct.gen": "moe_shared"}
OWN = set(SCOPES) | {"moe_assignments_held_pct.gen", "grouped_matmul_roofline"}


def test_the_configuration_is_the_catalogs_but_for_its_share():
    cfg = cells.Cell(CELL).config
    try:        # the catalog beside the guide, where it is installed
        rows = [json.loads(l) for l in open(CATALOG)]
        source = next(r for r in rows if r["name"] == "DeepSeek-V3.2-Exp")
        assert cfg["source"] == source["source_url"]
        assert sorted(k for k, v in source["config"].items()
                      if cfg.get(k, "missing") != v) == sorted(REDUCED)
        assert {k: source["config"][k] for k in REDUCED} == cfg["published"]
    except FileNotFoundError:
        pass
    assert cfg["reduced"] == REDUCED
    assert [cfg[k] for k in REDUCED] == [5, 1, 16, 16160]
    assert cfg["published"] == {"num_hidden_layers": 61, "first_k_dense_replace": 3,
                                "n_routed_experts": 256, "vocab_size": 129280}
    kw, ref = cfg["model"]["kwargs"], cfg["reference"]["kwargs"]
    assert (kw["n_embd"], kw["n_head"], kw["head_dim"], kw["q_lora_rank"], kw["kv_lora_rank"],
            kw["qk_rope_dim"], kw["v_head_dim"], kw["intermediate_size"],
            kw["moe_intermediate_size"], kw["num_experts"], kw["top_k"], kw["n_group"],
            kw["topk_group"], kw["shared_experts"], kw["route_scale"], kw["n_positions"]) == (
                cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["q_lora_rank"],
                cfg["kv_lora_rank"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                cfg["intermediate_size"], cfg["moe_intermediate_size"],
                cfg["n_routed_experts_the_router_chooses_among"], cfg["num_experts_per_tok"],
                cfg["n_group"], cfg["topk_group"], cfg["n_shared_experts"],
                cfg["routed_scaling_factor"], cfg["max_position_embeddings"]) == (
                    7168, 128, 192, 1536, 512, 64, 128, 18432, 2048, 256, 8, 8, 4, 1, 2.5,
                    163840)
    assert kw["indexer"] == [cfg["index_n_heads"], cfg["index_head_dim"],
                             cfg["index_topk"]] == [64, 128, 2048]
    assert (kw["n_layer"], kw["dense_layers"], kw["vocab_size"]) == (5, 1, 16160)
    assert kw["experts_held"] == cfg["experts_held"] == ref["experts_held"] == [0, 16]
    rs = cfg["rope_scaling"]
    assert kw["rope_yarn"] == [rs["factor"], rs["original_max_position_embeddings"],
                               rs["beta_fast"], rs["beta_slow"], rs["mscale"],
                               rs["mscale_all_dim"], 0.0]
    assert ref["rope_scaling"] == rs and ref["rope_theta"] == cfg["rope_theta"] == 10000
    assert (ref["n_head"], ref["q_lora_rank"], ref["kv_lora_rank"], ref["qk_nope_head_dim"],
            ref["qk_rope_head_dim"], ref["v_head_dim"], ref["index_n_heads"],
            ref["index_head_dim"], ref["index_topk"], ref["top_k"], ref["n_routed_experts"],
            ref["n_group"], ref["topk_group"], ref["first_k_dense_replace"],
            ref["routed_scaling_factor"], ref["vocab_size"]) == (
                128, 1536, 512, 128, 64, 128, 64, 128, 2048, 8, 256, 8, 4, 1, 2.5, 16160)
    assert ref["eps"] == cfg["rms_norm_eps"] == 1e-6 and cfg["tie_word_embeddings"] is False
    # what neither the config nor described_as fixes
    assert {"rope_pairings", "indexer_input", "indexer_norm", "indexer_precision",
            "selection", "softmax_scale", "scoring", "score_correction_bias", "mtp",
            "dtype", "weights", "deployment"} <= set(cfg["assumed"])
    assert "1/16" in cfg["assumed"]["deployment"] and "0.4 rows" in cfg["assumed"]["deployment"]
    assert "device_idle_pct.gen" in cfg["assumed"]["deployment"]


def test_the_program_builds_the_share_from_the_file():
    import jax
    from benchmarks.lib.build import model_from
    cfg = cells.Cell(CELL).config
    model = model_from(cfg)
    mcfg = model.cfg
    assert not mcfg.hybrid and mcfg.indexed_layers == 5 and tuple(mcfg.indexer) == (64, 128, 2048)
    assert (mcfg.moe_n_group, mcfg.moe_topk_group, mcfg.moe_dense_layers,
            mcfg.moe_experts_held, mcfg.moe_scoring) == (8, 4, 1, (0, 16), "sigmoid")
    assert mcfg.cache_lanes == (640,) and mcfg.rope_interleaved and mcfg.untied_head
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    zeros = 5 * 3 * 7168 + 7168          # ln1_b, ln2_b, out_b a layer; lnf_b
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) - zeros
    assert held == model.num_params() == 4_636_894_464
    assert "4,636,894,464 parameters = 9,273,788,928 B" in cfg["reduced_why"]
    w = arith_ds.deepseek_v32_weights(cfg["model"]["kwargs"])
    assert w["dense"] + w["gathered"] + arith_step.bank_params(w["bank"]) == held
    assert arith_step.bank_params(w["bank"]) == 4 * 704_643_072
    assert arith_ds.attention_params(cfg["model"]["kwargs"]) == 201_081_088
    assert w["gathered"] == 16_256 * 7168
    assert w["dense"] == (5 * 201_081_088 + 396_361_728
                          + 4 * (1_835_264 + 44_040_192) + 7168 + 16_256 * 7168)
    # at the published counts the same arithmetic gives the published size
    whole = arith_ds.deepseek_v32_weights(dict(
        cfg["model"]["kwargs"], n_layer=61, dense_layers=3, vocab_size=129280,
        experts_held=None))
    total = whole["dense"] + whole["gathered"] + arith_step.bank_params(whole["bank"])
    assert 671.0e9 < total < 672.5e9


def test_the_arena_and_the_index_pages_are_the_engines():
    """``serve.arena_bytes`` is what ``lib/serving.py``'s divisor (K and V of
    128 heads of 192) turns into 8,448 blocks; what is held is the latent's
    640 lanes and the index keys' 128 a token a layer, 4.15 GB; both are held
    to the engine's arrays at the rehearse size."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from benchmarks.lib.build import model_from
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.serving.kv_cache import arena_bytes
    cfg = cells.Cell(CELL).config
    serve, mcfg = cfg["serve"], model_from(cfg).cfg
    per_block = 2 * mcfg.n_layer * 64 * mcfg.kv_heads * mcfg.head_dim * 2
    assert per_block == 31_457_280 and serve["arena_bytes"] == 8_448 * per_block
    assert serve["arena_bytes_really_held"] == arena_bytes(mcfg, 8_448, 64) == 3_460_300_800
    aux = jax.eval_shape(lambda: hybrid.init_aux(mcfg, 8_448, 64, 12, jnp.bfloat16))
    assert set(aux) == {"ki"} and aux["ki"].shape == (5, 8_448, 64, 128)
    assert serve["index_key_bytes"] == aux["ki"].size * 2 == 692_060_160
    assert serve["arena_bytes_really_held"] + serve["index_key_bytes"] == 4_152_360_960
    assert 8_448 * 64 == 540_672
    assert serve["serving"] == {"max_batch_size": 12, "prefill_chunk": 512, "block_size": 64,
                                "max_blocks_per_seq": 720, "dtype": "bfloat16"}
    assert 720 * 64 == 40_960 + 5_120
    # weights and caches: 13.4 GB of the 16.9 the runtime offers
    assert 13.4e9 < 9_273_788_928 + 4_152_360_960 < 13.5e9
    # the rehearse size, through the harness's own arithmetic to an engine
    cells.merge(cfg, cfg["rehearse"])
    model = model_from(cfg)
    lanes = model.cfg.kv_heads * model.cfg.head_dim
    blocks = cfg["serve"]["arena_bytes"] // (2 * model.cfg.n_layer * 16 * lanes * 4)
    assert blocks == 65
    eng = deepspeed_tpu.init_serving(
        model=model, params=model.init_params(jax.random.PRNGKey(0)),
        config={"serving": dict(cfg["serve"]["serving"], num_blocks=blocks, dtype="bfloat16")})
    try:
        assert eng._k_pages.shape == (3, 65, 16, 256) and eng._v_pages is None
        assert eng._k_pages.nbytes == arena_bytes(model.cfg, 65, 16)
        assert eng._aux["ki"].shape == (3, 65, 16, 16) and eng._aux["ki"].dtype == jnp.bfloat16
        assert eng.alloc.num_blocks == 65 and eng._moe_count_rows == 1
    finally:
        eng.close()


# ---- the caches' work, by hand ------------------------------------------------------ #
def test_a_rows_work_by_hand():
    """One decode row at 34,700 keys, five layers: 34,701 index keys of 128
    lanes read and 2 x 64 x 128 operations each; 2,048 chosen tokens, each ONE
    cached vector of 576 numbers (1,152 B) for all 128 heads, and 2 x 128 x
    (576 + 512) = 278,528 operations each.  A chunk of 512 from position
    1,024: its sequence's 1,536 index keys read once for all its rows; every
    row attends all its keys (under topk), the bytes its sequence's latent
    once."""
    assert arith_ds.keys_attended([0, 2047, 2048, 34_700]).tolist() == [1, 2048, 2048, 2048]
    s_flops, s_bytes = arith_ds.score_rows([34_700], [], 5)
    assert s_bytes == 5 * 34_701 * 128 * 2 and s_flops == 5 * 2 * 64 * 128 * 34_701
    a_flops, a_bytes = arith_ds.attend_rows([34_700], [], 5, 128, 512, 64)
    assert a_bytes == 5 * 2048 * 1152 and a_flops == 5 * 278_528 * 2048
    assert a_flops / a_bytes == pytest.approx(241.8, rel=0.01)     # v5e's ridge: 240
    rows = 1024 + np.arange(512)
    s_flops, s_bytes = arith_ds.score_rows([], [(1024, 512)], 1)
    assert s_bytes == 1536 * 128 * 2 and s_flops == 2 * 64 * 128 * int((rows + 1).sum())
    a_flops, a_bytes = arith_ds.attend_rows([], [(1024, 512)], 1, 128, 512, 64)
    assert a_bytes == 1536 * 1152 and a_flops == 278_528 * int((rows + 1).sum())
    # past topk a chunk's rows choose 2,048 each: still its sequence once
    a_flops, a_bytes = arith_ds.attend_rows([], [(30_000, 512)], 1, 128, 512, 64)
    assert a_bytes == 30_512 * 1152 < 512 * 2048 * 1152
    assert a_flops == 278_528 * 512 * 2048
    assert arith_ds.indexer_of({"indexer": [4, 16, 48]}) == {"heads": 4, "head_dim": 16,
                                                             "topk": 48}
    assert arith_ds.indexer_of({}) == arith_ds.INDEXER == {"heads": 64, "head_dim": 128,
                                                           "topk": 2048}


def test_attention_counters_take_a_decode_row_at_its_position_and_a_chunk_once():
    """Over a stretch of 3 steps: one request decodes 3 tokens from 30,000
    keys, another runs two chunks of its prompt from 1,024; 12 + 512 rows a
    program."""
    cfg = cells.Cell(CELL).config
    srv = types.SimpleNamespace(
        model=types.SimpleNamespace(cfg=types.SimpleNamespace(
            n_head=128, kv_lora_rank=512, qk_rope_dim=64, n_layer=5)),
        cell=types.SimpleNamespace(config=cfg), slots=12, chunk=512,
        params={"wte": np.zeros(1, np.float16)})
    snaps = {"before": {1: (20_000, 30_000, 10_000), 2: (30_000, 1024, 0)},
             "after": {1: (20_000, 30_003, 10_003), 2: (30_000, 2048, 0)}}
    steps = [(0, 0, 1, 512, 0, 0, 0), (0, 0, 1, 512, 0, 0, 0), (0, 0, 1, 0, 0, 0, 0)]
    c = kind.attention_counters(srv, snaps, steps)
    decode, prompt = np.arange(30_000, 30_003), np.arange(1024, 2048)
    assert c["attention_rows_live"] == 1027 and c["attention_rows_idle"] == 3 * 524 - 1027
    assert c["traced_step_rows"] == [513, 513, 1]
    assert c["indexed_keys_resident"] == c["index_keys_scored"] == 5 * int(
        (decode + 1).sum() + (prompt + 1).sum())
    assert c["indexed_keys_attended"] == 5 * (3 * 2048 + int((prompt + 1).sum()))
    assert c["index_bytes"] == 5 * (int((decode + 1).sum()) + 1536 + 2048) * 256
    assert c["indexed_attend_bytes"] == 5 * (3 * 2048 + 1536 + 2048) * 1152
    assert c["paged_gqa_bytes"] == c["index_bytes"] + c["indexed_attend_bytes"]
    assert c["paged_gqa_flops"] == c["index_flops"] + c["indexed_attend_flops"]
    assert keye_vl2.keys_read_pct({"counters": c}) == pytest.approx(
        100.0 * c["indexed_keys_attended"] / c["indexed_keys_resident"])


# ---- the cell, its traffic and the metrics it joins ------------------------------- #
def test_the_cell_joins_the_metrics_that_read_its_scopes_and_counters():
    bench = cells.load_benchmark()
    cell = cells.Cell(CELL)
    listed = {m["name"]: m for m in cell.per_layer}
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    assert entry["chips"] == 1 and config["name"] == "deepseek-v3.2-exp"
    assert config["reduced"] == REDUCED
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    assert "1/16" in entry["why"] and "share" in entry["why"]
    assert cell.kind is kind and [m["name"] for m in cell.end_to_end] == [
        "serve_tokens_per_s", "setup_s"]
    # Keye's seven and the head's share, with Keye's cell in each list; and
    # the cell's own
    for name in JOINED + tuple(OWN):
        assert {KEYE, CELL} <= set(listed[name]["workloads"]) or name in OWN
        assert CELL in listed[name]["workloads"]
        assert listed[name]["moves"] == "serve_tokens_per_s"
        fn, args = cell.reader(name)
        assert callable(fn) and isinstance(args, dict)
    # what every resident serve cell reports, as Jamba2's cell does
    both = {m["name"] for m in cells.Cell("jamba2-3b.serve-chat-packed").per_layer
            if CELL in m.get("workloads", [])}
    assert {"step_mfu_pct.gen", "program_ms.gen", "chunk_program_time_pct.gen",
            "device_idle_pct.gen", "host_occupancy_pct.gen"} <= both <= set(listed)
    assert len(bench["per_layer"]) <= 128
    step_work = cell.config["step_work"]
    assert cells.resolve(step_work["weights"]) is arith_ds.deepseek_v32_weights
    assert cells.resolve(step_work["attention"])({"counters": {
        "paged_gqa_flops": 1, "paged_gqa_bytes": 2}}) == (1, 2)


def test_the_scopes_the_metrics_and_the_notes_name_are_the_programs():
    import inspect
    from deepspeed_tpu.models import gpt, hybrid
    from deepspeed_tpu.moe import dropless
    cell = cells.Cell(CELL)
    source = inspect.getsource(gpt) + inspect.getsource(hybrid) + inspect.getsource(dropless)
    for name, scope in (("attn_indexed_share_pct.gen", "attn_indexed"),
                        ("index_score_share_pct.gen", "index_score"),
                        ("index_topk_share_pct.gen", "index_topk"),
                        ("index_attend_share_pct.gen", "index_attend"),
                        ("lm_head_share_pct.gen", "head"), *SCOPES.items()):
        assert cell.reader(name)[1]["scopes"] == [scope]
        assert f'"{scope}"' in source, scope
    assert cell.reader("indexed_attention_roofline")[1] == {
        "scope": "index_attend", "flops": "indexed_attend_flops",
        "nbytes": "indexed_attend_bytes"}
    assert cell.reader("index_score_roofline")[1] == {
        "scope": "index_score", "flops": "index_flops", "nbytes": "index_bytes"}
    # without a trace there is nothing to read and nothing raises
    bare = {"trace": None, "counters": {}, "notes": {}, "cell": cell}
    for name in OWN:
        fn, args = cell.reader(name)
        assert fn(bare, **args) is None, name


def test_the_traffic_is_long_indexeds_lengths_under_twelve_slots():
    mix = cells.Cell(CELL).traffic
    assert mix["kind"] == "serve-backlog-resident-latent-indexed"
    assert kind.END_TO_END == resident.END_TO_END
    keye = cells.Cell(KEYE).traffic
    assert mix["prompt_tokens"] == keye["prompt_tokens"] == {
        "dist": "uniform", "min": 24576, "max": 40960}
    assert mix["output_tokens"] == keye["output_tokens"] == {
        "dist": "uniform", "min": 1024, "max": 5120}
    assert (mix["backlog_requests"], mix["check_requests"]) == (32, 2)
    assert {k for k in mix if not k.startswith("_")} == {
        "kind", "prompt_tokens", "output_tokens", "backlog_requests", "check_requests",
        "rehearse"}
    cohort, backlog, planned = resident.plan(mix, 12, 512, 163_840, 16_160, 5)
    # about 34,700 keys a slot when the window opens (prompt + age), 416,000 in
    # all: 77% of the 540,672 the arena holds
    at_its_age = [p + a for p, a, _ in planned]
    assert 33_000 < np.mean(at_its_age) < 36_500
    assert 400_000 < sum(at_its_age) < 432_000 and 0.74 < sum(at_its_age) / 540_672 < 0.80
    assert min(len(p) for p, _ in cohort) > 12 * 2048         # every context selects
    assert max(len(p) + n for p, n in cohort) <= 40_960 + 5_120 + 4
    assert len(cohort) == 12 and len(backlog) == 32
    assert all(24_576 <= len(p) <= 40_960 for p, _ in backlog)
    # a member finishes every 3,072 / 12 = 256 steps and brings 64 chunk steps
    assert np.mean([n for _, n in backlog]) / 12 == pytest.approx(256, rel=0.05)
    assert np.mean([-(-len(p) // 512) for p, _ in backlog]) == pytest.approx(64, rel=0.05)
    # one plan whatever the seed; the ids are the seed's
    again = resident.plan(mix, 12, 512, 163_840, 16_160, 6)
    assert [(len(p), n) for p, n in again[1]] == [(len(p), n) for p, n in backlog]
    assert list(again[1][0][0][:8]) != list(backlog[0][0][:8])
    assert max(max(p) for p, _ in backlog) < 16_160


# ---- the limits of the comparison that decides ``correct`` ------------------------ #
def _judged(monkeypatch, largest, scales, failed_besides=0, **notes):
    """``kind.run`` over a result the resident kind would have returned."""
    import statistics
    median = statistics.median(scales)
    theirs = sum(w > resident.LOGIT_MARGIN or (median > resident.NOISE_LIMIT
                                               and s > resident.NOISE_LIMIT)
                 for w, s in zip(largest, scales))
    out = {"correct": False, "attempted": 9, "failed": theirs + failed_besides,
           "notes": dict({"checked": len(largest), "wrong": theirs, "logit_gaps": largest,
                          "noise_scales": scales, "noise_scale_median": median,
                          "backlog_ran_dry": False, "cohort_filled": True}, **notes)}
    monkeypatch.setattr(resident, "run", lambda cell, args, ctx: out)
    cell = types.SimpleNamespace(traffic={})
    return kind.run(cell, None, None)


def test_the_kinds_count_takes_the_resident_kinds_place_for_the_run_alone(monkeypatch):
    seen = []
    monkeypatch.setattr(resident, "run", lambda *a: seen.append(
        resident.attention_counters) or {"notes": {"checked": 0}})
    theirs = resident.attention_counters
    kind.run(types.SimpleNamespace(traffic={}), None, None)
    assert seen == [kind.attention_counters] and resident.attention_counters is theirs


# the readings of PERF.md section 6 (my chip runs, PR 61): the two checked
# requests of each of five bf16 runs of the cell (seeds 3000006111-115)
BF16 = ([[1.057, 1.501], [1.751, 0.924], [1.062, 1.001], [1.165, 2.678], [1.213, 0.957]],
        [[0.5548, 0.5691], [0.5469, 0.5235], [0.4597, 0.5306], [0.5536, 0.4637], [0.5487, 0.4946]])


def test_a_sound_bf16_run_is_correct_by_these_limits(monkeypatch):
    for largest, scales in zip(*BF16):
        out = _judged(monkeypatch, largest, scales)
        assert out["notes"]["wrong"] == 0 and out["failed"] == 0 and out["correct"] is True
    assert out["notes"]["tie_tolerance"] == kind.LOGIT_MARGIN == 5.0
    assert out["notes"]["noise_limit"] == kind.NOISE_LIMIT == 0.8
    # room above the largest bf16 reading of each, and under what a token
    # unrelated to the reference loses by (6.7 in the mean)
    assert 1.8 * max(max(g) for g in BF16[0]) < kind.LOGIT_MARGIN <= 0.75 * 6.7
    assert 1.4 * max(max(s) for s in BF16[1]) < kind.NOISE_LIMIT

# the same cell with every matrix through float8_e4m3fn (seed 3000006121) and
# with the indexer's rope in the attention's pairing (seed 3000006122)
FLOAT8 = ([3.328, 4.453], [999.99, 999.99])
WRONG_PAIRING = ([9.627, 9.625], [999.99, 999.99])


def test_every_matrix_through_float8_is_refused_by_the_noise_limit_alone(monkeypatch):
    out = _judged(monkeypatch, *FLOAT8)
    assert max(FLOAT8[0]) < kind.LOGIT_MARGIN
    assert out["notes"]["wrong"] == 2 and out["correct"] is False
    assert out["compared"]["noise_scale_median"][0] > 100 * kind.NOISE_LIMIT


def test_a_wrong_selection_is_refused_by_both_limits(monkeypatch):
    out = _judged(monkeypatch, *WRONG_PAIRING)
    assert min(WRONG_PAIRING[0]) > 1.9 * kind.LOGIT_MARGIN
    assert out["notes"]["wrong"] == 2 and out["correct"] is False
    assert kind.judge(WRONG_PAIRING[0], [0.1, 0.1], 0.1) == 2      # by the gross limit alone


def test_a_run_that_ran_dry_or_served_short_is_not_correct(monkeypatch):
    ok = ([kind.LOGIT_MARGIN / 4] * 2, [kind.NOISE_LIMIT / 4] * 2)
    assert _judged(monkeypatch, *ok)["correct"] is True
    assert _judged(monkeypatch, *ok, backlog_ran_dry=True)["correct"] is False
    assert _judged(monkeypatch, *ok, cohort_filled=False)["correct"] is False
    short = _judged(monkeypatch, *ok, failed_besides=1)
    assert short["correct"] is False and short["failed"] == 1
    gross = _judged(monkeypatch, [kind.LOGIT_MARGIN * 1.1] + ok[0][1:], ok[1])
    assert gross["correct"] is False and gross["notes"]["wrong"] == 1
    assert gross["compared"]["largest_logit_gap"] == [kind.LOGIT_MARGIN * 1.1, kind.LOGIT_MARGIN]


def test_the_controls_are_named_and_an_unknown_one_is_refused(monkeypatch):
    assert set(kind.PLANTED) == {None, "weights-float8", "index-rope-interleaved"}
    monkeypatch.setattr(resident, "run", lambda *a: {"notes": {"checked": 0}})
    with pytest.raises(KeyError):
        kind.run(types.SimpleNamespace(traffic={"planted": "no-such-fault"}), None, None)


def test_the_wrong_pairing_is_planted_on_the_indexers_rope_alone():
    import jax.numpy as jnp
    from deepspeed_tpu.models import gpt
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (1, 3, 2, 16)), jnp.float32)
    pos = jnp.arange(3) + 5
    right = gpt.apply_rope(x, pos, rope_dim=8)
    whole = gpt.apply_rope(x, pos, interleaved=True)
    with kind._index_rope_interleaved():
        wrong = gpt.apply_rope(x, pos, rope_dim=8)
        assert np.array_equal(gpt.apply_rope(x, pos, interleaved=True), whole)
    assert np.abs(np.asarray(wrong - right)).max() > 0.1
    assert np.array_equal(gpt.apply_rope(x, pos, rope_dim=8), right)
    assert np.array_equal(wrong, gpt.apply_rope(x, pos, rope_dim=8, interleaved=True))


# ---- the rehearsal ------------------------------------------------------------------- #
@pytest.mark.parametrize("planted", [None, "index-rope-interleaved"])
def test_the_rehearsal_runs_the_cell_on_the_cpu(planted):
    more = ["--set", f'planted="{planted}"'] if planted else []
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed", "3000000611",
         "--seconds", "3", "--rehearse", *more],
        cwd=cells.ROOT, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["metrics"] == {} and line["attempted"] > 0
    assert set(JOINED) | {"serve_tokens_per_s", "setup_s", "step_mfu_pct.gen"} <= set(
        line["would_report"])
    gap = line["compared"]["largest_logit_gap"][0]
    if planted is None:
        # float32 on both sides: the served tokens are the reference's
        assert line["correct"] is True and gap < 1e-3
    else:
        assert gap > 1e-3           # a wrong selection shows in the logits
