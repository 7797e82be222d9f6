"""Tests of what the benchmark adds for Keye-VL-2.0-30B-A3B's long-indexed
cell: the configuration against the catalog's row, its arithmetic held to the
arrays the engine builds, the count of the caches' work by hand, the readers on
runs with nothing to read, the cell, its traffic's plan and its metrics, and
the kind's two limits; CPU only."""

import json
import types

import numpy as np
import pytest

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.kinds import serve_backlog_resident_indexed as kind
from benchmarks.lib import arith_keye_vl2 as arith_keye
from benchmarks.lib import arith_step, cells
from benchmarks.readers import keye_vl2

CELL = "keye-vl-2.0-30b-a3b.serve-long-indexed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SCOPES = {"attn_indexed_share_pct.gen": "attn_indexed", "index_score_share_pct.gen": "index_score",
          "index_topk_share_pct.gen": "index_topk", "index_attend_share_pct.gen": "index_attend",
          "moe_share_pct.gen": "moe", "moe_router_share_pct.gen": "moe_router",
          "moe_experts_share_pct.gen": "moe_experts", "lm_head_share_pct.gen": "head"}
ROOFLINES = {"index_score_roofline": ("index_score", "index_flops", "index_bytes"),
             "indexed_attention_roofline": ("index_attend", "indexed_attend_flops",
                                            "indexed_attend_bytes")}
NEW = tuple(SCOPES) + tuple(ROOFLINES) + ("indexed_keys_read_pct.gen",
                                          "experts_reached_pct.gen")


def test_the_configuration_is_the_catalogs_but_for_its_depth():
    cfg = cells.Cell(CELL).config
    try:        # the catalog beside the guide, where it is installed
        rows = [json.loads(l) for l in open(CATALOG)]
        source = next(r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert cfg["source"] == source["source_url"]
        assert sorted(k for k, v in source["config"].items()
                      if cfg.get(k, "missing") != v) == ["num_hidden_layers"]
        assert source["config"]["num_hidden_layers"] == 48
    except FileNotFoundError:
        pass
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["num_hidden_layers"] == 6
    kw, ref, sa = cfg["model"]["kwargs"], cfg["reference"]["kwargs"], cfg["sa_config"]
    assert (kw["n_embd"], kw["n_head"], kw["n_kv_head"], kw["head_dim"],
            kw["intermediate_size"], kw["num_experts"], kw["top_k"], kw["vocab_size"],
            kw["n_positions"]) == (
                cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], cfg["moe_intermediate_size"], cfg["num_experts"],
                cfg["num_experts_per_tok"], cfg["vocab_size"],
                cfg["max_position_embeddings"]) == (
                    2048, 32, 4, 128, 768, 128, 8, 151936, 262144)
    assert kw["indexer"] == [sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]] == [
        16, 64, 2048] and sa["indexer_num_kv_heads"] == 1
    assert (ref["indexer_heads"], ref["indexer_head_dim"], ref["topk"]) == (16, 64, 2048)
    assert kw["n_layer"] == cfg["num_hidden_layers"]
    assert (ref["n_head"], ref["n_kv_head"], ref["head_dim"], ref["top_k"],
            ref["vocab_size"]) == (32, 4, 128, 8, 151936)
    assert ref["eps"] == cfg["rms_norm_eps"] == 1e-6 and cfg["tie_word_embeddings"] is False
    assert ref["rope_theta"] == cfg["rope_theta"] == 10_000_000 and cfg["norm_topk_prob"] is True
    assert sum(cfg["rope_scaling"]["mrope_section"]) == cfg["head_dim"] // 2
    # what neither the config nor described_as fixes
    assert {"text_positions", "qk_norm", "indexer", "indexer_input", "indexer_rope",
            "indexer_precision", "chunk_sizes", "selection_ties", "feed_forward", "dtype",
            "weights", "deployment"} <= set(cfg["assumed"])
    assert "device_idle_pct.gen" in cfg["assumed"]["deployment"]


def test_the_program_builds_the_held_layers_from_the_file():
    import jax
    from benchmarks.lib.build import model_from
    cfg = cells.Cell(CELL).config
    model = model_from(cfg)
    mcfg = model.cfg
    assert mcfg.mixers == ("indexed",) * 6 and mcfg.ffns == ("moe_softmax",) * 6
    assert tuple(mcfg.indexer) == (16, 64, 2048) and mcfg.moe_norm_topk
    # what the harness and the resident kind read of a model's configuration
    assert (mcfg.n_layer, mcfg.kv_heads, mcfg.head_dim, mcfg.n_head) == (6, 4, 128, 32)
    assert all(k.window is None for k in mcfg.pattern) and mcfg.untied_head
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) - 2048   # lnf_b
    assert held == model.num_params() == 4_374_622_464
    assert "4,374,622,464 parameters = 8.75 GB" in cfg["reduced_why"]
    w = arith_keye.keye_vl2_weights(cfg["model"]["kwargs"])
    assert w["dense"] + w["gathered"] + arith_step.bank_params(w["bank"]) == held
    assert arith_step.bank_params(w["bank"]) == 6 * 603_979_776
    assert w["gathered"] == 151_936 * 2048
    assert w["dense"] == 6 * (625_381_760 - 603_979_776) + 2048 + 151_936 * 2048


def test_the_arena_and_the_index_pages_are_the_engines():
    """``serve.arena_bytes`` is what ``lib/serving.py``'s divisor (K and V of
    all six layers) turns into 5,440 blocks, and IS what is held; the index
    keys beside it are the program's own count; both are held to the engine's
    arrays at the rehearse size."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from benchmarks.lib.build import model_from
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.serving.kv_cache import arena_bytes
    cfg = cells.Cell(CELL).config
    serve, mcfg = cfg["serve"], model_from(cfg).cfg
    per_block = 2 * mcfg.n_layer * 64 * mcfg.kv_heads * mcfg.head_dim * 2
    assert per_block == 786_432 and serve["arena_bytes"] == 5_440 * per_block == arena_bytes(
        mcfg, 5_440, 64) == 4_278_190_080
    aux = jax.eval_shape(lambda: hybrid.init_aux(mcfg, 5_440, 64, 8, jnp.bfloat16))
    assert set(aux) == {"ki"}
    assert serve["index_key_bytes"] == aux["ki"].size * 2 == 6 * 5_440 * 64 * 64 * 2 == 267_386_880
    assert serve["serving"] == {"max_batch_size": 8, "prefill_chunk": 512, "block_size": 64,
                                "max_blocks_per_seq": 720, "dtype": "bfloat16"}
    assert 720 * 64 == 40_960 + 5_120
    # the rehearse size, through the harness's own arithmetic to an engine
    cells.merge(cfg, cfg["rehearse"])
    model = model_from(cfg)
    lanes = model.cfg.kv_heads * model.cfg.head_dim
    blocks = cfg["serve"]["arena_bytes"] // (2 * model.cfg.n_layer * 16 * lanes * 4)
    assert blocks == 200
    eng = deepspeed_tpu.init_serving(
        model=model, params=model.init_params(jax.random.PRNGKey(0)),
        config={"serving": dict(cfg["serve"]["serving"], num_blocks=blocks, dtype="bfloat16")})
    try:
        assert eng._k_pages.shape == eng._v_pages.shape == (3, 200, 16, 32)
        assert eng._k_pages.nbytes + eng._v_pages.nbytes == arena_bytes(model.cfg, 200, 16)
        assert eng._aux["ki"].shape == (3, 200, 16, 8) and eng._aux["ki"].dtype == jnp.bfloat16
        assert eng.alloc.num_blocks == 200 and eng.alloc.widths == (16,)
        assert eng._moe_count_rows == 3
    finally:
        eng.close()


# ---- the caches' work, by hand ------------------------------------------------------ #
def test_a_rows_work_by_hand():
    """One decode row at 34,700 keys, six layers: 34,701 index keys of 64
    lanes read and 2 x 16 x 64 operations each; 2,048 chosen tokens, each a K
    row and a V row of 512 lanes, and 4 x 32 x 128 operations each.  A chunk
    of 512 from position 1,024: its sequence's 1,536 index keys read once for
    all its rows; every row attends all its keys (under topk), the bytes its
    sequence's K and V once."""
    assert arith_keye.keys_attended([0, 2047, 2048, 34_700]).tolist() == [1, 2048, 2048, 2048]
    s_flops, s_bytes = arith_keye.score_rows([34_700], [], 6)
    assert s_bytes == 6 * 34_701 * 64 * 2 and s_flops == 6 * 2 * 16 * 64 * 34_701
    a_flops, a_bytes = arith_keye.attend_rows([34_700], [], 6, 32, 4, 128)
    assert a_bytes == 6 * 2048 * 2 * 512 * 2 and a_flops == 6 * 4 * 32 * 128 * 2048
    assert a_bytes / 6 == pytest.approx(4.2e6, rel=0.01)        # 4 MB a row a layer
    rows = 1024 + np.arange(512)
    s_flops, s_bytes = arith_keye.score_rows([], [(1024, 512)], 1)
    assert s_bytes == 1536 * 64 * 2 and s_flops == 2 * 16 * 64 * int((rows + 1).sum())
    a_flops, a_bytes = arith_keye.attend_rows([], [(1024, 512)], 1, 32, 4, 128)
    assert a_bytes == 1536 * 2 * 512 * 2 and a_flops == 4 * 32 * 128 * int((rows + 1).sum())
    # past topk a chunk's rows choose 2,048 each: still its sequence once
    _, a_bytes = arith_keye.attend_rows([], [(30_000, 512)], 1, 32, 4, 128)
    assert a_bytes == 30_512 * 2 * 512 * 2 < 512 * 2048 * 2 * 512 * 2
    assert arith_keye.indexer_of({"indexer": [4, 8, 48]}) == {"heads": 4, "head_dim": 8, "topk": 48}
    assert arith_keye.indexer_of({}) == arith_keye.INDEXER


def test_attention_counters_take_a_decode_row_at_its_position_and_a_chunk_once():
    """Over a stretch of 3 steps: one request decodes 3 tokens from 30,000
    keys, another runs two chunks of its prompt from 1,024; 8 + 512 rows a
    program."""
    cfg = cells.Cell(CELL).config
    srv = types.SimpleNamespace(
        model=types.SimpleNamespace(cfg=types.SimpleNamespace(
            n_head=32, kv_heads=4, head_dim=128, n_layer=6)),
        cell=types.SimpleNamespace(config=cfg), slots=8, chunk=512,
        params={"wte": np.zeros(1, np.float16)})
    snaps = {"before": {1: (20_000, 30_000, 10_000), 2: (30_000, 1024, 0)},
             "after": {1: (20_000, 30_003, 10_003), 2: (30_000, 2048, 0)}}
    steps = [(0, 0, 1, 512, 0, 0, 0), (0, 0, 1, 512, 0, 0, 0), (0, 0, 1, 0, 0, 0, 0)]
    c = kind.attention_counters(srv, snaps, steps)
    decode, prompt = np.arange(30_000, 30_003), np.arange(1024, 2048)
    assert c["attention_rows_live"] == 1027 and c["attention_rows_idle"] == 3 * 520 - 1027
    assert c["traced_step_rows"] == [513, 513, 1]
    assert c["indexed_keys_resident"] == c["index_keys_scored"] == 6 * int(
        (decode + 1).sum() + (prompt + 1).sum())
    assert c["indexed_keys_attended"] == 6 * (3 * 2048 + int((prompt + 1).sum()))
    assert c["index_bytes"] == 6 * (int((decode + 1).sum()) + 1536 + 2048) * 128
    assert c["indexed_attend_bytes"] == 6 * (3 * 2048 + 1536 + 2048) * 2048
    assert c["paged_gqa_bytes"] == c["index_bytes"] + c["indexed_attend_bytes"]
    assert c["paged_gqa_flops"] == c["index_flops"] + c["indexed_attend_flops"]
    assert keye_vl2.keys_read_pct({"counters": c}) == pytest.approx(
        100.0 * c["indexed_keys_attended"] / c["indexed_keys_resident"])


def _run_with_scope_times(times, counters):
    """A traced run whose one chip spent ``times[scope]`` seconds under each
    scope (what ``program_spans.read_stats`` gives)."""
    return {"trace": object(), "notes": {}, "counters": counters,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "_program_stats": {"first_tokens": [], "chips": [
                (1.0, [(frozenset({"attn", "attn_indexed", s}), t) for s, t in times.items()])]}}


@pytest.mark.parametrize("name", sorted(ROOFLINES))
def test_a_roofline_is_the_least_time_over_its_scopes_time(name):
    scope, flops, nbytes = ROOFLINES[name]
    fn, args = cells.Cell(CELL).reader(name)
    assert fn is keye_vl2.scope_roofline and args == {"scope": scope, "flops": flops,
                                                      "nbytes": nbytes}
    run = _run_with_scope_times({scope: 0.010, "other": 0.5}, {flops: 1e9, nbytes: 819e6})
    assert fn(run, **args) == pytest.approx(10.0)
    assert run["notes"]["roofline_bound"][scope] == "memory"
    # nothing to read: no trace, no counts (a run that was not traced), no op
    # under the scope (a parent commit)
    assert fn(dict(run, trace=None), **args) is None
    assert fn(_run_with_scope_times({scope: 0.01}, {}), **args) is None
    assert fn(_run_with_scope_times({"other": 0.01}, {flops: 1.0, nbytes: 1.0}), **args) is None
    assert keye_vl2.keys_read_pct({"counters": {}}) is None


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
    # the twelve every backlog serve cell reported when this one came (the
    # overlay's three went with PR 68)
    assert {"compiles_in_window.gen", "serve_step_ms.gen", "decode_batch_mean.gen",
            "kv_blocks_peak_pct.gen", "preemptions.gen", "device_idle_pct.gen",
            "sched_host_ms.gen", "table_build_ms.gen", "host_turnaround_ms.gen",
            "step_outside_ms.gen", "idle_wire_ms.gen", "step_mfu_pct.gen"} <= set(listed)
    # the other families' kernels are no part of this cell
    assert not {"paged_gqa_attention_roofline", "paged_attention_roofline",
                "paged_sparse_attention_roofline", "moe_experts_roofline"} & set(listed)
    step_work = cell.config["step_work"]
    assert step_work["weights"] == "benchmarks.lib.arith_keye_vl2:keye_vl2_weights"
    assert cells.resolve(step_work["attention"])({"counters": {
        "paged_gqa_flops": 1, "paged_gqa_bytes": 2}}) == (1, 2)


def test_the_scopes_the_metrics_name_are_the_programs():
    import inspect
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.moe import dropless
    cell = cells.Cell(CELL)
    source = inspect.getsource(hybrid) + inspect.getsource(dropless)
    for name, scope in SCOPES.items():
        assert cell.reader(name)[1]["scopes"] == [scope]
        assert f'jax.named_scope("{scope}")' in source
    for scope, _, _ in ROOFLINES.values():
        assert f'jax.named_scope("{scope}")' in source


def test_the_traffic_is_a_file_of_the_resident_kind_under_its_own_limits():
    mix = cells.Cell(CELL).traffic
    assert mix["kind"] == "serve-backlog-resident-indexed"
    assert kind.END_TO_END == resident.END_TO_END
    sala = cells.Cell("minicpm-sala-9b.serve-long-mixed").traffic
    assert mix["prompt_tokens"] == sala["prompt_tokens"] == {
        "dist": "uniform", "min": 24576, "max": 40960}
    assert mix["output_tokens"] == sala["output_tokens"] == {
        "dist": "uniform", "min": 1024, "max": 5120}
    assert (mix["backlog_requests"], mix["check_requests"]) == (32, 4)
    cohort, backlog, planned = resident.plan(mix, 8, 512, 262_144, 151_936, 5)
    # about 34,700 keys a slot when the window opens (prompt + age), 278,000 in
    # all: 80% of the 348,160 the arena holds
    at_its_age = [p + a for p, a, _ in planned]
    assert 33_000 < np.mean(at_its_age) < 36_500 and 0.74 < sum(at_its_age) / 348_160 < 0.85
    assert min(len(p) for p, _ in cohort) > 12 * 2048         # every context selects
    assert max(len(p) + n for p, n in cohort) <= 40_960 + 5_120 + 4
    assert len(backlog) == 32 and all(24_576 <= len(p) <= 40_960 for p, _ in backlog)


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
    return kind.run(None, None, None)


def test_the_kinds_count_takes_the_resident_kinds_place_for_the_run_alone(monkeypatch):
    seen = []
    monkeypatch.setattr(resident, "run", lambda *a: seen.append(
        resident.attention_counters) or {"notes": {"checked": 0}})
    theirs = resident.attention_counters
    kind.run(None, None, None)
    assert seen == [kind.attention_counters] and resident.attention_counters is theirs


# the readings of PERF.md section 6 (my chip runs, PR 51): the requests of two
# of the cell's bf16 runs (the largest gap of 36 requests and the largest
# median of nine runs among them), the same cell with every matrix through
# float8_e4m3fn, and with its index keys alone cached in that type
BF16 = ([0.501, 0.242, 0.386, 0.882, 0.380, 0.632, 0.296, 0.233],
        [0.1038, 0.0554, 0.0871, 0.2183, 0.1853, 0.1289, 0.1405, 0.0606])
FLOAT8 = ([0.904, 1.320, 0.485, 0.801], [0.2936, 999.99, 999.99, 0.2540])
FLOAT8_INDEX_KEYS = ([0.499, 0.329, 0.618, 0.301], [0.2046, 0.0949, 0.3941, 0.0994])


def test_a_sound_bf16_run_is_correct_by_these_limits(monkeypatch):
    for half in (slice(0, 4), slice(4, 8)):
        out = _judged(monkeypatch, BF16[0][half], BF16[1][half])
        assert out["notes"]["wrong"] == 0 and out["failed"] == 0 and out["correct"] is True
    assert out["notes"]["tie_tolerance"] == kind.LOGIT_MARGIN == 2.0
    assert out["notes"]["noise_limit"] == kind.NOISE_LIMIT == 0.2
    # room on both sides of each limit
    assert 2 * max(BF16[0]) < kind.LOGIT_MARGIN <= 4.1 / 2
    assert 1.45 * 0.1347 < kind.NOISE_LIMIT < 0.8 * min(FLOAT8[1])


def test_every_matrix_through_float8_is_refused_by_the_noise_limit_alone(monkeypatch):
    out = _judged(monkeypatch, *FLOAT8)
    assert max(FLOAT8[0]) < kind.LOGIT_MARGIN
    assert out["notes"]["wrong"] == 4 and out["correct"] is False
    assert out["compared"]["noise_scale_median"][0] > 100 * kind.NOISE_LIMIT


def test_float8_index_keys_alone_read_what_bf16_reads():
    """The finding, held so that a later limit does not pretend otherwise:
    the cell with its index keys cached in float8 reads a median inside
    bf16's own range of requests, and no limit between the two exists."""
    import statistics
    median = statistics.median(FLOAT8_INDEX_KEYS[1])
    assert 0.1347 < median < max(BF16[1]) and max(FLOAT8_INDEX_KEYS[0]) < max(BF16[0])
    assert kind.judge(*FLOAT8_INDEX_KEYS, median) == 0


def test_a_run_that_ran_dry_or_served_short_is_not_correct(monkeypatch):
    ok = ([kind.LOGIT_MARGIN / 4] * 4, [kind.NOISE_LIMIT / 4] * 4)
    assert _judged(monkeypatch, *ok)["correct"] is True
    assert _judged(monkeypatch, *ok, backlog_ran_dry=True)["correct"] is False
    assert _judged(monkeypatch, *ok, cohort_filled=False)["correct"] is False
    short = _judged(monkeypatch, *ok, failed_besides=1)
    assert short["correct"] is False and short["failed"] == 1
    gross = _judged(monkeypatch, [kind.LOGIT_MARGIN * 1.1] + ok[0][1:], ok[1])
    assert gross["correct"] is False and gross["notes"]["wrong"] == 1
    assert gross["compared"]["largest_logit_gap"] == [kind.LOGIT_MARGIN * 1.1, kind.LOGIT_MARGIN]
