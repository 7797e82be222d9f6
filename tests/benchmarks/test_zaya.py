"""Tests of what the benchmark adds for ZAYA1-8B's reasoning-resident cell:
the configuration against the catalog's row, its arithmetic held to the
arrays the engine builds, the cell, its traffic and its metrics, the new
scopes on a synthetic trace, the reader of the experts a step reaches, and the
cell's control flow at the rehearse size; CPU only."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.lib import arith_moe, arith_step, arith_zaya, cells
from benchmarks.readers import moe, zaya

CELL = "zaya1-8b.serve-reasoning-resident"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SCOPES = {"attn_cca_share_pct.gen": "attn_cca", "cca_mix_share_pct.gen": "cca_mix",
          "cca_attend_share_pct.gen": "cca_attend", "moe_router_share_pct.gen": "moe_router",
          "moe_experts_share_pct.gen": "moe_experts"}
NEW = tuple(SCOPES) + ("experts_reached_pct.gen",)


def test_the_configuration_is_the_catalogs_but_for_its_depth():
    cfg = cells.Cell(CELL).config
    try:        # the catalog beside the guide, where it is installed
        rows = [json.loads(l) for l in open(CATALOG)]
        source = next(r for r in rows if r["name"] == "ZAYA1-8B")
        assert cfg["source"] == source["source_url"]
        assert sorted(k for k, v in source["config"].items()
                      if cfg.get(k, "missing") != v) == ["num_hidden_layers"]
        assert source["config"]["num_hidden_layers"] == 40
    except FileNotFoundError:
        pass
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["num_hidden_layers"] == 20
    assert len(cfg["layer_types"]) == 40 and set(cfg["layer_types"]) == {"hybrid"}
    kw, ref = cfg["model"]["kwargs"], cfg["reference"]["kwargs"]
    assert (kw["n_embd"], kw["n_head"], kw["n_kv_head"], kw["head_dim"],
            kw["intermediate_size"], kw["num_experts"], kw["top_k"], kw["router_hidden"],
            kw["vocab_size"], kw["n_positions"], kw["cca_time0"], kw["cca_time1"],
            kw["partial_rotary_factor"]) == (
                cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], cfg["moe_intermediate_size"], cfg["num_experts"],
                cfg["num_experts_per_tok"], cfg["router_hidden_size"], cfg["vocab_size"],
                cfg["max_position_embeddings"], cfg["cca_time0"], cfg["cca_time1"],
                cfg["partial_rotary_factor"]) == (
                    2048, 8, 2, 128, 2048, 16, 1, 256, 262272, 131072, 2, 2, 0.5)
    assert kw["n_layer"] == cfg["num_hidden_layers"]
    assert (ref["n_head"], ref["n_kv_head"], ref["head_dim"], ref["vocab_size"]) == (8, 2, 128, 262272)
    assert ref["eps"] == cfg["rms_norm_eps"] == 1e-5 and cfg["tie_word_embeddings"] is True
    assert ref["rope_theta"] == cfg["rope_parameters"]["hybrid"]["rope_theta"] == 5_000_000
    # what neither the config nor described_as fixes, and what is left out
    assert {"sublayers", "padding", "convolutions", "qk_mean", "qk_norm", "values", "rope",
            "router_stream", "router_mlp", "router_choice", "dtype", "weights",
            "deployment"} <= set(cfg["assumed"])
    assert set(cfg["departures"]) == {"residual_scaling", "mod", "router_stream_at_the_cut"}
    assert "device_idle_pct.gen" in cfg["assumed"]["deployment"]


def test_the_program_builds_the_held_layers_from_the_file():
    import jax
    from benchmarks.lib.build import model_from
    cfg = cells.Cell(CELL).config
    model = model_from(cfg)
    mcfg = model.cfg
    assert mcfg.mixers == ("cca",) * 20 and mcfg.ffns == ("moe",) * 20
    # what the harness and the resident kind read of a model's configuration
    assert (mcfg.n_layer, mcfg.kv_heads, mcfg.head_dim, mcfg.n_head) == (20, 2, 128, 8)
    assert all(k.window is None for k in mcfg.pattern) and not mcfg.untied_head
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) - 2048   # lnf_b
    assert held == model.num_params() == 4_688_457_084
    assert "4,688,457,084 parameters = 9.38 GB" in cfg["reduced_why"]
    w = arith_zaya.zaya_weights(cfg["model"]["kwargs"])
    assert w["dense"] + arith_step.bank_params(w["bank"]) == held + 2048 and w["gathered"] == 0
    assert arith_step.bank_params(w["bank"]) == 20 * 201_326_592
    assert w["dense"] == 20 * (5_579_778 + 659_729) + 537_133_056 + 2 * 2048


def test_a_steps_least_work_is_the_issues_arithmetic():
    """48 live rows: the banks 20 x 15.3 experts x 25.2 MB = 7.7 GB, the head
    1.07 GB, attention's and the routers' weights 0.25 GB; with the cache of
    231,000 keys at 20,480 B, 13.7 GB and 16.8 ms at 819 GB/s."""
    w = arith_zaya.zaya_weights(cells.Cell(CELL).config["model"]["kwargs"])
    flops, nbytes = arith_step.step_work(w, 48)
    assert arith_moe.experts_reached(48, 16, 1) == pytest.approx(15.28, abs=0.01)
    assert arith_moe.experts_reached(32, 16, 1) == pytest.approx(13.97, abs=0.01)
    bank = 20 * 15.28 * 3 * 2048 * 2048 * 2
    assert nbytes == pytest.approx(bank + 2 * w["dense"], rel=1e-3)
    assert bank == pytest.approx(7.69e9, rel=1e-3)
    assert 2 * (w["dense"] - 537_133_056) == pytest.approx(0.25e9, rel=0.01)
    assert (nbytes + 231_000 * 20_480) / 819e9 == pytest.approx(16.8e-3, rel=0.01)


def test_the_arena_and_the_state_are_the_engines():
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from benchmarks.lib.build import model_from
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.serving.kv_cache import arena_bytes
    cfg = cells.Cell(CELL).config
    serve, mcfg = cfg["serve"], model_from(cfg).cfg
    # lib/serving.py's divisor is this model's true bytes a block
    per_block = 2 * mcfg.n_layer * 64 * mcfg.kv_heads * mcfg.head_dim * 2
    assert per_block == 64 * 20_480 and serve["arena_bytes"] == 4_000 * per_block
    assert serve["arena_bytes"] == arena_bytes(mcfg, 4_000, 64) == 256_000 * 20_480
    aux = jax.eval_shape(lambda: hybrid.init_aux(mcfg, 4_000, 64, 48, jnp.bfloat16))
    assert aux["cca_state"].shape == (20, 48, 2688)
    assert serve["state_bytes"] == aux["cca_state"].size * 2 == 5_160_960
    assert serve["serving"] == {"max_batch_size": 48, "prefill_chunk": 208, "block_size": 64,
                                "max_blocks_per_seq": 256, "dtype": "bfloat16"}
    assert (48 + 208) % 128 == 0
    # the rehearse size, through the harness's own arithmetic to an engine
    cells.merge(cfg, cfg["rehearse"])
    model = model_from(cfg)
    lanes = model.cfg.kv_heads * model.cfg.head_dim
    blocks = cfg["serve"]["arena_bytes"] // (2 * model.cfg.n_layer * 16 * lanes * 4)
    assert blocks == 200
    eng = deepspeed_tpu.init_serving(
        model=model, params=model.init_params(jax.random.PRNGKey(0)),
        config={"serving": dict(cfg["serve"]["serving"], num_blocks=blocks)})
    try:
        assert eng._k_pages.shape == eng._v_pages.shape == (3, 200, 16, 32)
        assert eng._k_pages.nbytes + eng._v_pages.nbytes == arena_bytes(model.cfg, 200, 16, 4)
        assert eng._aux["cca_state"].shape == (3, 4, 2 * 96 + 16)
        assert eng.cache_bytes_per_token == 2 * 32 * 4
        assert eng.alloc.num_blocks == 200
    finally:
        eng.close()


# ---- the files ------------------------------------------------------------------ #
def test_the_cell_its_traffic_and_its_metrics_resolve():
    cell = cells.Cell(CELL)
    listed = {m["name"]: m for m in cell.per_layer}
    for name in NEW:
        fn, args = cell.reader(name)
        assert callable(fn) and isinstance(args, dict)
        m = listed[name]
        assert CELL in m["workloads"] and m["unit"] == "%"
        assert m["moves"] == "serve_tokens_per_s"
    assert cell.chips == 1 and [m["name"] for m in cell.end_to_end] == [
        "serve_tokens_per_s", "setup_s"]
    # the twelve every backlog serve cell reported when this one came (the
    # overlay's three went with PR 68)
    assert {"compiles_in_window.gen", "serve_step_ms.gen", "decode_batch_mean.gen",
            "kv_blocks_peak_pct.gen", "preemptions.gen", "device_idle_pct.gen",
            "sched_host_ms.gen", "table_build_ms.gen", "host_turnaround_ms.gen",
            "step_outside_ms.gen", "idle_wire_ms.gen", "step_mfu_pct.gen"} <= set(listed)
    # the two kernels its program runs have ONE roofline each, which lists it
    # (PR 68); what reads another bank's scopes or stats is no part of it
    assert {"grouped_matmul_roofline", "paged_gqa_attention_roofline"} <= set(listed)
    assert not {"moe_experts_roofline", "moe_load_max_over_mean.gen",
                "moe_dispatch_share_pct.gen"} & set(listed)
    assert cell.config["step_work"] == {
        "_about": cell.config["step_work"]["_about"],
        "weights": "benchmarks.lib.arith_zaya:zaya_weights",
        "attention": "benchmarks.readers.paged_gqa:work"}
    bench = cells.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "zaya1-8b")
    assert entry["reduced"] == cell.config["reduced"] and entry["source"] == cell.config["source"]
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert workload["config"] == entry["name"] == "zaya1-8b"
    assert all(len(e["why"]) <= 200 for e in (entry, workload))


def test_the_traffic_is_reasoning_batchs_lengths_at_48_slots():
    cell = cells.Cell(CELL)
    mix = cell.traffic
    assert cell.kind.END_TO_END == resident.END_TO_END
    batch = cells.load_json(os.path.join(cells.BENCH_DIR, "traffic", "reasoning-batch.json"))
    assert mix["prompt_tokens"] == batch["prompt_tokens"] == {"dist": "uniform", "min": 512, "max": 2048}
    assert mix["output_tokens"] == batch["output_tokens"] == {"dist": "uniform", "min": 2048, "max": 10240}
    assert (mix["backlog_requests"], mix["check_requests"]) == (96, 8)
    cohort, backlog, planned = resident.plan(mix, 48, 208, 131_072, 262_272, 5)
    # about 4,800 tokens a slot when the window opens (prompt + age), 231,000
    # in all: 90% of the 256,000 the arena holds
    at_its_age = [p + a for p, a, _ in planned]
    assert 4_600 < np.mean(at_its_age) < 5_000 and 0.86 < sum(at_its_age) / 256_000 < 0.94
    assert len(cohort) == 48 and len(backlog) == 96
    # every request fits a table of 256 blocks of 64
    assert max(len(p) + n for p, n in cohort + backlog) <= 256 * 64
    assert all(512 <= len(p) <= 2048 for p, _ in backlog)


def test_the_scopes_the_metrics_name_are_the_programs():
    import inspect
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.moe import dropless
    cell = cells.Cell(CELL)
    source = inspect.getsource(hybrid) + inspect.getsource(dropless)
    for name, scope in SCOPES.items():
        fn, args = cell.reader(name)
        assert fn is moe.scope_share_pct and args == {"scopes": [scope]}
        assert f'jax.named_scope("{scope}")' in source


# ---- the readers ------------------------------------------------------------------ #
def test_the_five_scopes_read_a_synthetic_trace():
    """A step of 10 ms busy whose ops lie under the program's nested scopes
    (what ``program_spans.read_stats`` makes of a trace: a chip's busy time
    and each op's scopes and self time): each share is the self time under
    its scope over the busy time; a program without the scope (a parent
    commit) gives nothing to read, as a run without a trace."""
    ops = [(("attn", "attn_cca"), 0.5e-3),
           (("attn", "attn_cca", "cca_mix"), 1.0e-3),
           (("attn", "attn_cca", "cca_attend"), 3.0e-3),            # paged_gqa_attention
           (("mlp", "moe", "moe_router"), 0.5e-3),
           (("mlp", "moe", "moe_experts"), 4.5e-3),                 # grouped_matmul
           (("head",), 0.5e-3)]
    stats = lambda ops: {"first_tokens": [], "chips": [
        (sum(s for _, s in ops), [(frozenset(c), s) for c, s in ops])]}
    run = {"trace": object(), "notes": {}, "counters": {}, "_program_stats": stats(ops)}
    cell = cells.Cell(CELL)
    want = {"attn_cca_share_pct.gen": 45.0, "cca_mix_share_pct.gen": 10.0,
            "cca_attend_share_pct.gen": 30.0, "moe_router_share_pct.gen": 5.0,
            "moe_experts_share_pct.gen": 45.0}
    for name, value in want.items():
        fn, args = cell.reader(name)
        assert fn(run, **args) == pytest.approx(value), name
        assert fn({"trace": None}, **args) is None
    gone = dict(run, _program_stats=stats([(("attn",), 1e-3)]))
    assert moe.scope_share_pct(gone, scopes=["cca_mix"]) is None


def test_experts_reached_reads_the_commit_spans_stat():
    cell = cells.Cell(CELL)
    fn, args = cell.reader("experts_reached_pct.gen")
    assert fn is zaya.experts_reached_pct and args == {}
    spans = {moe.LOAD_SPAN: [{"batch": 48, zaya.STAT: 306}, {"batch": 48, zaya.STAT: 304},
                             {"batch": 48}]}
    run = {"trace": object(), "cell": cell, "_moe_span_stats": spans}
    assert fn(run) == pytest.approx(100 * 305 / 320)
    # a program whose spans carry no such stat, a run without a trace
    assert fn(dict(run, _moe_span_stats={moe.LOAD_SPAN: [{"batch": 48}]})) is None
    assert fn({"trace": None, "cell": cell}) is None


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
