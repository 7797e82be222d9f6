"""What PR 66 (Xing4.0-29B-A4B on the periodic walk: four residual streams a
token) adds to the benchmark: the configuration against the catalog's row,
its arithmetic held to the arrays the engine builds, the cell, its traffic's
plan, its kind with its counters, its notes' readers on synthetic counts, its
two limits and its four controls, and the cell's control flow at the
rehearse size; CPU only.  What the cell IS is held here; its place in a list
and the length of a list are not."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.kinds import serve_backlog_resident_hyper as kind
from benchmarks.lib import arith_step, arith_xing4, cells
from benchmarks.readers import paged_mla, xing4

CELL = "xing4.0-29b-a4b.serve-prompt-heavy"
CONFIG = "xing4.0-29b-a4b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
HELD = 5_537_658_874
EVERY_BACKLOG_CELLS = {
    "compiles_in_window.gen", "serve_step_ms.gen", "decode_batch_mean.gen",
    "kv_blocks_peak_pct.gen", "preemptions.gen", "device_idle_pct.gen", "sched_host_ms.gen",
    "table_build_ms.gen", "host_turnaround_ms.gen", "step_outside_ms.gen",
    "idle_wire_ms.gen", "step_mfu_pct.gen", "program_ms.gen", "chunk_program_time_pct.gen",
    "dispatched_ahead_pct.gen", "host_occupancy_pct.gen"}
# the cell's own, listed since PR 68 (PR 66 left them in ``notes.xing4_layers``):
# entry -> the scope it reads
SCOPES = {"hc_coeff_share_pct.gen": "hc_coeff", "hc_pre_share_pct.gen": "hc_pre",
          "hc_post_share_pct.gen": "hc_post", "attn_share_pct.gen": "attn",
          "attn_latent_share_pct.gen": "attn_latent", "mlp_share_pct.gen": "mlp",
          "lead_mlp_share_pct.gen": "lead_mlp", "moe_share_pct.gen": "moe",
          "moe_router_share_pct.gen": "moe_router", "moe_experts_share_pct.gen": "moe_experts",
          "moe_shared_expert_share_pct.gen": "moe_shared", "lm_head_share_pct.gen": "head"}
OWN = set(SCOPES) | {"paged_mla_attention_roofline", "grouped_matmul_roofline",
                     "hc_mix_bytes_pct.gen"}


def test_the_configuration_is_the_catalogs_but_for_its_depth():
    cfg = cells.Cell(CELL).config
    try:        # the catalog beside the guide, where it is installed
        rows = [json.loads(l) for l in open(CATALOG)]
        source = next(r for r in rows if r["name"] == "Xing4.0-29B-A4B")
        assert cfg["source"] == source["source_url"]
        assert sorted(k for k, v in source["config"].items()
                      if cfg.get(k, "missing") != v) == ["first_k_dense_replace",
                                                         "num_hidden_layers"]
        assert cfg["published"] == {k: source["config"][k] for k in cfg["reduced"]}
    except FileNotFoundError:
        pass
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace"]
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"]) == (7, 1)
    assert cfg["published"] == {"num_hidden_layers": 40, "first_k_dense_replace": 2}
    # no width, no expert and no vocabulary row is cut
    assert (cfg["hidden_size"], cfg["n_routed_experts"], cfg["vocab_size"]) == (3584, 64, 131_072)
    assert (cfg["hc_mult"], cfg["hc_sinkhorn_iters"], cfg["hc_eps"]) == (4, 20, 1e-6)
    kw, ref = cfg["model"]["kwargs"], cfg["reference"]["kwargs"]
    assert (kw["n_embd"], kw["n_layer"], kw["n_head"], kw["head_dim"], kw["q_lora_rank"],
            kw["kv_lora_rank"], kw["qk_rope_dim"], kw["v_head_dim"], kw["intermediate_size"],
            kw["moe_intermediate_size"], kw["num_experts"], kw["top_k"], kw["dense_layers"],
            kw["vocab_size"]) == (
        cfg["hidden_size"], cfg["num_hidden_layers"], cfg["num_attention_heads"],
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["q_lora_rank"],
        cfg["kv_lora_rank"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["n_routed_experts"],
        cfg["num_experts_per_tok"], cfg["first_k_dense_replace"], cfg["vocab_size"])
    assert kw["hyper"] == [cfg["hc_mult"], cfg["hc_sinkhorn_iters"], cfg["hc_eps"],
                           cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]]
    assert kw["rope_yarn"][:2] == [cfg["rope_scaling"]["factor"],
                                   cfg["rope_scaling"]["original_max_position_embeddings"]]
    assert kw["route_scale"] == cfg["routed_scaling_factor"] == ref["routed_scaling_factor"]
    for key in ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
                "mhc_h_res_clamp_max", "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "first_k_dense_replace", "rope_theta",
                "rope_scaling", "vocab_size"):
        assert ref[key] == cfg[key], key
    assert (ref["n_head"], ref["top_k"], ref["n_routed_experts"]) == (32, 4, 64)
    assert {"hc_sinkhorn_order", "hc_eps", "hc_clamp", "hc_entry_exit", "hc_precision",
            "hc_seeding", "mtp", "rope_pairings"} <= set(cfg["assumed"])


def test_the_program_builds_the_whole_stage_from_the_file():
    import jax
    from benchmarks.lib.build import model_from
    cfg = cells.Cell(CELL).config
    model = model_from(cfg)
    mcfg = model.cfg
    assert mcfg.hyper == (4, 20, 1e-6, -30.0, 30.0) and mcfg.indexer is None and not mcfg.hybrid
    # what the harness and the resident kind read of a model's configuration
    assert (mcfg.n_layer, mcfg.kv_heads, mcfg.head_dim, mcfg.n_head) == (7, 32, 192, 32)
    assert mcfg.cache_lanes == (640,) and mcfg.untied_head and mcfg.moe_dense_layers == 1
    assert mcfg.bank_experts == (0, 64) and mcfg.moe_top_k == 4 and mcfg.moe_shared_experts == 1
    assert (mcfg.moe_n_group, mcfg.moe_topk_group, mcfg.moe_scoring) == (1, 1, "sigmoid")
    assert mcfg.padded_vocab == mcfg.vocab_size == 131_072
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    zeros = 7 * 3 * 3584 + 3584         # ln1_b, ln2_b, out_b a layer; lnf_b
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) - zeros
    assert held == model.num_params() == HELD == cfg["parameters"]
    assert "5,537,658,874 parameters" in cfg["reduced_why"]
    assert shapes["blocks"]["hc_attn_phi"].shape == (7, 14_336, 24)
    assert shapes["blocks"]["moe"]["experts"]["wi"].shape == (6, 64, 3584, 2048)
    # the issue's arithmetic, a kind of layer
    mix = arith_xing4.mix_params(cfg["model"]["kwargs"])
    assert mix == 2 * (14_336 * 24 + 27) == 688_182
    assert arith_xing4.attention_params(cfg["model"]["kwargs"]) == 28_409_856 + 8_448 + mix
    w = arith_xing4.xing4_weights(cfg["model"]["kwargs"])
    assert w["dense"] + w["gathered"] + arith_step.bank_params(w["bank"]) == held
    assert w["gathered"] == 131_072 * 3584
    assert w["bank"] == {"layers": 6, "experts": 64, "held": 64, "top_k": 4,
                         "hidden": 3584, "width": 1024}
    assert arith_step.bank_params(w["bank"]) == 6 * 704_643_072
    dense_layer, expert_layer = 128_196_918, 744_989_046
    assert dense_layer + 6 * expert_layer + 939_524_096 + 3584 == held


def test_the_arena_is_the_engines():
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from benchmarks.lib.build import model_from
    from deepspeed_tpu.serving.kv_cache import arena_bytes, init_arena
    cfg = cells.Cell(CELL).config
    serve, mcfg = cfg["serve"], model_from(cfg).cfg
    block = serve["serving"]["block_size"]
    # lib/serving.py's divisor counts K and V heads where ONE latent is cached
    per_block = 2 * mcfg.n_layer * block * mcfg.kv_heads * mcfg.head_dim * 2
    blocks = serve["arena_bytes"] // per_block
    assert (block, blocks, blocks * block) == (64, 4224, 16 * 16_896)
    assert serve["arena_bytes"] == blocks * per_block
    assert arena_bytes(mcfg, blocks, block, 2) == serve["arena_bytes_really_held"] \
        == 270_336 * 7 * 1280 == 2_422_210_560
    kp, vp = jax.eval_shape(lambda: init_arena(mcfg, blocks, block, jnp.bfloat16))
    assert kp.shape == (7, 4224, 64, 640) and vp is None
    assert serve["serving"]["max_blocks_per_seq"] * block == 16_896 == 16_384 + 512
    # the rehearse size, through the harness's own arithmetic to an engine
    cells.merge(cfg, cfg["rehearse"])
    model = model_from(cfg)
    lanes = model.cfg.kv_heads * model.cfg.head_dim
    blocks = cfg["serve"]["arena_bytes"] // (2 * model.cfg.n_layer * 16 * lanes * 4)
    assert blocks == 65
    eng = deepspeed_tpu.init_serving(
        model=model, params=model.init_params(jax.random.PRNGKey(0)),
        config={"serving": dict(cfg["serve"]["serving"], num_blocks=blocks)})
    try:
        assert eng._k_pages.shape == (3, 65, 16, 256) and eng._v_pages is None
    finally:
        eng.close()


# ---- the files ------------------------------------------------------------------ #
def test_the_cell_its_traffic_and_its_metrics_resolve():
    cell = cells.Cell(CELL)
    bench = cells.load_benchmark()
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (workload["config"], workload["traffic"], workload["chips"]) == (
        CONFIG, "prompt-heavy", 1)
    assert 0 < len(workload["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cell.config["reduced"] and entry["source"] == cell.config["source"]
    assert entry["file"] == "benchmarks/configs/xing4.0-29b-a4b.json"
    assert 0 < len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert cell.chips == 1 and {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "setup_s"}
    listed = {m["name"]: m for m in cell.per_layer}
    assert EVERY_BACKLOG_CELLS | OWN <= set(listed)
    # every metric listed for the cell resolves to a reader, and moves the
    # end-to-end metric the cell reports
    for name, m in listed.items():
        fn, args = cell.reader(name)
        assert callable(fn) and isinstance(args, dict), name
        assert m["moves"] == "serve_tokens_per_s" and CELL in m["workloads"], name
    assert cell.config["step_work"] == {
        "_about": cell.config["step_work"]["_about"],
        "weights": "benchmarks.lib.arith_xing4:xing4_weights",
        "attention": "benchmarks.readers.paged_gqa:work"}
    for key in ("logits", "hidden", "head"):
        assert callable(cells.resolve(cell.config["reference"][key]))


def test_the_traffic_is_prompts_that_set_the_step():
    cell = cells.Cell(CELL)
    mix = cell.traffic
    assert cell.kind is kind and kind.END_TO_END == resident.END_TO_END
    assert mix["kind"] == "serve-backlog-resident-hyper"
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 4096, "max": 16_384}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 128, "max": 512}
    assert mix["backlog_requests"] == 256 and 2 <= mix["check_requests"] <= 4
    serving = cell.config["serve"]["serving"]
    slots, chunk = serving["max_batch_size"], serving["prefill_chunk"]
    assert (slots, chunk) == (16, 512)
    cohort, backlog, planned = resident.plan(mix, slots, chunk, 262_144, 131_072, 5)
    assert len(cohort) == 16 and len(backlog) == 256
    # about 10,400 keys a slot when the window opens (prompt + age)
    assert 9_800 < np.mean([p + a for p, a, _ in planned]) < 11_000
    # every request fits a table of 16,896 positions
    assert max(len(p) + n for p, n in cohort + backlog) <= 16_896
    assert min(n for _, n in backlog) >= 128 and max(n for _, n in backlog) <= 512
    # a request brings 20 chunk steps and a slot gives one up every 20 steps:
    # the one chunk a step the engine runs is in (nearly) every step
    chunks = np.mean([-(-len(p) // chunk) for p, _ in backlog])
    finishes_every = np.mean([n for _, n in backlog]) / slots
    assert 20 < chunks < 21 and 19.5 < finishes_every < 20.5
    assert chunks / finishes_every > 0.9
    # any window of 16 requests in a row brings near the mean: no lucky order
    per = np.asarray([-(-len(p) // chunk) for p, _ in backlog])
    runs = np.convolve(per, np.ones(16), "valid") / 16
    assert runs.min() > 17 and runs.max() < 24


def test_the_scopes_the_metrics_name_are_the_programs():
    import inspect
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.moe import dropless
    cell = cells.Cell(CELL)
    source = inspect.getsource(gpt) + inspect.getsource(dropless)
    for name, scope in SCOPES.items():
        assert cell.reader(name)[1] == {"scopes": [scope]}
        assert f'named_scope("{scope}")' in source, scope
    assert set(xing4.MIX_SCOPES) <= set(SCOPES.values())
    from deepspeed_tpu.ops.pallas import decode_attention as da
    assert cell.reader("paged_mla_attention_roofline")[0] is paged_mla.roofline
    assert f'"{paged_mla.KERNEL}"' in inspect.getsource(da)
    assert cell.reader("hc_mix_bytes_pct.gen")[0] is xing4.mix_bytes_pct


# ---- the kind's counters and notes ---------------------------------------------------- #
class _Srv:
    """What ``attention_counters`` reads of a ``Serving``."""
    slots, chunk = 16, 512

    def __init__(self):
        from benchmarks.lib.build import model_from
        self.cell = cells.Cell(CELL)
        self.model = model_from(self.cell.config)
        self.params = {"wte": np.zeros(1, np.float16)}


def test_the_kind_counts_a_chunks_keys_once():
    """Between two snapshots: one request decodes 3 tokens at positions
    10,000..10,002, another runs two chunks of its prompt from 1,024.  A pair
    costs 2 x 32 x (576 + 512) operations in each of 7 layers; a decode row
    reads its keys, the chunk its sequence's ONCE."""
    snaps = {"before": {1: (8000, 10_000, 2000), 2: (6000, 1024, 0)},
             "after": {1: (8000, 10_003, 2003), 2: (6000, 2048, 0)}}
    steps = [(0.0, 0.1, 1, 512, 0, 0, 0), (0.1, 0.2, 1, 512, 0, 0, 0), (0.2, 0.3, 1, 0, 0, 0, 0)]
    c = kind.attention_counters(_Srv(), snaps, steps)
    decode = [10_001, 10_002, 10_003]
    pairs = sum(decode) + sum(range(1025, 2049))
    assert c["paged_gqa_flops"] == 7 * 2 * 32 * (576 + 512) * pairs
    read = sum(decode) + 1536 + 2048
    rows = 3 + 1024
    assert c["paged_gqa_bytes"] == 7 * (read * 576 + rows * 32 * (576 + 512)) * 2
    assert (c["attention_rows_live"], c["attention_chunks"]) == (rows, 2)
    assert c["attention_rows_idle"] == 3 * 528 - rows
    assert c["traced_step_rows"] == [513, 513, 1]
    # the same count in keys, for the latent kernel's roofline; a chunk
    # multiplies hundreds of times the keys it reads
    assert (c["attention_keys_read"], c["attention_key_products"]) == (7 * read, 7 * pairs)
    assert sum(range(1025, 2049)) > 300 * (1536 + 2048) / 2


def test_the_mixes_bytes_are_one_read_and_one_write_of_the_streams():
    # a step of 528 rows: 14 mixes over 4 streams of 3584 read and written,
    # u and F(u): 0.53 GB, the issue's number; phi once a mix
    rows = arith_xing4.mix_bytes(528, 0, 7, 4, 3584, 2)
    assert rows == 14 * 528 * (2 * 4 + 2) * 3584 * 2 and 0.52e9 < rows < 0.54e9
    assert arith_xing4.mix_bytes(0, 1, 7, 4, 3584, 2) == 14 * 14_336 * 24 * 2


class _Trace:
    def __init__(self, seconds, busy=1.0, runs=2):
        self._s, self._busy, self._runs = seconds, busy, runs

    def op_seconds(self):
        return self._s

    def busy_s(self):
        return self._busy

    def program_runs(self):
        return self._runs


def test_the_cells_own_readers_read_synthetic_counts(monkeypatch):
    """The latent kernel's roofline over the kind's own counters of one
    chunk (``readers/paged_mla.py``: the listed entry, which PR 66 could only
    leave in the notes), and the mixes' bytes against the ``hc_*`` scopes."""
    cell = cells.Cell(CELL)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    snaps = {"before": {2: (6000, 1024, 0)}, "after": {2: (6000, 1536, 0)}}
    counters = kind.attention_counters(_Srv(), snaps, [(0.0, 0.1, 0, 512, 0, 0, 0)])
    run = {"trace": _Trace({paged_mla.KERNEL: 0.5}), "peaks": peaks, "cell": cell,
           "notes": {}, "counters": dict(counters, traced_step_rows=[528, 0, 528, 528])}
    # what ``step_mfu_pct`` takes and what the kernel's roofline takes are
    # ONE count: the reads and the pairs at ``arith_mla``'s cost of each
    assert paged_mla.work(run) == (counters["paged_gqa_flops"], counters["paged_gqa_bytes"])
    least = max(counters["paged_gqa_flops"] / 197e12, counters["paged_gqa_bytes"] / 819e9)
    assert paged_mla.roofline(run) == pytest.approx(100 * least / 0.5)
    assert run["notes"]["roofline_bound"][paged_mla.KERNEL] == "compute"
    assert paged_mla.roofline(dict(run, trace=_Trace({}))) is None
    # the mixes: 5% of a busy second for the last two steps' bytes
    monkeypatch.setattr(xing4, "scope_share_pct", lambda run, scopes: 5.0)
    least = arith_xing4.mix_bytes(2 * 528, 2, 7, 4, 3584, 2) / 819e9
    assert xing4.mix_bytes_pct(run) == pytest.approx(100 * least / 0.05)
    monkeypatch.setattr(xing4, "scope_share_pct", lambda run, scopes: None)
    assert xing4.mix_bytes_pct(run) is None          # a program without the scopes
    assert xing4.mix_bytes_pct(dict(run, trace=None)) is None


# ---- the limits and the controls ------------------------------------------------------- #
def test_the_kinds_limits_judge_a_sample():
    assert kind.judge([0.1, 0.2], [0.01, 0.02], 0.015) == 0
    assert kind.judge([kind.LOGIT_MARGIN + 0.01, 0.2], [0.0, 0.0], 0.0) == 1
    over = kind.NOISE_LIMIT * 1.5
    assert kind.judge([0.1, 0.1, 0.1], [over, over, 0.0], over) == 2
    assert kind.judge([0.1, 0.1, 0.1], [over, 0.0, 0.0], 0.0) == 0     # the median holds
    assert set(kind.PLANTED) == {None, "sinkhorn-1", "hpost-unscaled", "maps-bfloat16",
                                 "weights-float8"}
    # each limit between its two chip readings (PERF.md § 6, PR 66): the sound
    # runs' largest, and the least of what it must refuse (the gross limit: a
    # request of tokens unrelated to the reference; no planted control)
    assert 0.112 * 1.5 < kind.NOISE_LIMIT < 0.393 / 1.5
    assert 1.25e-6 * 10 < kind.MAPS_LIMIT < 5.0e-3 / 10
    assert 4.25 * 1.4 < kind.LOGIT_MARGIN < 8.21 / 1.2


def _rehearse_config():
    cfg = cells.Cell(CELL).config
    cells.merge(cfg, cfg["rehearse"])
    return cfg


@pytest.mark.parametrize("planted", ["sinkhorn-1", "hpost-unscaled", "maps-bfloat16"])
def test_a_planted_control_changes_the_maps_and_leaves_the_program_its_own(planted):
    import jax
    import jax.numpy as jnp
    from benchmarks.lib.build import model_from
    from deepspeed_tpu.models import gpt
    real = (gpt.sinkhorn_knopp, gpt.hyper_maps, gpt.hyper_write)
    model = model_from(_rehearse_config())
    p = jax.tree.map(lambda a: a[0], {k: v for k, v in model.init_params(
        jax.random.PRNGKey(1))["blocks"].items() if k.startswith("hc_attn")})
    X = jnp.asarray(np.random.default_rng(2).normal(0, 1.0, (6, 1, 4, 64)), jnp.float32)
    f = jnp.ones((6, 1, 64), jnp.float32)

    def through():
        u, maps = gpt.hyper_read(model.cfg, p, "attn", X, jnp.float32)
        return [np.asarray(a, np.float32) for a in (u, *maps, gpt.hyper_write(X, maps, f))]
    want = through()
    with kind.PLANTED[planted]():
        got = through()
    assert (gpt.sinkhorn_knopp, gpt.hyper_maps, gpt.hyper_write) == real
    u, hres, hpost, out = (np.abs(g - w).max() for g, w in zip(got, want))
    if planted == "sinkhorn-1":
        assert u == 0 and hpost == 0 and hres > 1e-3
        assert np.abs(got[1].sum(axis=-2) - 1).max() > 1e-3     # columns not there yet
    elif planted == "hpost-unscaled":
        assert u == 0 and hres == 0 and hpost == 0      # the maps are the model's
        assert np.abs((got[3] - want[3]) + 0.5 * want[2][..., None] * np.asarray(f)[..., None, :]
                      ).max() < 1e-6
    else:
        assert 1e-4 < hres < 3e-2 and 1e-4 < u < 3e-2 and 1e-4 < hpost < 3e-2


def test_the_first_sublayers_maps_are_the_references_and_a_control_is_not():
    """``maps_gaps`` on the rehearse model: the program's float32 maps lie at
    float32's rounding from the reference's, each control that touches the
    maps far over the limit, and the one that does not leaves them alone."""
    import jax
    from benchmarks.lib.build import model_from
    cfg = _rehearse_config()
    model = model_from(cfg)
    params = model.init_params(jax.random.PRNGKey(3))
    rng = np.random.default_rng(4)
    samples = [(rng.integers(0, 512, n).tolist(), [1, 2]) for n in (40, 56)]
    read = lambda: kind.maps_gaps(model, params, cfg["reference"], samples)
    assert len(read()) == 2 and max(read()) < 1e-5 < kind.MAPS_LIMIT
    for planted in ("sinkhorn-1", "maps-bfloat16"):
        with kind.PLANTED[planted]():
            assert min(read()) > 3 * kind.MAPS_LIMIT
    with kind.PLANTED["hpost-unscaled"]():
        assert max(read()) < 1e-5


# ---- the cell's control flow, at the rehearse size ---------------------------------- #
def _rehearse(*more):
    out = subprocess.run(
        [sys.executable, os.path.join(cells.ROOT, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "2", "--rehearse", *more],
        capture_output=True, text=True, timeout=600, cwd=cells.ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_the_cell_rehearses_on_the_cpu():
    line = _rehearse()
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["requests_wrong"] == [0, 0]
    assert line["compared"]["cohort_not_filled"] == [0, 0]
    assert line["compared"]["largest_logit_gap"][0] < 1e-3
    assert line["compared"]["first_maps_gap"][0] < 1e-5
    assert {"serve_tokens_per_s", "setup_s", "step_mfu_pct.gen"} <= set(line["would_report"])


@pytest.mark.parametrize("planted", ["sinkhorn-1", "maps-bfloat16", "hpost-unscaled"])
def test_a_planted_control_at_the_rehearse_size(planted):
    """The rehearse size serves in float32 and reads a gap of 0.0 (the test
    above).  A control on the maps comes out ``correct`` FALSE there, by the
    limit on the first sublayer's maps; ``Hpost`` without its factor 2 serves
    another model's tokens, which the two limits on the logits refuse at the
    published widths in bf16, on the chip (PERF.md § 6: this model's logits
    are a fiftieth of those)."""
    line = _rehearse("--set", f'planted="{planted}"')
    gap, limit = line["compared"]["first_maps_gap"]
    if planted == "hpost-unscaled":
        assert gap < limit
        assert line["compared"]["largest_logit_gap"][0] > 0.01
        assert line["compared"]["noise_scale_median"][0] > 0.01
    else:
        assert line["correct"] is False and line["failed"] == 2
        assert gap > 3 * limit
        assert line["compared"]["requests_whose_maps_are_wrong"] == [2, 0]


def test_a_program_without_the_family_is_refused_at_once():
    """A parent commit under this PR's benchmark files: the kind says that
    the program cannot build the configuration, before anything is built."""
    class Cell:
        config_name = CONFIG
        config = {"model": {"config": "deepspeed_tpu.models.gpt:no_such_family_config"}}
        traffic = {}
    with pytest.raises(cells.BenchmarkError, match="cannot build xing4.0-29b-a4b"):
        kind.run(Cell(), None, None)
