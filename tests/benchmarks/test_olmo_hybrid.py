"""Tests of what the benchmark adds for Olmo-Hybrid-7B's chat-resident cell:
the configuration against the catalog's row, its arithmetic held to the
arrays the engine builds, the cell, its traffic, its kind and its metrics,
the new scopes and the two rooflines on synthetic traces, and the cell's
control flow at the rehearse size; CPU only."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.kinds import serve_backlog_resident_delta as kind
from benchmarks.lib import arith_olmo_hybrid, arith_step, cells
from benchmarks.readers import moe, olmo_hybrid

CELL = "olmo-hybrid-7b.serve-chat-resident"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SCOPES = {"attn_delta_share_pct.gen": "attn_delta", "delta_conv_share_pct.gen": "delta_conv",
          "delta_update_share_pct.gen": "delta_update", "attn_full_share_pct.gen": "attn_full",
          "mlp_share_pct.gen": "mlp"}         # the last two folded from ``attn_mha``, ``dense_mlp`` (PR 68)
NEW = tuple(SCOPES) + ("delta_state_roofline", "delta_state_update_roofline",
                       "delta_state_moves_per_step.gen")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
STATE = 30 * 96 * 192 * 4


def test_the_configuration_is_the_catalogs_but_for_its_depth():
    cfg = cells.Cell(CELL).config
    try:        # the catalog beside the guide, where it is installed
        rows = [json.loads(l) for l in open(CATALOG)]
        source = next(r for r in rows if r["name"] == "Olmo-Hybrid-7B")
        assert cfg["source"] == source["source_url"]
        assert sorted(k for k, v in source["config"].items()
                      if cfg.get(k, "missing") != v) == ["layer_types", "num_hidden_layers"]
        assert source["config"]["num_hidden_layers"] == 32
        assert cfg["layer_types"] == source["config"]["layer_types"][:16]
    except FileNotFoundError:
        pass
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"] and cfg["num_hidden_layers"] == 16
    assert cfg["layer_types"] == 4 * (3 * ["linear_attention"] + ["full_attention"])
    kw, ref = cfg["model"]["kwargs"], cfg["reference"]["kwargs"]
    assert (kw["n_embd"], kw["n_head"], kw["n_kv_head"], kw["intermediate_size"],
            kw["vocab_size"], kw["n_positions"], kw["linear_heads"], kw["linear_heads"],
            kw["linear_key_head_dim"], kw["linear_value_head_dim"], kw["linear_conv_kernel_dim"],
            kw["linear_allow_neg_eigval"]) == (
                cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["intermediate_size"], cfg["vocab_size"], cfg["max_position_embeddings"],
                cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
                cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
                cfg["linear_conv_kernel_dim"], cfg["linear_allow_neg_eigval"]) == (
                    3840, 30, 30, 11008, 100352, 65536, 30, 30, 96, 192, 4, True)
    assert kw["head_dim"] == cfg["hidden_size"] // cfg["num_attention_heads"] == 128
    assert kw["layer_types"] == ref["layer_types"] == cfg["layer_types"]
    assert {k: ref[k] for k in ("n_head", "head_dim", "linear_heads", "linear_key_head_dim",
                                "linear_value_head_dim", "vocab_size")} == {
        "n_head": 30, "head_dim": 128, "linear_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "vocab_size": 100352}
    assert ref["eps"] == cfg["rms_norm_eps"] == 1e-6 and cfg["tie_word_embeddings"] is False
    assert cfg["rope_parameters"] == {"rope_theta": None} and cfg["attention_bias"] is False
    # what the config does not fix, and what is left out
    assert {"block", "mlp", "delta_rule", "neg_eigval", "decay", "convolution", "qk_l2",
            "output_norm_and_gate", "full_attention", "rope", "dtype", "weights",
            "deployment"} <= set(cfg["assumed"])
    assert set(cfg["departures"]) == {"none_from_the_equations", "the_cut", "state_layout"}
    assert "device_idle_pct.gen" in cfg["assumed"]["deployment"]
    assert "log(0.02 (h + 1))" in cfg["assumed"]["weights"]


def test_the_program_builds_the_held_layers_from_the_file():
    import jax
    from benchmarks.lib.build import model_from
    cfg = cells.Cell(CELL).config
    model = model_from(cfg)
    mcfg = model.cfg
    assert mcfg.mixers == 4 * (3 * ("delta",) + ("full",)) and mcfg.ffns == ("mlp",) * 16
    # what the harness and the resident kind read of a model's configuration
    assert (mcfg.n_layer, mcfg.kv_heads, mcfg.head_dim, mcfg.n_head) == (16, 30, 128, 30)
    assert all(k.window is None and not k.rope for k in mcfg.pattern) and mcfg.untied_head
    assert mcfg.norm_after and mcfg.qk_norm and mcfg.delta_neg_eigval
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) - 3840   # lnf_b
    assert held == model.num_params() == 4_100_788_944
    assert "4,100,788,944 parameters = 8.20 GB" in cfg["reduced_why"]
    w = arith_olmo_hybrid.olmo_hybrid_weights(cfg["model"]["kwargs"])
    assert w["dense"] + w["gathered"] == held and w["bank"] is None
    assert w["gathered"] == 100_352 * 3840
    assert w["dense"] == 12 * 215_570_172 + 4 * 185_809_920 + 3840 + 100_352 * 3840
    assert arith_olmo_hybrid.state_bytes(cfg["model"]["kwargs"]) == STATE == 2_211_840


def test_a_steps_least_work_is_the_issues_arithmetic():
    """80 live rows at 680 keys: the delta layers' states read and written
    4.25 GB and their mixers' weights 2.13 GB; all 16 MLPs 4.06 GB; the full
    layers' pages 3.34 GB and their projections 0.47 GB; the head 0.77 GB:
    15.0 GB and 18.3 ms at 819 GB/s by the issue's count; with the
    convolution states beside the states (0.13 GB) and the pages whole (43 of
    16 keys hold 680) 15.2 GB and 18.6 ms."""
    kw = cells.Cell(CELL).config["model"]["kwargs"]
    w = arith_olmo_hybrid.olmo_hybrid_weights(kw)
    _, weights = arith_step.step_work(w, 80)
    assert weights == 2 * w["dense"]
    assert 12 * 2 * 88_750_332 == pytest.approx(2.13e9, rel=2e-3)
    assert 16 * 2 * 126_812_160 == pytest.approx(4.06e9, rel=2e-3)
    assert 4 * 2 * 58_990_080 == pytest.approx(0.47e9, rel=5e-3)
    _, state, conv = arith_olmo_hybrid.delta_rows(80, 80, 12, kw)
    assert state == 80 * 12 * 2 * STATE == pytest.approx(4.25e9, rel=1e-3)
    assert conv == 80 * 12 * 2 * 3 * 11_520 * 2
    _, pages = arith_olmo_hybrid.full_rows(np.full(80, 679), [], 4, 16, kw)
    # 680 keys are 43 pages of 16; K and V of 3,840 lanes, four layers; q and o beside
    assert pages == 4 * (2 * 80 * 43 * 16 * 3840 * 2 + 2 * 80 * 3840 * 2)
    assert 54_400 * 61_440 == pytest.approx(3.34e9, rel=1e-3)
    assert pages == pytest.approx(3.34e9, rel=0.02)
    total = weights + state + conv + pages
    assert total - conv - (pages - 54_400 * 61_440) == pytest.approx(15.0e9, rel=2e-3)
    assert total == pytest.approx(15.2e9, rel=2e-3)
    assert total / 819e9 == pytest.approx(18.6e-3, rel=0.01)


def test_the_arena_and_the_states_are_the_engines():
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from benchmarks.lib.build import model_from
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.serving.kv_cache import arena_bytes
    cfg = cells.Cell(CELL).config
    serve, mcfg = cfg["serve"], model_from(cfg).cfg
    block = serve["serving"]["block_size"]
    # lib/serving.py's divisor counts 16 layers of K and V where 4 own pages
    per_block = 2 * mcfg.n_layer * block * mcfg.kv_heads * mcfg.head_dim * 2
    blocks = serve["arena_bytes"] // per_block
    assert blocks * block == 65_536 and serve["arena_bytes"] == blocks * per_block == 16_106_127_360
    assert serve["arena_bytes_really_held"] == arena_bytes(mcfg, blocks, block) == 65_536 * 61_440
    assert serve["serving"]["max_blocks_per_seq"] * block == 2_048
    assert (serve["serving"]["max_batch_size"], serve["serving"]["prefill_chunk"],
            serve["serving"]["dtype"]) == (80, 176, "bfloat16")
    # the cell's steadiness rests on it (PERF.md § 6, PR 47: a host that
    # sleeps on the token row reads 4-6% low in one run of six)
    assert serve["serving"]["poll_token_row"] is True
    aux = jax.eval_shape(lambda: hybrid.init_aux(mcfg, blocks, block, 80, jnp.bfloat16))
    assert aux["delta_state"].shape == (12, 80, 96, 30 * 192)
    assert aux["delta_conv"].shape == (12, 80, 3, 11_520)
    assert serve["delta_state_bytes"] == aux["delta_state"].size * 4 == 12 * 80 * STATE
    assert serve["delta_conv_bytes"] == aux["delta_conv"].size * 2 == 66_355_200
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
        assert eng._k_pages.shape == eng._v_pages.shape == (1, 200, 16, 64)
        assert eng._k_pages.nbytes + eng._v_pages.nbytes == arena_bytes(model.cfg, 200, 16, 4)
        assert eng._aux["delta_state"].shape == (3, 4, 8, 64)
        assert eng._aux["delta_conv"].shape == (3, 4, 3, 128)
        assert eng.cache_bytes_per_token == 2 * 64 * 4
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
        assert CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s"
        assert m["unit"] == ("count" if name.startswith("delta_state_moves") else "%")
    assert cell.chips == 1 and [m["name"] for m in cell.end_to_end] == [
        "serve_tokens_per_s", "setup_s"]
    # the twelve every backlog serve cell reported when this one came (the
    # overlay's three went with PR 68)
    assert {"compiles_in_window.gen", "serve_step_ms.gen", "decode_batch_mean.gen",
            "kv_blocks_peak_pct.gen", "preemptions.gen", "device_idle_pct.gen",
            "sched_host_ms.gen", "table_build_ms.gen", "host_turnaround_ms.gen",
            "step_outside_ms.gen", "idle_wire_ms.gen", "step_mfu_pct.gen"} <= set(listed)
    # ``paged_gqa_*`` is ALL the caches' work here, pages, states and
    # convolution states: the paged kernel's roofline divides pages alone and
    # does not list this cell; another family's layers are no part of it
    assert not {"paged_gqa_attention_roofline", "attn_linear_share_pct.gen",
                "attn_window_share_pct.gen"} & set(listed)
    assert cell.config["step_work"] == {
        "_about": cell.config["step_work"]["_about"],
        "weights": "benchmarks.lib.arith_olmo_hybrid:olmo_hybrid_weights",
        "attention": "benchmarks.readers.paged_gqa:work"}
    bench = cells.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "olmo-hybrid-7b")
    assert entry["reduced"] == cell.config["reduced"] and entry["source"] == cell.config["source"]
    # WHERE in its list an entry stands is pinned by no test: a later PR
    # appends behind it.  EVERY entry's why:
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (workload["config"], workload["traffic"]) == ("olmo-hybrid-7b", "chat-resident")
    assert all(0 < len(e["why"]) <= 200 for e in bench["configs"] + bench["workloads"])
    assert set(NEW) <= {m["name"] for m in bench["per_layer"]}


def test_the_traffic_is_chat_lengths_at_80_slots():
    cell = cells.Cell(CELL)
    mix = cell.traffic
    assert cell.kind is kind and kind.END_TO_END == resident.END_TO_END
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 128, "max": 512}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert (mix["backlog_requests"], mix["check_requests"]) == (384, 8)
    cohort, backlog, planned = resident.plan(mix, 80, 176, 65_536, 100_352, 5)
    # about 680 tokens a slot when the window opens (prompt + age), 54,400 in
    # all: 83% of the 65,536 the pages hold
    at_its_age = [p + a for p, a, _ in planned]
    assert 640 < np.mean(at_its_age) < 720 and 0.78 < sum(at_its_age) / 65_536 < 0.88
    assert len(cohort) == 80 and len(backlog) == 384
    # every request fits a table of 2,048 positions
    assert max(len(p) + n for p, n in cohort + backlog) <= 2_048
    assert all(128 <= len(p) <= 512 for p, _ in backlog)
    # a member finishes every 8 steps and brings 2.3 chunk steps
    chunks = np.mean([-(-len(p) // 176) for p, _ in backlog])
    assert 2.2 < chunks < 2.5 and 0.27 < chunks / 8 < 0.32


def test_the_scopes_the_metrics_name_are_the_programs():
    import inspect
    from deepspeed_tpu.models import hybrid
    cell = cells.Cell(CELL)
    source = inspect.getsource(hybrid)
    for name, scope in SCOPES.items():
        fn, args = cell.reader(name)
        assert fn is moe.scope_share_pct and args == {"scopes": [scope]}
        assert f'jax.named_scope("{scope}")' in source
    from deepspeed_tpu.ops.pallas import delta_rule
    assert f'name="{olmo_hybrid.KERNEL}"' in inspect.getsource(delta_rule)
    assert f'"{olmo_hybrid.STAT}"' in inspect.getsource(
        __import__("deepspeed_tpu.serving.engine", fromlist=["x"]))


# ---- the counters and the readers --------------------------------------------------- #
class _Srv:
    """What ``attention_counters`` reads of a ``Serving``."""
    slots, chunk, block = 80, 176, 16

    def __init__(self):
        self.cell = cells.Cell(CELL)
        self.params = {"wte": np.zeros(1, np.dtype("float16"))}       # two bytes a number


def test_the_kind_counts_pages_states_and_moves_from_the_lengths():
    """Two steps: 80 decode rows each, the second with a chunk of 100 prompt
    tokens of a request that starts: 2 x 80 + 1 moves a delta layer."""
    srv = _Srv()
    kw = srv.cell.config["model"]["kwargs"]
    before = {r: (300, 500 + r, 200 + r) for r in range(80)}
    after = {r: (300, 502 + r, 202 + r) for r in range(80)}
    after[99] = (100, 100, 1)                       # its one chunk yields its first token
    steps = [(0.0, 0.03, 80, 0, 0, 0, 0), (0.03, 0.07, 80, 100, 0, 0, 0)]
    c = kind.attention_counters(srv, {"before": before, "after": after}, steps)
    assert c["traced_step_state_moves"] == [80 * 12, 81 * 12]
    assert c["traced_step_decode_moves"] == [80 * 12, 80 * 12]
    assert c["delta_state_moves"] == 161 * 12 and c["traced_step_rows"] == [80, 180]
    assert c["delta_state_bytes_moved"] == 161 * 12 * 2 * STATE
    assert c["delta_conv_bytes_moved"] == 161 * 12 * 2 * 3 * 11_520 * 2
    assert c["attention_rows_live"] == 260 and c["attention_rows_idle"] == 2 * 256 - 260
    decode = np.concatenate([np.arange(500 + r, 502 + r) for r in range(80)])
    flops, pages = arith_olmo_hybrid.full_rows(decode, [(0, 100)], 4, 16, kw)
    # the chunk's pages 0..6 once, K and V of 3,840 lanes, a full layer
    assert pages == 4 * (2 * (int((decode // 16 + 1).sum()) + 7) * 16 * 3840 * 2
                         + 2 * 260 * 3840 * 2)
    assert c["full_pages_bytes"] == pages
    assert c["paged_gqa_bytes"] == pages + c["delta_state_bytes_moved"] + c["delta_conv_bytes_moved"]
    assert c["paged_gqa_flops"] == flops + arith_olmo_hybrid.delta_rows(260, 161, 12, kw)[0]


class _Trace:
    def __init__(self, runs, kernel_s):
        self.runs, self.kernel_s = runs, kernel_s

    def program_runs(self):
        return self.runs

    def op_seconds(self):
        return {"delta_state_update": self.kernel_s} if self.kernel_s else {}


def _run(ops, runs=3, kernel_s=0.018, counters=None):
    stats = {"first_tokens": [], "chips": [
        (sum(s for _, s in ops), [(frozenset(c), s) for c, s in ops])]}
    moves = {"traced_step_state_moves": [960, 972, 960, 960],
             "traced_step_decode_moves": [960, 960, 960, 960]}
    return {"trace": _Trace(runs, kernel_s), "notes": {}, "cell": cells.Cell(CELL),
            "peaks": PEAKS, "counters": moves if counters is None else counters,
            "_program_stats": stats}


def test_the_scopes_and_the_rooflines_read_a_synthetic_trace():
    """Three steps of 10 ms busy whose ops lie under the program's nested
    scopes: each share is the self time under its scope over the busy time;
    the rooflines divide the least time of the LAST three steps' moves (the
    device line holds three of the host's four) by the time under
    ``delta_update`` and by the kernel's."""
    ops = [(("attn", "attn_delta"), 3.0e-3),
           (("attn", "attn_delta", "delta_conv"), 1.5e-3),
           (("attn", "attn_delta", "delta_update"), 7.5e-3),          # the kernel among it
           (("attn", "attn_full"), 6.0e-3),
           (("mlp",), 9.0e-3),
           (("head",), 3.0e-3)]
    run = _run(ops)
    cell = run["cell"]
    want = {"attn_delta_share_pct.gen": 40.0, "delta_conv_share_pct.gen": 5.0,
            "delta_update_share_pct.gen": 25.0, "attn_full_share_pct.gen": 20.0,
            "mlp_share_pct.gen": 30.0}
    for name, value in want.items():
        fn, args = cell.reader(name)
        assert fn(run, **args) == pytest.approx(value), name
        assert fn({"trace": None}, **args) is None
    least = lambda moves: 2 * moves * STATE / 819e9
    fn, args = cell.reader("delta_state_roofline")
    assert fn is olmo_hybrid.delta_state_roofline and args == {}
    assert fn(run) == pytest.approx(100 * least(972 + 960 + 960) / 7.5e-3)
    assert run["notes"]["roofline_bound"]["delta_update"] == "memory"
    fn, _ = cell.reader("delta_state_update_roofline")
    assert fn(run) == pytest.approx(100 * least(3 * 960) / 0.018)
    assert 0 < fn(run) < 100
    # a program without the scope or the kernel (a parent commit), a kind
    # that left no count, a run without a trace: nothing to read
    gone = _run([(("attn",), 1e-3)], kernel_s=None)
    assert olmo_hybrid.delta_state_roofline(gone) is None
    assert olmo_hybrid.delta_state_update_roofline(gone) is None
    assert moe.scope_share_pct(gone, scopes=["delta_update"]) is None
    assert olmo_hybrid.delta_state_roofline(_run(ops, counters={})) is None
    assert olmo_hybrid.delta_state_update_roofline(_run(ops, counters={})) is None
    for fn in (olmo_hybrid.delta_state_roofline, olmo_hybrid.delta_state_update_roofline,
               olmo_hybrid.state_moves_per_step):
        assert fn({"trace": None, "counters": {}, "cell": cell}) is None


def test_the_moves_a_step_are_the_stats_spans_stat():
    cell = cells.Cell(CELL)
    fn, args = cell.reader("delta_state_moves_per_step.gen")
    assert fn is olmo_hybrid.state_moves_per_step and args == {}
    steps = [{olmo_hybrid.STAT: 960, "turnaround_ms": 1.0}, {olmo_hybrid.STAT: 972, "turnaround_ms": 1.0},
             {"turnaround_ms": 1.0}]
    run = {"trace": object(), "cell": cell, "_turnaround": (steps, [1.0, 1.0, 1.0])}
    assert fn(run) == pytest.approx(966.0)
    # a program whose span carries no such stat
    assert fn(dict(run, _turnaround=([{"turnaround_ms": 1.0}], [1.0]))) is None
    assert fn(dict(run, _turnaround=None)) is None


def test_the_kinds_limits_judge_a_sample():
    assert kind.judge([0.1, 0.2], [0.01, 0.02], 0.015) == 0
    assert kind.judge([kind.LOGIT_MARGIN + 0.01, 0.2], [0.0, 0.0], 0.0) == 1
    over = kind.NOISE_LIMIT * 1.5
    assert kind.judge([0.1, 0.1, 0.1], [over, over, 0.0], over) == 2
    assert kind.judge([0.1, 0.1, 0.1], [over, 0.0, 0.0], 0.0) == 0     # the median holds
    # each limit between its two chip readings (PERF.md § 6, PR 47): bf16's
    # largest over thirty runs, and the least of the control that must fail
    # (a bf16 state through the cell's own comparison; every matrix in float8)
    assert 0.438 * 1.15 < kind.NOISE_LIMIT < 0.628 / 1.15
    assert 1.001 * 1.5 < kind.LOGIT_MARGIN < 4.53 / 1.5


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
    assert set(NEW) <= set(line["would_report"])
