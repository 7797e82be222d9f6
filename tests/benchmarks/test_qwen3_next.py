"""What PR 64 (Qwen3-Next-80B-A3B-Instruct on the hybrid walk) adds to the
benchmark: the configuration against the catalog's row, its arithmetic held
to the arrays the engine builds, the cell, its traffic, its kind, the
accepted metrics that read it, its counters and its reader on synthetic
counts, and the cell's control flow at the rehearse size; CPU only.  What the
cell IS is held here; its place in a list and the length of a list are not."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.kinds import serve_backlog_resident_delta_moe as kind
from benchmarks.lib import arith_moe, arith_olmo_hybrid, arith_qwen3_next, arith_step, cells
from benchmarks.readers import (afmoe, held_experts, moe, olmo_hybrid, paged_gqa, qwen3_next,
                                zaya)

CELL = "qwen3-next-80b-a3b.serve-long-delta-moe"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TYPES = 3 * ["linear_attention"] + ["full_attention"]
# the accepted share metrics the cell joins, and the scope each reads
SCOPES = {"attn_delta_share_pct.gen": "attn_delta", "delta_conv_share_pct.gen": "delta_conv",
          "delta_update_share_pct.gen": "delta_update", "moe_share_pct.gen": "moe",
          "moe_router_share_pct.gen": "moe_router",
          "moe_experts_share_pct.gen": "moe_experts", "lm_head_share_pct.gen": "head",
          # what PR 64 left in ``notes.qwen3_next_layers``, listed since PR 68
          "attn_full_share_pct.gen": "attn_full", "attn_gate_share_pct.gen": "attn_gate",
          "moe_shared_expert_share_pct.gen": "moe_shared"}
OTHERS = {"delta_state_roofline": olmo_hybrid.delta_state_roofline,
          "delta_state_update_roofline": olmo_hybrid.delta_state_update_roofline,
          "delta_state_moves_per_step.gen": olmo_hybrid.state_moves_per_step,
          "experts_reached_pct.gen": zaya.experts_reached_pct,
          "moe_assignments_held_pct.gen": held_experts.assignments_held_pct,
          "grouped_matmul_roofline": afmoe.grouped_matmul_roofline,
          "paged_gqa_attention_roofline": paged_gqa.roofline,
          "moe_load_max_over_mean.gen": moe.load_max_over_mean}
STATE = 32 * 128 * 128 * 4
HELD = 3_677_613_120


def test_the_configuration_is_the_catalogs_but_for_its_three_cuts():
    cfg = cells.Cell(CELL).config
    try:        # the catalog beside the guide, where it is installed
        rows = [json.loads(l) for l in open(CATALOG)]
        source = next(r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert cfg["source"] == source["source_url"]
        assert sorted(k for k, v in source["config"].items()
                      if cfg.get(k, "missing") != v) == ["num_experts", "num_hidden_layers",
                                                         "vocab_size"]
        assert cfg["published"] == {k: source["config"][k] for k in cfg["reduced"]}
    except FileNotFoundError:
        pass
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (4, 256, 75_968)
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151_936}
    assert cfg["num_experts_the_router_chooses_among"] == 512 and cfg["experts_held"] == [0, 256]
    # layer i is full attention where (i + 1) % 4 == 0: the first period
    assert cfg["layer_types"] == TYPES == [
        "full_attention" if (i + 1) % cfg["full_attention_interval"] == 0
        else "linear_attention" for i in range(4)]
    kw, ref = cfg["model"]["kwargs"], cfg["reference"]["kwargs"]
    assert (kw["n_embd"], kw["n_head"], kw["n_kv_head"], kw["head_dim"], kw["intermediate_size"],
            kw["vocab_size"], kw["n_positions"], kw["linear_key_heads"], kw["linear_heads"],
            kw["linear_key_head_dim"], kw["linear_value_head_dim"], kw["linear_conv_kernel_dim"],
            kw["num_experts"], kw["top_k"], kw["moe_intermediate_size"],
            kw["shared_expert_intermediate_size"], kw["partial_rotary_factor"]) == (
                cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"],
                cfg["max_position_embeddings"], cfg["linear_num_key_heads"],
                cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"],
                cfg["num_experts_the_router_chooses_among"], cfg["num_experts_per_tok"],
                cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"],
                cfg["partial_rotary_factor"]) == (
                    2048, 16, 2, 256, 5120, 75_968, 262_144, 16, 32, 128, 128, 4, 512, 10,
                    512, 512, 0.25)
    assert kw["experts_held"] == ref["experts_held"] == cfg["experts_held"]
    assert kw["layer_types"] == ref["layer_types"] == TYPES
    assert {k: ref[k] for k in ("n_head", "n_kv_head", "head_dim", "rope_dim", "linear_key_heads",
                                "linear_heads", "top_k", "vocab_size")} == {
        "n_head": 16, "n_kv_head": 2, "head_dim": 256, "rope_dim": 64, "linear_key_heads": 16,
        "linear_heads": 32, "top_k": 10, "vocab_size": 75_968}
    assert ref["rope_theta"] == cfg["rope_theta"] == 1e7 and ref["eps"] == cfg["rms_norm_eps"]
    assert cfg["norm_topk_prob"] is True and cfg["tie_word_embeddings"] is False
    # what the config does not fix, what is left out, and the deployment
    assert {"block", "norm", "layer_types", "packing", "convolution", "qk_l2", "delta_rule",
            "output_norm_and_gate", "full_attention", "feed_forward", "mtp", "dtype",
            "weights"} <= set(cfg["assumed"])
    assert "1 + w" in cfg["assumed"]["norm"] and "MTP" in cfg["assumed"]["mtp"]
    assert "24 chips" in cfg["deployment"] and "device_idle_pct.gen" in cfg["deployment"]
    assert "TWO chips" in cfg["deployment"] and "twelve pipeline stages" in cfg["deployment"]
    assert set(cfg["departures"]) == {"none_from_the_equations", "the_share", "state_layout",
                                      "vocab_multiple"}


def test_the_program_builds_the_held_share_from_the_file():
    import jax
    from benchmarks.lib.build import model_from
    cfg = cells.Cell(CELL).config
    model = model_from(cfg)
    mcfg = model.cfg
    assert mcfg.mixers == 3 * ("delta",) + ("full",) and mcfg.ffns == ("moe_softmax",) * 4
    # what the harness and the resident kind read of a model's configuration
    assert (mcfg.n_layer, mcfg.kv_heads, mcfg.head_dim, mcfg.n_head) == (4, 2, 256, 16)
    assert all(k.window is None and k.rope for k in mcfg.pattern) and mcfg.untied_head
    assert mcfg.rope_dim == 64 and mcfg.qk_norm == "head" and mcfg.attn_gate
    assert (mcfg.delta_key_heads, mcfg.delta_heads) == (16, 32) and not mcfg.delta_neg_eigval
    assert mcfg.bank_experts == (0, 256) and mcfg.moe_num_experts == 512 and mcfg.moe_top_k == 10
    assert mcfg.moe_shared_experts == 1 and mcfg.moe_shared_gate and mcfg.moe_norm_topk
    assert mcfg.padded_vocab == mcfg.vocab_size == 75_968
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) - 2048   # lnf_b
    assert held == model.num_params() == HELD
    assert "3,677,613,120 parameters = 7.36 GB" in cfg["reduced_why"]
    w = arith_qwen3_next.qwen3_next_weights(cfg["model"]["kwargs"])
    assert w["dense"] + w["gathered"] + arith_step.bank_params(w["bank"]) == held
    assert w["gathered"] == 75_968 * 2048
    assert w["bank"] == {"layers": 4, "experts": 512, "held": 256, "top_k": 10,
                         "hidden": 2048, "width": 512}
    assert w["dense"] == (3 * 33_718_464 + 27_263_488 + 4 * 4_200_448 + 2048 + 75_968 * 2048)
    assert arith_qwen3_next.state_bytes(cfg["model"]["kwargs"]) == STATE == 2_097_152
    # the accepted delta rooflines read the state's bytes of THIS file right
    assert arith_olmo_hybrid.state_bytes(cfg["model"]["kwargs"]) == STATE
    assert arith_qwen3_next.packed_lanes(cfg["model"]["kwargs"]) == 8192


def test_a_steps_least_work_is_the_issues_arithmetic():
    """A chunk step's 544 rows reach every held expert (4 x 1.61 GB = 6.4 GB),
    32 decode rows alone about 46% of them; the full layer's pages at 34,700
    keys a slot are 2.3 GB; the states 0.4 GB read and written."""
    kw = cells.Cell(CELL).config["model"]["kwargs"]
    w = arith_qwen3_next.qwen3_next_weights(kw)
    _, chunk_step = arith_step.step_work(w, 544)
    _, decode_step = arith_step.step_work(w, 32)
    bank = 4 * 256 * 3_145_728 * 2
    assert bank == pytest.approx(6.44e9, rel=1e-3)
    assert chunk_step - 2 * w["dense"] == pytest.approx(bank, rel=0.02)
    assert arith_moe.experts_reached(32, 512, 10) / 512 == pytest.approx(0.468, abs=0.005)
    assert decode_step - 2 * w["dense"] == pytest.approx(0.468 * bank, rel=0.02)
    _, pages = arith_qwen3_next.full_rows(np.full(32, 34_699), [], 1, 64, kw)
    assert pages == 2 * 32 * 543 * 64 * 512 * 2 + 2 * 32 * 4096 * 2
    assert pages == pytest.approx(2.28e9, rel=0.01)
    # a chunk deep in a prompt needs its sequence's pages ONCE for 512 queries
    _, once = arith_qwen3_next.full_rows([], [(30_208, 512)], 1, 64, kw)
    assert once == 2 * 480 * 64 * 512 * 2 + 2 * 512 * 4096 * 2
    flops, state, conv = arith_qwen3_next.delta_rows(544, 33, 3, kw)
    assert state == 33 * 3 * 2 * STATE == pytest.approx(0.415e9, rel=0.01)
    assert conv == 33 * 3 * 2 * 3 * 8192 * 2
    assert flops == 3 * 544 * (6 * 32 * 128 * 128 + 2 * 4 * 8192)


def test_the_arena_and_the_states_are_the_engines():
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from benchmarks.lib.build import model_from
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.serving.kv_cache import arena_bytes, init_arena
    cfg = cells.Cell(CELL).config
    serve, mcfg = cfg["serve"], model_from(cfg).cfg
    block = serve["serving"]["block_size"]
    # lib/serving.py's divisor counts 4 layers of K and V where ONE owns pages
    per_block = 2 * mcfg.n_layer * block * mcfg.kv_heads * mcfg.head_dim * 2
    blocks = serve["arena_bytes"] // per_block
    assert (block, blocks, blocks * block) == (64, 20_480, 1_310_720)
    assert arena_bytes(mcfg, blocks, block, 2) == serve["arena_bytes_really_held"] \
        == 1_310_720 * 2048 == serve["arena_bytes"] // 4
    kp, vp = jax.eval_shape(lambda: init_arena(mcfg, blocks, block, jnp.bfloat16))
    assert kp.shape == vp.shape == (1, 20_480, 64, 512)
    aux = jax.eval_shape(lambda: hybrid.init_aux(mcfg, blocks, block, 32, jnp.bfloat16))
    assert aux["delta_state"].shape == (3, 32, 128, 4096)
    assert aux["delta_conv"].shape == (3, 32, 3, 8192)
    assert serve["delta_state_bytes"] == aux["delta_state"].size * 4 == 3 * 32 * STATE
    assert serve["delta_conv_bytes"] == aux["delta_conv"].size * 2 == 3 * 32 * 49_152
    assert serve["serving"]["max_blocks_per_seq"] * block == 46_080 >= 40_960 + 5_120
    # the rehearse size, through the harness's own arithmetic to an engine
    cells.merge(cfg, cfg["rehearse"])
    model = model_from(cfg)
    lanes = model.cfg.kv_heads * model.cfg.head_dim
    blocks = cfg["serve"]["arena_bytes"] // (2 * model.cfg.n_layer * 16 * lanes * 4)
    assert blocks == 64
    eng = deepspeed_tpu.init_serving(
        model=model, params=model.init_params(jax.random.PRNGKey(0)),
        config={"serving": dict(cfg["serve"]["serving"], num_blocks=blocks)})
    try:
        assert eng._k_pages.shape == eng._v_pages.shape == (1, 64, 16, 32)
        assert eng._k_pages.nbytes + eng._v_pages.nbytes == arena_bytes(model.cfg, 64, 16, 4)
        assert eng._aux["delta_state"].shape == (3, 4, 8, 64)
        assert eng._aux["delta_conv"].shape == (3, 4, 3, 96)
    finally:
        eng.close()


# ---- the files ------------------------------------------------------------------ #
def test_the_cell_its_traffic_and_its_metrics_resolve():
    cell = cells.Cell(CELL)
    bench = cells.load_benchmark()
    assert CELL in {w["name"] for w in bench["workloads"]}
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (workload["config"], workload["traffic"], workload["chips"]) == (
        "qwen3-next-80b-a3b", "long-delta-moe", 1)
    assert 0 < len(workload["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == "qwen3-next-80b-a3b")
    assert entry["reduced"] == cell.config["reduced"] and entry["source"] == cell.config["source"]
    assert entry["file"] == "benchmarks/configs/qwen3-next-80b-a3b.json"
    assert 0 < len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert cell.chips == 1 and [m["name"] for m in cell.end_to_end] == [
        "serve_tokens_per_s", "setup_s"]
    listed = {m["name"]: m for m in cell.per_layer}
    # the sixteen every backlog serve cell reports
    assert {"compiles_in_window.gen", "serve_step_ms.gen", "decode_batch_mean.gen",
            "kv_blocks_peak_pct.gen", "preemptions.gen", "device_idle_pct.gen",
            "sched_host_ms.gen", "table_build_ms.gen", "host_turnaround_ms.gen",
            "step_outside_ms.gen", "idle_wire_ms.gen", "step_mfu_pct.gen", "program_ms.gen",
            "chunk_program_time_pct.gen", "dispatched_ahead_pct.gen",
            "host_occupancy_pct.gen"} <= set(listed)
    # every metric listed for the cell resolves to a reader, and moves the
    # end-to-end metric the cell reports
    for name, m in listed.items():
        fn, args = cell.reader(name)
        assert callable(fn) and isinstance(args, dict), name
        assert m["moves"] == "serve_tokens_per_s" and CELL in m["workloads"], name
    assert len(bench["per_layer"]) <= 128
    assert cell.config["step_work"] == {
        "_about": cell.config["step_work"]["_about"],
        "weights": "benchmarks.lib.arith_qwen3_next:qwen3_next_weights",
        "attention": "benchmarks.readers.qwen3_next:work"}


def test_the_traffic_is_the_long_lengths_at_32_slots():
    cell = cells.Cell(CELL)
    mix = cell.traffic
    assert cell.kind is kind and kind.END_TO_END == resident.END_TO_END
    assert mix["kind"] == "serve-backlog-resident-delta-moe"
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 24_576, "max": 40_960}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 1024, "max": 5120}
    assert (mix["backlog_requests"], mix["check_requests"]) == (96, 4)
    slots = cell.config["serve"]["serving"]["max_batch_size"]
    chunk = cell.config["serve"]["serving"]["prefill_chunk"]
    assert (slots, chunk) == (32, 512)
    cohort, backlog, planned = resident.plan(mix, slots, chunk, 262_144, 75_968, 5)
    # about 34,700 keys a slot when the window opens (prompt + age)
    at_its_age = [p + a for p, a, _ in planned]
    assert 33_500 < np.mean(at_its_age) < 36_000
    assert 0.80 < sum(at_its_age) / 1_310_720 < 0.90
    assert len(cohort) == 32 and len(backlog) == 96
    # every request fits a table of 46,080 positions, every id the held slice
    assert max(len(p) + n for p, n in cohort + backlog) <= 46_080
    assert max(int(np.max(p)) for p, _ in backlog[:8]) < 75_968
    # a member finishes every 96 steps and brings 64 chunk steps
    chunks = np.mean([-(-len(p) // 512) for p, _ in backlog])
    assert 63 < chunks < 65.5 and 0.64 < chunks / 96 < 0.69


def test_the_scopes_the_metrics_name_are_the_programs():
    import inspect
    from deepspeed_tpu.models import gpt, hybrid
    from deepspeed_tpu.moe import dropless
    cell = cells.Cell(CELL)
    source = inspect.getsource(hybrid) + inspect.getsource(gpt) + inspect.getsource(dropless)
    listed = {m["name"] for m in cell.per_layer}
    assert set(SCOPES) | set(OTHERS) <= listed
    for name, scope in SCOPES.items():
        fn, args = cell.reader(name)
        assert fn is moe.scope_share_pct and args == {"scopes": [scope]}
        assert f'jax.named_scope("{scope}")' in source
    for name, reader in OTHERS.items():
        assert cell.reader(name) == (reader, {})
    assert cell.reader("moe_dispatch_share_pct.gen")[1] == {
        "scopes": ["moe_dispatch", "moe_combine"]}
    for scope in ("moe_dispatch", "moe_combine"):
        assert f'jax.named_scope("{scope}")' in source, scope
    assert '"moe_assignments_held"' in inspect.getsource(
        __import__("deepspeed_tpu.serving.engine", fromlist=["x"]))


# ---- the counters and the readers --------------------------------------------------- #
class _Srv:
    """What ``attention_counters`` reads of a ``Serving``."""
    slots, chunk, block = 32, 512, 64

    def __init__(self):
        self.cell = cells.Cell(CELL)
        self.params = {"wte": np.zeros(1, np.dtype("float16"))}       # two bytes a number


def test_the_kind_counts_pages_states_and_moves_from_the_lengths():
    """Two steps: 32 decode rows each, the second with a chunk of 512 prompt
    tokens deep in a request's prompt: 2 x 32 + 1 moves a delta layer, the
    chunk's pages once."""
    srv = _Srv()
    kw = srv.cell.config["model"]["kwargs"]
    before = {r: (30_000, 31_000 + r, 1000 + r) for r in range(32)}
    after = {r: (30_000, 31_002 + r, 1002 + r) for r in range(32)}
    before[99], after[99] = (30_000, 10_240, 0), (30_000, 10_752, 0)
    steps = [(0.0, 0.01, 32, 0, 0, 0, 0), (0.01, 0.04, 32, 512, 0, 0, 0)]
    c = kind.attention_counters(srv, {"before": before, "after": after}, steps)
    assert c["traced_step_state_moves"] == [32 * 3, 33 * 3]
    assert c["traced_step_decode_moves"] == [32 * 3, 32 * 3]
    assert c["delta_state_moves"] == 65 * 3 and c["traced_step_rows"] == [32, 544]
    assert c["delta_state_bytes_moved"] == 65 * 3 * 2 * STATE
    assert c["delta_conv_bytes_moved"] == 65 * 3 * 2 * 3 * 8192 * 2
    assert c["attention_rows_live"] == 576 and c["attention_chunks"] == 1
    assert c["attention_rows_idle"] == 2 * 544 - 576
    decode = np.concatenate([np.arange(31_000 + r, 31_002 + r) for r in range(32)])
    flops, pages = arith_qwen3_next.full_rows(decode, [(10_240, 512)], 1, 64, kw)
    # pages ALONE under the names the paged kernel's roofline divides
    assert (c["paged_gqa_flops"], c["paged_gqa_bytes"]) == (flops, pages)
    run = {"counters": c}
    assert paged_gqa.work(run) == (flops, pages)
    # the step's share of the peak takes the states beside them
    assert qwen3_next.work(run) == (
        flops + c["delta_flops"],
        pages + c["delta_state_bytes_moved"] + c["delta_conv_bytes_moved"])
    assert qwen3_next.work({"counters": {}}) is None
    # a kind that counted pages alone: the pages alone
    assert qwen3_next.work({"counters": {"paged_gqa_bytes": 7, "paged_gqa_flops": 3}}) == (3, 7)


def test_the_kinds_limits_judge_a_sample():
    assert kind.judge([0.1, 0.2], [0.01, 0.02], 0.015) == 0
    assert kind.judge([kind.LOGIT_MARGIN + 0.01, 0.2], [0.0, 0.0], 0.0) == 1
    over = kind.NOISE_LIMIT * 1.5
    assert kind.judge([0.1, 0.1, 0.1], [over, over, 0.0], over) == 2
    assert kind.judge([0.1, 0.1, 0.1], [over, 0.0, 0.0], 0.0) == 0     # the median holds
    assert set(kind.PLANTED) == {None, "state-bfloat16", "weights-float8", "router-not-renormalised"}
    # each limit between its two chip readings (PERF.md § 6, PR 64): bf16's
    # largest over eight runs, and the least of the control that must fail it
    assert 0.211 * 1.3 < kind.NOISE_LIMIT < 999.0
    assert 0.00347 * 1.25 < kind.STATE_LIMIT < 0.00608 / 1.25
    assert 1.949 * 1.5 < kind.LOGIT_MARGIN


def test_a_kept_slots_state_is_held_to_the_references_recurrence():
    """``state_gaps`` at the rehearse size: a slot's first-layer state as the
    program keeps it (``[dk, Hv dv]``) against the reference's recurrence over
    the same tokens; a state of another length of the sequence, or one
    rounded through bf16, stands off."""
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import reference_qwen3_next as ref
    from benchmarks.lib.build import model_from
    cfg = cells.Cell(CELL).config
    cells.merge(cfg, cfg["rehearse"])
    assert cfg["reference"]["states"].endswith(":qwen3_next_first_state")
    params = model_from(cfg).init_params(jax.random.PRNGKey(3))
    kw = cfg["reference"]["kwargs"]
    ids = np.random.default_rng(0).integers(0, 512, 50).astype(np.int32)
    padded = np.zeros(64, np.int32)
    padded[:50] = ids
    want = np.asarray(ref.qwen3_next_first_state(params, jnp.asarray(padded), 50, **kw))
    assert want.shape == (4, 8, 16) and np.abs(want).max() > 1e-3
    as_kept = lambda state: state.transpose(1, 0, 2).reshape(8, 64)
    gaps = kind.state_gaps(params, cfg["reference"], [
        (ids, as_kept(want)), (ids[:49], as_kept(want)),
        (ids, as_kept(np.asarray(jnp.asarray(want).astype(jnp.bfloat16).astype(jnp.float32))))])
    assert gaps[0] < 1e-6 and gaps[1] > 0.05 and 1e-4 < gaps[2] < 1e-2


def test_the_planted_state_rounds_what_a_slot_keeps():
    """``state-bfloat16``: inside the block the delta mixer hands back a
    state every number of which a bf16 holds; outside it the mixer is the
    program's own again."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import hybrid
    real = hybrid.MIXERS["delta"]
    seen = {}

    def mixer(cfg, p, h, kp, vp, held, li, step):
        return h, kp, vp, dict(held, delta_state=held["delta_state"] * (1 + 2.0 ** -12))
    hybrid.MIXERS["delta"] = (mixer, real[1])
    try:
        with kind.PLANTED["state-bfloat16"]():
            state = jnp.full((1, 1, 8, 16), 1.0, jnp.float32)
            seen["in"] = hybrid.MIXERS["delta"][0](
                None, None, 0, 0, 0, {"delta_state": state}, 0, None)[3]["delta_state"]
        seen["out"] = hybrid.MIXERS["delta"][0](
            None, None, 0, 0, 0, {"delta_state": state}, 0, None)[3]["delta_state"]
    finally:
        hybrid.MIXERS["delta"] = real
    assert seen["in"].dtype == jnp.float32 and float(seen["in"][0, 0, 0, 0]) == 1.0
    assert float(seen["out"][0, 0, 0, 0]) == 1 + 2.0 ** -12
    assert hybrid.MIXERS["delta"] is real


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
    assert {"serve_tokens_per_s", "setup_s", "step_mfu_pct.gen"} <= set(line["would_report"])


def test_a_program_without_the_family_is_refused_at_once():
    """A parent commit under this PR's benchmark files: the kind says that
    the program cannot build the configuration, before anything is built."""
    class Cell:
        config_name = "qwen3-next-80b-a3b"
        config = {"model": {"config": "deepspeed_tpu.models.gpt:no_such_family_config"}}
        traffic = {}
    with pytest.raises(cells.BenchmarkError, match="cannot build qwen3-next-80b-a3b"):
        kind.run(Cell(), None, None)
