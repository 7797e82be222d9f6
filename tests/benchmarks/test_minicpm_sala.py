"""Tests of what the benchmark adds for MiniCPM-SALA's long-mixed cell: the
configuration against the catalog's row, its arithmetic held to the arrays
the engine builds, the count of the caches' work by hand, the readers on runs
with nothing to read, the traffic's plan, and the kind's two limits on the
readings of PERF.md § 6; CPU only."""

import json
import types

import numpy as np
import pytest

from benchmarks.kinds import serve_backlog_resident as resident
from benchmarks.kinds import serve_backlog_resident_hybrid as kind
from benchmarks.lib import arith_sala, cells
from benchmarks.readers import sala

CELL = "minicpm-sala-9b.serve-long-mixed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
S, L = "minicpm4", "lightning-attn"
PUBLISHED = [S] + [L] * 8 + [S] + [L] * 6 + [S, S] + [L] * 4 + [S] + [L] * 6 + [S] * 3
NEW = ("attn_sparse_share_pct.gen", "sparse_select_share_pct.gen",
       "attn_linear_share_pct.gen", "mlp_share_pct.gen", "sparse_keys_read_pct.gen",
       "paged_sparse_attention_share_pct.gen", "paged_sparse_attention_roofline")


def test_the_configuration_is_the_catalogs_but_for_depth_and_the_layers_kept():
    cfg = cells.Cell(CELL).config
    try:        # the catalog beside the guide, where it is installed
        rows = [json.loads(l) for l in open(CATALOG)]
        source = next(r for r in rows if r["name"] == "MiniCPM-SALA")
        assert source["config"]["mixer_types"] == PUBLISHED
        assert cfg["source"] == source["source_url"]
        differs = sorted(k for k, v in source["config"].items()
                         if cfg.get(k, "missing") != v)
        assert differs == sorted(cfg["reduced"])
    except FileNotFoundError:
        pass
    assert cfg["reduced"] == ["num_hidden_layers", "mixer_types"]
    # the published layers 9..24: 4 sparse and 12 linear, the published 1 : 3
    assert len(PUBLISHED) == 32 and PUBLISHED.count(S) == 8
    assert cfg["mixer_types"] == PUBLISHED[9:25] == (
        [S] + [L] * 6 + [S, S] + [L] * 4 + [S] + [L] * 2)
    assert cfg["num_hidden_layers"] == 16
    kw, ref = cfg["model"]["kwargs"], cfg["reference"]["kwargs"]
    assert kw["mixer_types"] == ref["mixer_types"] == cfg["mixer_types"]
    assert (kw["first_layer"], kw["published_layers"]) == (9, 32) == (
        ref["first_layer"], ref["published_layers"])
    assert (kw["n_embd"], kw["n_head"], kw["n_kv_head"], kw["head_dim"],
            kw["intermediate_size"], kw["vocab_size"], kw["n_positions"]) == (
                cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["head_dim"], cfg["intermediate_size"],
                cfg["vocab_size"], cfg["max_position_embeddings"]) == (
                    4096, 32, 2, 128, 16384, 73448, 524288)
    assert (kw["scale_emb"], kw["scale_depth"], kw["dim_model_base"]) == (
        cfg["scale_emb"], cfg["scale_depth"], cfg["dim_model_base"]) == (12, 1.4, 256)
    assert (cfg["lightning_nh"], cfg["lightning_nkv"], cfg["lightning_head_dim"]) == (
        32, 32, 128)
    # every size the source does not give is under ``assumed``
    assert {"sparse_config", "selection_softmax", "selection_ties", "decay",
            "output_norm", "gates", "mup_denominator"} <= set(cfg["assumed"])
    assert arith_sala.SPARSE == {"kernel": 32, "stride": 16, "block": 64, "topk": 64,
                                 "init_blocks": 1, "window": 2048, "dense_len": 8192}


def test_the_program_builds_the_published_layers_from_the_file():
    from benchmarks.lib.build import model_from
    from deepspeed_tpu.models import hybrid
    cfg = cells.Cell(CELL).config
    model = model_from(cfg)
    mcfg = model.cfg
    assert mcfg.mixers == tuple({S: "sparse", L: "linear"}[m] for m in cfg["mixer_types"])
    assert [k.depth for k in mcfg.pattern] == list(range(9, 25))
    assert tuple(mcfg.sparse) == tuple(arith_sala.SPARSE.values())
    assert mcfg.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert (mcfg.scale_emb, mcfg.head_divisor) == (12.0, 16.0)
    # what the harness and the resident kind read of a model's configuration
    assert (mcfg.n_layer, mcfg.kv_heads, mcfg.head_dim, mcfg.n_head) == (16, 2, 128, 32)
    assert all(k.window is None for k in mcfg.pattern)
    # the decay of the first linear layer kept, the published layer 10
    s = hybrid.linear_decay(mcfg)
    assert s.shape == (12, 32)
    assert s[0, 0] == pytest.approx(2 ** (-8 / 32) * (1 - 10 / 31 + 1e-5))
    # the parameters, to the arrays ``init_params`` builds (its ``lnf_b``
    # is a zero the source does not have)
    import jax
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) - 4096
    sparse, linear = 253_763_840, 285_225_216    # with 2 x 4,096 + 2 x 128 (+ 4,096) of norms
    assert held == model.num_params() == 4 * sparse + 12 * linear + 2 * 73_472 * 4096 + 4096
    assert held == 5_039_644_672 and "5,039.6 M parameters = 10.08 GB" in cfg["reduced_why"]
    w = arith_sala.sala_weights(cfg["model"]["kwargs"])
    assert w["dense"] + w["gathered"] == held and w["bank"] is None


def test_the_arena_the_compressed_keys_and_the_states_are_the_engines():
    """``serve.arena_bytes`` is the number ``lib/serving.py``'s divisor (K and
    V of 16 layers) turns into 11,264 blocks; what is really held is beside
    it, the program's own count, and all of it is held to the engine's arrays
    at the rehearse size."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from benchmarks.lib.build import model_from
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.serving.kv_cache import arena_bytes
    cfg = cells.Cell(CELL).config
    serve, mcfg = cfg["serve"], model_from(cfg).cfg
    per_block = 2 * mcfg.n_layer * 64 * mcfg.kv_heads * mcfg.head_dim * 2
    assert per_block == 1_048_576 and serve["arena_bytes"] == 11_264 * per_block
    assert serve["arena_bytes_really_held"] == arena_bytes(mcfg, 11_264, 64) == (
        720_896 * 4 * 2 * 256 * 2) == 2_952_790_016
    assert (serve["compressed_key_bytes"], serve["state_bytes"]) == hybrid.aux_bytes(
        mcfg, 11_264, 16) == (92_274_688, 402_653_184)
    assert serve["serving"] == {"max_batch_size": 16, "prefill_chunk": 512,
                                "block_size": 64, "max_blocks_per_seq": 768,
                                "dtype": "bfloat16"}
    assert 768 * 64 >= 40_960 + 5_120
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
        assert eng._k_pages.shape == eng._v_pages.shape == (3, 200 * 2, 16, 16)
        assert eng._k_pages.nbytes + eng._v_pages.nbytes == arena_bytes(model.cfg, 200, 16)
        assert (eng._aux["kc"].nbytes, eng._aux["state"].nbytes) == hybrid.aux_bytes(
            model.cfg, 200, 4)
        assert eng._aux["kc"].dtype == jnp.bfloat16 and eng._aux["state"].dtype == jnp.float32
        assert eng.alloc.num_blocks == 200 and eng.alloc.widths == (16,)
    finally:
        eng.close()


# ---- the caches' work, by hand ------------------------------------------------------ #
def test_pages_keys_and_compressed_keys_a_row():
    t = np.asarray([0, 63, 8191, 8192, 34_700, 40_959])
    assert arith_sala.pages_attended(t).tolist() == [1, 1, 128, 64, 64, 64]
    assert arith_sala.keys_attended(t).tolist() == [
        1, 64, 8192, 63 * 64 + 1, 63 * 64 + 34_700 % 64 + 1, 4096]
    # compressed keys that end at or before t: none is scored under dense_len
    assert arith_sala.compressed_keys_read(t).tolist() == [
        0, 0, 0, 8193 // 16 - 1, 34_701 // 16 - 1, 2559]


def test_a_rows_bytes_by_hand():
    """One decode row at 34,700 keys, four sparse layers: 64 pages of 64
    keys of K and of V for each of 2 K/V heads, the query and the output of
    32 heads (an idle row costs nothing); 2,167 compressed keys a head.
    Twelve linear layers move a state of 32 x 128 x 128 float32 in and out."""
    flops, nbytes, compressed = arith_sala.sparse_rows([34_700], [], 4, 32, 2, 128)
    keys = 64 * 64
    assert nbytes == 4 * (2 * keys * 256 * 2 + 2 * 1 * 4096 * 2)
    assert flops == 4 * 2 * 2 * keys * 32 * 128
    assert compressed == 4 * 2167 * 256 * 2
    assert nbytes / 4 == pytest.approx(4.2e6, rel=0.01)       # 4 MB a row a layer
    # a chunk of 512 rows past ``dense_len``: each chooses 64 pages (the
    # operations), and the bytes are the sequence's pages up to its end ONCE:
    # what one masked pass over the chunk's context reads
    cf, cb, cc = arith_sala.sparse_rows([], [(34_304, 512)], 4, 32, 2, 128)
    assert cf == 512 * flops
    assert cb == 4 * (2 * 544 * 64 * 256 * 2 + 2 * 512 * 4096 * 2)
    assert cc == 4 * (34_816 // 16 - 1) * 256 * 2            # the LAST row's compressed keys
    # a chunk inside ``dense_len``: its rows' pages are the span's too
    _, db, dc = arith_sala.sparse_rows([], [(1024, 512)], 4, 32, 2, 128)
    assert db == 4 * (2 * 24 * 64 * 256 * 2 + 2 * 512 * 4096 * 2) and dc == 0
    lin_flops, lin_bytes = arith_sala.linear_rows(1, 1, 12, 32, 128)
    assert lin_bytes == 12 * 2 * 32 * 128 * 128 * 4 == 50_331_648
    assert lin_flops == 12 * 4 * 32 * 128 * 128


def _served(prompt_tokens, resident, generated):
    return types.SimpleNamespace(request=types.SimpleNamespace(
        rid=id(prompt_tokens), prompt=[0] * prompt_tokens, prefilled=resident,
        generated=[0] * generated))


def test_attention_counters_take_a_decode_row_at_its_position_and_a_chunk_once():
    """Over a stretch of 3 steps: one request decodes 3 tokens from 30,000
    keys, another runs two chunks of its prompt from 1,024; 16 + 512 rows a
    program."""
    cfg = cells.Cell(CELL).config
    srv = types.SimpleNamespace(
        model=types.SimpleNamespace(cfg=types.SimpleNamespace(n_head=32, kv_heads=2,
                                                              head_dim=128)),
        cell=types.SimpleNamespace(config=cfg), slots=16, chunk=512,
        params={"wte": np.zeros(1, np.float16)})
    snaps = {"before": {1: (20_000, 30_000, 10_000), 2: (30_000, 1024, 0)},
             "after": {1: (20_000, 30_003, 10_003), 2: (30_000, 2048, 0)}}
    steps = [(0, 0, 1, 512, 0, 0, 0), (0, 0, 1, 512, 0, 0, 0), (0, 0, 1, 0, 0, 0, 0)]
    c = kind.attention_counters(srv, snaps, steps)
    decode, prompt = np.arange(30_000, 30_003), np.arange(1024, 2048)
    assert c["attention_rows_live"] == 1027 and c["attention_rows_idle"] == 3 * 528 - 1027
    assert c["traced_step_rows"] == [513, 513, 1]
    assert c["sparse_keys_resident"] == 8 * int((decode + 1).sum() + (prompt + 1).sum())
    assert c["sparse_keys_attended"] == 8 * int(
        arith_sala.keys_attended(decode).sum() + (prompt + 1).sum())
    assert c["attention_chunks"] == 2
    # the decode rows' chosen pages, the two chunks' spans (pages 0..23 and
    # 0..31) once each; the idle rows nothing
    pages = 3 * 64 + 24 + 32
    assert c["paged_sparse_bytes"] == 4 * (2 * pages * 64 * 256 * 2 + 2 * 1027 * 4096 * 2)
    assert c["paged_sparse_flops"] == 4 * 4 * (3 * 64 + int((prompt // 64 + 1).sum())) * 64 * 4096
    # the states moved once a decode row and once a chunk
    assert c["state_bytes_moved"] == 12 * 2 * (3 + 2) * 32 * 128 * 128 * 4
    compressed = 4 * int(arith_sala.compressed_keys_read(decode).sum()) * 256 * 2
    assert c["paged_gqa_bytes"] == c["paged_sparse_bytes"] + compressed + c["state_bytes_moved"]
    assert c["paged_gqa_flops"] > c["paged_sparse_flops"]
    assert sala.keys_read_pct({"counters": c}) == pytest.approx(
        100.0 * c["sparse_keys_attended"] / c["sparse_keys_resident"])


class FakeTrace:
    def __init__(self, seconds):
        self.seconds = seconds

    def op_seconds(self):
        return self.seconds


def test_the_roofline_is_the_least_time_over_the_kernels_time():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = {"trace": FakeTrace({"paged_sparse_attention": 0.010}), "notes": {},
           "peaks": peaks, "counters": {"paged_sparse_flops": 1e9,
                                        "paged_sparse_bytes": 819e6}}
    assert sala.roofline(run) == pytest.approx(10.0)
    assert run["notes"]["roofline_bound"]["paged_sparse_attention"] == "memory"


def test_readers_give_none_where_there_is_nothing_to_read():
    counted = {"paged_sparse_flops": 1.0, "paged_sparse_bytes": 1.0}
    assert sala.roofline({"trace": None, "counters": counted}) is None
    assert sala.roofline({"trace": FakeTrace({}), "counters": counted}) is None     # a parent
    assert sala.roofline({"trace": FakeTrace({"paged_sparse_attention": 1.0}),
                          "counters": {}}) is None
    assert sala.keys_read_pct({"counters": {}}) is None
    assert sala.keys_read_pct({"counters": {"sparse_keys_resident": 0}}) is None


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
    # overlay's three went with PR 68: their sum is ``device_idle_pct.gen``)
    assert {"compiles_in_window.gen", "serve_step_ms.gen", "decode_batch_mean.gen",
            "kv_blocks_peak_pct.gen", "preemptions.gen", "device_idle_pct.gen",
            "sched_host_ms.gen", "table_build_ms.gen", "host_turnaround_ms.gen",
            "step_outside_ms.gen", "idle_wire_ms.gen", "step_mfu_pct.gen"} <= set(listed)
    assert not [n for n in listed if n.startswith("idle_") and n.endswith("_pct.gen")]
    # the other families' kernels are no part of this cell
    assert not {"paged_gqa_attention_roofline", "paged_attention_roofline",
                "paged_mla_attention_roofline", "moe_experts_roofline"} & set(listed)
    assert cell.config["step_work"]["weights"] == "benchmarks.lib.arith_sala:sala_weights"


def test_the_scopes_and_the_kernel_the_metrics_name_are_the_programs():
    import inspect
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.ops.pallas import decode_attention
    cell = cells.Cell(CELL)
    source = inspect.getsource(hybrid)
    for name in NEW[:4]:
        (scope,) = cell.reader(name)[1]["scopes"]
        assert f'jax.named_scope("{scope}")' in source
    assert cell.reader(NEW[5])[1]["ops"] == [sala.KERNEL]
    assert f'name="{sala.KERNEL}"' in inspect.getsource(decode_attention)


def test_the_traffic_is_a_file_of_the_resident_kind_under_its_own_limits():
    mix = cells.Cell(CELL).traffic
    assert mix["kind"] == "serve-backlog-resident-hybrid"
    assert kind.END_TO_END == resident.END_TO_END
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 24576, "max": 40960}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 1024, "max": 5120}
    assert (mix["backlog_requests"], mix["check_requests"]) == (48, 4)
    cohort, backlog, planned = resident.plan(mix, 16, 512, 524_288, 73_448, 5)
    # about 34,700 keys a slot when the window opens (prompt + age), 555,000 in
    # all: 77% of the 720,896 the arena holds
    at_its_age = [p + a for p, a, _ in planned]
    assert 33_500 < np.mean(at_its_age) < 36_000 and 0.72 < sum(at_its_age) / 720_896 < 0.82
    assert min(len(p) for p, _ in cohort) > 4 * 8192 * 0.7       # every context selects
    assert max(len(p) + n for p, n in cohort) <= 40_960 + 5_120 + 4
    assert len(backlog) == 48
    assert all(24_576 <= len(p) <= 40_960 for p, _ in backlog)


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


# the readings of PERF.md section 6 (my chip runs, PR 39): requests of the
# cell's runs (the largest gap of 60 first) and ``tools/serve_parity.py`` in
# bf16, and the same model with every matrix through float8_e4m3fn
BF16 = ([0.0141, 0.0059, 0.0080, 0.0056, 0.0067, 0.0055, 0.0075, 0.0041],
        [0.0036, 0.0026, 0.0031, 0.0033, 0.0029, 0.0030, 0.0023, 0.0057])
FLOAT8 = ([0.0428, 0.0662, 0.0650, 0.0666, 0.0580],
          [0.1210, 999.99, 999.99, 999.99, 999.99])


def test_a_sound_bf16_run_is_correct_by_these_limits(monkeypatch):
    out = _judged(monkeypatch, *BF16)
    assert out["notes"]["wrong"] == 0 and out["failed"] == 0 and out["correct"] is True
    assert out["notes"]["tie_tolerance"] == kind.LOGIT_MARGIN == 0.15
    assert out["notes"]["noise_limit"] == kind.NOISE_LIMIT == 0.02
    # room on both sides of each limit
    assert 10 * max(BF16[0]) < kind.LOGIT_MARGIN < 0.34 / 2
    assert 3 * max(BF16[1]) < kind.NOISE_LIMIT < min(FLOAT8[1]) / 5


def test_the_weights_through_float8_are_refused_by_the_noise_limit_alone(monkeypatch):
    out = _judged(monkeypatch, *FLOAT8)
    assert out["notes"]["wrong"] == 5 and out["correct"] is False
    assert max(FLOAT8[0]) < kind.LOGIT_MARGIN              # not by each limit
    assert out["compared"]["largest_logit_gap"] == [0.0666, kind.LOGIT_MARGIN]
    assert out["compared"]["noise_scale_median"] == [999.99, kind.NOISE_LIMIT]
    assert out["compared"]["requests_wrong"] == [5, 0]
    # one noisy request does not fail a run whose median is sound
    scales = list(BF16[1])
    scales[0] = 0.2
    assert _judged(monkeypatch, BF16[0], scales)["correct"] is True


def test_the_gross_limit_refuses_a_token_unrelated_to_the_reference(monkeypatch):
    largest = list(BF16[0])
    largest[3] = 0.34
    out = _judged(monkeypatch, largest, BF16[1])
    assert out["notes"]["wrong"] == 1 and out["failed"] == 1 and out["correct"] is False


def test_what_else_fails_a_run_still_fails_it(monkeypatch):
    assert _judged(monkeypatch, *BF16, failed_besides=2)["failed"] == 2
    assert _judged(monkeypatch, *BF16, failed_besides=2)["correct"] is False
    assert _judged(monkeypatch, *BF16, backlog_ran_dry=True)["correct"] is False
    assert _judged(monkeypatch, *BF16, cohort_filled=False)["correct"] is False
