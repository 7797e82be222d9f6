"""Tests of what the benchmark adds for SmallThinker's long-context cell: the
configuration against the catalog's row, the window-aware count of the
attention kernel's bytes by hand, the resident first cohort, and the new
reader on runs with nothing to read; CPU only."""

import json
import types

import numpy as np
import pytest

from benchmarks.kinds import serve_backlog_resident as kind
from benchmarks.lib import arith_window, cells, draws
from benchmarks.readers import paged_gqa

CELL = "smallthinker-21b-a3b.serve-long-context"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_configuration_is_the_catalogs_but_for_depth_and_the_two_lists():
    source = {"head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
              "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
              "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
              "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
              "num_attention_heads": 28, "num_hidden_layers": 52,
              "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
              "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
              "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
              "sliding_window_size": 4096, "tie_word_embeddings": False,
              "vocab_size": 151936}
    try:        # the catalog beside the guide, where it is installed
        rows = [json.loads(l) for l in open(CATALOG)]
        assert next(r for r in rows if r["name"] ==
                    "SmallThinker-21BA3B-Instruct")["config"] == source
    except FileNotFoundError:
        pass
    cfg = cells.Cell(CELL).config
    differs = sorted(k for k, v in source.items() if cfg.get(k, "missing") != v)
    assert differs == sorted(cfg["reduced"]) == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout"]
    # two whole periods, the lists cut to their first 8 entries
    assert cfg["num_hidden_layers"] == 8
    assert cfg["rope_layout"] == source["rope_layout"][:8]
    assert cfg["sliding_window_layout"] == source["sliding_window_layout"][:8]
    kw = cfg["model"]["kwargs"]
    assert (kw["n_embd"], kw["n_head"], kw["n_kv_head"], kw["head_dim"],
            kw["intermediate_size"], kw["num_experts"], kw["top_k"], kw["window"],
            kw["n_layer"], kw["n_positions"], kw["vocab_size"]) == (
                2560, 28, 4, 128, 768, 64, 6, 4096, 8, 16384, 151936)
    ref = cfg["reference"]["kwargs"]
    assert ref["rope_layout"] == cfg["rope_layout"]
    assert ref["sliding_window_layout"] == cfg["sliding_window_layout"]
    # the arena: K and V x 8 layers x 512 lanes x 2 B a token, 14,336 blocks of 16
    assert cfg["serve"]["arena_bytes"] == 14336 * 16 * 2 * 8 * 512 * 2 == 3_758_096_384
    assert cfg["serve"]["serving"] == {"max_batch_size": 32, "prefill_chunk": 224,
                                       "dtype": "bfloat16"}
    # 32 + 224 rows x 6 assignments: whole 128-row tiles of the bank's kernel
    assert (32 + 224) * 6 % 128 == 0


def test_the_repeated_moe_keys_equal_the_sources():
    """``readers/moe.py:bank_least_seconds`` reads OLMoE's spellings at the
    top level; the file carries them beside the source's own."""
    cfg = cells.Cell(CELL).config
    assert cfg["num_experts"] == cfg["moe_num_primary_experts"] == 64
    assert cfg["num_experts_per_tok"] == cfg["moe_num_active_primary_experts"] == 6
    assert cfg["intermediate_size"] == cfg["moe_ffn_hidden_size"] == 768
    assert "num_experts" in cfg["repeats_the_sources_keys"]


def test_the_program_builds_the_published_model_from_the_file():
    from benchmarks.lib.build import model_from
    from deepspeed_tpu.models.gpt import LayerKind
    mcfg = model_from(cells.Cell(CELL).config).cfg
    assert mcfg.pattern == (LayerKind(None, False),) + 3 * (LayerKind(4096, True),)
    assert (mcfg.kv_heads * mcfg.head_dim, mcfg.attn_dim, mcfg.n_layer) == (512, 3584, 8)


# ---- the window-aware count, by hand -------------------------------------------- #
def test_pages_a_row_can_see():
    # blocks of 16: a query at 100 sees keys 0..100 in 7 pages; under a
    # window of 32 the keys 69..100, which lie in pages 4, 5 and 6
    assert arith_window.pages_seen([100], 16)[0] == 7
    assert arith_window.pages_seen([100], 16, window=32)[0] == 3
    # inside the window nothing is cut; at 32 the oldest key seen is 1, still
    # in page 0; at 47 it is 16, the first of page 1: two pages hold the 32
    assert list(arith_window.pages_seen([0, 15, 16, 31, 32, 47, 48], 16, 32)) == [
        1, 1, 2, 2, 3, 2, 3]


def test_attention_bytes_of_smallthinkers_row_by_hand():
    """One decode row at 7,900 resident tokens, SmallThinker's shapes: K and
    V pages of 16 x 512 lanes in bf16, q and o of 28 x 128."""
    qo = 2 * 28 * 128 * 2
    page = 16 * 512 * 2
    f_full, b_full = arith_window.rows([7900], 16, 512, 28, 128)
    assert b_full == 2 * 494 * page + qo                 # 7,901 keys: 494 pages
    assert f_full == 4 * 494 * 16 * 28 * 128
    _, b_win = arith_window.rows([7900], 16, 512, 28, 128, window=4096)
    # keys 3,805..7,900: pages 237..493
    assert b_win == 2 * 257 * page + qo
    # the 8-layer cut: 2 full and 6 window layers; an idle row costs nothing
    flops, nbytes = arith_window.attention(np.asarray([7900]), [], {None: 2, 4096: 6},
                                           16, 512, 28, 128)
    assert nbytes == 2 * b_full + 6 * b_win
    # about the 83 MB the cell's arithmetic gives a row (2 x 7,900 + 6 x 4,112 keys)
    assert 2 * b_full + 6 * b_win == pytest.approx(83e6, rel=0.02)
    assert flops > 0


def test_attention_counters_take_a_decode_row_at_its_position_and_a_chunk_once():
    """Between two snapshots: a request 30 tokens into a prompt of 100 runs
    24 more (a chunk of 24), another decodes 3 steps from 50 resident; 2
    programs of 4 + 24 rows."""
    from deepspeed_tpu.models.gpt import LayerKind
    cfg = types.SimpleNamespace(pattern=(LayerKind(None, False), LayerKind(16, True)),
                                n_layer=4, n_head=4, head_dim=8)
    srv = types.SimpleNamespace(model=types.SimpleNamespace(cfg=cfg), slots=4, chunk=24,
                                block=4, lanes=16,
                                params={"wte": np.zeros(1, np.float16)})
    snaps = {"before": {1: (100, 30, 0), 2: (40, 50, 11)},
             "after": {1: (100, 54, 0), 2: (40, 53, 14)}}
    steps = [(0, 0, 1, 24, 0, 0, 0), (0, 0, 1, 0, 0, 0, 0)]
    c = kind.attention_counters(srv, snaps, steps)
    rows = list(range(30, 54)) + [50, 51, 52]
    assert c["attention_rows_live"] == 27 and c["attention_rows_idle"] == 2 * 28 - 27
    assert (c["attention_chunks"], c["chunk_queries_per_row"]) == (1, 0)
    want = arith_window.attention(np.asarray([50, 51, 52]), [(30, 24)], {None: 2, 16: 2},
                                  4, 16, 4, 8, itemsize=2)
    assert (c["paged_gqa_flops"], c["paged_gqa_bytes"]) == want
    # by hand.  OPERATIONS: every query over the pages it sees, a row at t
    # t // 4 + 1 pages of 4 keys in a full layer
    full_pages = sum(t // 4 + 1 for t in rows)
    win_pages = sum(t // 4 + 1 - max(t - 15, 0) // 4 for t in rows)
    assert want[0] == 2 * (full_pages + win_pages) * 4 * 4 * 4 * 8
    # BYTES: the decode rows' pages, and the chunk's span ONCE: pages 0..13 in
    # a full layer, 3..13 under the window (the first query, at 30, sees from
    # key 15); the idle rows nothing; q and o of the 27 live rows a layer
    read_full = sum(t // 4 + 1 for t in (50, 51, 52)) + 14
    read_win = sum(t // 4 + 1 - max(t - 15, 0) // 4 for t in (50, 51, 52)) + 11
    assert want[1] == (2 * (read_full + read_win) * 2 * 4 * 16 * 2
                       + 4 * 27 * 2 * 4 * 8 * 2)
    assert c["attention_keys_read"] == 2 * (read_full + read_win) * 4
    assert c["attention_key_products"] == 2 * (full_pages + win_pages) * 4


# ---- the resident first cohort --------------------------------------------------- #
MIX = cells.load_json(cells.os.path.join(cells.ROOT, "benchmarks/traffic/long-context.json"))


def test_the_van_der_corput_orders_cover_the_range_evenly():
    assert kind.radical_inverse(8).tolist() == [0, .5, .25, .75, .125, .625, .375, .875]
    assert kind.radical_inverse(4, 3) == pytest.approx([0, 1 / 3, 2 / 3, 1 / 9])
    dealt = kind.dealt(np.arange(32), 2)
    assert sorted(dealt) == list(range(32))
    # every aligned run of 4 holds one item of each quarter of the range
    assert all(sorted(dealt[i:i + 4] // 8) == [0, 1, 2, 3] for i in range(0, 32, 4))


def test_the_first_cohort_is_resident_at_its_age():
    cohort, backlog, planned = kind.plan(MIX, 32, 224, 16384, 151936, seed=2 ** 31 + 5)
    again, backlog2, planned2 = kind.plan(MIX, 32, 224, 16384, 151936, seed=7)
    assert len(cohort) == 32 and len(backlog) == MIX["backlog_requests"] == 96
    # ONE plan whatever the seed (every length and order constructed); other ids
    assert planned == planned2
    assert [(len(p), n) for p, n in cohort + backlog] == [
        (len(p), n) for p, n in again + backlog2]
    assert any((p != q).any() for (p, _), (q, _) in zip(cohort, again))
    assert sorted(p for p, _, _ in planned) == sorted(draws.quantiles(MIX["prompt_tokens"], 32))
    ahead = 2
    for (ids, new), (prompt, age, whole) in reversed(list(zip(cohort, planned))):
        assert 0 <= age < whole and 1024 <= whole <= 5120
        # prefilled short of prompt + age by what it generates during the fill
        assert len(ids) == prompt + age - min(age, ahead)
        assert new == whole - age + ahead and len(ids) + new <= 16384
        ahead += -(-len(ids) // 224)
    # what remains of the members is the outputs' residual life: while the
    # window is shorter than the shortest output, one member finishes every
    # mean output / slots = 3,072 / 32 = 96 steps, the first after 48
    remaining = sorted(whole - age for _, age, whole in planned)
    assert remaining[:10] == [48 + 96 * k for k in range(10)]
    assert remaining == sorted(draws.residual_quantiles(MIX["output_tokens"], 32))
    # a request met at a random moment is a long one (E[L^2] / E[L] = 3,527
    # for 1,024..5,120) half done: the window opens on about 7,900 tokens a slot
    assert np.mean([w for _, _, w in planned]) == pytest.approx(3527, abs=100)
    assert np.mean([p + a for p, a, _ in planned]) == pytest.approx(7900, abs=150)
    # prompt, rest and whole go across the members independently
    P, A, W = (np.asarray(x, float) for x in zip(*planned))
    assert abs(np.corrcoef(P, W - A)[0, 1]) < 0.15 and abs(np.corrcoef(P, W)[0, 1]) < 0.15


def test_each_two_requests_that_finish_bring_two_mean_prompts():
    _, backlog, _ = kind.plan(MIX, 32, 224, 16384, 151936, seed=3)
    prompts = [len(p) for p, _ in backlog]
    assert sorted(prompts) == sorted(draws.quantiles(MIX["prompt_tokens"], 96))
    assert all(4096 <= p <= 8192 for p in prompts)
    assert {a + b for a, b in zip(prompts[::2], prompts[1::2])} == {2 * 6144}
    # so a step carries a chunk as often as in a long run: 6,144 / 224 chunk
    # steps a request that finishes, one every 96 steps
    chunks = [-(-p // 224) for p in prompts]
    assert np.mean(chunks[:8]) / 96 == pytest.approx(6144 / 224 / 96, rel=0.03)
    assert sorted(n for _, n in backlog) == sorted(
        draws.quantiles(MIX["output_tokens"], 96))


# ---- the reader where there is nothing to read ----------------------------------- #
def test_the_roofline_reader_returns_none_without_its_kernel_or_its_count():
    assert paged_gqa.roofline({"trace": None, "counters": {}, "notes": {}}) is None
    trace = types.SimpleNamespace(op_seconds=lambda: {"paged_attention": 1.0})
    run = {"trace": trace, "counters": {"paged_gqa_bytes": 8.19e9, "paged_gqa_flops": 1.0},
           "notes": {}, "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert paged_gqa.roofline(run) is None and run["notes"] == {}     # a parent's program
    assert paged_gqa.roofline(dict(run, counters={})) is None
    trace.op_seconds = lambda: {"paged_gqa_attention": 0.05}
    assert paged_gqa.roofline(run) == pytest.approx(100 * 0.01 / 0.05)
    assert run["notes"]["roofline_bound"] == {"paged_gqa_attention": "memory"}


def test_an_op_family_is_summed_over_the_compilers_suffixes():
    from benchmarks.readers import op_family
    assert op_family.share_pct({"trace": None}, ["grouped_matmul"]) is None
    secs = {"dynamic-slice_bitcast_fusion": 0.27, "dynamic-slice_bitcast_fusion.12.remat3": 0.53,
            "dynamic-slice_bitcast_fusion.13.remat3": 0.26, "dynamic-slice_fusion": 0.2,
            "grouped_matmul": 0.49, "paged_gqa_attention": 0.68}
    trace = types.SimpleNamespace(op_seconds=lambda: secs, busy_s=lambda: 2.45)
    run = {"trace": trace}
    assert op_family.share_pct(run, ["dynamic-slice_bitcast_fusion"]) == pytest.approx(
        100 * 1.06 / 2.45)
    assert op_family.share_pct(run, ["grouped_matmul"]) == pytest.approx(100 * 0.49 / 2.45)
    assert op_family.share_pct(run, ["paged_attention"]) == 0.0       # a program without the op


def test_the_new_metrics_list_the_cell():
    cell = cells.Cell(CELL)
    listed = {m["name"]: m for m in cell.per_layer}
    for name in ("attn_full_share_pct.gen", "attn_window_share_pct.gen",
                 "paged_gqa_attention_share_pct.gen", "paged_gqa_attention_roofline",
                 "kv_window_freed_pct.gen", "moe_bank_copy_share_pct.gen",
                 "grouped_matmul_share_pct.gen"):
        fn, args = cell.reader(name)
        assert callable(fn) and isinstance(args, dict)
        assert CELL in listed[name]["workloads"] and listed[name]["unit"] == "%"
    assert cell.chips == 1 and cell.kind is kind
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s", "setup_s"]


# ---- the comparison that decides ``correct``, on planted faults ------------------- #
def toy_hidden(params, ids, **_):
    """A reference whose logits are a table a position (``hidden`` IS the
    logits): the check's arithmetic without a model."""
    return params["table"][:ids.shape[0]]


def toy_head(params, hidden, **_):
    return hidden


TOY = {"hidden": "tests.benchmarks.test_smallthinker:toy_hidden",
       "head": "tests.benchmarks.test_smallthinker:toy_head", "kwargs": {"q_block": 256}}


def toy_samples(noise, n_samples=8, seed=0, prompt=40, new=600, vocab=64):
    """Reference logits as the chip showed them (PERF.md § 6, PR 31: the best
    at about 4.5, the second 0-0.3 below it, the rest far off) and what a
    model whose logits differ from them by Gaussian noise of scale ``noise[i]``
    (best against second) would serve."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    table = rng.uniform(0.0, 3.0, (768, vocab))
    best = rng.integers(0, vocab, 768)
    second = (best + 1 + rng.integers(0, vocab - 1, 768)) % vocab
    table[np.arange(768), best] = 4.5
    table[np.arange(768), second] = 4.5 - rng.uniform(0.0, 0.3, 768)
    samples = []
    for i in range(n_samples):
        noisy = table + rng.normal(0.0, noise[i] / np.sqrt(2.0), table.shape)
        samples.append((list(range(prompt)),
                        noisy[prompt - 1:prompt + new - 1].argmax(-1).tolist()))
    return {"table": jnp.asarray(table, jnp.float32)}, samples


def test_noise_scale_recovers_the_scale_that_flipped_the_tokens():
    rng = np.random.default_rng(5)
    margins = rng.uniform(0.0, 0.3, 4000)
    for scale in (0.02, 0.15):
        flips = (rng.normal(0.0, scale, 4000) > margins).sum()
        assert kind.noise_scale(margins, flips) == pytest.approx(scale, rel=0.2)
    assert kind.noise_scale(margins, 0) == 0.0
    # a request stuck on one word, its best logit far ahead: nothing can flip
    assert kind.noise_scale(np.full(1000, 0.9), 0) == 0.0


@pytest.mark.parametrize("noise, wrong", [
    ([0.025] * 8, 0),                         # served bf16 on the chip: 0.012-0.037
    ([0.025] * 7 + [0.11], 0),                # with the one request in thirty that read 0.109
    ([0.16] * 8, 8),                          # the bank through float8_e4m3: median 0.16
    ([0.035, 0.059, 0.0, 0.146, 0.178, 0.264, 0.391, 0.779], 5),   # as its 8 requests read:
    #   two under the limit, one stuck on a word, 0.059 on either side of 0.06
], ids=["bf16", "bf16-one-outlier", "float8-bank", "float8-bank-as-read"])
def test_the_check_refuses_a_float8_bank_and_passes_bf16(noise, wrong):
    params, samples = toy_samples(noise)
    check = kind.check_sample(None, params, TOY, samples)
    assert check["checked"] == 8 and check["wrong"] in (wrong, wrong + (0.059 in noise))
    assert (check["noise_scale_median"] > kind.NOISE_LIMIT) == (wrong > 0)
    if max(noise) < 0.2:   # no token off by more than the second's 0.3: the gross limit sees nothing
        assert max(check["largest"]) < 0.5 < kind.LOGIT_MARGIN


def test_the_check_refuses_a_token_far_from_the_references_best():
    params, samples = toy_samples([0.0] * 2, n_samples=2)
    prompt, generated = samples[0]
    worst = int(np.asarray(params["table"][len(prompt) + 9]).argmin())
    samples[0] = (prompt, generated[:10] + [worst] + generated[11:])
    check = kind.check_sample(None, params, TOY, samples)
    assert check["wrong"] == 1 and check["largest"][0] > kind.LOGIT_MARGIN
    assert check["noise_scale_median"] < kind.NOISE_LIMIT
