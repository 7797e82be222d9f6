"""Tests of what the benchmark adds for a mixture-of-experts model: the
bank's arithmetic by hand, the readers of ``benchmarks/readers/moe.py`` on a
synthetic trace with answers worked out on paper, and the new files against
``BENCHMARK.json``; CPU only."""

import os
import types

import pytest

from benchmarks.lib import arith_moe, cells
from benchmarks.lib.trace import Trace
from benchmarks.readers import moe
from benchmarks.readers import program_spans as ps

ROOT = cells.ROOT
BENCH = cells.load_benchmark()
CELL = "olmoe-1b-7b.serve-decode-heavy"
US = 1e-6
NEW = {"moe_share_pct.gen": 100 * 10 / 12, "moe_router_share_pct.gen": 100 * 1 / 12,
       "moe_dispatch_share_pct.gen": 100 * 2 / 12,
       "moe_experts_share_pct.gen": 100 * 7 / 12, "attn_share_pct.gen": 100 * 2 / 12}


# ---- the bank's arithmetic ---------------------------------------------------- #
def test_olmoe_bank_by_hand():
    """One layer of OLMoE-1B-7B: 64 experts of 3 x 2048 x 1024."""
    assert arith_moe.expert_params(2048, 1024) == 6_291_456
    assert 64 * arith_moe.expert_params(2048, 1024) * 2 == 805_306_368       # 805 MB
    assert arith_moe.experts_reached(128, 64, 8) == pytest.approx(64.0, abs=1e-5)
    assert arith_moe.experts_reached(16, 64, 8) == pytest.approx(64 * (1 - 0.875 ** 16))
    assert arith_moe.experts_reached(1, 64, 8) == pytest.approx(8.0)
    flops, nbytes = arith_moe.expert_bank_call(128, 64, 8, 2048, 1024)
    assert flops == 2 * 1024 * 6_291_456
    # the bank once, and 1,024 rows of 2048 read and written, in bf16
    assert nbytes == pytest.approx(805_306_368 + 2 * 1024 * 2048 * 2, rel=1e-6)
    # a single live row reads its 8 experts and no more
    _, one = arith_moe.expert_bank_call(1, 64, 8, 2048, 1024)
    assert one == pytest.approx((8 * 6_291_456 + 2 * 8 * 2048) * 2)
    # an ungated (two-matrix) expert
    assert arith_moe.expert_params(2048, 1024, gated=False) == 4_194_304


# ---- the readers on a trace with known answers -------------------------------- #
@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    from jax.profiler import ProfileData
    text = open(os.path.join(ROOT, "benchmarks/testdata/moe.xspace.txt")).read()
    text = "\n".join(l for l in text.splitlines() if not l.startswith("#"))
    path = tmp_path_factory.mktemp("trace") / "moe.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


# a small model, so that the least time can be worked out on paper: 4 experts
# of 3 x 128 x 64, top 2, 2 layers, bf16; a chip of 1e12 B/s and 1e15 FLOP/s
TOY = {"num_experts": 4, "num_experts_per_tok": 2, "hidden_size": 128,
       "intermediate_size": 64, "num_hidden_layers": 2, "dtype": "bfloat16"}
PEAKS = {"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 1e12}


@pytest.fixture()
def run(xplane):
    """A traced run as ``benchmarks/run.py`` hands it to a reader, with the
    stats read from the synthetic file and not from ``.bench_trace``."""
    return {"trace": Trace.from_file(xplane), "counters": {}, "notes": {},
            "peaks": PEAKS, "cell": types.SimpleNamespace(config=TOY),
            "_program_stats": ps.read_stats(xplane),
            "_moe_span_stats": moe.read_span_stats(xplane)}


def _least_us():
    """Three programs, a bank call a layer each: 128, 100 and 16 live rows
    (the fourth was dispatched after the chip's last op and is no call).
    Memory bound: (experts reached x 24,576 + 2 x rows x 2 x 128) x 2 B."""
    per_expert, total = 3 * 128 * 64, 0.0
    for rows in (128, 100, 16):
        reached = 4 * (1 - 0.5 ** rows)
        total += 2 * (reached * per_expert + 2 * rows * 2 * 128) * 2 / 1e12
    return total / US


def test_span_stats_are_read_by_span(run):
    stats = run["_moe_span_stats"]
    assert [s["batch"] for s in stats["serve.decode.dispatch"]] == [128, 100, 77]
    assert [s[moe.START] for s in stats["serve.decode.dispatch"]] == pytest.approx(
        [1 * US, 12 * US, 20 * US])
    assert [s["tokens"] for s in stats["serve.prefill.dispatch"]] == [16]
    assert [s["moe_experts_touched"] for s in stats["serve.decode.commit"]] == [64, 63]


def test_load_is_the_mean_of_the_decode_steps(run):
    assert moe.load_max_over_mean(run) == pytest.approx(1.5)


def test_rooflines_are_least_time_over_scope_and_over_kernel(run):
    # a layer: 327,680 B + 299,008 B + 212,989 B at 1e12 B/s; two layers
    assert _least_us() == pytest.approx(2 * (0.32768 + 0.299008 + 0.212989), rel=1e-5)
    assert moe.experts_roofline(run) == pytest.approx(100 * _least_us() / 7)
    assert moe.grouped_matmul_roofline(run) == pytest.approx(100 * _least_us() / 6)
    assert run["notes"]["moe_bank_calls"] == 3 * 2
    assert run["notes"]["roofline_bound"] == {"moe_experts": "memory",
                                              "grouped_matmul": "memory"}


@pytest.mark.parametrize("name", sorted(NEW))
def test_scope_shares_by_their_metric_files(run, name):
    """The new share metrics need no code: their files name the scopes."""
    fn, args = cells.Cell(CELL).reader(name)
    assert fn is ps.scope_share_pct
    assert fn(run, **args) == pytest.approx(NEW[name])


BANK_CELLS = next(m for m in BENCH["per_layer"]
                  if m["name"] == "grouped_matmul_roofline")["workloads"]


@pytest.mark.parametrize("cell_name", BANK_CELLS)
def test_the_one_bank_roofline_reads_every_cell_whose_program_runs_the_kernel(run, cell_name):
    """``grouped_matmul_roofline`` is ONE entry (PR 68 folded Trinity's copy
    into it): its reader takes the bank from the configuration's
    ``step_work`` function, an EXPERT layer a call at the held share of what
    a bank of the router's width needs for the program's live rows.  By hand
    over the synthetic trace's three programs (128, 100 and 16 live rows) and
    the kernel's 6 us; OLMoE's whole bank in every layer reads what
    ``readers/moe.py`` read of it to the digit."""
    import jax.numpy as jnp
    from benchmarks.lib import arith
    from benchmarks.readers import afmoe
    cell = cells.Cell(cell_name)
    fn, args = cell.reader("grouped_matmul_roofline")
    assert fn is afmoe.grouped_matmul_roofline and args == {}
    bank = cells.resolve(cell.config["step_work"]["weights"])(
        cell.config["model"]["kwargs"])["bank"]
    least = 0.0
    for rows in (128, 100, 16):
        flops, nbytes = arith_moe.expert_bank_call(
            rows, bank["experts"], bank["top_k"], bank["hidden"], bank["width"],
            itemsize=jnp.dtype(cell.config["dtype"]).itemsize)
        least += bank["layers"] * arith.roofline_seconds(flops, nbytes, PEAKS)[0]
    least *= bank["held"] / bank["experts"]
    mine = dict(run, cell=cell, notes={})
    assert fn(mine) == pytest.approx(100 * least / (6 * US))
    assert mine["notes"]["moe_bank_calls"] == 3 * bank["layers"]
    if cell_name == CELL:
        assert bank["held"] == bank["experts"] == cell.config["num_experts"]
        assert fn(mine) == moe.grouped_matmul_roofline(dict(run, cell=cell, notes={}))


READERS = [moe.load_max_over_mean, moe.experts_roofline, moe.grouped_matmul_roofline]


@pytest.mark.parametrize("reader", READERS)
def test_an_untraced_run_gives_none(reader):
    assert reader({"trace": None, "notes": {}, "counters": {}}) is None


@pytest.mark.parametrize("reader", READERS)
def test_a_dense_model_or_a_parent_gives_none(reader, xplane):
    """Device ops without the scope or the kernel, spans without the stats:
    nothing is read, nothing raised, nothing half-read left in the notes."""
    t = Trace.from_file(xplane)
    for d in t.devices:
        d.ops = [o for o in d.ops if o[0] != "grouped_matmul"]
    dense = {"trace": t, "notes": {}, "counters": {}, "peaks": PEAKS,
             "cell": types.SimpleNamespace(config=TOY),
             "_program_stats": {"first_tokens": [], "chips": [
                 (12 * US, [(frozenset({"attn"}), 12 * US)])]},
             "_moe_span_stats": {"serve.decode.dispatch": [{"batch": 128}]}}
    if reader is not moe.load_max_over_mean:
        assert reader(dense) is None
    dense["_moe_span_stats"] = {}
    assert reader(dense) is None
    assert dense["notes"] == {}


# ---- the files ------------------------------------------------------------------ #
def test_every_new_metric_is_listed_for_the_cell_and_resolves():
    cell = cells.Cell(CELL)
    listed = {m["name"]: m for m in cell.per_layer}
    new = set(NEW) | {"moe_experts_roofline", "grouped_matmul_roofline",
                      "moe_load_max_over_mean.gen"}
    assert new <= set(listed)
    for name in new:
        fn, args = cell.reader(name)
        assert callable(fn) and isinstance(args, dict)
        m = listed[name]
        assert m["moves"] == "serve_tokens_per_s" and CELL in m["workloads"]
        assert m["unit"] == ("ratio" if "load" in name else "%")
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s", "setup_s"]


def test_the_configuration_is_the_catalogs_but_for_its_depth():
    """Every number of the source's config.json under its own key; only
    ``num_hidden_layers`` differs, and it is listed."""
    source = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
              "hidden_size": 2048, "intermediate_size": 1024,
              "max_position_embeddings": 4096, "model_type": "olmoe",
              "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
              "num_experts_per_tok": 8, "num_hidden_layers": 16,
              "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
              "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}
    cfg = cells.Cell(CELL).config
    differs = [k for k, v in source.items() if cfg.get(k, "missing") != v]
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 8
    kw = cfg["model"]["kwargs"]
    assert (kw["n_embd"], kw["n_head"], kw["intermediate_size"], kw["num_experts"],
            kw["top_k"], kw["n_layer"], kw["n_positions"], kw["vocab_size"]) == (
                2048, 16, 1024, 64, 8, 8, 4096, 50304)
    # the arena: K and V x 8 layers x 2048 lanes x 2 B a token, 4,097 blocks of 16
    assert cfg["serve"]["arena_bytes"] == 4097 * 16 * 2 * 8 * 2048 * 2 == 4_296_015_872
    assert cfg["serve"]["serving"] == {"max_batch_size": 128, "dtype": "bfloat16"}
