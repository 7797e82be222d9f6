"""Tests of ``step_mfu_pct`` (the whole serve step's share of the chip's peak),
of a share that reads 0.0 where a change took its op out of the program, of
the paged roofline's kernel names as data, and of the longer ``decode-heavy``
backlog; CPU only, on synthetic runs."""

import os

import numpy as np
import pytest

from benchmarks.kinds import serve_backlog
from benchmarks.lib import arith_moe, arith_step, cells, draws
from benchmarks.lib.build import model_from
from benchmarks.readers import device_trace, op_family, step_share

BENCH = cells.load_benchmark()
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SERVE_CELLS = [w for m in BENCH["per_layer"] if m["name"].startswith("step_mfu_pct.")
               for w in m["workloads"]]
# leaves the program holds as zeros and the sources of the three later
# families do not have (no bias in a norm or a projection)
ABSENT = ("ln1_b", "ln2_b", "lnf_b", "qkv_b", "out_b")


class FakeTrace:
    """A trace of ``steps`` programs, each a list of (op, seconds), with
    ``gap`` seconds of idle behind it."""

    def __init__(self, steps, ops, gap=0.002, runs="steps"):
        self.steps, self.ops, self.gap = steps, ops, gap
        self.runs = steps if runs == "steps" else runs

    def window_s(self):
        return self.steps * (sum(s for _, s in self.ops) + self.gap)

    def busy_s(self):
        return self.steps * sum(s for _, s in self.ops)

    def op_seconds(self):
        out = {}
        for name, s in self.ops:
            out[name] = out.get(name, 0.0) + self.steps * s
        return out

    def program_runs(self):
        return self.runs


def attention_counters(rows):
    """The counters a kind would leave for ``rows`` live rows a step:
    whichever the configuration's ``step_work.attention`` reads."""
    n = sum(rows)
    return {"paged_flops": 4e6 * n, "paged_bytes": 9e6 * n,
            "paged_gqa_flops": 4 * 32 * 128 * 5000 * n, "paged_gqa_bytes": 9e6 * n,
            "attention_keys_read": 5000 * n, "attention_key_products": 5000 * n,
            "attention_rows_live": n, "attention_rows_idle": len(rows),
            "traced_step_rows": list(rows)}


def a_run(workload, trace, rows=(130, 130, 194, 130)):
    cell = cells.Cell(workload)
    return {"trace": trace, "counters": attention_counters(rows), "notes": {},
            "cell": cell, "peaks": PEAKS}


@pytest.mark.parametrize("workload", SERVE_CELLS)
def test_step_mfu_counts_the_work_and_not_the_ops(workload):
    fn, args = cells.Cell(workload).reader(
        "step_mfu_pct.tpot" if "chat" in workload else "step_mfu_pct.gen")
    assert fn is step_share.mfu_pct and args == {}
    ops = [("dynamic-slice_bitcast_fusion", 0.020), ("paged_attention", 0.012),
           ("fusion", 0.004)]
    base = a_run(workload, FakeTrace(4, ops))
    value = fn(base)
    assert 0.0 < value < 100.0
    assert base["notes"]["roofline_bound"]["step"] == "memory"
    assert base["notes"]["step_work"]["steps"] == 4
    # (i) the same counters and window under other op names: the same value
    renamed = [("copy.7", 0.020), ("paged_attention_v2", 0.012), ("loop_fusion", 0.004)]
    assert fn(a_run(workload, FakeTrace(4, renamed))) == value
    # (ii) a copy of 20 ms a step fewer: higher by the ratio of the windows
    gone = a_run(workload, FakeTrace(4, ops[1:]))
    assert fn(gone) == pytest.approx(value * (0.036 + 0.002) / (0.016 + 0.002))
    # a host that holds the chip back lowers it
    assert fn(a_run(workload, FakeTrace(4, ops, gap=0.010))) < value
    # (iv) no trace, no count of the stretch's rows, no attention count: None
    assert fn(dict(base, trace=None)) is None
    assert fn(dict(base, counters={})) is None
    assert fn(dict(base, counters={"traced_step_rows": [130]})) is None


@pytest.mark.parametrize("workload", SERVE_CELLS)
def test_step_mfu_reads_the_steps_whose_programs_the_device_line_holds(workload):
    """The device's part of a trace can start after the host's: the steps
    read are the last ``program_runs``, attention's count cut by their rows."""
    ops = [("fusion", 0.030)]
    rows = (64, 130, 130, 130)
    whole = step_share.mfu_pct(a_run(workload, FakeTrace(3, ops), rows[1:]))
    late = a_run(workload, FakeTrace(3, ops, runs=3), rows)
    assert step_share.mfu_pct(late) == pytest.approx(whole, rel=1e-4)
    assert late["notes"]["step_work"]["steps_counted_by_the_host"] == 4
    # a trace without the line of program runs: every step the host counted
    every = a_run(workload, FakeTrace(3, ops, runs=None), rows)
    assert step_share.mfu_pct(every) > whole
    # a step that ran nothing (no live row) counts nothing
    idle = a_run(workload, FakeTrace(3, ops), (0,) + rows[1:])
    idle["counters"].update(attention_counters(rows[1:]), traced_step_rows=[0, *rows[1:]])
    assert step_share.mfu_pct(idle) == pytest.approx(whole)


def test_step_mfu_by_hand_on_olmoe():
    """One step of 128 rows: every dense weight once, all 64 experts of 8
    layers (128 rows of top 8 reach them all), 2 GB of cache; 50 ms."""
    cell = cells.Cell("olmoe-1b-7b.serve-decode-heavy")
    kw = cell.config["model"]["kwargs"]
    w = arith_step.olmoe_weights(kw)
    dense = 8 * (4 * 2048 * 2048 + 4 * 2048 + 2048 * 64) + 2048 + 50304 * 2048
    assert w["dense"] == dense and w["gathered"] == 50304 * 2048
    assert arith_step.bank_params(w["bank"]) == 8 * 64 * 3 * 2048 * 1024
    flops, nbytes = arith_step.step_work(w, 128)
    bank_f, bank_b = arith_moe.expert_bank_call(128, 64, 8, 2048, 1024)
    assert flops == 2 * dense * 128 + 8 * bank_f
    assert nbytes == pytest.approx(2 * dense + 8 * bank_b)
    assert nbytes == pytest.approx(6.44e9 + 0.55e9, rel=0.01)
    run = {"trace": FakeTrace(1, [("anything", 0.048)]), "notes": {}, "cell": cell,
           "peaks": PEAKS, "counters": {"paged_flops": 1e9, "paged_bytes": 2e9,
                                        "traced_step_rows": [128]}}
    assert step_share.mfu_pct(run) == pytest.approx(100 * ((nbytes + 2e9) / 819e9) / 0.050)
    assert arith_step.step_work(w, 0) == (0, 0)


def test_a_bank_that_holds_a_share_gets_that_share():
    kw = cells.Cell("mistral-small-4-119b.serve-reasoning-batch").config["model"]["kwargs"]
    w = arith_step.mistral4_weights(kw)
    assert (w["bank"]["experts"], w["bank"]["held"], w["bank"]["top_k"]) == (128, 32, 4)
    full_f, full_b = arith_moe.expert_bank_call(128, 128, 4, 4096, 2048)
    none = dict(w, bank=None)
    flops, nbytes = arith_step.step_work(w, 128)
    flops0, nbytes0 = arith_step.step_work(none, 128)
    assert flops - flops0 == pytest.approx(5 * full_f / 4)
    assert nbytes - nbytes0 == pytest.approx(5 * full_b / 4)
    # 128 rows of 4 reach 98.3% of 128 experts: 31.5 of the 32 held, 1.58 GB a layer
    assert (nbytes - nbytes0) / 5 == pytest.approx(31.45 * 3 * 4096 * 2048 * 2, rel=0.01)


# ---- (iii) the weights the arithmetic counts are the engine's arrays ---------- #
def _leaves(config):
    import jax
    shapes = jax.eval_shape(model_from(config).init_params, jax.random.PRNGKey(0))
    return {jax.tree_util.keystr(path): int(np.prod(leaf.shape))
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}


def _grouped(leaves, untied):
    """{"dense", "gathered", "bank", "absent"}: parameters by what a step
    needs of them, from the leaves' names."""
    out = dict.fromkeys(("dense", "gathered", "bank", "absent"), 0)
    for name, n in leaves.items():
        if "['experts']" in name:
            out["bank"] += n
        elif name == ("['wte']" if untied else "['wpe']"):
            out["gathered"] += n
        elif untied and name.endswith(tuple(f"['{b}']" for b in ABSENT)):
            out["absent"] += n
        else:
            out["dense"] += n
    return out


@pytest.mark.parametrize("size", ["rehearse", "as-run"])
@pytest.mark.parametrize("workload", SERVE_CELLS)
def test_the_weights_counted_are_the_arrays_the_engine_builds(workload, size):
    config = cells.Cell(workload).config
    if size == "rehearse":
        cells.merge(config, config["rehearse"])
    weights = cells.resolve(config["step_work"]["weights"])(config["model"]["kwargs"])
    leaves = _leaves(config)
    built = _grouped(leaves, untied="['lm_head']" in leaves)
    bank = arith_step.bank_params(weights["bank"]) if weights["bank"] else 0
    assert (weights["dense"], weights["gathered"], bank) == (
        built["dense"], built["gathered"], built["bank"])
    # what the program holds beyond the source: zero biases, under 2% of the
    # dense weights at the tiny size and a thousandth as run
    assert built["absent"] <= (0.02 if size == "rehearse" else 1e-3) * built["dense"]
    assert sum(built.values()) == sum(leaves.values())


def test_the_weights_as_run_are_the_counts_perf_md_states():
    total = lambda w: w["dense"] + w["gathered"] + (
        arith_step.bank_params(w["bank"]) if w["bank"] else 0)
    kw = lambda name: cells.load_json(os.path.join(
        cells.ROOT, "benchmarks/configs", name + ".json"))["model"]["kwargs"]
    assert total(arith_step.gpt2_weights(kw("gpt2-124m"))) == 124_475_904
    assert total(arith_step.olmoe_weights(kw("olmoe-1b-7b"))) == pytest.approx(3.56e9, rel=2e-3)
    assert total(arith_step.smallthinker_weights(kw("smallthinker-21b-a3b"))) == pytest.approx(
        3.967e9, rel=2e-4)
    # the program's own count (``GPT.num_params``, test_mistral4.py), to the parameter
    assert total(arith_step.mistral4_weights(kw("mistral-small-4-119b"))) == 4_563_716_992


# ---- a share of the device's time is 0.0 where the op is gone ------------------ #
def test_a_share_whose_op_a_change_removed_reads_zero_and_a_roofline_none():
    trace = FakeTrace(2, [("paged_gqa_attention", 0.010), ("grouped_matmul", 0.008),
                          ("dynamic-slice_bitcast_fusion.12.remat3", 0.018)])
    run = {"trace": trace, "counters": {}, "notes": {}, "peaks": PEAKS}
    for metric in ("kv_layer_copy_share_pct.gen", "kv_layer_copy_share_pct.tpot",
                   "moe_bank_copy_share_pct.gen"):
        spec = cells.load_json(os.path.join(cells.ROOT, "benchmarks/metrics", metric + ".json"))
        fn = cells.resolve(spec["reader"])
        args = dict(spec["args"], ops=["an_op_no_program_holds"])
        assert fn(run, **args) == 0.0 and isinstance(fn(run, **args), float)
        assert fn({"trace": None}, **args) is None
    # the K/V layer copy is named whole, the banks' copies by family
    assert device_trace.op_share_pct(run, ["dynamic-slice_bitcast_fusion"]) == 0.0
    assert op_family.share_pct(run, ["dynamic-slice_bitcast_fusion"]) == pytest.approx(50.0)
    # a quotient by no time is not a number
    run["counters"] = {"paged_flops": 1.0, "paged_bytes": 8.19e9}
    assert device_trace.paged_attention_roofline(run, kernels=["paged_attention"]) is None


def test_the_line_keeps_a_metric_that_reads_zero(monkeypatch):
    cell = cells.Cell("smallthinker-21b-a3b.serve-long-context")
    cell.per_layer = [m for m in cell.per_layer if m["name"] in (
        "moe_bank_copy_share_pct.gen", "paged_gqa_attention_roofline")]
    run = {"trace": FakeTrace(1, [("fusion", 0.040)]), "counters": {}, "notes": {},
           "peaks": PEAKS, "cell": cell}
    assert cells.per_layer_values(cell, run) == {
        "moe_bank_copy_share_pct.gen": {"value": 0.0, "unit": "%"}}


# ---- the paged roofline's kernels are data -------------------------------------- #
def test_the_paged_roofline_sums_the_kernels_its_metric_file_names():
    fn, args = cells.Cell("gpt2-124m.serve-decode-heavy").reader("paged_attention_roofline")
    assert args == {"kernels": ["paged_attention", "paged_gqa_attention"]}
    counters = {"paged_flops": 1.0, "paged_bytes": 8.19e9}       # 10 ms at 819 GB/s
    read = lambda ops: fn({"trace": FakeTrace(1, ops), "counters": counters, "notes": {},
                           "peaks": PEAKS}, **args)
    assert read([("paged_attention", 0.040)]) == pytest.approx(25.0)
    assert read([("paged_gqa_attention", 0.020)]) == pytest.approx(50.0)     # its successor
    assert read([("paged_attention", 0.030), ("paged_gqa_attention", 0.010)]) == pytest.approx(25.0)
    assert read([("paged_mla_attention", 0.040)]) is None
    run = {"trace": FakeTrace(1, [("paged_gqa_attention", 0.02)]), "counters": counters,
           "notes": {}, "peaks": PEAKS}
    fn(run, **args)
    assert run["notes"]["roofline_bound"] == {"paged_attention": "memory"}


def test_the_trace_counts_its_program_runs():
    from jax.profiler import ProfileData
    from benchmarks.lib.trace import Trace
    import tempfile
    text = open(os.path.join(cells.ROOT, "benchmarks/testdata/synthetic.xspace.txt")).read()
    text = "\n".join(l for l in text.splitlines() if not l.startswith("#"))
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "synthetic.xplane.pb")
        with open(path, "wb") as f:
            f.write(ProfileData.text_proto_to_serialized_xspace(text))
        assert Trace.from_file(path).program_runs() == 1
        bare = text.replace('name: "XLA Modules"', 'name: "Steps"')
        with open(path, "wb") as f:
            f.write(ProfileData.text_proto_to_serialized_xspace(bare))
        assert Trace.from_file(path).program_runs() is None


# ---- the longer backlog -------------------------------------------------------- #
HEAVY = cells.load_json(os.path.join(cells.ROOT, "benchmarks/traffic/decode-heavy.json"))


def test_the_backlog_outlasts_a_step_three_times_shorter():
    """A slot finishes a request every 512 tokens (the outputs' mean), so
    ``n`` queued are all admitted after ``512 n`` tokens whatever the slots:
    the ceiling over the window, against what the two cells read (ledger,
    PR 67: 5,628 and 7,214 tokens/s; 5,049.7 and 2,429.8 at PR 36)."""
    n = HEAVY["backlog_requests"]
    mean = np.mean(draws.quantiles(HEAVY["output_tokens"], n))
    assert mean == pytest.approx(512, abs=1)
    assert n * mean / BENCH["run_seconds"] > 3 * 7214
    # all of it is queued at once, behind full slots: the harness hands the
    # engine a queue as deep as the traffic's backlog (``lib/serving.py``; the
    # program's default admits 1,024, and is not edited)
    from deepspeed_tpu.serving.config import DeepSpeedServingConfig
    assert n == 2048 > DeepSpeedServingConfig().max_queue
    source = open(os.path.join(cells.ROOT, "benchmarks/lib/serving.py")).read()
    assert 'serving.setdefault("max_queue"' in source and "backlog_requests" in source


@pytest.mark.parametrize("key", ["prompt_tokens", "output_tokens"])
def test_every_sixteen_requests_hold_the_same_range_as_at_384(key):
    spec = HEAVY[key]
    edges = [draws.quantile_fn(spec)(s / 16) for s in range(17)]
    spans = {}
    for n in (384, HEAVY["backlog_requests"]):
        _, backlog = serve_backlog.plan(dict(HEAVY, backlog_requests=n), 8, 1024, 50257,
                                        seed=2 ** 31 + 37)
        got = np.asarray([len(p) if key == "prompt_tokens" else o for p, o in backlog])
        assert sorted(got) == sorted(draws.quantiles(spec, n))
        blocks = got.reshape(-1, 16)
        # one request of each sixteenth of the distribution in every 16
        for block in blocks:
            strata = np.clip(np.searchsorted(edges, np.sort(block), side="right") - 1, 0, 15)
            assert (np.abs(strata - np.arange(16)) <= 1).all()
        means = blocks.mean(1)
        spans[n] = (blocks.min(1).max(), blocks.max(1).min(), means.min(), means.max())
        assert means.max() - means.min() < 0.05 * got.mean()
    lo384, hi384, _, _ = spans[384]
    lo, hi, mean_lo, mean_hi = spans[HEAVY["backlog_requests"]]
    width = spec["max"] - spec["min"]
    # the least of a block lies in the first sixteenth and the most in the last, at either depth
    assert max(lo, lo384) <= spec["min"] + width / 16 + 1
    assert min(hi, hi384) >= spec["max"] - width / 16 - 1


def test_the_first_cohort_does_not_depend_on_the_backlogs_depth():
    a, _ = serve_backlog.plan(dict(HEAVY, backlog_requests=384), 16, 1024, 50257, seed=5)
    b, _ = serve_backlog.plan(HEAVY, 16, 1024, 50257, seed=5)
    assert [(p.tolist(), n) for p, n in a] == [(p.tolist(), n) for p, n in b]
