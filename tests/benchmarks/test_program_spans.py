"""Tests of the readers of the program's own names
(``benchmarks/readers/program_spans.py``) on hand-built traces and on a
synthetic trace file with answers worked out on paper; CPU only."""

import os

import pytest

from benchmarks.lib import cells
from benchmarks.lib.trace import DeviceTrace, Trace
from benchmarks.readers import program_spans as ps

ROOT = cells.ROOT
US = 1e-6


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    from jax.profiler import ProfileData
    text = open(os.path.join(ROOT, "benchmarks/testdata/program_spans.xspace.txt")).read()
    text = "\n".join(l for l in text.splitlines() if not l.startswith("#"))
    path = tmp_path_factory.mktemp("trace") / "program_spans.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


@pytest.fixture()
def run(xplane):
    """A traced run as ``benchmarks/run.py`` hands it to a reader, with the
    stats read from the synthetic file and not from ``.bench_trace``."""
    return {"trace": Trace.from_file(xplane), "counters": {}, "notes": {},
            "peaks": None, "_program_stats": ps.read_stats(xplane)}


def _hand(ops, host_spans, host_events):
    """A Trace of one chip from [(name, start, duration)] and host tuples."""
    return Trace([DeviceTrace("/device:TPU:0", ops, [])], host_spans, host_events)


# ---- host spans: durations ---------------------------- #
def test_span_ms_per_step_sums_the_named_spans_over_the_steps(run):
    table = ps.span_ms_per_step(run, ["serve.prefill.build", "serve.decode.build"])
    sched = ps.span_ms_per_step(run, ["serve.admit", "serve.grow"])
    assert table == pytest.approx(1e3 * 2.0 * US / 2)
    assert sched == pytest.approx(1e3 * 1.5 * US / 2)
    assert ps.span_ms_per_step(run, ["serve.no_such_phase"]) is None


def test_the_breakdown_names_the_leaf_span_over_a_gap(run):
    """The overlay of the device's gaps on the host's spans as a METRIC went
    with PR 68 (good to the profiler's offset only); the breakdown, which
    gives a whole gap to one event for the next issue's writer, still names
    leaves."""
    names = [n for n, _ in run["trace"].breakdown()["idle_gaps"]]
    assert names == ["bench.engine_step:serve.prefill.fetch",
                     "bench.engine_step:serve.decode.fetch"]
    assert not hasattr(ps, "idle_under_pct")


def test_a_program_without_spans_gives_the_readers_nothing():
    """The parent commit: the benchmark's own spans, JAX's own events, no
    ``serve.*`` span, no stat on any op."""
    t = _hand([("%a.1 = f32[] x(", 0.0, 1.0), ("%b.2 = f32[] y(", 5.0, 15.0)],
              [("bench.engine_step", 0.0, 20.0)],
              [("np.asarray(jax.Array)", 1.0, 4.0), ("PjitFunction(step_fn)", 4.0, 5.0)])
    parent = {"trace": t, "notes": {}, "_program_stats": {
        "first_tokens": [], "chips": [(16.0, [(frozenset(), 1.0), (frozenset(), 15.0)])]}}
    assert ps.span_ms_per_step(parent, ["serve.admit", "serve.grow"]) is None
    assert ps.first_token_mean_ms(parent, "queue_ms") is None
    assert ps.scope_share_pct(parent, ["attn"]) is None
    assert parent["notes"] == {}, "nothing half-read is left in the notes"


@pytest.mark.parametrize("reader,args", [
    (ps.span_ms_per_step, {"spans": ["serve.admit"]}),
    (ps.first_token_mean_ms, {"stat": "queue_ms"}),
    (ps.scope_share_pct, {"scopes": ["attn"]}),
])
def test_an_untraced_run_gives_none(reader, args):
    assert reader({"trace": None, "notes": {}}, **args) is None


# ---- what needs the stats ------------------------------------------------------ #
def test_first_token_stats_are_averaged_and_counted(run):
    assert ps.first_token_mean_ms(run, "queue_ms") == pytest.approx(2.0)
    assert ps.first_token_mean_ms(run, "lane_wait_ms") == pytest.approx(20.0)
    assert ps.first_token_mean_ms(run, "prefill_ms") == pytest.approx(120.0)
    assert run["notes"]["first_token_events"] == 2
    assert ps.first_token_mean_ms(run, "no_such_stat") is None


def test_the_scope_is_read_from_the_ops_metadata(xplane):
    ops = ps.device_ops_with_scope(xplane)["/device:TPU:0"]
    assert [(round(s / US), round(d / US)) for _, s, d in ops] == [
        (0, 4), (10, 10), (11, 4), (16, 3), (26, 4), (30, 2)]
    assert ops[0][0].endswith("/attn/dot_general:")
    assert ops[4][0].endswith("/rematted_computation/mlp/dot_general:")   # a ref_value
    assert ops[5][0] is None


@pytest.mark.parametrize("path,scopes", [
    ("jit(fused)/transpose(jvp(blocks))/while/body/closed_call/attn/dot_general:",
     {"fused", "blocks", "while", "body", "closed_call", "attn", "dot_general"}),
    ("jit(fused)/jvp(blocks)/while/body/checkpoint/rematted_computation/mlp/mul:",
     {"fused", "blocks", "while", "body", "checkpoint", "rematted_computation",
      "mlp", "mul"}),
    ("jit(fused)/optimizer/reshape;jit(fused)/optimizer/fused_adam/pallas_call:",
     {"fused", "optimizer", "reshape", "fused_adam", "pallas_call"}),
    ("jit(fused)/jvp(mlp_head)/dot_general:", {"fused", "mlp_head", "dot_general"}),
    (None, set()),
])
def test_scopes_are_whole_components_also_inside_wrappers(path, scopes):
    assert ps._components(path) == scopes


def test_scope_shares_are_self_time_over_busy_time(run):
    share = lambda *scopes, **kw: ps.scope_share_pct(run, list(scopes), **kw)
    assert share("attn") == pytest.approx(20.0)          # inside transpose(jvp(..))
    assert share("mlp") == pytest.approx(20.0)           # inside a remat wrapper
    assert share("head", "cross_entropy") == pytest.approx(20.0)
    assert share("optimizer") == pytest.approx(15.0)
    assert share(*ps.PROGRAM_SCOPES, invert=True) == pytest.approx(10.0)
    # the while's own time is under blocks alone: scoped, and no layer's
    assert run["notes"]["scope_share_pct"] == pytest.approx({
        "attn": 20.0, "mlp": 20.0, "cross_entropy": 20.0, "optimizer": 15.0,
        "blocks": 15.0, "none": 10.0})
    assert sum(run["notes"]["scope_share_pct"].values()) == pytest.approx(100.0)


# ---- the files ------------------------------------------------------------------ #
NEW = sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "benchmarks/metrics"))
             if "program_spans:" in open(os.path.join(ROOT, "benchmarks/metrics", f)).read())


@pytest.mark.parametrize("name", NEW)
def test_every_metric_of_this_reader_reads_the_synthetic_trace(run, name):
    """Each metric file of this reader, with its own arguments, finds a
    number in a trace that holds the program's names (how MANY files name it
    is nobody's to hold: the overlay's six went with PR 68)."""
    fn, args = cells.Cell("gpt2-124m.serve-chat-steady").reader(name)
    assert fn.__module__ == ps.__name__
    assert isinstance(fn(run, **args), float), name
    assert fn is not getattr(ps, "idle_under_pct", None)


def test_spans_the_metrics_name_are_spans_the_program_opens():
    from deepspeed_tpu.serving.engine import SERVE_STEP_SPANS
    cell = cells.Cell("gpt2-124m.serve-chat-steady")
    named = {s for name in NEW for s in cell.reader(name)[1].get("spans", [])}
    assert named and named <= set(SERVE_STEP_SPANS)
    scopes = {s for name in NEW for s in cell.reader(name)[1].get("scopes", [])}
    assert scopes == set(ps.PROGRAM_SCOPES)
