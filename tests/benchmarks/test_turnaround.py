"""Tests of the readers of the serve step's own turn-round
(``benchmarks/readers/turnaround.py``) on a synthetic trace file with answers
worked out on paper; CPU only.

The trace, in milliseconds (``_xspace`` writes it; ``shift`` moves the WHOLE
host line against the device's, as the profiler does from run to run):

* device: four program runs on ``XLA Modules``, 10-20, 24-34, 39-49, 52-62,
  two ops each on ``XLA Ops``; window 10-62 = 52, idle 4 + 5 + 3 = 12;
* host, one thread: four steps.  Step n launches its program 0.5 before it
  starts (the launch's way out) and has its row 1.0 after it ends (the row's
  way back), so the wire is 1.5 in every gap and the host's turn-round is the
  rest: 2.5, 3.5, 1.5.  A step's spans tile it: admit, dispatch (0.5),
  fetch (launch to result), commit, stats (0.3); between two steps, nothing.

  | step | commit_ms | outside_ms | prepare_ms | turnaround_ms | result_wait_ms |
  | 1    | 0.9       | 0.3        | 1.3        | 2.5           | 11.5           |
  | 2    | 0.9       | 1.3        | 1.3        | 3.5           | 11.5           |
  | 3    | 0.6       | 0.1        | 0.8        | 1.5           | 11.5           |

  Step 0's turn-round began before the trace and holds the profiler's start
  (900 ms of ``outside_ms``): it is stamped, and never read.
"""

import os

import pytest

from benchmarks.lib import cells
from benchmarks.lib.trace import Trace
from benchmarks.readers import turnaround as ta
from benchmarks.readers.device_trace import device_idle_pct

ROOT = cells.ROOT
RUNS = [(10.0, 20.0), (24.0, 34.0), (39.0, 49.0), (52.0, 62.0)]
WAY_OUT, WAY_BACK = 0.5, 1.0
# (commit_ms, outside_ms, prepare_ms) of the turn-round BEFORE each step's program
PARTS = [(0.5, 900.0, 1.0), (0.9, 0.3, 1.3), (0.9, 1.3, 1.3), (0.6, 0.1, 0.8)]
STATS = ("turnaround_ms", "commit_ms", "outside_ms", "prepare_ms", "result_wait_ms",
         "attention_rows")
NAMES = ("bench.engine_step", "serve.admit", "serve.decode.dispatch",
         "serve.decode.fetch", "serve.decode.commit", "serve.stats")
NEW = sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "benchmarks/metrics"))
             if "readers.turnaround:" in open(os.path.join(ROOT, "benchmarks/metrics", f)).read())


def _event(metadata_id, start_ms, end_ms, stats=()):
    body = "".join(f" stats {{ metadata_id: {STATS.index(k) + 1} "
                   f"{'int64_value' if k == 'attention_rows' else 'double_value'}: {v} }}"
                   for k, v in stats)
    return (f"events {{ metadata_id: {metadata_id} offset_ps: {round(start_ms * 1e9)} "
            f"duration_ps: {round((end_ms - start_ms) * 1e9)}{body} }}")


def _xspace(shift=0.0, modules=True, stamped=True, op_gap=0.0):
    """The trace above as a text ``XSpace``.  ``modules``: with the ``XLA
    Modules`` line; ``stamped``: the program writes the turn-round's stats
    (else: a parent commit); ``op_gap``: idle between a program's two ops."""
    ops = [_event(1 + i % 2, *iv) for s, e in RUNS
           for i, iv in enumerate([(s, s + 6.0 - op_gap), (s + 6.0, e)])]
    runs = [_event(3, s, e) for s, e in RUNS]
    host = []
    # a step ends with the commit and the stats that FOLLOW its program: the
    # commit_ms of the next turn-round (0.5 after the last)
    commits_after = [p[0] for p in PARTS[1:]] + [0.5]
    for (start, end), (commit, outside, prepare), after in zip(RUNS, PARTS, commits_after):
        launch, result = start - WAY_OUT + shift, end + WAY_BACK + shift
        enter, leave = launch - prepare, result + after
        stats = [("attention_rows", 5)]
        if stamped:
            stats = [("turnaround_ms", commit + outside + prepare), ("commit_ms", commit),
                     ("outside_ms", outside), ("prepare_ms", prepare),
                     ("result_wait_ms", result - launch)] + stats
        host += [_event(1, enter, leave),
                 _event(2, enter, launch - 0.5), _event(3, launch - 0.5, launch),
                 _event(4, launch, result), _event(5, result, leave - 0.3),
                 _event(6, leave - 0.3, leave, stats)]
    md = lambda names: "\n".join(
        f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for i, n in enumerate(names, 1))
    lines = f'  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0\n    ' + "\n    ".join(ops) + "\n  }\n"
    if modules:
        lines += ('  lines { id: 3 name: "XLA Modules" timestamp_ns: 0\n    '
                  + "\n    ".join(runs) + "\n  }\n")
    return (
        'planes {\n  id: 1 name: "/device:TPU:0"\n' + lines
        + md(["%fusion.1 = bf16[8]{0} fusion(%a)", "%fusion.2 = bf16[8]{0} fusion(%b)",
              "jit_step_fn(1)"])
        + '\n}\nplanes {\n  id: 2 name: "/host:CPU"\n'
        + '  lines { id: 7 name: "python" timestamp_ns: 0\n    ' + "\n    ".join(host)
        + "\n  }\n" + md(NAMES) + "\n"
        + "\n".join(f'  stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                    for i, n in enumerate(STATS, 1)) + "\n}\n")


def _run(tmp_path, **how):
    """A traced run as ``benchmarks/run.py`` hands it to a reader, with the
    steps read from the synthetic file and not from ``.bench_trace``."""
    from jax.profiler import ProfileData
    path = tmp_path / f"t{len(list(tmp_path.iterdir()))}.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(_xspace(**how)))
    run = {"trace": Trace.from_file(str(path)), "counters": {}, "notes": {}, "peaks": None}
    run["_turnaround"] = ta._with_notes(run, ta.read_steps(str(path)))
    return run


def _three(run):
    return (ta.stat_mean_ms(run, "turnaround_ms"), ta.stat_mean_ms(run, "outside_ms"),
            ta.wire_ms(run))


# ---- by hand, on three steps ------------------------------------------------------ #
def test_means_are_over_the_steps_whose_gap_the_device_line_holds(tmp_path):
    run = _run(tmp_path)
    turnaround, outside, wire = _three(run)
    assert turnaround == pytest.approx((2.5 + 3.5 + 1.5) / 3)
    assert outside == pytest.approx((0.3 + 1.3 + 0.1) / 3), "step 0's 900 ms is not in it"
    assert wire == pytest.approx(WAY_OUT + WAY_BACK)
    assert ta.stat_mean_ms(run, "no_such_stat") is None
    notes = run["notes"]
    assert notes["turnaround_steps"] == 3
    assert notes["turnaround_ms_p50_p99_max"] == pytest.approx([2.5, 3.48, 3.5])
    assert notes["result_wait_ms_p50_p99_max"] == pytest.approx([11.5] * 3)
    assert notes["turnaround_parts_ms"] == pytest.approx({
        "commit_ms": 0.8, "outside_ms": 0.5667, "prepare_ms": 1.1333,
        "result_wait_ms": 11.5}, abs=1e-4)
    assert notes["idle_between_programs_ms"] == pytest.approx((4 + 5 + 3) / 3)
    # the new pair is the idle the device's own line shows, to the digit here
    # (the programs' ops run back to back)
    idle_ms_a_step = device_idle_pct(run) / 100 * 52 / 3
    assert turnaround + wire == pytest.approx(idle_ms_a_step)


def test_a_step_that_followed_no_program_is_not_read(tmp_path):
    """Steps and runs are matched by count from the end; a step that carries
    no turn-round (the chip waited for WORK before it) drops its gap too."""
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(_xspace()))
    read = ta.read_steps(str(path))
    assert [round(1e3 * (e - s), 6) for s, e in read["runs"]] == [10.0] * 4
    assert len(read["steps"]) == 4 and len(read["fetch_ends"]) == 4
    del read["steps"][2]["turnaround_ms"]
    steps, gaps = ta.stretch(read, None)
    assert [st["turnaround_ms"] for st in steps] == [2.5, 1.5]
    assert gaps == pytest.approx([4.0, 3.0])
    # an empty step between two programs ran none: it matches no run
    read["steps"].insert(3, {"attention_rows": 0})
    assert ta.stretch(read, None)[1] == pytest.approx([4.0, 3.0])


# ---- the profiler's offset between the two lines ---------------------------------- #
@pytest.mark.parametrize("shift,skew,inside", [
    (0.0, 1.0, True),
    (+1.5, 2.5, False),
    (-1.5, -0.5, False),
])
def test_an_offset_between_the_lines_moves_the_skew_and_not_the_durations(
        tmp_path, shift, skew, inside):
    """One program, one host path, and the profiler's host line laid at
    three offsets: the three metrics are the same to the microsecond, and the
    note says by how much the lines were off (causality holds it to [0,
    wire]).  The overlay of the two lines, which split the chip's idle time
    between "the fetch" and "host work" by where the host's line happened to
    lie (4.5 / 5.8, 7.5 / 2.9 and 6.0 / 4.3 ms of these three runs: ISSUE
    36's table), went with PR 68; what it summed to stays, and no shift moves
    it."""
    run, plain = _run(tmp_path, shift=shift), _run(tmp_path)
    assert _three(run) == pytest.approx(_three(plain), abs=1e-3)      # ms: to 1 us
    assert _three(run) == pytest.approx((2.5, 1.7 / 3, 1.5), abs=1e-3)
    assert run["notes"]["host_device_skew_ms"] == pytest.approx(skew)
    assert (0.0 <= run["notes"]["host_device_skew_ms"] <= ta.wire_ms(run)) == inside
    assert device_idle_pct(run) == pytest.approx(device_idle_pct(plain))
    assert device_idle_pct(run) == pytest.approx(100 * 12.0 / 52)
    # the true split, which no shift moves: the host's 7.5 ms, the wire's 4.5
    assert 3 * ta.stat_mean_ms(run, "turnaround_ms") == pytest.approx(7.5)
    assert 3 * ta.wire_ms(run) == pytest.approx(4.5)


# ---- traces that hold less --------------------------------------------------------- #
def test_without_the_modules_line_the_gaps_that_hold_a_boundary_are_read(tmp_path):
    """A gap of 0.1 ms between a program's two ops is shorter than the
    shortest turn-round stamped (1.5 ms): no program boundary lies in it."""
    run = _run(tmp_path, modules=False, op_gap=0.1)
    assert len(run["trace"].devices[0].gaps()) == 3 + 4
    assert _three(run) == pytest.approx((2.5, 1.7 / 3, 1.5))
    assert "host_device_skew_ms" not in run["notes"], "no program run to lay a fetch against"
    with_line = _run(tmp_path, op_gap=0.1)
    assert _three(with_line) == pytest.approx((2.5, 1.7 / 3, 1.5))


def test_a_program_without_the_stats_gives_the_readers_nothing(tmp_path):
    """The parent commit: ``serve.stats`` with ``attention_rows`` and no
    turn-round."""
    parent = _run(tmp_path, stamped=False)
    assert _three(parent) == (None, None, None)
    assert parent["notes"] == {}, "nothing half-read is left in the notes"


@pytest.mark.parametrize("reader,args", [
    (ta.stat_mean_ms, {"stat": "turnaround_ms"}),
    (ta.stat_mean_ms, {"stat": "outside_ms"}),
    (ta.wire_ms, {}),
])
def test_an_untraced_run_gives_none(reader, args):
    assert reader({"trace": None, "notes": {}}, **args) is None


# ---- the files ---------------------------------------------------------------------- #
BENCH = cells.load_benchmark()
SERVE = {w["name"] for w in BENCH["workloads"] if ".serve-" in w["name"]}


def test_there_are_six_and_none_is_held_to_program_spans():
    assert NEW == ["host_turnaround_ms.gen", "host_turnaround_ms.tpot",
                   "idle_wire_ms.gen", "idle_wire_ms.tpot",
                   "step_outside_ms.gen", "step_outside_ms.tpot"]
    for name in NEW:
        text = open(os.path.join(ROOT, "benchmarks/metrics", name + ".json")).read()
        assert "program_spans:" not in text, name


@pytest.mark.parametrize("name", NEW)
def test_metric_file_resolves_and_reads_the_synthetic_trace(tmp_path, name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] and set(entry["workloads"]) <= SERVE
    assert (entry["unit"], entry["better"], entry["source"]) == ("ms", "lower", "program_span")
    if name.endswith(".tpot"):
        assert "gpt2-124m.serve-chat-steady" in entry["workloads"]
    else:
        assert set(entry["workloads"]) == SERVE - {"gpt2-124m.serve-chat-steady"}
    fn, args = cells.Cell(entry["workloads"][0]).reader(name)
    assert fn.__module__ == ta.__name__
    value = fn(_run(tmp_path), **args)
    assert isinstance(value, float) and value > 0.0
    assert fn(_run(tmp_path, stamped=False), **args) is None
