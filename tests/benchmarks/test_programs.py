"""Tests of the readers of the serve program's own record
(``benchmarks/readers/programs.py``) on a hand-made trace with answers worked
out on paper (``benchmarks/testdata/programs.xspace.txt``: its header holds
the timeline and the table); CPU only.  Every other trace here is that file
with one thing changed in its text."""

import functools
import json
import os
import re

import pytest

from benchmarks.lib import cells
from benchmarks.lib.trace import Trace
from benchmarks.readers import programs as pg

ROOT = cells.ROOT
TEXT = open(os.path.join(ROOT, "benchmarks/testdata/programs.xspace.txt")).read()
STAT_ID = {name: int(key) for key, name in re.findall(
    r'stat_metadata \{ key: (\d+) value \{ id: \d+ name: "(\w+)" \} \}', TEXT)}
METRICS = (pg.program_ms, pg.chunk_program_time_pct, pg.dispatched_ahead_pct,
           pg.host_occupancy_pct)
ON_PAPER = (83.0 / 6, 50.0, 100.0 * 4 / 6, 100.0 * 11.1 / 83.0)
NEW = sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "benchmarks/metrics"))
             if "readers.programs:" in open(os.path.join(ROOT, "benchmarks/metrics", f)).read())


def _stat(name, kind="int64"):
    return rf"stats \{{ metadata_id: {STAT_ID[name]} {kind}_value: [-\d.e]+ \}}"


def host_line_laid_off(text, ms):
    """The profiler lays the WHOLE host line ``ms`` off the device's."""
    return text.replace('name: "python" timestamp_ns: 0',
                        f'name: "python" timestamp_ns: {round(ms * 1e6)}')


def without_the_run_at(text, start_ms):
    """One program run less on ``XLA Modules`` (its ops stay)."""
    line = f"    events {{ metadata_id: 4 offset_ps: {round(start_ms * 1e9)} duration_ps"
    assert text.count(line) == 1
    return "\n".join(l for l in text.split("\n") if not l.startswith(line))


def fetched_late(text, start_ms, took_ms, late_ms):
    """The fetch span that opened at ``start_ms`` ends ``late_ms`` later."""
    span = f"offset_ps: {round(start_ms * 1e9)} duration_ps: {round(took_ms * 1e9)} stats"
    assert text.count(span) == 1
    return text.replace(span, f"offset_ps: {round(start_ms * 1e9)} "
                              f"duration_ps: {round((took_ms + late_ms) * 1e9)} stats")


def with_another_modules_run(text, start_ms, end_ms):
    """A run of some other program on ``XLA Modules``, between two of ours."""
    text = text.replace(
        '  event_metadata { key: 4 value { id: 4 name: "jit_step_fn(1)" } }',
        '  event_metadata { key: 4 value { id: 4 name: "jit_step_fn(1)" } }\n'
        '  event_metadata { key: 5 value { id: 5 name: "jit_convert_element_type(2)" } }')
    line = '  lines { id: 3 name: "XLA Modules" timestamp_ns: 0\n'
    assert text.count(line) == 1 and "jit_convert_element_type" in text
    return text.replace(line, line + f"    events {{ metadata_id: 5 offset_ps: "
                        f"{round(start_ms * 1e9)} duration_ps: {round((end_ms - start_ms) * 1e9)} }}\n")


def no_program_carries_a_chunk(text):
    return re.sub(_stat("chunk_tokens"),
                  f"stats {{ metadata_id: {STAT_ID['chunk_tokens']} int64_value: 0 }}", text)


def as_the_parent_writes_it(text):
    """The spans without the number and without the record."""
    for name, kind in (("program", "int64"), ("ahead", "int64"), ("device_ms", "double"),
                       ("host_ms", "double")):
        text = re.sub(" " + _stat(name, kind), "", text)
    return text


def _run(tmp_path, text=TEXT):
    """A traced run as ``benchmarks/run.py`` hands it to a reader, with the
    programs read from the text and not from ``.bench_trace``."""
    from jax.profiler import ProfileData
    path = tmp_path / f"t{len(list(tmp_path.iterdir()))}.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    run = {"trace": Trace.from_file(str(path)), "counters": {}, "notes": {}, "peaks": None}
    run["_programs"] = pg._with_notes(run, pg.read(str(path)))
    return run


def _four(run):
    return tuple(reader(run) for reader in METRICS)


# ---- by hand, on six programs ----------------------------------------------------- #
def test_the_stretch_is_the_programs_launched_after_the_first_traced_steps(tmp_path):
    """P10's wait and P11's host part hold the profiler's start (910 and 901.8
    ms): neither is read.  P18 is in flight when the trace ends: it has a run
    on the device's line and no landing, and is no landed program."""
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TEXT))
    found = pg.read(str(path))
    assert [st["program"] for st in found["landings"]] == list(range(10, 18))
    assert found["first"] == 11 and sorted(found["launched"]) == list(range(11, 19))
    assert [round(1e3 * (e - s), 6) for s, e in found["runs"]] == [
        10.0, 10.0, 20.0, 10.0, 10.0, 20.0, 10.0, 10.0]
    landed = pg.stretch(found)
    assert [st["program"] for st in landed] == [12, 13, 14, 15, 16, 17]
    assert [st["device_ms"] for st in landed] == [10.0, 20.0, 10.0, 11.5, 21.5, 10.0]
    assert max(st["host_ms"] for st in landed) == 2.0


def test_the_four_metrics_are_the_tables(tmp_path):
    run = _run(tmp_path)
    assert _four(run) == pytest.approx(ON_PAPER)


def test_a_trace_with_a_program_in_flight_at_both_ends_joins_by_number(tmp_path):
    """Eight landings and eight runs, and they are NOT the same eight: P10 has
    no run, P18 no landing.  A match by count from either end would pair each
    program with the run one place away; by number, six join, each fetch span
    ending the row's way back (1.0) after its own run."""
    notes = _run(tmp_path)["notes"]
    assert notes["program_join"] == {"landed": 6, "runs": 8, "programs": 6,
                                     "residual_ms_p50": 1.0, "residual_ms_max_off_p50": 0.0,
                                     "run_ms_mean": round(80.0 / 6, 4),
                                     "program_ms": round(83.0 / 6, 4)}


def test_a_mixed_stretch_reads_ahead_and_not_ahead_apart(tmp_path):
    """chat-steady's: launched ahead, the program's clock and the device's
    agree as durations; not ahead, ``device_ms`` holds both wires (1.5), and
    the chip's wait before the run is the host's parts and the same wires."""
    notes = _run(tmp_path)["notes"]
    assert notes["program_clock_check_ms"] == {
        "ahead": {"programs": 4, "p50": 0.0, "max": 0.0},
        "not_ahead": {"programs": 2, "p50": 1.5, "max": 1.5}}
    gaps = notes["idle_gap_parts_ms"]
    assert gaps["not_ahead"] == {
        "programs": 2, "gap_ms": [3.5, 3.5], "wire_ms": [1.5, 1.5],
        "commit_ms": [0.55, 0.599], "outside_ms": [0.15, 0.199], "prepare_ms": [1.3, 1.3]}
    # ahead, the host's parts are hidden: the gap holds none of them
    assert gaps["ahead"]["gap_ms"] == gaps["ahead"]["wire_ms"] == [0.0, 0.0]
    assert gaps["ahead"]["programs"] == 4 and gaps["ahead"]["prepare_ms"] == [1.2, 1.2]


def test_the_two_kinds_are_read_apart_in_milliseconds_a_program(tmp_path):
    kinds = _run(tmp_path)["notes"]["programs_by_kind"]
    assert kinds["decode"] == {
        "programs": 4, "device_ms_mean_p50_max": [10.375, 10.0, 11.5], "joined": 4,
        "run_ms_mean_p50_max": [10.0, 10.0, 10.0],
        "top_ops_ms_a_program": [["fusion", 6.0], ["paged_attention", 4.0]]}
    assert kinds["chunk"] == {
        "programs": 2, "device_ms_mean_p50_max": [20.75, 20.75, 21.5], "joined": 2,
        "run_ms_mean_p50_max": [20.0, 20.0, 20.0],
        "top_ops_ms_a_program": [["fusion", 8.0], ["sort", 8.0], ["paged_attention", 4.0]]}


# ---- traces that hold less, or lie otherwise ---------------------------------------- #
@pytest.mark.parametrize("laid_off_ms", [2.4, -1.5])
def test_the_lines_laid_apart_move_the_residual_and_nothing_else(tmp_path, laid_off_ms):
    run, plain = _run(tmp_path, host_line_laid_off(TEXT, laid_off_ms)), _run(tmp_path)
    assert _four(run) == pytest.approx(_four(plain))
    join = run["notes"].pop("program_join")
    assert join["programs"] == 6 and join["residual_ms_max_off_p50"] == 0.0
    assert join["residual_ms_p50"] == pytest.approx(1.0 + laid_off_ms)
    plain["notes"].pop("program_join")
    assert run["notes"] == plain["notes"], "durations, subtracted pair by pair"


def test_a_stretch_with_no_chunk_program_reads_a_share_of_nothing_as_zero(tmp_path):
    run = _run(tmp_path, no_program_carries_a_chunk(TEXT))
    program_ms, chunk_pct, ahead_pct, host_pct = _four(run)
    assert chunk_pct == 0.0 and chunk_pct is not None
    assert (program_ms, ahead_pct, host_pct) == pytest.approx(
        (ON_PAPER[0], ON_PAPER[2], ON_PAPER[3]))
    assert list(run["notes"]["programs_by_kind"]) == ["decode"]
    assert run["notes"]["programs_by_kind"]["decode"]["programs"] == 6


def test_a_run_missing_from_the_device_line_fails_the_join_and_no_metric(tmp_path):
    """P16's run is not on the line: from there on each program takes the run
    of the one after it, which ends a whole run (10 ms) later than its own.
    The join says so, leaves out what needs it, and the four metrics read
    what they read."""
    run = _run(tmp_path, without_the_run_at(TEXT, 167.0))
    assert _four(run) == pytest.approx(ON_PAPER)
    join = run["notes"]["program_join"]
    assert join["programs"] == 0 and join["runs"] == 7 and join["landed"] == 6
    assert "half the shortest joined run (5.000 ms)" in join["why"]
    assert "-10.000 ms off" in join["why"] and "late_fetches" not in join
    assert "program_clock_check_ms" not in run["notes"]
    assert "idle_gap_parts_ms" not in run["notes"]
    kinds = run["notes"]["programs_by_kind"]
    assert kinds["chunk"] == {"programs": 2, "device_ms_mean_p50_max": [20.75, 20.75, 21.5]}


def test_rows_fetched_late_are_counted_and_a_run_too_many_is_not_taken_for_them(tmp_path):
    """The host, paused, lands P13's row 7 ms late (seen on the chip: 19-110 ms,
    PERF.md section 6, PR 53): its residual alone leaves the band of 5 ms, its
    neighbours' do not, and the join holds by number.  A pause that outlasts
    P14's run (10 ms) holds P14's row back too, by that run less: P13's 17 ms
    late and P14's 7.  P13's and P14's both late by the SAME: that is what a
    run too many BEFORE them on the line looks like; three in a row cannot be
    (the engine is one program ahead at most)."""
    late = lambda *by: _run(tmp_path, functools.reduce(
        lambda text, row: fetched_late(text, *row), by, TEXT))
    P12, P13, P14 = (114.0, 7.0), (124.0, 17.0), (141.4, 9.6)
    one = late(P13 + (7.0,))
    join = one["notes"]["program_join"]
    assert (join["programs"], join["late_fetches"], join["late_fetch_ms_max"]) == (6, 1, 7.0)
    assert join["residual_ms_max_off_p50"] == 0.0, "of those inside the band"
    assert _four(one) == pytest.approx(ON_PAPER)
    # a late row's ``device_ms`` holds the pause and the one behind it is
    # short by it: the note reads the joined programs with and without them
    assert join["program_ms"] == round(83.0 / 6, 4)
    assert join["program_ms_less_late"] == (10.0 + 11.5 + 21.5 + 10.0) / 4
    assert (join["run_ms_mean"], join["run_ms_mean_less_late"]) == (round(80.0 / 6, 4), 12.5)
    assert "program_ms_less_late" not in _run(tmp_path)["notes"]["program_join"]
    pair = late(P13 + (17.0,), P14 + (7.0,))["notes"]["program_join"]
    assert (pair["programs"], pair["late_fetches"], pair["late_fetch_ms_max"]) == (6, 2, 17.0)
    assert pair["program_ms_less_late"] == round((10.0 + 21.5 + 10.0) / 3, 4), "less P13-P15"
    for by in ((P13 + (7.0,), P14 + (7.0,)), (P13 + (27.0,), P14 + (7.0,))):
        join = late(*by)["notes"]["program_join"]
        assert join["programs"] == 0 and "late_fetches" not in join
        assert "no row or pair of rows fetched late" in join["why"]
    # the first joined program's has no neighbour before it to vouch for it
    assert late(P12 + (7.0,))["notes"]["program_join"]["programs"] == 0
    # twelve runs of 10 ms on end, each row 1 ms behind its run; rows 5, 6, 7 late
    runs = [(0.01 * k, 0.01 * (k + 1)) for k in range(12)]
    rows = lambda by: [{"program": k, pg.LANDED: 10.0, pg.END: e + 1e-3 * (1.0 + by.get(k, 0.0))}
                       for k, (_, e) in enumerate(runs)]
    assert pg.join(rows({5: 17.0, 6: 7.0}), runs)[1]["late_fetches"] == 2
    assert pg.join(rows({5: 27.0, 6: 17.0, 7: 7.0}), runs)[1]["programs"] == 0, "three in a row"


def test_another_modules_run_on_the_line_is_not_a_program_of_the_engine(tmp_path):
    """A short run of some other jitted function between P14's and P15's (the
    chip idles 3.5 ms there): the step program is the module that ran most, and
    the band is half the shortest run JOINED, not of the line."""
    run, plain = _run(tmp_path, with_another_modules_run(TEXT, 151.0, 151.2)), _run(tmp_path)
    assert run["notes"]["program_join"] == plain["notes"]["program_join"]
    assert run["notes"]["program_clock_check_ms"] == plain["notes"]["program_clock_check_ms"]


def test_a_trace_without_the_modules_line_reads_the_metrics_and_says_why(tmp_path):
    text = re.sub(r'  lines \{ id: 3 name: "XLA Modules".*?\n  \}\n', "", TEXT, flags=re.S)
    run = _run(tmp_path, text)
    assert _four(run) == pytest.approx(ON_PAPER)
    assert run["notes"]["program_join"] == {"programs": 0, "why": "no XLA Modules line"}


def test_a_program_without_the_stats_gives_the_readers_nothing(tmp_path):
    """The parent commit: the same spans, no number and no record."""
    parent = _run(tmp_path, as_the_parent_writes_it(TEXT))
    assert _four(parent) == (None, None, None, None)
    assert parent["notes"] == {}, "nothing half-read is left in the notes"


@pytest.mark.parametrize("reader", METRICS)
def test_an_untraced_run_gives_none(reader):
    assert reader({"trace": None, "notes": {}}) is None


def test_a_stretch_whose_programs_carry_no_host_part_reads_an_occupancy_of_zero(tmp_path):
    text = re.sub(" " + _stat("host_ms", "double"), "", TEXT)
    assert pg.host_occupancy_pct(_run(tmp_path, text)) == 0.0


# ---- stretches as the chip gave them ----------------------------------------------------- #
CHIP = json.load(open(os.path.join(ROOT, "benchmarks/testdata/programs_chip_stretches.json")))


@pytest.mark.parametrize("kept", CHIP["stretches"], ids=lambda k: f"{k['cell']}-{k['seed']}")
def test_a_stretch_of_the_chips_joins_again_as_its_line_said(kept):
    """Traced runs of the two cells whose prompts outlast the stretch, and one
    of chat-steady's (my chip runs, PR 53): every landed program of the
    stretch joins, by number, and where all were launched ahead ``program_ms``
    is the joined runs' mean once the rows a pause held back are left out."""
    found = {"first": kept["first"], "landings": [
        dict(zip(("program", "ahead", "chunk_tokens", pg.LANDED, "host_ms", pg.END), row))
        for row in kept["landings"]]}
    landed = pg.stretch(found)
    at, note = pg.join(landed, kept["runs"])
    assert len(at) == note["programs"] == len(landed) == note["landed"]
    assert sum(st[pg.LANDED] for st in landed) / len(landed) == pytest.approx(kept["program_ms"])
    assert [at[k + 1] - at[k] for k in sorted(at)[:-1]] == [1] * (len(at) - 1), "by number"
    on_the_chip = kept["program_join_on_the_chip"]
    if on_the_chip["programs"]:
        assert note == on_the_chip
    else:
        # the pause that made the rule a row OR A PAIR: 110 ms, longer than the
        # next program's run (19), so two rows waited and the chip ran dry
        assert "no single late fetch" in on_the_chip["why"]
        assert (note["late_fetches"], note["late_fetch_ms_max"]) == (2, 109.9564)
        assert note["program_ms"] == pytest.approx(1.0334 * note["run_ms_mean"], rel=1e-4)
    if all(st["ahead"] for st in landed):
        less_late = "_less_late" if "late_fetches" in note else ""
        assert note["program_ms" + less_late] == pytest.approx(
            note["run_ms_mean" + less_late], rel=3e-3)


# ---- the files ------------------------------------------------------------------------ #
BENCH = cells.load_benchmark()
SERVE = {w["name"] for w in BENCH["workloads"] if ".serve-" in w["name"]}
CHAT = "gpt2-124m.serve-chat-steady"
ENGINE, SCHEDULER = "serve engine (serving/engine.py)", "scheduler (serving/scheduler.py)"
TABLE = {       # metric -> (layer, better)
    "program_ms": (ENGINE, "lower"),
    "chunk_program_time_pct": (SCHEDULER, "lower"),
    "dispatched_ahead_pct": (ENGINE, "higher"),
    "host_occupancy_pct": (ENGINE, "lower"),
}


def test_there_are_eight_two_a_quantity():
    assert NEW == sorted([f"{name}.gen" for name in TABLE]
                         + [f"{name}.tpot" for name in TABLE if name != "chunk_program_time_pct"]
                         + ["chunk_program_time_pct.ttft"])


@pytest.mark.parametrize("name", NEW)
def test_metric_file_resolves_and_reads_the_synthetic_trace(tmp_path, name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    quantity, suffix = name.rsplit(".", 1)
    layer, better = TABLE[quantity]
    assert (entry["layer"], entry["better"], entry["source"]) == (layer, better, "program_counter")
    assert entry["unit"] == ("ms" if quantity == "program_ms" else "%")
    if suffix == "gen":
        assert set(entry["workloads"]) == SERVE - {CHAT}        # every backlog serve cell
        assert entry["moves"] == "serve_tokens_per_s"
    else:
        assert CHAT in entry["workloads"] and not set(entry["workloads"]) & (SERVE - {CHAT})
        assert entry["moves"] == {"tpot": "tpot_p90_ms", "ttft": "ttft_p90_ms"}[suffix]
    fn, args = cells.Cell(entry["workloads"][0]).reader(name)
    assert fn.__module__ == pg.__name__ and args == {}
    value = fn(_run(tmp_path))
    assert isinstance(value, float) and value > 0.0
    assert fn(_run(tmp_path, as_the_parent_writes_it(TEXT))) is None


def test_each_entry_is_listed_once_under_its_own_name():
    """WHERE an entry stands in the list is nobody's to hold (a later PR
    appends behind it, a ``benchmark`` PR folds what stands before it): each
    of the eight is there, once."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert [names.count(name) for name in NEW] == [1] * len(NEW)
