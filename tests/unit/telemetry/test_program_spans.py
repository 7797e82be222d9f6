"""The program's spans on the profiler's clock (``telemetry/tracing.py``): a
span is a ``jax.profiler.TraceAnnotation`` whether or not a ``Tracer`` is
configured, never touches the traced program, and the serving engine tiles
its step with them and stamps a request where things happen."""

import glob
import itertools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.gpt import GPT, GPTConfig
from deepspeed_tpu.serving import DeepSpeedServingConfig, ServingEngine
from deepspeed_tpu.serving.engine import (PROGRAM_STATS, SERVE_STEP_SPANS,
                                          TURNAROUND_STATS, ServeStepTimeout)
from deepspeed_tpu.telemetry import Tracer, maybe_span, set_global_tracer


def _capture(tmp_path, body):
    """Run ``body`` under a profiler session; -> the host events by name."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "plugins/profile/*/*.xplane.pb"))[-1]
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    return {e.name: (e.duration_ns, dict(e.stats))
            for line in host.lines for e in line.events}


def _open_and_close(span):
    with span:
        pass


# ---- the span itself --------------------------------------------------------- #
def test_span_is_in_a_profiler_capture_with_its_scalar_stats(tmp_path):
    device_value = jnp.ones((4,))

    def body():
        with maybe_span("serve.decode.build", batch=7, rate=0.5, slo="standard",
                        loss=device_value) as sp:
            time.sleep(0.001)
            sp.set(admitted=3)
    events = _capture(tmp_path, body)
    duration_ns, stats = events["serve.decode.build"]     # the name is kept clean
    assert duration_ns >= 1e6
    assert stats == {"batch": 7, "rate": 0.5, "slo": "standard", "admitted": 3}


def test_span_without_a_session_or_a_tracer_is_inert():
    set_global_tracer(None)
    with maybe_span("serve.admit", rid=1) as sp:
        sp.set(admitted=0)                    # nothing to write to: no error


def test_array_attribute_is_never_forced_and_stays_in_the_ring_only(tmp_path):
    class Exploding:
        def __array__(self, *a, **k):
            raise AssertionError("forced")
        __float__ = __int__ = __str__ = __repr__ = __array__
    tr = Tracer()
    bomb = Exploding()
    events = _capture(tmp_path, lambda: _open_and_close(tr.span("fwd", step=3, loss=bomb)))
    assert events["fwd"][1] == {"step": 3}
    assert tr.snapshot()[-1]["args"]["loss"] is bomb


def test_one_entry_records_on_the_profiler_and_in_the_ring(tmp_path):
    tr = Tracer()

    def body():
        with maybe_span("train_batch", tr, step=1):
            with maybe_span("serve.admit", tr) as sp:
                sp.set(admitted=2)
    events = _capture(tmp_path, body)
    assert {"train_batch", "serve.admit"} <= set(events)
    inner, outer = tr.snapshot()
    assert inner["name"] == "serve.admit" and inner["parent"] == outer["sid"]
    assert inner["args"] == {"admitted": 2} and outer["args"] == {"step": 1}


def test_a_closed_tracer_still_leaves_the_annotation(tmp_path):
    tr = Tracer()
    tr.close()
    events = _capture(tmp_path, lambda: _open_and_close(tr.span("late")))
    assert "late" in events and tr.snapshot() == []


# ---- spans never touch the program ------------------------------------------ #
def _lowered(tracer):
    def step(x, w):
        with jax.named_scope("mlp"):
            return jnp.tanh(x @ w).sum()
    with maybe_span("train_batch", tracer, step=0):
        with maybe_span("fwd", tracer):
            return jax.jit(jax.grad(step)).lower(
                jnp.ones((4, 8)), jnp.ones((8, 8))).as_text(debug_info=True)


def test_lowered_program_is_identical_with_and_without_a_tracer():
    # from one line: the text records the call stack's line numbers
    plain, traced = [_lowered(tracer) for tracer in (None, Tracer())]
    assert traced == plain
    assert "mlp" in plain and "train_batch" not in plain and "fwd/" not in plain


def test_engine_step_lowers_identically_with_tracing_configured(tmp_path):
    """The fused train step of a tiny engine, lowered under the engine's own
    spans: byte-identical whether ``telemetry.tracing`` built a Tracer or not,
    and every op of ``_apply_updates`` is under the ``optimizer`` scope."""
    import deepspeed_tpu
    from deepspeed_tpu.parallel import mesh as mesh_lib

    def lowered(telemetry):
        model = GPT(GPTConfig(vocab_size=64, n_positions=16, n_embd=16, n_layer=1,
                              n_head=2, dtype="float32"))
        config = {"train_micro_batch_size_per_gpu": 1,
                  "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                  "steps_per_print": 10 ** 9}
        if telemetry:
            config["telemetry"] = {"enabled": True, "tracing": True,
                                   "trace_dir": str(tmp_path),
                                   "watchdog_enabled": False}
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=model.init_params(jax.random.PRNGKey(0)),
            config=config, seed=3)
        assert (engine.tracer is not None) == telemetry
        ids = np.zeros((1, jax.device_count(), 16), np.int32)
        st = engine.state
        carry = (st.params, st.opt_state, st.scaler, st.skipped)
        with engine._span("train_batch", step=0):
            text = engine._build_fused_step().lower(
                carry, (ids, ids), jax.random.PRNGKey(0)).as_text(debug_info=True)
        engine.close()
        mesh_lib.reset_mesh()
        return text

    plain, traced = [lowered(telemetry) for telemetry in (False, True)]
    assert traced == plain
    assert "/optimizer/" in plain and "train_batch" not in plain


# ---- the serving engine's spans and stamps ----------------------------------- #
@pytest.fixture(scope="module")
def tiny_model():
    model = GPT(GPTConfig(vocab_size=128, n_positions=128, n_embd=32, n_layer=2,
                          n_head=4, dtype="float32"))
    return model, model.init_params(jax.random.PRNGKey(0))


def _engine(tiny_model, tracer=None, **over):
    model, params = tiny_model
    cfg = dict(block_size=8, num_blocks=64, max_batch_size=4, prefill_chunk=8,
               dtype="float32")
    cfg.update(over)
    return ServingEngine(model, config=DeepSpeedServingConfig(**cfg), params=params,
                         tracer=tracer)


def test_leaf_spans_tile_the_step(tiny_model):
    """Every leaf is a sibling, in the order the work happens, and nothing of
    a step lies under no leaf.  Held on the Tracer's injected clock, which
    reads 0, 1, 2 ...: a span's open and its close are a read each, so a leaf
    opens ONE read after the leaf before it closed exactly when no span was
    opened in between, and the first opens one read after ``step()`` was
    called.  (What the spans' own bookkeeping costs in microseconds is a
    reading of the chip's host, PERF.md section 6, and no test's.)

    The order: admit, grow, the builds, ONE dispatch, then a fetch and the
    commits of its row for each program that lands in the step: its own (the
    order of ``SERVE_STEP_SPANS``, where the step is not dispatched ahead),
    the one before it (dispatched ahead), or both (the step in which the
    engine stops being ahead)."""
    import re
    ticks = itertools.count()
    tr = Tracer(clock=lambda: next(ticks))
    eng = _engine(tiny_model, tracer=tr)
    rng = np.random.default_rng(0)
    for n in (20, 5, 11):
        eng.submit(list(rng.integers(1, 128, size=n)), max_new_tokens=6)
    eng.step()                                  # compiles the program
    eng.step()
    seen, shapes = set(), set()
    for _ in range(8):
        mark = len(tr.snapshot())
        t0 = next(ticks)
        stats = eng.step()
        t1 = next(ticks)
        # (a request's serve.first_token mark sits inside the commit it came in)
        step = [r for r in tr.snapshot()[mark:] if r["name"] != "serve.first_token"]
        assert all(r["depth"] == 0 and r["parent"] == 0 for r in step), \
            "leaves are siblings: nothing encloses them"
        names = [r["name"] for r in step]
        kinds = " ".join(n.rsplit(".", 1)[-1] for n in names)
        assert re.fullmatch(r"admit grow build( build)? dispatch"
                            r"( fetch( commit){1,2}){1,2} stats", kinds), names
        assert names[2:4][:kinds.count("build")] == [
            "serve.prefill.build", "serve.decode.build"][:kinds.count("build")]
        if not stats["dispatched_ahead"]:
            assert names == [n for n in SERVE_STEP_SPANS if n in set(names)], \
                "in the order of the list"
        shapes.add((stats["dispatched_ahead"], kinds.count("fetch")))
        assert step[0]["t0"] == t0 + 1 and step[-1]["t1"] == t1 - 1
        assert [b["t0"] - a["t1"] for a, b in zip(step, step[1:])] == [1] * (len(step) - 1), \
            "no read of the tracer's clock between two leaves"
        seen |= set(names)
    # steps 3..10: the first prompt's last chunk alone (the pair that names a
    # program with no decode row) and the other prompts' chunks beside decode
    # rows, each launched before the row of the one before it is fetched;
    # the last chunk's step fetches that row and its own; then decode rows
    # alone, each step its own row
    assert shapes == {(1, 1), (1, 2), (0, 1)}
    assert seen == set(SERVE_STEP_SPANS)
    eng.close()




def _step_spans(tr, eng):
    """{span name: [args of each event]} of one ``eng.step()``, and its stats."""
    mark = len(tr.snapshot())
    stats = eng.step()
    by = {}
    for r in tr.snapshot()[mark:]:
        by.setdefault(r["name"], []).append(r["args"])
    return by, stats


def _less_record(events):
    """The fetch events' stats as they were when the span opened."""
    return [{k: v for k, v in args.items() if k not in PROGRAM_STATS} for args in events]


def test_spans_carry_counts_where_the_work_happens(tiny_model):
    tr = Tracer()
    eng = _engine(tiny_model, tracer=tr)
    fut = eng.submit(list(range(1, 13)), max_new_tokens=3)
    (submitted,) = tr.snapshot()
    assert (submitted["name"], submitted["args"]) == (
        "serve.submit", {"rid": fut.request.rid}), "joins the request's spans"
    by, stats = _step_spans(tr, eng)            # a chunk, and no decode row yet
    assert by["serve.admit"] == [{"admitted": 1}]
    chunk = {"rid": fut.request.rid, "start": 0, "tokens": 8}
    assert by["serve.prefill.build"] == [chunk]
    assert by["serve.prefill.dispatch"] == [dict(chunk, chunk_tokens=8, program=1)]
    # prompt is left behind the chunk, so nothing that arrives could change
    # the next program: this one stays in flight, its row is not fetched here
    assert "serve.prefill.fetch" not in by and "serve.prefill.commit" not in by
    assert (stats["dispatched_ahead"], by["serve.stats"][0]["dispatched_ahead"]) == (0, 0)
    assert stats["program"] == 1, "the step that starts lands no row"
    # 12 prompt tokens in blocks of 8: two pages, one full group, no window
    assert by["serve.grow"] == [{"batch": 0, "pages_full": 2, "pages_window": 0,
                                 "pages_given_back": 0}]
    assert not any(n.startswith("serve.decode.") for n in by)
    assert (stats["programs"], stats["prefill_tokens"], stats["decode_batch"]) == (1, 8, 0)
    # how attention took the step: the chunk's 8 tokens the queries of ONE
    # row beside the 4 slots' rows, on the span and in the stats
    # and all 4 + 8 rows through the dense matrices
    packed = {"chunk_queries_per_row": 8, "attention_rows": 4 + 1, "dense_rows": 4 + 8}
    assert {k: by["serve.stats"][0][k] for k in packed} == packed
    assert {k: stats[k] for k in packed} == packed
    by, stats = _step_spans(tr, eng)            # last chunk: the first token
    last = {"rid": fut.request.rid, "start": 8, "tokens": 4}
    # launched before the first chunk's row was fetched; then that row, and
    # (the lane idle, the queue empty, a slot free) its own: two fetches,
    # each under the stats of the program whose row it brings
    assert by["serve.prefill.build"] == [last]
    assert by["serve.prefill.dispatch"] == [dict(last, chunk_tokens=4, program=2)]
    assert _less_record(by["serve.prefill.fetch"]) == [
        dict(chunk, chunk_tokens=8, program=1), dict(last, chunk_tokens=4, program=2)]
    assert by["serve.prefill.commit"] == [dict(chunk, program=1), dict(last, program=2)]
    assert (stats["dispatched_ahead"], by["serve.stats"][0]["dispatched_ahead"]) == (1, 1)
    assert stats["program"] == 2, "the step that stops being ahead lands two"
    assert eng.steps_dispatched_ahead == 1 and len(fut.request.generated) == 1
    by, stats = _step_spans(tr, eng)            # the first decode step
    assert by["serve.decode.build"] == [{"batch": 1}]
    assert by["serve.decode.commit"] == [{"batch": 1, "program": 3}]
    for name in ("serve.decode.dispatch", "serve.decode.fetch"):
        assert _less_record(by[name]) == [{"batch": 1, "chunk_tokens": 0, "program": 3}], name
    assert by["serve.prefill.build"] == [None]
    assert not any(n in by for n in ("serve.prefill.dispatch", "serve.prefill.fetch",
                                     "serve.prefill.commit"))
    # what the step's tables cost: the one upload, no entry changed (the
    # request was handed its two blocks at admission), nothing reloaded; no
    # chunk in this step, so no queries a row of one, and the same rows; no
    # tile of the tables a run (this kernel copies page by page); and the
    # step's turn-round, since the step before ran a program.  The
    # engine's constants (which paged kernel it runs, the bytes the arena
    # holds a token a layer) are attributes of the engine and not written on
    # every step's event; the first is in ``step()``'s stats
    table = {"table_edits": 0, "table_reloads": 0, "tile_runs_pct": 0.0,
             "upload_bytes": 4 * eng._layout.packed_size}
    (on_span,) = by["serve.stats"]
    assert on_span == dict(table, chunk_queries_per_row=0, attention_rows=5,
                           dense_rows=4, dispatched_ahead=0, program=3,
                           **{k: stats[k] for k in TURNAROUND_STATS})
    assert {k: stats[k] for k in table} == table
    assert stats["paged_tile_pages"] == eng.paged_tile_pages == 0   # the einsum
    assert eng.cache_bytes_per_token == 2 * 32 * 4      # K and V, 32 lanes, f32
    assert (stats["programs"], stats["prefill_tokens"], stats["decode_batch"]) == (1, 0, 1)
    fut.result()
    idle = eng.step()                           # nothing to run: no program,
    assert (idle["programs"], idle["upload_bytes"]) == (0, 0)       # no upload
    assert "program" not in idle
    assert (idle["chunk_queries_per_row"], idle["attention_rows"],
            idle["dense_rows"]) == (0, 0, 0)
    eng.close()


# ---- the step's own turn-round, as durations on the engine's one clock --------- #
class _Ticks:
    """A clock that reads 1, 2, 3 ...: every duration is a count of the
    reads between two stamps, exact in floating point."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        self.now += 1.0
        return self.now


def _turnaround(stats):
    return {k: stats[k] for k in TURNAROUND_STATS if k in stats}


def _stamped(eng):
    """``eng._dispatch`` and ``eng._fetch`` wrapped to keep each program's
    ``t_launch`` and each row's ``t_result``, in the order they were taken."""
    launches, results, dispatch, fetch = [], [], eng._dispatch, eng._fetch

    def keeping(inner, kept):
        def call(*args):
            out = inner(*args)
            kept.append(out[1])
            return out
        return call
    eng._dispatch, eng._fetch = keeping(dispatch, launches), keeping(fetch, results)
    return launches, results


def test_turnaround_parts_sum_to_the_time_between_two_results(tiny_model):
    """A lone request whose prompt is one chunk: no step is dispatched ahead,
    and each step's program waits for the host's whole turn-round."""
    eng = _engine(tiny_model)
    eng._clock = _Ticks()
    launches, results = _stamped(eng)
    eng.submit(list(range(1, 8)), max_new_tokens=8)
    assert _turnaround(eng.step()) == {}, "the first step: nothing to turn round from"
    for _ in range(6):                         # decode rows
        stats = eng.step()
        t = _turnaround(stats)
        assert set(t) == set(TURNAROUND_STATS) and stats["dispatched_ahead"] == 0
        result_before, result, launch = results[-2], results[-1], launches[-1]
        assert t["commit_ms"] + t["outside_ms"] + t["prepare_ms"] == t["turnaround_ms"]
        assert t["turnaround_ms"] == (launch - result_before) * 1e3
        assert t["result_wait_ms"] == (result - launch) * 1e3 == 1e3    # one read on
        assert sum(t.values()) - t["turnaround_ms"] == (result - result_before) * 1e3
        assert min(t.values()) > 0.0
    assert eng.steps_dispatched_ahead == 0
    eng.close()


def test_a_step_dispatched_ahead_has_no_turnaround_and_its_parts_are_durations(tiny_model):
    """Under a backlog (more requests than slots) a step launches its program
    before the row of the one before it is on the host: the chip never has no
    program, so ``turnaround_ms`` is 0.0; the four parts are still the
    host's durations, and ``result_wait_ms`` is what the host waited behind
    its launch for the row of the program BEFORE.  ``dispatched_ahead`` is in
    the stats and on ``serve.stats``."""
    tr = Tracer()
    eng = _engine(tiny_model, tracer=tr, max_batch_size=2)
    eng._clock = _Ticks()
    launches, results = _stamped(eng)
    for n in (3, 4, 5, 6, 7):
        eng.submit(list(range(1, n + 1)), max_new_tokens=6)
    first = eng.step()              # launches, and fetches nothing
    assert _turnaround(first) == {} and first["dispatched_ahead"] == 0
    second = _turnaround(eng.step())        # ahead; settled at that launch
    assert second["turnaround_ms"] == 0.0 and second["commit_ms"] > 0.0
    ahead = 0
    while len(eng.sched.waiting) > 1:           # (the queue's last leaves it
        exit_before = eng._t_exit               # empty beside a free slot)
        by, stats = _step_spans(tr, eng)
        t = _turnaround(stats)
        assert stats["dispatched_ahead"] == by["serve.stats"][0]["dispatched_ahead"] == 1
        assert {k: by["serve.stats"][0][k] for k in TURNAROUND_STATS} == t
        assert t["turnaround_ms"] == 0.0
        # one row came in this step, behind the launch; the row before it
        # came in the step before
        assert results[-2] < exit_before < launches[-1] < results[-1]
        assert t["commit_ms"] == (exit_before - results[-2]) * 1e3 > 0.0    # from its row
        assert t["outside_ms"] == 1e3                   # t_exit to t_enter
        assert t["commit_ms"] + t["outside_ms"] + t["prepare_ms"] == (
            launches[-1] - results[-2]) * 1e3
        assert t["result_wait_ms"] == (results[-1] - launches[-1]) * 1e3 == 1e3
        kinds = [n.rsplit(".", 1)[-1] for n in by]
        assert kinds.index("dispatch") < kinds.index("fetch") and kinds.count("fetch") == 1
        ahead += 1
    assert ahead >= 4 and eng.steps_dispatched_ahead == ahead + 1
    eng.run()
    eng.close()


def test_time_between_two_steps_is_outside_and_nowhere_else(tiny_model):
    """What the caller does between two ``step()`` calls (here: reads of the
    clock, as ``submit()`` makes one for a request's arrival) is
    ``outside_ms``; no other part sees it."""
    eng = _engine(tiny_model)
    clock = eng._clock = _Ticks()
    eng.submit(list(range(1, 8)), max_new_tokens=12)
    eng.step()
    eng.step()
    plain = _turnaround(eng.step())
    assert plain["outside_ms"] == 1e3           # t_exit to t_enter: one read on
    clock.now += 5.0                            # the caller sleeps 5 ticks
    slept = _turnaround(eng.step())
    assert slept["outside_ms"] == plain["outside_ms"] + 5e3
    assert slept["turnaround_ms"] == plain["turnaround_ms"] + 5e3
    for k in ("commit_ms", "prepare_ms", "result_wait_ms"):
        assert slept[k] == plain[k], k
    eng.close()


def test_a_sleep_between_two_steps_lands_in_outside_on_the_real_clock(tiny_model):
    eng = _engine(tiny_model)
    eng.submit(list(range(1, 8)), max_new_tokens=12)
    for _ in range(3):
        eng.step()
    time.sleep(0.05)
    t = _turnaround(eng.step())
    assert 50.0 <= t["outside_ms"] <= t["turnaround_ms"]
    assert t["commit_ms"] + t["prepare_ms"] + t["result_wait_ms"] < 50.0
    assert t["commit_ms"] + t["outside_ms"] + t["prepare_ms"] == pytest.approx(
        t["turnaround_ms"], abs=1e-6)
    eng.close()


def test_a_step_the_chip_waited_for_work_before_carries_no_turnaround(tiny_model):
    """The first step, every step that runs no program, and the step after
    one that left the engine with no request, whether or not an empty step
    lies between: the chip waited for work there, not for the host."""
    tr = Tracer()
    eng = _engine(tiny_model, tracer=tr)
    fut = eng.submit([1, 2, 3], max_new_tokens=3)
    by, first = _step_spans(tr, eng)
    assert _turnaround(first) == {} and not set(by["serve.stats"][0]) & set(TURNAROUND_STATS)
    assert set(_turnaround(eng.step())) == set(TURNAROUND_STATS)
    fut.result()
    empty = eng.step()                          # nothing to run
    assert empty["programs"] == 0 and _turnaround(empty) == {}
    eng.submit([4, 5, 6], max_new_tokens=3)
    by, after_empty = _step_spans(tr, eng)
    assert after_empty["programs"] == 1 and _turnaround(after_empty) == {}
    assert not set(by["serve.stats"][0]) & set(TURNAROUND_STATS)
    assert set(_turnaround(eng.step())) == set(TURNAROUND_STATS)
    eng.run()                                   # the last step had a program,
    assert not eng.sched.has_work               # and left nothing to turn round to
    eng.submit([7, 8, 9], max_new_tokens=3)     # a server: minutes later
    assert _turnaround(eng.step()) == {}
    assert set(_turnaround(eng.step())) == set(TURNAROUND_STATS)
    eng.close()


def test_the_first_step_after_an_incident_carries_no_turnaround(tiny_model):
    from deepspeed_tpu.serving.engine import ServeStepTimeout
    eng = _engine(tiny_model)
    fut = eng.submit(list(range(1, 5)), max_new_tokens=8)   # one chunk, requeued too
    eng.step()
    assert _turnaround(eng.step())
    eng._recover_incident(ServeStepTimeout("wedged", op="decode", deadline_s=1.0,
                                           step=eng.step_count))
    assert _turnaround(eng.step()) == {}, "the re-jit's compile is no turn-round"
    assert set(_turnaround(eng.step())) == set(TURNAROUND_STATS)
    assert fut.result() is not None
    eng.close()


@pytest.mark.parametrize("timeout_s", [0.0, 30.0], ids=["inline", "bounded-worker"])
def test_turnaround_is_on_the_main_threads_stats_span(tiny_model, timeout_s):
    """With ``serve_step_timeout_s`` the fetch runs on the bounded worker's
    thread, its span on its line (the launch is inline, on the main thread's:
    it does not wait for the chip); ``t_result`` is taken there and carried to
    ``serve.stats``, which the main thread opens: the same keys, in
    ``step()``'s stats and on the span, value for value."""
    import threading
    tr = Tracer()
    eng = _engine(tiny_model, tracer=tr, serve_step_timeout_s=timeout_s)
    eng.submit(list(range(1, 8)), max_new_tokens=5)
    eng.step()                                  # compiles, in the inline launch
    for _ in range(3):
        mark = len(tr.snapshot())
        stats = eng.step()
        recs = {r["name"]: r for r in tr.snapshot()[mark:]}
        fetch = next(r for n, r in recs.items() if n.endswith(".fetch"))
        main = threading.get_ident()
        assert (fetch["tid"] != main) == bool(timeout_s)
        assert recs["serve.decode.dispatch"]["tid"] == main
        assert recs["serve.stats"]["tid"] == main
        on_span = recs["serve.stats"]["args"]
        assert {k: on_span[k] for k in TURNAROUND_STATS} == _turnaround(stats)
        assert set(_turnaround(stats)) == set(TURNAROUND_STATS)
        # the fetch span holds the stamp, whichever thread opened it: the wait
        # the step says is what its program's record says (not ahead: from
        # its own launch)
        assert fetch["args"]["device_ms"] == stats["result_wait_ms"]
    eng.close()


def test_turnaround_is_on_the_profilers_line_a_step(tiny_model, tmp_path):
    from jax.profiler import ProfileData
    eng = _engine(tiny_model)
    fut = eng.submit(list(range(1, 8)), max_new_tokens=5)
    eng.step()
    seen = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        while not fut.done:
            seen.append(_turnaround(eng.step()))
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "plugins/profile/*/*.xplane.pb"))[-1]
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    events = sorted((e.start_ns, dict(e.stats)) for line in host.lines
                    for e in line.events if e.name == "serve.stats")
    assert len(events) == len(seen) >= 4
    for (_, on_span), stats in zip(events, seen):
        assert {k: on_span[k] for k in TURNAROUND_STATS} == pytest.approx(stats)
        assert on_span["dispatched_ahead"] == 0
        assert not {"paged_tile_pages", "cache_bytes_per_token"} & set(on_span)
    eng.close()


def test_registry_times_the_turnaround_and_no_decode_step(tiny_model, tmp_path):
    """One histogram for one: ``serve_turnaround_ms`` where
    ``serve_decode_step_ms`` was, observed in every step that carries the
    stats; ``serve_program_ms`` at every landing; ``serve_step_ms`` a step,
    from the step's own two stamps."""
    from deepspeed_tpu.runtime.config import DeepSpeedTelemetryConfig
    from deepspeed_tpu.telemetry import TelemetryHub
    hub = TelemetryHub.from_config(DeepSpeedTelemetryConfig(
        enabled=True, jsonl_path=str(tmp_path / "t.jsonl"), flush_every=2))
    model, params = tiny_model
    eng = ServingEngine(model, params=params, telemetry=hub,
                        config=DeepSpeedServingConfig(
                            block_size=8, num_blocks=64, max_batch_size=4,
                            prefill_chunk=8, dtype="float32", telemetry_every=2))
    fut = eng.submit(list(range(1, 8)), max_new_tokens=5)
    turns, landed, fetch = [], [], eng._fetch
    eng._fetch = lambda flight: landed.append(fetch(flight)) or landed[-1]
    while not fut.done:
        turns.append(_turnaround(eng.step()))
    hists = hub.registry.snapshot()["histograms"]
    assert "serve_decode_step_ms" not in hists
    assert hists["serve_step_ms"]["count"] == len(turns)
    assert hists["serve_turnaround_ms"]["count"] == len(turns) - 1
    assert hists["serve_turnaround_ms"]["sum"] == pytest.approx(
        sum(t["turnaround_ms"] for t in turns[1:]))
    # a landed program's device time, next to it; a lone request: one a step
    assert hists["serve_program_ms"]["count"] == len(turns)
    assert hists["serve_program_ms"]["sum"] == pytest.approx(sum(ms for _, _, ms in landed))
    # the periodic serve_step record carries the stats to an operator
    hub.flush()
    records = [json.loads(l) for l in open(tmp_path / "t.jsonl")]
    steps = [r for r in records if r.get("kind") == "serve_step"]
    assert steps and all(set(TURNAROUND_STATS) <= set(r) for r in steps)
    assert all("program" in r for r in steps)
    eng.close()
    hub.close()


# ---- the program, not the step: its number, and its own durations -------------- #
def _landings(tr, mark=0):
    """The stats of each landing event since ``mark``, in the order the rows
    landed: a ``fetch`` span that carries the program's record."""
    return [r["args"] for r in tr.snapshot()[mark:]
            if r["name"].endswith(".fetch") and "device_ms" in r["args"]]


def _clocked(tiny_model, **over):
    """A traced engine on the ``_Ticks`` clock with its stamps kept."""
    tr = Tracer()
    eng = _engine(tiny_model, tracer=tr, **over)
    eng._clock = _Ticks()
    return (tr, eng) + _stamped(eng)


def test_a_programs_number_is_on_every_span_that_touches_it_across_two_steps(tiny_model):
    """Programs are numbered from 1 in the order they are launched.  Under a
    backlog a program's dispatch is one step's and its fetch and commits the
    next step's: the number is the same on all of them, on the ``serve.stats``
    of the step that launched it and on the ``serve.first_token`` of each
    request whose first token its row brought."""
    tr = Tracer()
    eng = _engine(tiny_model, tracer=tr, max_batch_size=2)
    futs = [eng.submit(list(range(1, n + 1)), max_new_tokens=4) for n in (3, 12, 5, 6)]
    by_step = []
    while eng.sched.has_work:
        by_step.append(_step_spans(tr, eng))
    touched = {}            # program -> [(step, kind of span)]
    for i, (by, stats) in enumerate(by_step):
        launched = [a["program"] for n, ev in by.items() if n.endswith(".dispatch") for a in ev]
        assert launched == ([stats["program"]] if stats["programs"] else [])
        assert by["serve.stats"][0].get("program") == stats.get("program")
        for name, events in by.items():
            for args in events:
                if name != "serve.stats" and "program" in (args or {}):
                    touched.setdefault(args["program"], []).append((i, name.rsplit(".", 1)[-1]))
    assert sorted(touched) == list(range(1, eng.programs_launched + 1)), "none skipped"
    for number, spans in touched.items():
        kinds = [k for _, k in spans]
        assert kinds[0] == "dispatch" and kinds[1] == "fetch" and "commit" in kinds, number
        assert set(kinds) <= {"dispatch", "fetch", "commit", "first_token"}
    assert any(len({i for i, _ in spans}) == 2 for spans in touched.values()), \
        "launched in one step, landed in the next"
    # each request's first token names the program whose row brought it: the
    # one that carried its prompt's last chunk
    marks = {r["args"]["rid"]: r["args"]["program"] for r in tr.snapshot()
             if r["name"] == "serve.first_token"}
    last_chunk = {}
    for by, _ in by_step:
        for args in by.get("serve.prefill.commit", []):
            last_chunk[args["rid"]] = args["program"]       # the last one stays
    assert marks == last_chunk and set(marks) == {f.request.rid for f in futs}
    eng.close()


def test_a_lone_request_is_never_ahead_and_its_record_is_its_own_wait(tiny_model):
    tr, eng, launches, results = _clocked(tiny_model)
    eng.submit(list(range(1, 8)), max_new_tokens=8)
    for k in range(1, 7):
        stats = eng.step()
        (rec,) = _landings(tr)[k - 1:]
        assert (rec["program"], rec["ahead"], rec["chunk_tokens"]) == (k, 0, 7 * (k == 1))
        assert rec["device_ms"] == (results[-1] - launches[-1]) * 1e3
        assert rec["device_ms"] == stats["result_wait_ms"] if k > 1 else True
        assert stats["program"] == k
        if k == 1:
            assert "host_ms" not in rec, "the chip waited for work, not for the host"
        else:
            assert rec["host_ms"] == stats["turnaround_ms"] == (
                stats["commit_ms"] + stats["outside_ms"] + stats["prepare_ms"])
    eng.close()


def test_under_a_backlog_a_programs_device_time_runs_from_row_to_row(tiny_model):
    """The step that starts being ahead lands nothing; every step after it
    launches program k and lands k-1, whose ``device_ms`` is the period since
    the row before it, not since its own launch; the step in which the
    engine stops being ahead lands two, k-1 then k, and
    k's device time starts at k-1's row though its launch came before that."""
    tr, eng, launches, results = _clocked(tiny_model, max_batch_size=2)
    for n in (3, 4, 5, 6, 7):
        eng.submit(list(range(1, n + 1)), max_new_tokens=6)
    first = eng.step()
    assert first["program"] == 1 and _landings(tr) == []
    stopped = None
    while eng.sched.has_work:
        mark = len(tr.snapshot())
        stats = eng.step()
        landed, k = _landings(tr, mark), stats.get("program")
        if stats["dispatched_ahead"] and len(landed) == 1:
            (rec,) = landed
            assert rec["program"] == k - 1 and eng._flight.number == k
            # its launch came before the row of the program before it, where
            # that one was ahead too: then it ran from that row to its own
            if rec["ahead"]:
                assert launches[-2] < results[-2]
                assert rec["device_ms"] == (results[-1] - results[-2]) * 1e3
        elif stats["dispatched_ahead"]:
            before, own = landed
            assert (before["program"], own["program"]) == (k - 1, k) and stopped is None
            assert own["ahead"] == 1 and launches[-1] < results[-2] < results[-1]
            assert own["device_ms"] == (results[-1] - results[-2]) * 1e3
            assert eng._flight is None
            stopped = k
    assert stopped is not None and eng.steps_dispatched_ahead >= 5
    records = _landings(tr)
    assert [r["program"] for r in records] == list(range(1, eng.programs_launched + 1))
    assert all(r["ahead"] for r in records[1:stopped])
    assert all("host_ms" in r for r in records[1:]) and "host_ms" not in records[0]
    eng.close()


def test_device_time_and_turnaround_sum_to_the_time_between_two_rows(tiny_model):
    """Over consecutive programs, ahead or not: ``sum(device_ms) +
    sum(turnaround_ms) = t_result(last) - t_result(first)``, exactly: the
    chip either had a program of this engine or waited for the host."""
    tr, eng, launches, results = _clocked(tiny_model, max_batch_size=2)
    for n in (3, 12, 5, 6):
        eng.submit(list(range(1, n + 1)), max_new_tokens=7)
    turnaround = {}
    while eng.sched.has_work:
        stats = eng.step()
        if stats["programs"]:
            turnaround[stats["program"]] = stats.get("turnaround_ms")
    records = _landings(tr)
    assert len(records) == len(results) == eng.programs_launched >= 12
    assert {r["ahead"] for r in records} == {0, 1}, "both kinds of step in the run"
    total = sum(r["device_ms"] + turnaround[r["program"]] for r in records[1:])
    assert total == (results[-1] - results[0]) * 1e3
    for r in records[1:]:
        assert (turnaround[r["program"]] == 0.0) == bool(r["ahead"])
    eng.close()


def test_a_drain_before_a_preemption_lands_the_row_as_its_own_event(tiny_model):
    """An arena too small for its demand: a growth that needs a victim lands
    the row in flight first (``_drain`` inside ``serve.grow``), so the step
    launches a program that is NOT ahead and whose device time starts at its
    own launch; every program still lands once, in order."""
    tr, eng, launches, results = _clocked(tiny_model, block_size=4, num_blocks=10,
                                          max_blocks_per_seq=9)
    rng = np.random.default_rng(5)
    for n, new in ((10, 20), (14, 16), (6, 24), (12, 12), (9, 18)):
        eng.submit(list(map(int, rng.integers(1, 128, size=n))), max_new_tokens=new)
    drained_then_launched = 0
    while eng.sched.has_work:
        in_flight = eng._flight
        mark = len(tr.snapshot())
        stats = eng.step()
        names = [r["name"].rsplit(".", 1)[-1] for r in tr.snapshot()[mark:]]
        if in_flight is not None and stats["programs"] and not stats["dispatched_ahead"]:
            # the row landed before this step's dispatch, not behind it
            assert names.index("fetch") < names.index("dispatch")
            drained_then_launched += 1
            own = [r for r in _landings(tr, mark) if r["program"] == stats["program"]]
            if own:                     # (its own row landed in this step too)
                assert own[0]["ahead"] == 0
                assert own[0]["device_ms"] == (results[-1] - launches[-1]) * 1e3
    assert eng.sched.preemption_count > 0 and drained_then_launched > 0
    records = _landings(tr)
    assert [r["program"] for r in records] == list(range(1, eng.programs_launched + 1))
    assert all(r["device_ms"] > 0.0 for r in records)
    eng.close()


def test_the_first_program_after_the_chip_waited_carries_no_host_part(tiny_model):
    """After an empty step, and after an incident's re-jit: the program's
    record is there, with its own wait, and no ``host_ms``; an abandoned
    program leaves no record and its number is not given again."""
    tr, eng, launches, results = _clocked(tiny_model)
    eng.submit([1, 2, 3], max_new_tokens=3).result()
    assert eng.step()["programs"] == 0                      # nothing to run
    eng.submit([4, 5, 6], max_new_tokens=8)
    stats = eng.step()
    rec = _landings(tr)[-1]
    assert rec["program"] == stats["program"] == eng.programs_launched
    assert "host_ms" not in rec and rec["device_ms"] == (results[-1] - launches[-1]) * 1e3
    assert "host_ms" in (eng.step(), _landings(tr)[-1])[1]
    eng.submit(list(range(1, 20)), max_new_tokens=2)        # prompt left: stays ahead
    eng.step()
    abandoned = eng._flight.number
    landed = len(_landings(tr))
    eng._recover_incident(ServeStepTimeout("wedged", op="decode", deadline_s=1.0,
                                           step=eng.step_count))
    assert eng._flight is None and eng.programs_launched == abandoned
    after = eng.step()
    assert after["program"] == abandoned + 1 and not _turnaround(after)
    new = _landings(tr)[landed:]
    assert abandoned not in [r["program"] for r in _landings(tr)]
    assert all("host_ms" not in r for r in new if r["program"] == abandoned + 1)
    eng.run()
    assert [r["program"] for r in _landings(tr)] == [
        k for k in range(1, eng.programs_launched + 1) if k != abandoned]
    eng.close()


def test_a_wedged_fetch_leaves_no_record_when_its_worker_comes_back(tiny_model):
    """Under ``serve_step_timeout_s`` a wedged fetch is abandoned with its
    worker; released, the worker ends its span, and writes no record on it:
    the program's tokens are computed again under other numbers."""
    import threading
    from deepspeed_tpu.testing import fault_injection
    tr = Tracer()
    eng = _engine(tiny_model, tracer=tr, max_batch_size=2, serve_step_timeout_s=0.5)
    for n in (5, 12, 7, 4):
        eng.submit(list(range(1, n + 1)), max_new_tokens=6)
    for _ in range(4):
        eng.step()
    wedged, behind = eng._flight.number, eng._flight.number + 1
    fault_injection.install_plan([{"site": "serve.step", "action": "wedge", "on_hit": 1}])
    try:
        with pytest.raises(ServeStepTimeout):
            eng.step()
    finally:
        fault_injection.clear_plan()
    eng.run()
    for t in threading.enumerate():
        if t.name.startswith("ds-tpu-bounded") and t is not threading.current_thread():
            t.join(timeout=0.2)     # (the engine's own worker stays: a daemon)
    fetched = [r["args"] for r in tr.snapshot() if r["name"].endswith(".fetch")]
    assert wedged in [a["program"] for a in fetched], "the abandoned span did end"
    landed = [r["program"] for r in _landings(tr)]
    assert wedged not in landed and behind not in landed
    assert landed == [k for k in range(1, eng.programs_launched + 1)
                      if k not in (wedged, behind)]
    eng.close()


def test_the_record_is_on_the_fetching_threads_line_and_the_number_on_the_main(tiny_model):
    import threading
    tr = Tracer()
    eng = _engine(tiny_model, tracer=tr, serve_step_timeout_s=30.0)
    eng.submit(list(range(1, 8)), max_new_tokens=5)
    eng.step()                                  # compiles, in the inline launch
    main = threading.get_ident()
    for _ in range(3):
        mark = len(tr.snapshot())
        stats = eng.step()
        recs = {r["name"]: r for r in tr.snapshot()[mark:]}
        fetch = recs["serve.decode.fetch"]
        assert fetch["tid"] != main and set(PROGRAM_STATS) <= set(fetch["args"])
        assert fetch["args"]["program"] == stats["program"]
        on_stats = recs["serve.stats"]
        assert on_stats["tid"] == main and on_stats["args"]["program"] == stats["program"]
        assert recs["serve.decode.commit"]["tid"] == main
        assert recs["serve.decode.commit"]["args"]["program"] == stats["program"]
    eng.close()


def test_the_record_is_on_the_profilers_line_a_landed_program(tiny_model, tmp_path):
    from jax.profiler import ProfileData
    eng = _engine(tiny_model, max_batch_size=2)
    for n in (3, 12, 5):
        eng.submit(list(range(1, n + 1)), max_new_tokens=4)
    eng.step()                                  # compiles; program 1 in flight
    seen = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        while eng.sched.has_work:
            seen.append(eng.step())
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "plugins/profile/*/*.xplane.pb"))[-1]
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    events = [(e.start_ns, e.name, dict(e.stats)) for line in host.lines for e in line.events]
    landings = [st for _, n, st in sorted(events) if n.endswith(".fetch")]
    assert all(set(PROGRAM_STATS) - {"host_ms"} <= set(st) for st in landings)
    assert [st["program"] for st in landings] == list(range(1, eng.programs_launched + 1))
    stats = [st for _, n, st in sorted(events) if n == "serve.stats"]
    assert [st.get("program") for st in stats] == [s.get("program") for s in seen]
    first = [st for _, n, st in events if n == "serve.first_token"]
    assert len(first) == 3 and all(1 <= st["program"] <= eng.programs_launched for st in first)
    eng.close()



@pytest.mark.parametrize("wide", [16, 4])
def test_table_stats_over_plain_decode(tiny_model, wide):
    """``table_edits``, ``table_reloads`` and ``upload_bytes`` in ``step()``'s
    stats and on ``serve.stats``.  Over plain decode nothing is reloaded and
    a row gains an entry every ``block_size`` tokens; the one upload has one
    size whatever the step holds, and it does not follow the ARENA (a table
    ``wide`` blocks wide costs an entry a column in the room kept for one
    admission, and no more: the parent uploaded ``rows x wide`` a step)."""
    tr = Tracer()
    eng = _engine(tiny_model, tracer=tr, max_blocks_per_seq=wide,
                  num_blocks=64 if wide == 16 else 256)
    lay = eng._layout
    assert lay.edits == wide + (4 + 1 + 1)      # an admission, 4 rows, a chunk
    assert 4 * lay.packed_size == 4 * (4 * 12 + 4 + 2 * lay.edits)
    futs = [eng.submit(list(range(1, n)), max_new_tokens=20) for n in (4, 7, 10)]
    seen = []
    while not all(f.done for f in futs):
        by, stats = _step_spans(tr, eng)
        (on_span,) = by["serve.stats"]
        assert {k: on_span[k] for k in ("table_edits", "table_reloads",
                                        "upload_bytes")} == {
            k: stats[k] for k in ("table_edits", "table_reloads", "upload_bytes")}
        seen.append(stats)
    assert {s["upload_bytes"] for s in seen} == {4 * lay.packed_size}
    assert not any(s["table_reloads"] for s in seen)
    # prompts of 3, 6 and 9 tokens are handed 1, 1 and 2 blocks of 8 as they
    # are bound; with 20 new tokens each they cross into a next block 2, 3
    # and 2 times more; a finished request's row goes back to trash with the
    # next program, which the last to finish does not see
    assert sum(s["table_edits"] for s in seen) == 4 + (2 + 3 + 2) + 2
    assert seen[0]["table_edits"] == 4 and max(
        s["table_edits"] for s in seen[1:]) <= 2
    eng.close()


def test_a_chunk_beside_decode_rows_is_one_dispatch_and_one_fetch(tiny_model):
    """The step's one program is named for its decode rows; its ``batch``
    counts every row that carries a request, the chunk's tokens included
    (``benchmarks/readers/moe.py`` takes it for the rows of one bank call).
    Its row is fetched once, under the same name and stats: behind the next
    step's launch where prompt was left behind its chunk."""
    tr = Tracer()
    eng = _engine(tiny_model, tracer=tr)
    first = eng.submit([5, 6, 7], max_new_tokens=8)
    eng.step()                                  # its whole prompt: first token
    late = eng.submit(list(range(1, 12)), max_new_tokens=4)
    chunks = [{"rid": late.request.rid, "start": start, "tokens": n}
              for start, n in ((0, 8), (8, 3))]
    pairs = [{"batch": 1 + c["tokens"], "chunk_tokens": c["tokens"], "program": 2 + i}
             for i, c in enumerate(chunks)]
    commits = [dict(c, program=pair["program"]) for c, pair in zip(chunks, pairs)]
    fetched = ([], pairs)                       # the second step fetches both rows
    for chunk, pair, rows in zip(chunks, pairs, fetched):
        by, stats = _step_spans(tr, eng)
        assert not any(n.endswith((".dispatch", ".fetch")) and "prefill" in n for n in by)
        assert by["serve.decode.dispatch"] == [pair]
        assert _less_record(by.get("serve.decode.fetch", [])) == rows
        assert by["serve.prefill.build"] == [chunk]
        assert by.get("serve.prefill.commit", []) == (commits if rows else [])
        assert by["serve.decode.build"] == [{"batch": 1}]
        assert by.get("serve.decode.commit", []) == [
            {"batch": 1, "program": pair["program"]} for pair in rows]
        assert (stats["programs"], stats["prefill_tokens"], stats["decode_batch"]) \
            == (1, chunk["tokens"], 1)
    assert len(late.request.generated) == 1, "decodes from the next step"
    assert len(first.request.generated) == 3
    eng.run()
    assert eng.compiled_programs() == 1
    eng.close()


def test_request_is_stamped_where_it_happens(tiny_model):
    eng = _engine(tiny_model)
    a = eng.submit(list(range(1, 20)), max_new_tokens=4).request
    b = eng.submit(list(range(1, 10)), max_new_tokens=4).request
    assert a.admitted_at is None and a.prefill_started_at is None
    eng.step()                                  # both admitted; a's first chunk
    assert a.arrival <= a.admitted_at <= a.prefill_started_at
    assert b.admitted_at == a.admitted_at and b.prefill_started_at is None
    eng.run()
    for r in (a, b):
        assert r.arrival <= r.admitted_at <= r.prefill_started_at <= r.first_token_at
    assert b.prefill_started_at > a.prefill_started_at    # one lane: b waited
    assert (a.prefill_chunks, b.prefill_chunks) == (3, 2)
    eng.close()


def test_preempted_request_is_stamped_anew(tiny_model):
    eng = _engine(tiny_model)
    r = eng.submit(list(range(1, 12)), max_new_tokens=8).request
    while r.first_token_at is None:
        eng.step()
    first = (r.admitted_at, r.prefill_started_at, r.first_token_at)
    eng.sched.preempt(r)
    assert r.preemptions == 1
    eng.run()
    assert r.admitted_at > first[0] and r.prefill_started_at > first[1]
    assert r.admitted_at <= r.prefill_started_at
    assert r.first_token_at == first[2], "the first token came once"
    assert r.prefill_chunks == 2                # 11 + 1 tokens of context again
    eng.close()


def test_first_token_stats_sum_to_the_programs_ttft(tiny_model):
    tr = Tracer()
    eng = _engine(tiny_model, tracer=tr)
    futs = [eng.submit(list(range(1, n)), max_new_tokens=3) for n in (25, 6, 14)]
    eng.run()
    marks = [r for r in tr.snapshot() if r["name"] == "serve.first_token"]
    assert len(marks) == len(futs), "one a request"
    for f in futs:
        r = f.request
        st = next(m["args"] for m in marks if m["args"]["rid"] == r.rid)
        assert st["queue_ms"] + st["lane_wait_ms"] + st["prefill_ms"] == pytest.approx(
            (r.first_token_at - r.arrival) * 1e3, abs=1e-6)
        assert min(st["queue_ms"], st["lane_wait_ms"], st["prefill_ms"]) >= 0.0
        assert st["chunks"] == r.prefill_chunks == -(-len(r.prompt) // 8)
    eng.close()


def test_first_token_is_on_the_profilers_line_with_its_stats(tiny_model, tmp_path):
    eng = _engine(tiny_model)
    eng.submit([1, 2, 3], max_new_tokens=2).result()      # the program warm
    fut = eng.submit(list(range(1, 12)), max_new_tokens=2)
    events = _capture(tmp_path, fut.result)
    assert set(SERVE_STEP_SPANS) <= set(events)
    _, st = events["serve.first_token"]
    r = fut.request
    assert st["rid"] == r.rid and st["chunks"] == 2
    assert st["queue_ms"] + st["lane_wait_ms"] + st["prefill_ms"] == pytest.approx(
        (r.first_token_at - r.arrival) * 1e3, abs=1e-6)
    eng.close()
