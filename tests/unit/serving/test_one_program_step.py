"""One program a serve step: the prompt chunk rides with the decode rows.

A step builds ONE upload for ``[max_batch_size + prefill_chunk, 1]`` tokens,
dispatches one program (whose attention takes the chunk's tokens packed,
several queries a row: ``test_packed_chunk.py``), and fetches each program's
token row once and commits the chunk and the decode rows from it, in the same
step or behind the next step's launch (``test_dispatch_ahead.py``).  Whatever the traffic puts in the rows (a multi-chunk
prompt arriving while others decode, a prefix-cache hit, a preemption with
recompute, a restore from a snapshot) and whatever the model (learned
positions, ALiBi, rope with grouped K/V heads), every request's tokens are
what sequential ``generate()`` gives in float32, and one program is compiled.
"""

import json

import numpy as np
import pytest

import jax

from deepspeed_tpu.models.gpt import GPT, GPTConfig
from deepspeed_tpu.ops.pallas import decode_attention as da
from deepspeed_tpu.serving import DeepSpeedServingConfig, ServingEngine
from deepspeed_tpu.serving.engine import unpack_step
from deepspeed_tpu.telemetry.tracing import Tracer
from tests.unit.serving_helpers import sequential_tokens, tiny_engine

MODELS = {
    "learned": {},
    "alibi": {"position_encoding": "alibi"},
    "rope_gqa": {"position_encoding": "rope", "norm": "rmsnorm", "n_kv_head": 2},
}
SLOTS, CHUNK = 4, 8


@pytest.fixture(scope="module", params=sorted(MODELS))
def model_and_params(request):
    model = GPT(GPTConfig(vocab_size=128, n_positions=128, n_embd=32, n_layer=2,
                          n_head=4, dtype="float32", **MODELS[request.param]))
    return model, model.init_params(jax.random.PRNGKey(3))


# sequential ``generate()``, one program a model (``serving_helpers.py``)
reference = sequential_tokens
SERVING = dict(block_size=8, num_blocks=64, max_batch_size=SLOTS,
               prefill_chunk=CHUNK, dtype="float32")


def engine(model_and_params, tracer=None, **over):
    """A NEW engine: for a prefix cache's pins, counters read as totals, a
    snapshot taken mid-flight, a tracer, a stand-in for ``_dispatch``."""
    model, params = model_and_params
    return ServingEngine(model, config=DeepSpeedServingConfig(**dict(SERVING, **over)),
                         params=params, tracer=tracer)


def kept_engine(model_and_params, **over):
    """The worker's engine of this configuration, idle (``tiny_engine``)."""
    return tiny_engine(*model_and_params, **dict(SERVING, **over))


def prompts_of(seed, lens):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, 128, size=n))) for n in lens]


# ---- what rides in the rows --------------------------------------------------- #
def multi_chunk_arrival(mp):
    """A prompt of four chunks arrives while two requests decode: each of its
    chunks shares a program with their rows."""
    eng = kept_engine(mp)
    prompts, new = prompts_of(0, (5, 9, 29, 3)), (14, 12, 6, 9)
    futs = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts[:2], new)]
    for _ in range(4):
        eng.step()
    futs += [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts[2:], new[2:])]
    mixed = 0
    while eng.sched.has_work:
        st = eng.step()
        mixed += bool(st["decode_batch"] and st["prefill_tokens"])
    assert mixed >= 5 and futs[2].request.prefill_chunks == 4
    return eng, prompts, new, futs


def prefix_hit(mp):
    """The second request adopts the first's two full blocks and prefills
    only its tail, beside the first's decode row."""
    eng = engine(mp, prefix_cache=True)
    system = prompts_of(1, (16,))[0]
    prompts = [system + t for t in prompts_of(2, (3, 6, 11))]
    new = (10, 8, 7)
    futs = [eng.submit(prompts[0], max_new_tokens=new[0])]
    for _ in range(4):
        eng.step()
    futs += [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts[1:], new[1:])]
    eng.run()
    assert eng.prefix.hits == 2
    assert [f.request.prefill_chunks for f in futs] == [3, 1, 2]
    return eng, prompts, new, futs


def preempt_recompute(mp):
    """An arena of 36 tokens under 150 of demand: requests are evicted in the
    growth pass (before the step's chunk is chosen) and recomputed."""
    eng = engine(mp, block_size=4, num_blocks=10, max_blocks_per_seq=9)
    prompts, new = prompts_of(4, (10, 14, 6, 12, 9)), (20, 16, 24, 12, 18)
    futs = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, new)]
    eng.run()
    assert eng.sched.preemption_count > 0 and eng.alloc.eviction_count > 0
    return eng, prompts, new, futs


def snapshot_restore(mp):
    """Snapshot mid-flight (one request decoding, one between two chunks, one
    waiting); a fresh engine re-prefills prompt + generated and goes on."""
    old = engine(mp, max_batch_size=2)
    prompts, new = prompts_of(5, (5, 19, 8)), (10, 6, 12)
    for p, m in zip(prompts, new):
        old.submit(p, max_new_tokens=m)
    for _ in range(3):
        old.step()
    states = lambda: sorted((r.prefilled, len(r.generated))
                            for r in old.sched.active.values())
    # a request waits, so the third program is in flight: its positions have
    # moved, its token has not landed; the snapshot lands it first
    assert states() == [(7, 2), (16, 0)] and len(old.sched.waiting) == 1
    snap = json.loads(json.dumps(old.snapshot()))
    assert states() == [(7, 3), (16, 0)]
    old.close()
    eng = kept_engine(mp, max_batch_size=2)
    futs = eng.restore(snap)
    eng.run()
    return eng, prompts, new, futs


TRAFFIC = [multi_chunk_arrival, prefix_hit, preempt_recompute, snapshot_restore]


@pytest.mark.parametrize("traffic", TRAFFIC, ids=lambda f: f.__name__)
def test_mixed_traffic_is_token_identical_in_one_program(model_and_params, traffic):
    model, params = model_and_params
    eng, prompts, new, futs = traffic(model_and_params)
    for p, m, f in zip(prompts, new, futs):
        assert f.done and f.token_ids == reference(model, params, p, m)
    assert eng.compiled_programs() == 1
    eng.alloc.check_consistent()
    if traffic in (prefix_hit, preempt_recompute):
        eng.close()                 # their own; the others' is the worker's


def test_a_host_that_polls_for_the_token_row_serves_the_same_tokens(model_and_params):
    """``poll_token_row``: the dispatching thread spins on the row's readiness
    and then reads it; the tokens, the stamps and the one program are those
    of a host that sleeps on it."""
    model, params = model_and_params
    eng = kept_engine(model_and_params, poll_token_row=True)
    prompts, new = prompts_of(9, (5, 19, 8)), (10, 6, 12)
    futs = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, new)]
    waits = []
    while eng.sched.has_work:
        st = eng.step()
        if st["programs"]:
            waits.append(st.get("result_wait_ms"))
    for p, m, f in zip(prompts, new, futs):
        assert f.done and f.token_ids == reference(model, params, p, m)
    # (the first step's program stays in flight, prompt being left: it waits
    # for no row, and the second waits for the first's)
    assert all(w is not None and w >= 0 for w in waits[1:]) and len(waits) > 8
    assert eng.compiled_programs() == 1


# ---- the step itself ------------------------------------------------------------ #
def test_the_program_has_one_shape_whatever_the_step_holds(model_and_params,
                                                           monkeypatch):
    """Chunk alone, chunk beside decode rows, decode rows alone: the same one
    upload, and the program makes of it the same ``[slots + chunk, 1]``
    tokens, told apart only by what is in them; its attention runs the slots
    a query a row and the chunk as ONE row of its 8 queries (what the rule
    gives these shapes), never ``slots + chunk`` rows."""
    eng = engine(model_and_params)
    shapes, kinds, attended = set(), set(), set()
    dispatch = eng._dispatch
    unpack = jax.jit(unpack_step, static_argnums=0)
    reference = da.paged_attention_reference
    monkeypatch.setattr(da, "paged_attention_reference", lambda q, *a, **kw: (
        attended.add(q.shape[:2]), reference(q, *a, **kw))[1])

    def spy(phase, packed, reload, stats):
        assert reload is None
        ids, positions, _, (tables,), (wb,), wo = jax.tree.map(      # one layer group
            np.asarray, unpack(eng._layout, packed, eng._previous, eng._tables))
        assert (ids >= 0).all(), "a row that names a token of the program before got it"
        shapes.add((packed.shape,) + tuple(
            a.shape for a in (ids, positions, tables, wb, wo)))
        live = wb[:, 0] != 0
        assert int(live.sum()) == stats.get("batch", stats["chunk_tokens"])
        assert not tables[~live].any() and not wo[~live].any(), "idle rows: trash only"
        n = stats["chunk_tokens"]
        assert live[SLOTS:SLOTS + n].all() and not live[SLOTS + n:].any()
        if n:                                   # a token a row, one table repeated
            assert (np.diff(positions[SLOTS:SLOTS + n]) == 1).all()
            assert (tables[SLOTS:SLOTS + n] == tables[SLOTS]).all()
        kinds.add((phase, bool(n), bool(live[:SLOTS].any())))
        return dispatch(phase, packed, reload, stats)

    eng._dispatch = spy
    eng.submit(prompts_of(6, (11,))[0], max_new_tokens=5)
    eng.step()
    eng.submit(prompts_of(7, (13,))[0], max_new_tokens=3)
    eng.run()
    R = SLOTS + CHUNK
    assert shapes == {((eng._layout.packed_size,), (R, 1), (R,),
                       (R, eng.max_blocks_per_seq), (R, 1), (R, 1))}
    assert kinds == {("prefill", True, False), ("decode", True, True),
                     ("decode", False, True)}
    assert attended == {(SLOTS, 1), (1, CHUNK)}
    assert (eng.chunk_queries_per_row, eng.attention_rows) == (CHUNK, SLOTS + 1)
    assert eng.compiled_programs() == 1
    eng.close()


def test_a_step_opens_at_most_one_dispatch_and_every_program_one_fetch(model_and_params):
    """A step launches at most one program, under the span named for what the
    program carries; every program's row is fetched ONCE, under the same
    name and stats (its number among them; the fetch adds the program's own
    record, ``PROGRAM_STATS``), in the step that launched it or (a prompt with
    chunks left: dispatched ahead) behind the next step's launch."""
    from deepspeed_tpu.serving.engine import PROGRAM_STATS
    at_launch = lambda args: {k: v for k, v in args.items()
                              if k not in PROGRAM_STATS}
    tr = Tracer()
    eng = engine(model_and_params, tracer=tr)
    for p, m in zip(prompts_of(8, (3, 20, 9)), (9, 4, 6)):
        eng.submit(p, max_new_tokens=m)
    seen, dispatched, fetched, ahead = set(), [], [], 0
    while eng.sched.has_work:
        mark = len(tr.snapshot())
        st = eng.step()
        spans = [(r["name"], r["args"]) for r in tr.snapshot()[mark:]]
        launches = [(n, a) for n, a in spans if n.endswith(".dispatch")]
        fetches = [(n, a) for n, a in spans if n.endswith(".fetch")]
        phase = "decode" if st["decode_batch"] else "prefill"
        assert [n for n, _ in launches] == [f"serve.{phase}.dispatch"] * st["programs"]
        assert len(fetches) <= 2
        for _, args in launches:
            assert args["chunk_tokens"] == st["prefill_tokens"]
            if phase == "decode":
                assert args["batch"] == st["decode_batch"] + st["prefill_tokens"]
            else:
                assert args["tokens"] == st["prefill_tokens"]
        if st["dispatched_ahead"]:          # the launch first, then the row before
            names = [n for n, _ in spans]
            assert names.index(launches[0][0]) < names.index(fetches[0][0])
        dispatched += [(n.rsplit(".", 1)[0], a) for n, a in launches]
        fetched += [(n.rsplit(".", 1)[0], at_launch(a)) for n, a in fetches]
        ahead += st["dispatched_ahead"]
        seen.add((phase, bool(st["prefill_tokens"])))
    assert fetched == dispatched and ahead == eng.steps_dispatched_ahead >= 2
    assert [a["program"] for _, a in dispatched] == list(range(1, len(dispatched) + 1))
    assert seen == {("prefill", True), ("decode", True), ("decode", False)}
    eng.close()


def test_the_last_chunk_gives_the_first_token_and_decode_starts_a_step_later(
        model_and_params):
    model, params = model_and_params
    eng = kept_engine(model_and_params)
    prompt = prompts_of(9, (CHUNK + 3,))[0]
    r = eng.submit(prompt, max_new_tokens=4).request
    got = []
    while eng.sched.has_work:
        eng.step()
        got.append((r.prefilled, len(r.generated)))
    n = len(prompt)
    # (the first chunk stays in flight: there is prompt left behind it)
    assert got == [(CHUNK, 0), (n, 1), (n + 1, 2), (n + 2, 3), (n + 3, 4)]
    assert r.generated == reference(model, params, prompt, 4)
