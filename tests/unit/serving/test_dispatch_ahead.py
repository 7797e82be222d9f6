"""The next serve step is dispatched while this one runs.

Under a backlog ``ServingEngine.step()`` launches program n+1 BEFORE it
fetches program n's token row: n+1 takes n's token array on the device, and a
row whose token the host has not seen says which entry of it (``-(1 + i)``,
``unpack_step``).  Whether a step is dispatched ahead follows from what the
scheduler observes (a queue, no free slot, prompt left), so the oracle needs
no switch: a request served ALONE, with free slots and an empty queue, is
served launch, fetch, commit by the rule itself, and every request of a
backlog must get the tokens it gets alone (and the dense ``generate()``'s,
where the model has one).  Whatever is unusual lands the row in flight first
and loses no token.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.gpt import (GPT, GPTConfig, olmoe_config,
                                      smallthinker_config, zaya_config)
from deepspeed_tpu.serving import DeepSpeedServingConfig, ServingEngine
from deepspeed_tpu.serving.engine import (ServeStepTimeout, StepLayout,
                                          unpack_step)
from deepspeed_tpu.serving.kv_cache import PagedKVAllocator
from deepspeed_tpu.serving.scheduler import EXPIRED
from deepspeed_tpu.testing import fault_injection
from tests.unit.serving_helpers import (  # noqa: F401  (a fixture among them)
    deadline_on_the_wedge_alone, idle, sequential_tokens, tiny_engine)

V, SLOTS, CHUNK = 128, 3, 8
SERVING = dict(block_size=8, num_blocks=96, max_batch_size=SLOTS,
               prefill_chunk=CHUNK, dtype="float32")
FAMILIES = {
    "gpt2": lambda: GPTConfig(vocab_size=V, n_positions=128, n_embd=32, n_layer=2,
                              n_head=4, dtype="float32"),
    "olmoe": lambda: olmoe_config(vocab_size=V, n_positions=128, n_embd=32,
                                  n_layer=2, n_head=4, intermediate_size=16,
                                  num_experts=4, top_k=2, dtype=jnp.float32),
    # window and full layers: the ring gives pages back in ``serve.grow``
    # while the program that still reads them is in flight
    "smallthinker_ring": lambda: smallthinker_config(
        vocab_size=V, n_positions=128, n_embd=32, n_layer=4, n_head=4,
        n_kv_head=2, head_dim=8, intermediate_size=16, num_experts=4, top_k=2,
        window=12, dtype=jnp.float32),
    # a hybrid stack: the convolutions' state a slot (``aux``) rides the
    # program, donated from one to the next like the arena
    "zaya_hybrid_aux": lambda: zaya_config(
        vocab_size=V, n_positions=128, n_embd=64, n_layer=3, n_head=4,
        n_kv_head=2, head_dim=16, intermediate_size=32, num_experts=4,
        router_hidden=16, dtype="float32"),
}
HAS_GENERATE = ("gpt2",)            # the dense sequential path, as an oracle
# (prompt tokens, new tokens): one, two and three chunks, a prompt that ends
# on a chunk's edge, and requests that end after one and two tokens
REQUESTS = [(5, 9), (19, 6), (8, 1), (3, 14), (16, 2), (11, 7), (2, 11)]


@pytest.fixture(scope="module")
def models():
    made = {}

    def get(name):
        if name not in made:
            model = GPT(FAMILIES[name]())
            made[name] = (model, model.init_params(jax.random.PRNGKey(11)))
        return made[name]
    return get


def engine(mp, **over):
    """A NEW engine: the tests below read its counters as totals, count its
    programs from zero, stand in its ``_dispatch`` and ``_drain``, wedge it
    or close it with a row in flight."""
    model, params = mp
    return ServingEngine(model, config=DeepSpeedServingConfig(**dict(SERVING, **over)),
                         params=params)


def prompts_of(seed, lens):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, V, size=n))) for n in lens]


def alone(mp, prompt, new, **over):
    """The request with the engine to itself: free slots, an empty queue,
    which an idle engine IS (``tiny_engine``: the worker's engine of this
    configuration, one compile for every request that asks).  The rule
    dispatches a step ahead only while prompt is left behind its chunk; from
    the last chunk on every step is launch, fetch, commit."""
    eng = tiny_engine(*mp, **dict(SERVING, **over))
    fut = eng.submit(prompt, max_new_tokens=new)
    ahead = []
    while not fut.done:
        ahead.append(eng.step()["dispatched_ahead"])
    chunks = -(-len(prompt) // CHUNK)
    assert ahead[:chunks] == [0] + [1] * (chunks - 1) and not any(ahead[chunks:])
    assert idle(eng)
    return fut.token_ids


def drive(eng):
    """Step until nothing is left; -> each step's stats.  ``has_work`` holds
    as long as a row is in flight, so the loop lands it by itself."""
    stats = []
    while eng.sched.has_work:
        stats.append(eng.step())
        assert eng._flight is None or eng.sched.has_work
    assert eng._flight is None
    return stats


# ---- the property: a backlog's tokens are those each request gets alone --------- #
@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_backlog_serves_every_request_the_tokens_it_gets_alone(models, family):
    mp = models(family)
    model, params = mp
    prompts = prompts_of(1, [p for p, _ in REQUESTS])
    eng = engine(mp)
    futs = [eng.submit(p, max_new_tokens=n) for p, (_, n) in zip(prompts, REQUESTS)]
    stats = drive(eng)
    for prompt, (_, new), f in zip(prompts, REQUESTS, futs):
        assert f.done and len(f.token_ids) == new
        assert f.token_ids == alone(mp, prompt, new), (family, len(prompt), new)
        if family in HAS_GENERATE:
            assert f.token_ids == sequential_tokens(model, params, prompt, new)
    ran = [s for s in stats if s["programs"]]
    ahead = sum(s["dispatched_ahead"] for s in ran)
    # seven requests on three slots: a queue until the last is admitted, and
    # prompt left for a while after; every step of that stretch but the first
    assert ahead == eng.steps_dispatched_ahead >= len(ran) // 2
    assert [s["dispatched_ahead"] for s in ran[:8]] == [0] + [1] * 7
    assert eng.compiled_programs() == 1
    assert eng.tokens_generated == sum(n for _, n in REQUESTS)
    eng.alloc.check_consistent()
    eng.close()


# ---- what a row in flight looks like to the next program ------------------------ #
def test_unpack_step_takes_a_negative_token_from_the_program_before():
    alloc = PagedKVAllocator(16, 8, 4)
    lay = StepLayout.of(alloc, slots=2, chunk=4)
    packed = np.zeros((lay.packed_size,), np.int32)
    packed[4 * lay.rows + lay.slots::2] = lay.state_size        # no edit
    rows = packed[:4 * lay.rows].reshape(lay.rows, 4)
    rows[:, 0] = [-1 - 1, 77, -1 - 5, 9, 0, 0]      # previous[1], 77, previous[5], 9
    previous = jnp.asarray([40, 41, 42, 43, 44, 45, 1000, 1001], jnp.int32)  # rows, counts
    state = jnp.zeros((lay.state_size,), jnp.int32)
    ids = unpack_step(lay, jnp.asarray(packed), previous, state)[0]
    assert np.asarray(ids)[:, 0].tolist() == [41, 77, 45, 9, 0, 0]


class Rows:
    """Stands in ``eng._dispatch``: keeps every program's ``[rows, 4]`` upload
    (token, position, slot, live) and what slot each request held."""

    def __init__(self, eng):
        self.eng, self.inner, self.programs = eng, eng._dispatch, []
        eng._dispatch = self

    def __call__(self, phase, packed, reload, stats):
        lay = self.eng._layout
        self.programs.append((
            packed[:4 * lay.rows].reshape(lay.rows, 4).copy(),
            {r.rid: r.slot for r in self.eng.sched.active.values()},
            self.eng._flight is not None))
        return self.inner(phase, packed, reload, stats)


def test_the_chunks_last_row_and_a_decode_row_feed_the_next_program(models):
    """A prompt of two chunks beside a decoding sequence, with a queue behind
    them.  The sequence's decode row of program n+1 names its own slot's row
    of n; the request whose prompt ended in n's chunk names row ``slots +
    n_chunk - 1``; a token the host has (the first program after a fetch)
    rides as itself."""
    eng = engine(models("gpt2"), max_batch_size=2)
    watch = Rows(eng)
    a, b, c = (eng.submit(p, max_new_tokens=8).request
               for p in prompts_of(2, (4, 11, 6)))
    for _ in range(4):
        eng.step()
    (r0, _, _), (r1, _, f1), (r2, _, f2), (r3, held, f3) = watch.programs[:4]
    assert (f1, f2, f3) == (True, True, True)
    sa, sb = held[a.rid], held[b.rid]
    # program 0: a's prompt (one chunk, 4 tokens); 1: a decodes from row
    # ``slots + 4 - 1`` of 0, b's first chunk; 2: a from its own slot's row
    # of 1, b's last chunk (3 tokens); 3: a again, b from ``slots + 3 - 1``
    assert r0[2:6, 0].tolist() == a.prompt and not r0[:2, 3].any()
    assert r1[sa].tolist() == [-1 - (2 + 4 - 1), 4, sa, 1]
    assert r1[2:10, 0].tolist() == b.prompt[:8]
    assert r2[sa].tolist() == [-1 - sa, 5, sa, 1]
    assert r2[2:5, 0].tolist() == b.prompt[8:] and not r2[5:, 3].any()
    assert r3[sa].tolist() == [-1 - sa, 6, sa, 1]
    assert r3[sb].tolist() == [-1 - (2 + 3 - 1), 11, sb, 1]
    drive(eng)
    for r in (a, b, c):
        assert r.generated == alone(models("gpt2"), r.prompt, 8)
    eng.close()


def test_a_request_that_ends_by_max_new_tokens_has_no_row_in_the_next_program(models):
    """The host knows without the row that a request's last token is in
    flight: the next program has no row for it, it gets no token past its
    last, and it holds its slot until that token is committed."""
    mp = models("gpt2")
    eng = engine(mp, max_batch_size=2)
    watch = Rows(eng)
    prompts = prompts_of(3, (4, 5, 6, 7))
    short, long_, *_ = futs = [eng.submit(p, max_new_tokens=n)
                               for p, n in zip(prompts, (3, 9, 4, 5))]
    slot = None
    while not short.done:
        eng.step()
        slot = short.request.slot if short.request.slot >= 0 else slot
        if len(short.request.generated) == 2 and eng._ends_in_flight(short.request):
            # its third token is in flight: it still holds its slot ...
            assert eng.sched.active[slot] is short.request and eng.sched.waiting
            at = len(watch.programs)
    drive(eng)
    # ... and the program launched next carried nothing in its row
    assert not watch.programs[at][0][slot, 3], "no row past the last token"
    for p, n, f in zip(prompts, (3, 9, 4, 5), futs):
        assert len(f.token_ids) == n and f.token_ids == alone(mp, p, n)
    eng.close()


def test_an_eos_is_seen_a_step_late_and_the_extra_token_is_dropped(models):
    mp = models("gpt2")
    prompts = prompts_of(4, (6, 9, 4, 12, 5))
    free = [alone(mp, p, 12) for p in prompts]
    # a token that some request generates in mid-stream ends it there
    eos = next(t for out in free for t in out[2:9])
    want = [out[:out.index(eos) + 1] if eos in out else out for out in free]
    assert any(len(w) < 12 for w in want)
    for p, w in zip(prompts, want):             # alone, the EOS is seen at once
        assert alone(mp, p, 12, eos_token_id=eos) == w
    eng = engine(mp, max_batch_size=2, eos_token_id=eos)
    futs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    stats = drive(eng)
    assert [f.token_ids for f in futs] == want, "never a token past the EOS"
    assert eng.tokens_generated == sum(map(len, want))
    # the row that ran for nothing: a step whose program held more rows than
    # the tokens that were committed from it
    rows = sum(s["decode_batch"] for s in stats) + len(prompts)   # a first token each
    assert rows > eng.tokens_generated
    eng.close()


# ---- whatever is unusual lands the row in flight first -------------------------- #
def counting_drains(eng):
    """``eng._drain`` wrapped: -> the list of whether each call found a row
    (every step calls it behind its launch; what is unusual, before)."""
    found, inner = [], eng._drain

    def drain():
        found.append(eng._flight is not None)
        return inner()
    eng._drain = drain
    return found


def test_a_preemption_lands_the_row_in_flight_first(models):
    """An arena of 36 tokens under 150 of demand: a growth that finds no free
    page needs a victim, and no victim is chosen while a row is in flight."""
    mp = models("gpt2")
    eng = engine(mp, block_size=4, num_blocks=10, max_blocks_per_seq=9,
                 max_batch_size=4)
    found, in_flight_at_preempt = counting_drains(eng), []
    hook = eng.sched.on_preempt
    eng.sched.on_preempt = lambda victim: (
        in_flight_at_preempt.append(eng._flight is not None), hook(victim))
    prompts, new = prompts_of(5, (10, 14, 6, 12, 9)), (20, 16, 24, 12, 18)
    futs = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, new)]
    drive(eng)
    assert eng.sched.preemption_count > 0 and any(found)
    assert in_flight_at_preempt and not any(in_flight_at_preempt)
    assert eng.steps_dispatched_ahead > 0
    for p, m, f in zip(prompts, new, futs):
        assert f.token_ids == alone(mp, p, m)
    eng.alloc.check_consistent()
    eng.close()


def test_a_deadline_cancels_a_request_only_after_its_row_in_flight_landed(models):
    mp = models("gpt2")
    eng = engine(mp, max_batch_size=2, deadline_ms={"realtime": 5000.0})
    now = [100.0]
    eng._clock = lambda: now[0]
    prompts = prompts_of(6, (5, 6, 7, 4))
    doomed = eng.submit(prompts[0], max_new_tokens=30, slo="realtime")
    others = [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
    for _ in range(5):
        eng.step()
    r = doomed.request
    had = len(r.generated)
    assert eng._flight is not None and r.rid in eng._flight.feeds, "a row in flight"
    now[0] += 10.0                              # past its deadline
    eng.step()
    assert r.state == EXPIRED and len(r.generated) == had + 1, "no token lost"
    assert r.generated == alone(mp, prompts[0], 30)[:had + 1]
    drive(eng)
    for p, f in zip(prompts[1:], others):
        assert f.token_ids == alone(mp, p, 6)
    eng.alloc.check_consistent()
    eng.close()


def test_a_snapshot_lands_the_row_in_flight_and_restores_to_the_same_tokens(models):
    mp = models("gpt2")
    old = engine(mp, max_batch_size=2)
    prompts, new = prompts_of(7, (5, 19, 8, 3)), (10, 6, 12, 7)
    for p, m in zip(prompts, new):
        old.submit(p, max_new_tokens=m)
    for _ in range(4):
        old.step()
    assert old._flight is not None
    owed = {rid for rid in old._flight.feeds}
    before = {r.rid: len(r.generated) for r in old.sched.active.values()}
    snap = json.loads(json.dumps(old.snapshot()))
    assert old._flight is None
    for d in snap["requests"]:
        if d["rid"] in owed:
            assert len(d["generated"]) == before[d["rid"]] + 1, "the token is in it"
    old.close()
    eng = engine(mp, max_batch_size=2)
    futs = eng.restore(snap)
    drive(eng)
    for p, m, f in zip(prompts, new, futs):
        assert f.token_ids == alone(mp, p, m)
    eng.close()


def test_a_wedged_fetch_is_the_bounded_call_and_the_stream_goes_on(
        models, deadline_on_the_wedge_alone):
    """Under ``serve_step_timeout_s`` the fetch of a row is what the deadline
    bounds; the launch runs inline.  A wedged row takes the program launched
    behind it along: both are computed again, token for token."""
    mp = models("gpt2")
    eng = engine(mp, max_batch_size=2, serve_step_timeout_s=0.5)
    prompts, new = prompts_of(8, (5, 12, 7, 4)), (9, 6, 8, 5)
    futs = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, new)]
    for _ in range(4):
        eng.step()
    assert eng._flight is not None
    fault_injection.install_plan([{"site": "serve.step", "action": "wedge",
                                   "on_hit": 1}])
    try:
        with pytest.raises(ServeStepTimeout, match="deadline"):
            eng.step()
    finally:
        fault_injection.clear_plan()
    assert eng.incident_count == 1 and eng._flight is None
    assert not eng.sched.active and len(eng.sched.waiting) == 4
    assert not np.asarray(eng._previous).any()
    drive(eng)
    for p, m, f in zip(prompts, new, futs):
        assert f.token_ids == alone(mp, p, m)
    assert eng.compiled_programs() == 1
    eng.close()


def test_a_table_reload_lands_the_row_in_flight_first(models):
    """Four long prompts admitted in one step outgrow the upload's edits:
    the tables go whole, behind the row that was in flight."""
    mp = models("gpt2")
    eng = engine(mp, max_batch_size=4, num_blocks=128)
    found = counting_drains(eng)
    first = eng.submit(prompts_of(9, (20,))[0], max_new_tokens=4)
    eng.step()
    assert eng._flight is not None
    late = prompts_of(10, (100, 100, 100))
    futs = [eng.submit(p, max_new_tokens=3) for p in late]
    # (a step lands what is in flight behind its launch anyway: the first
    # step had nothing, and the second nothing left: the reload had landed it)
    assert eng.step()["table_reloads"] == 1 and found == [False, True, False]
    drive(eng)
    assert first.token_ids == alone(mp, first.request.prompt, 4)
    for p, f in zip(late, futs):
        assert f.token_ids == alone(mp, p, 3, num_blocks=128)
    eng.close()


# ---- the rule, and the callers that wait ------------------------------------------ #
def test_a_lone_request_is_never_ahead_and_a_backlog_always(models):
    mp = models("gpt2")
    eng = engine(mp)
    fut = eng.submit(prompts_of(11, (6,))[0], max_new_tokens=5)
    while not fut.done:
        stats = eng.step()
        assert stats["dispatched_ahead"] == 0 and eng._flight is None
    assert eng.steps_dispatched_ahead == 0 and not eng.sched.has_work
    # no slot free, then a queue: every step but the first launches ahead
    futs = [eng.submit(p, max_new_tokens=40) for p in prompts_of(12, (3,) * 5)]
    assert eng.step()["dispatched_ahead"] == 0
    for _ in range(20):
        stats = eng.step()
        assert stats["dispatched_ahead"] == 1 and eng._flight is not None
        assert eng.sched.has_work
    assert eng.steps_dispatched_ahead == 20
    eng.close()                                 # lands the row in flight
    assert eng._flight is None
    assert sum(len(f.token_ids) for f in futs) == eng.tokens_generated - 5


def test_result_lands_its_requests_last_token_without_another_launch(models):
    mp = models("gpt2")
    eng = engine(mp, max_batch_size=2)
    prompts = prompts_of(13, (4, 5, 6, 7))
    futs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, (5, 9, 6, 4))]
    watch = Rows(eng)
    assert futs[0].result() == alone(mp, prompts[0], 5)
    launched = len(watch.programs)
    # its fifth token came in by a drain: the step before had launched the
    # program that holds it, and nothing was launched behind that
    assert eng._flight is None and launched == 4 + 1
    for p, n, f in zip(prompts, (5, 9, 6, 4), futs):
        assert f.result() == alone(mp, p, n)
    assert eng.compiled_programs() == 1
    eng.close()
