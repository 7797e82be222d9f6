"""The serve step's block tables live on the device and change by edits.

The engine uploads ONE small array a step and the program makes every row's
table and write coordinates from its table state (``serving/engine.py``:
``StepLayout``, ``unpack_step``, ``ServingEngine._pack``).  The oracle is
what the engine did before: whole tables built on the host from
``PagedKVAllocator.block_table`` / ``write_map`` (``oracle_inputs`` below is
that builder).  Held here, at every dispatch of seeded random traffic:

* what the program computes from the upload and its state equals the
  oracle's arrays, value for value (so the model gets what it always got);
* after the step the table state on the device equals ``block_table`` for
  every slot a sequence holds, and is all trash for every other slot (one
  freed in the step's commit: with the next upload);
* the tokens of the step's live rows equal those of the parent's program
  (whole tables as inputs, and a prompt token a single-query row of its own)
  fed the oracle's arrays over the same arena (GPT-2, OLMoE, SmallThinker,
  tiny).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.gpt import (GPT, GPTConfig, LayerKind, olmoe_config,
                                      smallthinker_config)
from deepspeed_tpu.serving import DeepSpeedServingConfig, ServingEngine
from deepspeed_tpu.serving.engine import StepLayout, unpack_step
from deepspeed_tpu.serving.scheduler import DECODE
from deepspeed_tpu.testing import fault_injection
from tests.unit.serving_helpers import (  # noqa: F401  (a fixture among them)
    deadline_on_the_wedge_alone, sequential_tokens)

V = 128
SERVING = dict(block_size=8, num_blocks=64, max_batch_size=4, prefill_chunk=8,
               dtype="float32")
MODELS = {
    "gpt2": lambda: GPTConfig(vocab_size=V, n_positions=128, n_embd=32, n_layer=2,
                              n_head=4, dtype="float32"),
    # a full layer without positions, then a layer that sees 12 keys: two
    # layer groups, the second's table a ring
    "ring": lambda: GPTConfig(vocab_size=V, n_positions=128, n_embd=32, n_layer=2,
                              n_head=4, dtype="float32", position_encoding="rope",
                              norm="rmsnorm", layer_pattern=(
                                  LayerKind(None, False), LayerKind(12, True))),
    "olmoe": lambda: olmoe_config(vocab_size=V, n_positions=128, n_embd=32,
                                  n_layer=2, n_head=4, intermediate_size=16,
                                  num_experts=4, top_k=2, dtype=jnp.float32),
    "smallthinker": lambda: smallthinker_config(
        vocab_size=V, n_positions=128, n_embd=32, n_layer=4, n_head=4,
        n_kv_head=2, head_dim=8, intermediate_size=16, num_experts=4, top_k=2,
        window=12, dtype=jnp.float32),
}


@pytest.fixture(scope="module")
def models():
    made = {}

    def get(name):
        if name not in made:
            model = GPT(MODELS[name]())
            made[name] = (model, model.init_params(jax.random.PRNGKey(7)))
        return made[name]
    return get


def engine(model_and_params, **over):
    model, params = model_and_params
    return ServingEngine(model, config=DeepSpeedServingConfig(**dict(SERVING, **over)),
                         params=params)


# ---- the oracle: the step's inputs as whole tables built on the host ----------- #
def last_token(eng, r):
    """The last token of ``r``'s context: on the host, or still a row of the
    program in flight (the engine is a step ahead: ``test_dispatch_ahead.py``),
    which the next program takes on the device."""
    flight = eng._flight
    if flight is not None and r.rid in flight.feeds:
        return int(np.asarray(flight.tokens)[flight.feeds[r.rid]])
    return r.context[-1]


def oracle_inputs(eng, decode, pf):
    """(ids, positions, tables, write blocks, write offsets) of the step that
    is about to run, a row a decode slot and a row a chunk token, from
    ``block_table`` and ``write_map``: how ``ServingEngine`` built them before
    the tables moved to the device."""
    cfg, alloc = eng._config, eng.alloc
    R = cfg.max_batch_size + cfg.prefill_chunk
    ids, positions = np.zeros((R, 1), np.int32), np.zeros((R,), np.int32)
    tables = [np.zeros((R, w), np.int32) for w in alloc.widths]
    wb = [np.zeros((R, 1), np.int32) for _ in alloc.widths]
    wo = np.zeros((R, 1), np.int32)
    if pf is not None:
        req, start, n = pf
        rows = slice(cfg.max_batch_size, cfg.max_batch_size + n)
        ids[rows, 0] = req.context[start:start + n]
        positions[rows] = np.arange(start, start + n)
        for g in range(alloc.n_groups):
            tables[g][rows] = alloc.block_table(req.rid, g)
            wb[g][rows, 0], wo[rows, 0] = alloc.write_map(req.rid, start, n, g)
    for r in decode:
        ids[r.slot, 0] = last_token(eng, r)
        positions[r.slot] = r.prefilled
        for g in range(alloc.n_groups):
            tables[g][r.slot] = alloc.block_table(r.rid, g)
            wb[g][r.slot], wo[r.slot] = alloc.write_map(r.rid, r.prefilled, 1, g)
    return ids, positions, tables, wb, wo


def device_tables(eng):
    """The table state fetched back: a ``[slots, width]`` array a group."""
    lay, flat = eng._layout, np.asarray(eng._tables)
    assert flat.shape == (lay.state_size,)
    return [flat[at:at + lay.slots * w].reshape(lay.slots, w)
            for at, w in zip(lay.offsets, lay.widths)]


def assert_tables_are_the_allocators(eng):
    """Every held slot's row is its sequence's ``block_table``; every other
    row is trash, but for a slot freed in this step's commit, whose row goes
    back to trash with the next upload."""
    held = {r.slot: r.rid for r in eng.sched.active.values()}
    for g, table in enumerate(device_tables(eng)):
        for slot in set(range(eng._layout.slots)) - eng.alloc._cleared:
            want = (eng.alloc.block_table(held[slot], g) if slot in held
                    else np.zeros_like(table[slot]))
            np.testing.assert_array_equal(table[slot], want, err_msg=(
                f"group {g}, slot {slot}, held by {held.get(slot)}"))


class Watch:
    """Stands in ``eng._dispatch``: holds what the program makes of every
    upload to the oracle, and (``parent``: the parent's program, jitted) the
    step's tokens to the parent's over the same arena."""

    def __init__(self, eng, parent=None):
        self.eng, self.parent, self._dispatch = eng, parent, eng._dispatch
        self.unpack = jax.jit(unpack_step, static_argnums=0)
        self.steps = self.reloads = self.edits = 0
        self.upload_bytes = set()
        eng._dispatch = self

    def __call__(self, phase, packed, reload, stats):
        eng = self.eng
        assert packed.shape == (eng._layout.packed_size,) and packed.dtype == np.int32
        # every decoding sequence but those whose last token is in flight
        decode = [r for r in eng.sched.active.values() if r.state == DECODE
                  and not eng._ends_in_flight(r)]
        want = oracle_inputs(eng, decode, eng.sched.next_prefill())
        state = eng._tables if reload is None else reload
        ids, positions, _, tables, wb, wo = jax.tree.map(
            np.asarray, self.unpack(eng._layout, packed, eng._previous, state))
        got = (ids, positions, list(tables), list(wb), wo)
        for name, g, w in zip(("ids", "positions", "tables", "write blocks",
                               "write offsets"), got, want):
            for a, b in zip(*(x if isinstance(x, list) else [x] for x in (g, w))):
                np.testing.assert_array_equal(a, b, err_msg=f"{name}, step {self.steps}")
        live = int((wb[0][:, 0] != 0).sum())
        assert live == stats.get("batch", stats["chunk_tokens"])
        if self.parent is not None:
            theirs = self.parent(eng.params, *map(jnp.asarray, want[:2]),
                                 eng._k_pages, eng._v_pages,
                                 *(tuple(map(jnp.asarray, x)) for x in want[2:4]),
                                 jnp.asarray(want[4]))
        tokens, *stamps = self._dispatch(phase, packed, reload, stats)
        if self.parent is not None:
            # the rows that carry a request: a chunk's rows past its tokens
            # are computed for nobody (packed beside live queries they see
            # the request's table, alone they saw the trash block)
            rows = np.flatnonzero(wb[0][:, 0])
            np.testing.assert_array_equal(
                np.asarray(tokens)[rows], np.asarray(theirs).reshape(-1)[rows],
                err_msg=f"tokens, step {self.steps}")
        self.steps += 1
        self.reloads += reload is not None
        return (tokens, *stamps)

    def step(self):
        st = self.eng.step()
        assert_tables_are_the_allocators(self.eng)
        self.eng.alloc.check_consistent()
        self.edits += st["table_edits"]
        if st["programs"]:
            self.upload_bytes.add(st["upload_bytes"] - st["table_reloads"]
                                  * 4 * self.eng._layout.state_size)
        return st


def random_traffic(watch, seed, steps=40, rate=0.35, lens=(1, 40), new=(1, 24),
                   prefix=()):
    """Seeded submits between steps, then the drain; every step checked."""
    rng = np.random.default_rng(seed)
    eng, futs = watch.eng, []
    for _ in range(steps):
        while rng.random() < rate:
            prompt = list(prefix) + list(map(int, rng.integers(
                1, V, size=rng.integers(*lens))))
            futs.append(eng.submit(prompt, max_new_tokens=int(rng.integers(*new))))
        watch.step()
    while eng.sched.has_work:
        watch.step()
    assert futs and all(f.done for f in futs)
    return futs


# ---- the property: device tables == the allocator's, after every step ---------- #
def one_full_group(models):
    w = Watch(engine(models("gpt2")))
    random_traffic(w, seed=1)
    assert w.reloads == 0
    return w


def full_and_window_ring(models):
    """Long outputs past a window of 12 in blocks of 4: the ring's columns
    are given back and taken again while the full group's table grows."""
    w = Watch(engine(models("ring"), block_size=4))
    assert w.eng.alloc.widths == (32, 6)
    random_traffic(w, seed=2, lens=(1, 50), new=(8, 40))
    assert w.eng.alloc.given_back_ever > 50 and w.reloads == 0
    return w


def prefix_cache(models):
    """Requests that share 16 tokens adopt their two blocks before they have
    a slot: the row is handed them when it is bound."""
    w = Watch(engine(models("gpt2"), prefix_cache=True))
    system = list(range(1, 17))
    random_traffic(w, seed=3, lens=(1, 20), prefix=system)
    assert w.eng.prefix.hits > 3 and w.reloads == 0
    return w


def preemption(models):
    """An arena of 36 tokens under far more demand: rows are evicted in the
    growth pass, go back to trash whole, and are handed to others."""
    w = Watch(engine(models("gpt2"), block_size=4, num_blocks=10,
                     max_blocks_per_seq=9))
    random_traffic(w, seed=4, rate=0.5, lens=(4, 14), new=(8, 20))
    assert w.eng.sched.preemption_count > 3 and w.reloads == 0
    return w


def snapshot_restore(models):
    old = Watch(engine(models("gpt2")))
    rng = np.random.default_rng(5)
    for n in (30, 5, 17, 9, 22, 3):
        old.eng.submit(list(map(int, rng.integers(1, V, size=n))), max_new_tokens=12)
    for _ in range(6):
        old.step()
    snap = json.loads(json.dumps(old.eng.snapshot()))
    old.eng.close()
    w = Watch(engine(models("gpt2")))
    futs = w.eng.restore(snap)
    while w.eng.sched.has_work:
        w.step()
    assert len(futs) == 6 and all(f.done for f in futs) and w.reloads == 0
    return w


def edit_overflow_reload(models):
    """Four prompts of 13 blocks admitted in ONE step are 52 entries where an
    upload holds 22: the tables go whole, once, and edits carry on."""
    w = Watch(engine(models("gpt2")))
    assert w.eng._layout.edits == 16 + (4 + 1 + 1)
    rng = np.random.default_rng(6)
    for _ in range(4):
        w.eng.submit(list(map(int, rng.integers(1, V, size=100))), max_new_tokens=20)
    st = w.step()
    assert (st["table_reloads"], st["table_edits"]) == (1, 0)
    assert st["upload_bytes"] == 4 * (w.eng._layout.packed_size
                                      + w.eng._layout.state_size)
    random_traffic(w, seed=6, steps=10)
    assert w.reloads == 1 and w.edits > 20
    return w


SCENARIOS = [one_full_group, full_and_window_ring, prefix_cache, preemption,
             snapshot_restore, edit_overflow_reload]


@pytest.mark.parametrize("scenario", SCENARIOS, ids=lambda f: f.__name__)
def test_device_tables_equal_the_allocators_after_every_step(models, scenario):
    w = scenario(models)
    assert w.steps > 10 and w.edits > 0
    # one upload of one size whatever the step held, and one program
    assert w.upload_bytes == {4 * w.eng._layout.packed_size}
    assert w.eng.compiled_programs() == 1
    assert not w.eng.alloc.drain_edits()[1], "a finished engine owes no entry"
    w.eng.close()


# ---- parity with the parent's program ------------------------------------------- #
def parent_program(eng):
    """The step program as it was: whole tables and write maps as inputs."""
    model, mcfg, moe = eng.module, eng.module.cfg, eng._moe_experts

    def step_fn(params, ids, positions, kp, vp, tables, wb, wo):
        kw = {"with_expert_counts": True} if moe else {}
        logits, *_ = model.paged_step(params, ids, positions, kp, vp, tables,
                                      wb, wo, **kw)
        if mcfg.padded_vocab != mcfg.vocab_size:
            logits = jnp.where((jnp.arange(mcfg.padded_vocab) < mcfg.vocab_size)
                               [None, None], logits, -1e30)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.jit(step_fn)


REQUESTS = [(23, 9), (5, 40), (40, 6), (4, 42), (3, 44), (31, 8), (6, 40)]


# (model, blocks of all layers in the arena, tokens of the four prompts that
# are admitted in one step): an arena that preempts under REQUESTS and still
# holds more new entries in one step than an upload does (19; 58 with a full
# and three window groups)
@pytest.mark.parametrize("name, num_blocks, at_once", [
    ("gpt2", 24, 20), ("olmoe", 24, 20), ("smallthinker", 20, 24)])
def test_tokens_are_the_parents_token_for_token(models, name, num_blocks, at_once):
    """A fixed set of requests, more than the slots hold, in an arena that
    preempts; then four prompts at once (a reload).  Every step's token row
    is the parent program's over the oracle's inputs and the same arena, and
    the whole is served by ONE compiled program."""
    model, params = models(name)
    eng = engine((model, params), block_size=4, num_blocks=num_blocks,
                 max_blocks_per_seq=12)
    w = Watch(eng, parent=parent_program(eng))
    rng = np.random.default_rng(11)
    prompts = [list(map(int, rng.integers(1, V, size=n))) for n, _ in REQUESTS]
    futs = [eng.submit(p, max_new_tokens=m) for p, (_, m) in zip(prompts, REQUESTS)]
    chunked = 0
    while eng.sched.has_work:
        st = w.step()
        chunked += bool(st["decode_batch"] and st["prefill_tokens"])
    assert chunked > 3 and eng.sched.preemption_count > 0
    # the same requests alone, one at a time: the same tokens again
    for p, (_, m), f in zip(prompts[:3], REQUESTS, futs):
        solo = eng.submit(p, max_new_tokens=m)
        while not solo.done:
            w.step()
        assert solo.token_ids == f.token_ids
    for _ in range(4):
        eng.submit(list(map(int, rng.integers(1, V, size=at_once))),
                   max_new_tokens=4)
    assert w.step()["table_reloads"] == 1
    while eng.sched.has_work:
        w.step()
    assert eng.compiled_programs() == 1
    eng.close()


def test_a_wedged_step_empties_the_tables_and_the_stream_goes_on(
        models, deadline_on_the_wedge_alone):
    """Incident recovery re-jits the program and rebuilds the arena; the
    table state is rebuilt empty with them and every request recomputes
    through edits, token-identical."""
    model, params = models("gpt2")
    eng = engine((model, params), serve_step_timeout_s=0.5)
    w = Watch(eng)
    rng = np.random.default_rng(12)
    prompts = [list(map(int, rng.integers(1, V, size=n))) for n in (19, 6, 12)]
    futs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    for _ in range(4):
        w.step()
    fault_injection.install_plan([{"site": "serve.step", "action": "wedge",
                                   "on_hit": 1}])
    try:
        with pytest.raises(Exception, match="deadline"):
            eng.step()
    finally:
        fault_injection.clear_plan()
    assert eng.incident_count == 1
    assert not np.asarray(eng._tables).any(), "nobody has a slot: all trash"
    while eng.sched.has_work:
        w.step()
    for p, f in zip(prompts, futs):
        assert f.token_ids == sequential_tokens(model, params, p, 10)
    assert w.reloads == 0
    eng.close()


# ---- the layout ------------------------------------------------------------------ #
def test_the_upload_holds_one_admission_and_every_rows_growth():
    from deepspeed_tpu.serving.kv_cache import PagedKVAllocator
    alloc = PagedKVAllocator(4096, 16, 1024, windows=(None, 4096, 4096, 4096),
                             chunk=224)
    lay = StepLayout.of(alloc, slots=32, chunk=224)
    assert lay.widths == (1024, 271, 271, 271) and lay.rows == 256
    assert lay.edits == 1837 + 4 * (32 + 14 + 1)
    assert lay.offsets == (0, 32 * 1024, 32 * 1295, 32 * 1566)
    assert lay.state_size == 32 * 1837
    assert 4 * lay.packed_size == 4 * (4 * 256 + 32 + 2 * 2025) < 64 * 1024
