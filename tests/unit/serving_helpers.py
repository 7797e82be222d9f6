"""A tiny model served to the tests, each program compiled once a worker.

What the tests of the serving path share, beside ``paged_bank.py``: ``Driver``
(``model.paged_step`` by hand); ``tiny_engine``, ``idle`` and what is served
on them; ``Recording`` and ``served_logits`` (the engine's logits);
``compiled_text`` (an ahead-of-time compile several tests read); ``jitted``
(a plain reference compiled); ``sequential_tokens`` (``generate()`` as an
oracle); two fixtures.  No test lives here.

The caches hold Python objects of this process and nothing on disk
(``tests/conftest.py`` says why); ``--dist loadfile`` gives a file to one
worker, so a cache here is a cache a worker, and nothing may depend on which
files shared it.  A cached program is a program under ONE selection rule and
ONE matmul precision: both are in every key (``_traced_under``), because the
``kernels`` fixture replaces ``ops.pallas.use_kernel`` for one test and the
rule is read while a program is traced.
"""

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import bounded
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.gpt import GPT, gpt_generate
from deepspeed_tpu.ops import pallas
from deepspeed_tpu.ops.pallas import decode_attention as da
from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.serving.kv_cache import init_arena
from deepspeed_tpu.testing import fault_injection

# the names ``ops.pallas.use_kernel`` is asked (its docstring's list)
KERNELS = ("ce", "fused_adam", "flash_attention", "decode_attention", "paged_attention",
           "paged_gqa_attention", "paged_mla_attention", "paged_sparse_attention",
           "sparse_block_scores", "grouped_matmul", "delta_state_update",
           "mamba_state_update", "mamba_chunk_scan")


def _traced_under():
    """What a trace reads that no argument carries: the platform, the kernels
    the rule selects, and the default matmul precision (an autouse context in
    most of the files that come here)."""
    return (pallas.platform(), pallas.interpret(),
            tuple(k for k in KERNELS if pallas.use_kernel(k)),
            jax.config.jax_default_matmul_precision)


def _program_key(model, *more):
    # ``GPTConfig`` is a plain dataclass (the product's: not frozen here)
    return (type(model), repr(model.cfg), *more, _traced_under())


# ---- ``model.paged_step`` by hand -------------------------------------------------- #
_STEPS = {}


class Driver:
    """``model.paged_step`` driven by hand, as the engine's step drives it:
    ``slots`` decode rows and a prompt chunk of ``chunk`` rows over pages of
    ``block_size`` tokens; slot ``s`` owns the blocks ``1 + s * blocks_a_slot
    ..`` in logical order.  A hybrid stack's step takes its ``aux`` and each
    row's slot and whether it is live; ``static`` are the step's other
    keywords (``with_expert_counts=True``: the counts are kept in ``counts``).
    ``round_through`` rounds the ``leaves`` of ``aux`` (all of them: None)
    through that type after every step, a planted lower precision; ``forget``
    names the leaves zeroed before every chunk but a sequence's first (a chunk
    that does not carry its state in).

    The weights are an ARGUMENT of the jitted step, and the step is kept by
    what its program depends on, so two ``Driver``s of one model at one size
    share one compile whatever their weights."""

    def __init__(self, model, params, *, slots, chunk, block_size, blocks_a_slot,
                 dtype=jnp.float32, static=(), round_through=None, leaves=None,
                 forget=()):
        cfg = model.cfg
        self.params, self.slots, self.chunk = params, slots, chunk
        self.BS, self.MB = block_size, blocks_a_slot
        self.round_through, self.leaves, self.forget = round_through, leaves, forget
        blocks = 1 + slots * blocks_a_slot
        self.kp, self.vp = init_arena(cfg, blocks, block_size, dtype)
        self.aux = hybrid.init_aux(cfg, blocks, block_size, slots, dtype) or None
        self.counts = None
        static = dict(static)
        key = _program_key(model, chunk, tuple(sorted(static.items())))
        if key not in _STEPS:
            _STEPS[key] = jax.jit(lambda params, *a, **kw: model.paged_step(
                params, *a, chunk=chunk, **static, **kw))
        self.fn = _STEPS[key]

    def step(self, decode=(), chunk=None):
        """``decode``: (slot, token, position) a decode row; ``chunk``: (slot,
        first position, tokens).  -> logits ``[slots + chunk, vocab]``."""
        R, BS, MB = self.slots + self.chunk, self.BS, self.MB
        ids, pos, slot = (np.zeros(R, np.int32) for _ in range(3))
        live = np.zeros(R, bool)
        for s, token, t in decode:
            ids[s], pos[s], slot[s], live[s] = token, t, s, True
        if chunk is not None:
            s, start, tokens = chunk
            at = slice(self.slots, self.slots + len(tokens))
            ids[at], pos[at], slot[at], live[at] = tokens, start + np.arange(len(tokens)), s, True
            if start and self.forget:
                self.aux = {k: jnp.zeros_like(v) if k in self.forget else v
                            for k, v in self.aux.items()}
        tables = np.where(live[:, None], 1 + slot[:, None] * MB + np.arange(MB)[None], 0)
        wb = np.where(live, tables[np.arange(R), pos // BS], 0)
        wo = np.where(live, pos % BS, 0)
        rows = ({} if self.aux is None else
                dict(aux=self.aux, slots=jnp.asarray(slot), live=jnp.asarray(live)))
        logits, self.kp, self.vp, *rest = self.fn(
            self.params, jnp.asarray(ids)[:, None], jnp.asarray(pos), self.kp, self.vp,
            jnp.asarray(tables, jnp.int32), jnp.asarray(wb, jnp.int32)[:, None],
            jnp.asarray(wo, jnp.int32)[:, None], **rows)
        if self.aux is not None:
            self.aux, *rest = rest
        if rest:
            self.counts, = rest
        if self.round_through is not None:
            self.aux = {k: v.astype(self.round_through).astype(v.dtype)
                        if self.leaves is None or k in self.leaves else v
                        for k, v in self.aux.items()}
        return np.asarray(logits)[:, 0]

    def sequence(self, seq, chunks, slot=0):
        """Logits of every position of ``seq``: its prompt prefilled in
        chunks of the lengths ``chunks``, the rest decoded a token a step."""
        out, start = [], 0
        for n in chunks:
            rows = self.step(chunk=(slot, start, seq[start:start + n]))
            out.append(rows[self.slots:self.slots + n])
            start += n
        for t in range(start, len(seq)):
            out.append(self.step(decode=[(slot, seq[t], t)])[slot][None])
        return np.concatenate(out)


# ---- an engine kept for the worker --------------------------------------------------- #
_ENGINES = {}


def idle(eng) -> bool:
    """Whether ``eng`` is what a request finds "with the engine to itself":
    no program in flight, no request running or waiting, every block free (as
    many as a new engine's) and the allocator's books in order.  On the CPU
    nothing is donated, so a kept engine's arena is a live array between
    tests: this is what guarantees that no test reads another's pages."""
    eng.alloc.check_consistent()
    return (not eng._closed and eng._flight is None and not eng.sched.active
            and not eng.sched.waiting
            and eng.alloc.free_pages == eng.alloc.num_blocks - 1)


def tiny_engine(model, params, **serving):
    """An engine for (``model.cfg``, ``serving``), kept for the worker and
    handed out again only when it is :func:`idle` (one that a failed test left
    mid-flight is dropped, not handed on).  The weights are an argument of the
    engine's step (``serving/engine.py``), so one engine serves every set of
    weights of its configuration.  Its counters run on from test to test: read
    them as differences, and do not ``close()`` it.  A test whose claim is
    about a NEW engine builds its own."""
    cfg, dtype = model.cfg, jnp.dtype(serving.get("dtype", "bfloat16"))
    layout = repr(cfg.paged_layout(
        serving["block_size"], serving.get("max_blocks_per_seq")
        or -(-cfg.n_positions // serving["block_size"]),
        serving["prefill_chunk"], dtype))
    key = _program_key(model, repr(sorted(serving.items())), layout)
    eng = _ENGINES.get(key)
    if eng is not None and not idle(eng):
        del _ENGINES[key]
        eng = None
    if eng is None:
        eng = _ENGINES[key] = deepspeed_tpu.init_serving(
            model=model, params=params, config={"serving": serving})
    else:
        eng.params = params
    # ``tests/conftest.py:_reset_mesh`` runs after every test: a kept engine
    # must hold no mesh (these are built without one)
    assert not mesh_lib.has_mesh()
    return eng


def served_tokens(model, params, prompts, new, **serving):
    """``prompts`` through the worker's engine of this configuration, idle
    before and after -> (each one's tokens, the engine)."""
    eng = tiny_engine(model, params, **serving)
    futures = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
    tokens = [f.result() for f in futures]
    assert idle(eng)
    return tokens, eng


def restored_tokens(model, params, prompt, new, after, **serving):
    """``snapshot()`` taken with ``after`` of ``new`` tokens served, on an
    engine of its own (it is closed with the request half served), and
    ``restore()`` into a second engine, the worker's, whose slots hold what
    others left -> the tokens the restored request is served."""
    eng = deepspeed_tpu.init_serving(model=model, params=params, config={"serving": serving})
    f = eng.submit(prompt, max_new_tokens=new)
    while len(f.token_ids) < after:
        eng.step()
    snap = eng.snapshot()
    eng.close()
    (g,) = tiny_engine(model, params, **serving).restore(snap)
    return g.result()


def preempted(model, params, prompts, new, each_program=lambda st: None, **serving):
    """``prompts`` together on an engine of its OWN (``preemptions`` is read
    as a total) whose arena cannot hold them all: the allocator's books hold
    at every step, at least one request is preempted, and ``state_slots_reset``
    counts a first chunk a request and one more a preemption -> tokens."""
    eng = deepspeed_tpu.init_serving(model=model, params=params, config={"serving": serving})
    futures = [eng.submit(p, max_new_tokens=new) for p in prompts]
    reset = 0
    while not all(f.done for f in futures):
        st = eng.step()
        eng.alloc.check_consistent()
        reset += st.get("state_slots_reset", 0)
        if st["programs"]:
            each_program(st)
    assert st["preemptions"] >= 1
    assert reset == len(prompts) + st["preemptions"]
    eng.close()
    return [f.token_ids for f in futures]


def dense_path_refusal(model, params, path, ids):
    """What ``forward_logits``, ``generate`` or the loss (``path``) says as it
    refuses a stack that only ``init_serving`` runs."""
    ids = jnp.asarray(ids)[None]
    call = {"forward": lambda: model.forward_logits(params, ids),
            "generate": lambda: model.generate(params, ids, 4),
            "loss": lambda: model(params, (ids, ids), None, False)}[path]
    with pytest.raises(NotImplementedError) as e:
        call()
    return str(e.value)


def reference_tokens(reference_logits, params, prompt, tokens, vocab=None):
    """Teacher-forced -> (the reference's best token at every position
    ``tokens`` were served at, the largest gap between its best logit and the
    served token's)."""
    seq = np.concatenate([prompt, tokens]).astype(np.int32)
    lg = reference_logits(params, seq)[len(prompt) - 1:len(seq) - 1, :vocab]
    return lg.argmax(-1).tolist(), float((lg.max(-1) - lg[np.arange(len(tokens)), tokens]).max())


# ---- the engine's logits ---------------------------------------------------------------- #
class Recording(GPT):
    """The model as served, its step's logits kept: the engine fetches
    tokens alone, and the comparison is on logits."""

    def __init__(self, cfg):
        super().__init__(cfg)
        self.logits = []

    def paged_step(self, *args, **kw):
        out = super().paged_step(*args, **kw)
        jax.debug.callback(lambda lg: self.logits.append(np.asarray(lg[:, 0])), out[0])
        return out


def served_logits(cfg, params, prompt, new, serving, vocab=None, new_engine=False):
    """``prompt`` through ``ServingEngine`` for ``new`` tokens -> (tokens,
    the logits of every position it computed ``[len - 1, vocab]``, the engine's
    stats a step, the engine): a prompt token is a row behind the slots, a
    decode step the row of the request's slot.  The engine is a kept one
    (:func:`tiny_engine`: one compile a configuration) unless ``new_engine``:
    for a claim about where a NEW allocator lays its blocks."""
    if new_engine:
        eng = deepspeed_tpu.init_serving(model=Recording(cfg), params=params,
                                         config={"serving": serving})
    else:
        eng = tiny_engine(Recording(cfg), params, **serving)
    model = eng.module
    del model.logits[:]
    fut = eng.submit(prompt, max_new_tokens=new)
    rows, stats = {}, []
    while not fut.done:
        req, slot, at = fut.request, fut.request.slot, fut.request.prefilled
        stats.append(eng.step())
        eng.alloc.check_consistent()
        jax.effects_barrier()
        lg, st = model.logits[-1][:, :vocab], stats[-1]
        for i in range(st["prefill_tokens"]):
            rows[at + i] = lg[serving["max_batch_size"] + i]
        if st["decode_batch"]:
            rows[at] = lg[slot]
    assert eng.compiled_programs() == 1
    if new_engine:
        eng.close()
    return req.generated, np.stack([rows[t] for t in range(len(rows))]), stats, eng


# ---- a plain reference, compiled ---------------------------------------------------------- #
_JITTED = {}


def jitted(fn, **static):
    """``fn`` with the keywords ``static`` bound, compiled once a set of them:
    a plain reference's forward pass (``benchmarks/lib/reference_*.py``) costs
    seconds a call op by op and milliseconds compiled, and the cases of a
    file ask for it again and again."""
    static = {k: tuple(v) if isinstance(v, list) else v for k, v in static.items()}
    key = (fn, repr(sorted(static.items())))
    if key not in _JITTED:
        _JITTED[key] = jax.jit(functools.partial(fn, **static))
    return _JITTED[key]


# ---- ``generate()`` as an oracle ---------------------------------------------------------- #
_GENERATE = {}


def sequential_tokens(model, params, prompt, n_new, prompt_bucket=128, new_bucket=32):
    """The tokens sequential ``generate()`` gives ``prompt``: ``gpt_generate``
    itself, jitted once a model with the prompt right-padded to a bucket and
    its length traced (``prompt_len``, the bucketed form ``init_inference``
    serves), so that every request of a file shares ONE program where the
    eager call compiled its scan anew for every (prompt, ``n_new``).  Greedy
    decoding is prefix-stable: ``n_new`` tokens are the first of the
    bucket's."""
    cfg = model.cfg
    bucket = min(prompt_bucket, cfg.n_positions - new_bucket)
    assert len(prompt) <= bucket and n_new <= new_bucket
    key = _program_key(model, bucket, new_bucket)
    if key not in _GENERATE:
        _GENERATE[key] = jax.jit(lambda params, ids, n: gpt_generate(
            cfg, params, ids, new_bucket, prompt_len=n))
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :len(prompt)] = prompt
    out = _GENERATE[key](params, jnp.asarray(ids), jnp.int32(len(prompt)))
    return list(np.asarray(out)[0, bucket:bucket + n_new])


# ---- an ahead-of-time compile that several tests read ------------------------------------- #
_TEXTS = {}


def compiled_text(chip, key, build):
    """``build()`` (-> a compiled program) once a (``chip``, ``key``), and its
    text: two tests that read one program compile it once.  The rule
    (``test_chip_compile.py:on_the_chip``) is part of the key like every
    cache's here."""
    key = (chip, key, _traced_under())
    if key not in _TEXTS:
        _TEXTS[key] = build()
    return _TEXTS[key]


# ---- the kernels' constants at the size of a test ------------------------------------------- #
@pytest.fixture
def small_tiles(monkeypatch):
    """The rule's constants lowered for the size of a test: an attend step of
    2 pages (16 keys), a copy's tile of 4 (32 keys).  They are read when an
    engine is built and when its step is traced: a test under this fixture
    builds its own engines."""
    monkeypatch.setattr(da, "_TILE_ROWS", 16)
    monkeypatch.setattr(da, "_TILE_PAGES", 2)
    monkeypatch.setattr(da, "_RUN_TILE_ROWS", 32)


# ---- a deadline that a loaded machine cannot trip ------------------------------------------- #
class _DoneOrWedged(threading.Event):
    """``wait(bound)`` of a bounded call's job: without a bound for as long
    as no thread is parked in the fault plan's ``wedge`` (the injector logs
    the rule BEFORE it parks: an event, not a time), under ``bound`` from
    then on."""

    def wait(self, timeout=None):
        while not super().wait(0.005):
            plan = fault_injection.get_injector()
            if (plan is not None and not fault_injection._WEDGE_RELEASE.is_set()
                    and any(fired["action"] == "wedge" for fired in plan.log)):
                return super().wait(timeout)
        return True


@pytest.fixture
def deadline_on_the_wedge_alone(monkeypatch):
    """``serve_step_timeout_s`` bounds a fetch by the wall clock, and on a
    machine that five other workers load an HONEST fetch may be held past half
    a second: an incident nobody scripted.  Under this fixture a fetch is held
    to the engine's deadline only once the plan's wedge has fired; the wedged
    step times out after its configured deadline as it does in the product."""
    class Job(bounded._Job):
        __slots__ = ()

        def __init__(self, fn, args, kwargs):
            super().__init__(fn, args, kwargs)
            self.done = _DoneOrWedged()
    monkeypatch.setattr(bounded, "_Job", Job)
