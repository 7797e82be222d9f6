"""A serve step WITHOUT a prompt chunk takes only its decode rows through what
is a function of a row alone (``models/gpt.py:_rows_that_carry``): the norms,
every projection, the gate, the MLP or the router with its shared expert, the
head.  Four tiny stacks of the periodic walk, seeded, float32 on the CPU:
GPT-2's (layer norm, biases, learned positions), OLMoE's (a bank, the norm on
q and k), Mistral-Small-4's (latent attention, one cached array) and
Trinity's (a dense lead before the bank, a window three layers in four, the
output gate, a norm on every sublayer's output).

The parent's step, every row through everything, is kept here as
``_all_rows``: ``fn`` over all rows."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import gpt
from deepspeed_tpu.models.gpt import (GPT, GPTConfig, mistral4_config,
                                      olmoe_config, trinity_config)
from deepspeed_tpu.serving.kv_cache import init_arena

V, SLOTS, CHUNK, BS, MB, BLOCKS = 120, 3, 8, 8, 4, 12
ROWS = SLOTS + CHUNK
F32 = dict(dtype=jnp.float32, moe_aux_coeff=0.0)
CONFIGS = {
    "gpt2": lambda: GPTConfig(vocab_size=V, n_positions=64, n_embd=48, n_layer=2,
                              n_head=4, dtype="float32"),
    "olmoe": lambda: olmoe_config(vocab_size=V, n_positions=64, n_embd=64, n_layer=2,
                                  n_head=4, intermediate_size=32, num_experts=8,
                                  top_k=2, **F32),
    "latent": lambda: mistral4_config(
        vocab_size=V, n_positions=4096, n_embd=64, n_layer=2, n_head=4, head_dim=32,
        q_lora_rank=48, kv_lora_rank=128, qk_rope_dim=16, v_head_dim=24,
        intermediate_size=32, num_experts=8, top_k=2, **F32),
    "lead_and_window": lambda: trinity_config(
        vocab_size=V, n_positions=64, n_embd=64, n_layer=8, n_head=4, n_kv_head=2,
        head_dim=24, intermediate_size=96, moe_intermediate_size=32, num_experts=16,
        top_k=4, dense_layers=1, window=16, **F32),
}


def _all_rows(fn, xs, chunk, live, totals=0):
    """The parent: every row through ``fn``, whatever it carries."""
    return fn(*xs)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module", params=list(CONFIGS))
def stack(request):
    cfg = CONFIGS[request.param]()
    params = GPT(cfg).init_params(jax.random.PRNGKey(0))
    # gains off 1 and shifts off 0, so that a norm of an empty row shows
    blocks = {k: (v + 0.2 * jax.random.normal(jax.random.PRNGKey(i), v.shape)
                  if k.endswith(("_g", "_b")) else v)
              for i, (k, v) in enumerate(params["blocks"].items())}
    return cfg, dict(params, blocks=blocks)


def _inputs(cfg, with_chunk: bool):
    """Three decode rows at positions 5, 9 and 12 over an arena of seeded
    keys, and behind them the chunk's eight rows: tokens 8..15 of a fourth
    sequence, or nothing (the trash block, position 0)."""
    P = len(cfg.pattern)
    arena = [None if a is None else jax.random.normal(jax.random.PRNGKey(5), a.shape)
             for a in init_arena(cfg, BLOCKS, BS, dtype=jnp.float32)]
    positions = np.zeros(ROWS, np.int32)
    tables = np.zeros((ROWS, MB), np.int32)
    positions[:SLOTS] = (5, 9, 12)
    tables[:SLOTS, :2] = 1 + 2 * np.arange(SLOTS)[:, None] + np.arange(2)[None]
    if with_chunk:
        positions[SLOTS:] = 8 + np.arange(CHUNK)
        tables[SLOTS:, :2] = (7, 8)
    wb = np.take_along_axis(tables, positions[:, None] // BS, axis=1)
    wo = (positions[:, None] % BS) * (wb != 0)
    ids = jax.random.randint(jax.random.PRNGKey(7), (ROWS, 1), 0, V)
    return (ids, jnp.asarray(positions), *arena, (jnp.asarray(tables),) * P,
            (jnp.asarray(wb),) * P, jnp.asarray(wo))


def _step(cfg, params, with_chunk: bool):
    step = jax.jit(functools.partial(gpt.gpt_paged_step, cfg, chunk=CHUNK,
                                     with_expert_counts=bool(cfg.moe_num_experts)))
    return step(params, *_inputs(cfg, with_chunk))


def _pages(out):
    """The arena's arrays without the trash block."""
    return [a[:, 1:] for a in out[1:3] if a is not None]


def test_a_step_without_a_chunk_gives_its_decode_rows_what_all_rows_gave(
        stack, monkeypatch):
    cfg, params = stack
    got = _step(cfg, params, with_chunk=False)
    monkeypatch.setattr(gpt, "_rows_that_carry", _all_rows)
    want = _step(cfg, params, with_chunk=False)
    np.testing.assert_allclose(got[0][:SLOTS], want[0][:SLOTS], atol=2e-5, rtol=0)
    assert jnp.array_equal(got[0][:SLOTS].argmax(-1), want[0][:SLOTS].argmax(-1))
    for a, b in zip(_pages(got), _pages(want)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=0)
    if cfg.moe_num_experts:
        assert jnp.array_equal(got[3], want[3])
        experts_layers = cfg.n_layer - cfg.moe_dense_layers
        assert int(got[3].sum()) == SLOTS * cfg.moe_top_k * experts_layers
    # the chunk's rows: nothing went through them, and a norm of an empty row
    # (the layer norm's as the RMS norm's) is finite
    assert float(jnp.abs(got[0][SLOTS:]).max()) == 0.0
    assert all(bool(jnp.isfinite(a).all()) for a in got[:3] if a is not None)


def test_a_step_with_a_chunk_is_the_parents_to_the_bit(stack, monkeypatch):
    """Op by op, so that what is compared is the arithmetic and not how one
    compiler fused two texts of it (compiled for the CPU the dense lead's
    stack differs in the seventh digit)."""
    cfg, params = stack
    step = functools.partial(gpt.gpt_paged_step, cfg, params, *_inputs(cfg, True),
                             chunk=CHUNK, with_expert_counts=bool(cfg.moe_num_experts))
    with jax.disable_jit():
        got = step()
        monkeypatch.setattr(gpt, "_rows_that_carry", _all_rows)
        want = step()
    assert float(jnp.abs(got[0][SLOTS:]).max()) > 0.0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert jnp.array_equal(a, b)


def _conds(jaxpr):
    """Every ``cond`` equation of ``jaxpr``, in the bodies of its loops,
    calls and branches too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _conds(sub)


def test_no_branch_takes_a_layers_slice_or_the_arena(stack):
    """What a ``lax.cond`` takes as an operand XLA copies out for it.  A
    layer's weights sliced outside the branch would be 1.79 GB a step of
    Trinity's, the arena 6.4 GB: a branch takes the rows' activations and the
    STACK, of which it slices its layer where the dot reads it."""
    cfg, params = stack
    args = _inputs(cfg, with_chunk=False)
    jaxpr = jax.make_jaxpr(functools.partial(
        gpt.gpt_paged_step, cfg, chunk=CHUNK,
        with_expert_counts=bool(cfg.moe_num_experts)))(params, *args).jaxpr
    conds = list(_conds(jaxpr))
    # two regions a layer of the walk's traced bodies, and the head
    lead = -(-cfg.moe_dense_layers // len(cfg.pattern)) * len(cfg.pattern)
    traced = lead + (len(cfg.pattern) if lead < cfg.n_layer else 0)
    assert len(conds) == 2 * traced + 1
    whole = {leaf.shape for leaf in jax.tree.leaves(params)}
    sliced = {leaf.shape[1:] for leaf in jax.tree.leaves(params["blocks"])
              if leaf.ndim >= 3}
    arena = {a.shape for a in args[2:4] if a is not None}
    assert not sliced & whole and ROWS not in {s[0] for s in whole | arena}
    for eqn in conds:
        for shape in (v.aval.shape for v in eqn.invars):
            assert shape not in sliced | arena, shape
            # the predicate or an index, a row's activations, or a leaf whole
            assert not shape or shape[0] == ROWS or shape in whole, shape
