"""A prompt chunk attends PACKED: ``Sq`` consecutive tokens a row.

The step program hands a layer's attention its decode rows a query each and
the prompt chunk as ``chunk / Sq`` rows of ``Sq`` queries under the table and
the position of the first (``models/gpt.py:gpt_paged_step``'s ``chunk``,
``ops/pallas/decode_attention.py:_rows_and_chunk``).  The reference is the
same model over the same inputs and the same arena with one row a token
(``chunk=0``: what the program did before): at every dispatch of a seeded
stream, in float32 at ``highest``, the live rows' logits agree to rounding and
their best tokens are the same, a model family a case, at the ``Sq`` the rule
gives these shapes (the whole chunk one row) and at half and a quarter of it
(rows that are part live, rows that are all trash).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.gpt import (GPT, GPTConfig, mistral4_config,
                                      olmoe_config, smallthinker_config)
from deepspeed_tpu.ops.pallas import decode_attention as da
from deepspeed_tpu.serving import DeepSpeedServingConfig, ServingEngine
from deepspeed_tpu.serving.engine import unpack_step

V, SLOTS, CHUNK, WINDOW = 128, 3, 8, 12
SERVING = dict(block_size=4, num_blocks=64, max_batch_size=SLOTS,
               prefill_chunk=CHUNK, dtype="float32")
TOL = 2e-5          # the order of the sums alone (tests/unit/test_olmoe.py)
FAMILIES = {
    "gpt2": lambda: GPTConfig(vocab_size=V, n_positions=128, n_embd=32, n_layer=2,
                              n_head=4, dtype="float32"),
    "alibi": lambda: GPTConfig(vocab_size=V, n_positions=128, n_embd=32, n_layer=2,
                               n_head=4, dtype="float32",
                               position_encoding="alibi"),
    "olmoe": lambda: olmoe_config(vocab_size=V, n_positions=128, n_embd=32,
                                  n_layer=2, n_head=4, intermediate_size=16,
                                  num_experts=4, top_k=2, dtype=jnp.float32),
    # a full layer, then three that see 12 keys through a ring of pages
    "smallthinker": lambda: smallthinker_config(
        vocab_size=V, n_positions=128, n_embd=32, n_layer=4, n_head=4,
        n_kv_head=2, head_dim=8, intermediate_size=16, num_experts=4, top_k=2,
        window=WINDOW, dtype=jnp.float32),
    # the latent cache: one array, every head against the one cached vector
    "mistral4": lambda: mistral4_config(
        vocab_size=V, n_positions=128, n_embd=64, n_layer=2, n_head=4,
        head_dim=32, q_lora_rank=48, kv_lora_rank=128, qk_rope_dim=16,
        v_head_dim=24, intermediate_size=32, num_experts=8, top_k=2,
        rope_yarn=(16.0, 32, 32.0, 1.0, 1.0, 1.0, 0.1), dtype=jnp.float32,
        moe_aux_coeff=0.0),
}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@functools.lru_cache(maxsize=None)
def built(family):
    """(model, seeded weights, its step with one row a token) of a family,
    once a module: the reference program (``chunk=0``) packs nothing and asks
    the rule nothing, so the three ``Sq`` of a family share its compile."""
    model = GPT(FAMILIES[family]())
    moe = {"with_expert_counts": True} if model.cfg.moe_num_experts else {}
    return (model, model.init_params(jax.random.PRNGKey(7)), jax.jit(
        lambda params, *a: model.paged_step(params, *a, chunk=0, **moe)[0]))


class Both:
    """Stands in ``eng._dispatch``: before the step runs, the model over the
    step's inputs and arena with the chunk packed and with one row a token;
    keeps what kinds of step it saw."""

    def __init__(self, eng, Sq, a_row_a_token):
        self.eng, self.Sq, self._dispatch = eng, Sq, eng._dispatch
        self.unpack = jax.jit(unpack_step, static_argnums=0)
        moe = {"with_expert_counts": True} if eng._moe_experts else {}
        # packed under THIS case's rule: a new function, a new trace
        self.packed = jax.jit(lambda params, *a: eng.module.paged_step(
            params, *a, chunk=CHUNK, **moe)[0])
        self.a_row_a_token = a_row_a_token
        self.seen, self.worst = set(), 0.0
        eng._dispatch = self

    def __call__(self, phase, packed, reload, stats):
        eng = self.eng
        ids, positions, _, tables, wb, wo = self.unpack(
            eng._layout, packed, eng._previous,
            eng._tables if reload is None else reload)
        args = (eng.params, ids, positions, eng._k_pages, eng._v_pages,
                tables, wb, wo)
        got, want = (np.asarray(f(*args))[:, 0, :V]
                     for f in (self.packed, self.a_row_a_token))
        live = np.asarray(wb[0])[:, 0] != 0
        n, start = stats["chunk_tokens"], int(positions[SLOTS])
        assert live[SLOTS:SLOTS + n].all() and not live[SLOTS + n:].any()
        self.worst = max(self.worst, float(np.abs(got - want)[live].max()))
        np.testing.assert_allclose(got[live], want[live], atol=TOL, rtol=0)
        np.testing.assert_array_equal(got[live].argmax(-1), want[live].argmax(-1))
        kinds = {
            "no chunk": n == 0, "a chunk": n > 0,
            "a row part live": n % self.Sq > 0,
            "a row all trash": 0 < n <= CHUNK - self.Sq,
            "beside every other slot decoding": n and live[:SLOTS].sum() == SLOTS - 1,
            "across the window's edge": n and start < WINDOW <= start + n,
            "past the window": n and start > WINDOW}
        self.seen |= {kind for kind, seen in kinds.items() if seen}
        return self._dispatch(phase, packed, reload, stats)


@pytest.mark.parametrize("Sq", [CHUNK, CHUNK // 2, CHUNK // 4])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_packed_chunk_gives_the_logits_of_a_row_a_token(monkeypatch, family, Sq):
    """Two requests decode while a prompt of four chunks (the last ragged:
    29 tokens) and one of two (11) come in; then decode alone.  The rule is
    replaced for the smaller ``Sq`` only: the shapes here give the whole
    chunk."""
    model, params, a_row_a_token = built(family)
    assert da.paged_chunk_queries(CHUNK, 1, 4, 128, 128, 128, jnp.float32) == CHUNK
    monkeypatch.setattr(da, "paged_chunk_queries", lambda *shape: Sq)
    # an engine of its own: its program is traced under this case's rule
    eng = ServingEngine(model, config=DeepSpeedServingConfig(**SERVING),
                        params=params)
    both = Both(eng, Sq, a_row_a_token)
    rng = np.random.default_rng(5)
    prompts = [list(map(int, rng.integers(1, V, size=n))) for n in (5, 9, 29, 11)]
    futs = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts[:2], (30, 26))]
    for _ in range(3):
        stats = eng.step()
    futs.append(eng.submit(prompts[2], max_new_tokens=5))
    while not futs[2].done:
        stats = eng.step()
        if stats["prefill_tokens"]:
            assert (stats["chunk_queries_per_row"], stats["attention_rows"]) == (
                Sq, SLOTS + CHUNK // Sq)
    futs.append(eng.submit(prompts[3], max_new_tokens=4))
    while eng.sched.has_work:
        stats = eng.step()
    assert (stats["chunk_queries_per_row"], stats["attention_rows"]) == (
        0, SLOTS + CHUNK // Sq)                     # the last steps: no chunk
    want = {"no chunk", "a chunk", "beside every other slot decoding",
            "a row part live"}
    want |= {"a row all trash"} if Sq < CHUNK else set()
    if family == "smallthinker":
        want |= {"across the window's edge", "past the window"}
    assert want <= both.seen, both.seen
    assert all(f.done for f in futs) and eng.compiled_programs() == 1
    eng.close()
