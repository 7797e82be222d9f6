"""MiniCPM-SALA on the serving path (``models/hybrid.py``), at a tiny size on
the CPU with seeded weights, against the plain float32 reference
(``benchmarks/lib/reference_minicpm_sala.py``): prefill then decode through
the paged cache on both sides of ``dense_len``, the three forms of the linear
layer, a selection wide enough to hold every block, a step with decode rows
and a chunk together, a slot reused, a request preempted, the step's stats,
and what ``init_serving`` and the dense paths refuse."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmarks.lib import reference_minicpm_sala as ref
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.gpt import GPT, minicpm_sala_config
from deepspeed_tpu.serving.kv_cache import init_arena
from tests.unit import serving_helpers
from tests.unit.serving_helpers import Driver, jitted, served_tokens

MIXERS = ["minicpm4", "lightning-attn", "lightning-attn", "minicpm4", "minicpm4",
          "lightning-attn"]
# compressed keys of 8 every 4, blocks (pages) of 16, the top 4 of them (the
# first and the two of the last 32 keys among them), every key up to 64
SPARSE = dict(kernel=8, stride=4, block=16, topk=4, init_blocks=1, window=32,
              dense_len=64)
WIDTHS = dict(vocab_size=512, n_positions=256, n_embd=64, n_head=4, n_kv_head=2,
              head_dim=16, intermediate_size=128, mixer_types=MIXERS, first_layer=3,
              published_layers=12)
REF = dict(n_head=4, n_kv_head=2, head_dim=16, mixer_types=MIXERS, first_layer=3,
           published_layers=12, vocab_size=512, q_block=32)
BS, SLOTS, CHUNK, MB = 16, 3, 8, 16
SERVING = dict(max_batch_size=SLOTS, prefill_chunk=CHUNK, block_size=BS,
               num_blocks=64, dtype="float32")
# float32 against float32: what is left is the order of the sums (the chunked
# form against the recurrence, pages against one pass): 2e-5 of logits of 0.5
TOL = 1e-4


def config(sparse=SPARSE, **kw):
    return minicpm_sala_config(**dict(WIDTHS, **kw), sparse=tuple(sparse.values()),
                               dtype="float32")


@pytest.fixture(scope="module")
def tiny():
    model = GPT(config())
    return model, model.init_params(jax.random.PRNGKey(0))


def reference_logits(params, seq):
    ids = np.zeros(-(-len(seq) // 32) * 32, np.int32)
    ids[:len(seq)] = seq
    fn = jitted(ref.sala_logits, sparse=SPARSE, **REF)
    return np.asarray(fn(params, jnp.asarray(ids)))[:len(seq)]


def _ids(n, seed):
    return np.random.default_rng(seed).integers(0, 512, n).astype(np.int32)


# ``round_through=`` rounds the linear layers' states through that type
# after every step (a planted lower precision)
driver = functools.partial(Driver, slots=SLOTS, chunk=CHUNK, block_size=BS,
                           blocks_a_slot=MB, leaves=("state",))


def chunks_of(prompt):
    """A prompt of ``prompt`` tokens in whole chunks and what is left."""
    return (CHUNK,) * (prompt // CHUNK) + ((prompt % CHUNK,) if prompt % CHUNK else ())


# ---- the served logits against the reference's full forward pass --------------- #
@pytest.mark.parametrize("prompt, total", [(20, 52), (50, 90), (100, 140)])
def test_prefill_then_decode_agree_with_the_reference_on_both_sides_of_dense_len(
        tiny, prompt, total):
    """Contexts that stay under ``dense_len`` (64: every key attended), cross
    it while decoding, and lie beyond it from the prompt on (the selection
    runs in the chunks and in the decode rows; 140 keys are 9 blocks of which
    4 are attended)."""
    model, params = tiny
    seq = _ids(total, seed=total)
    got = driver(model, params).sequence(seq, chunks_of(prompt))
    want = reference_logits(params, seq)[:, :512]
    assert np.abs(got[:, :512] - want).max() < TOL
    assert np.abs(want).max() > 0.1


def test_a_bf16_state_fails_the_tolerance(tiny):
    """The planted lower precision: the states rounded through bf16 after
    every step read 30 times the tolerance or more."""
    model, params = tiny
    seq = _ids(140, seed=140)
    got = driver(model, params, round_through=jnp.bfloat16).sequence(seq, chunks_of(100))
    assert np.abs(got[:, :512] - reference_logits(params, seq)[:, :512]).max() > 30 * TOL


def test_a_step_with_decode_rows_and_a_chunk_together(tiny):
    """Two sequences decode while a third's prompt runs in the chunk rows of
    the same steps, in another slot: every row's logits are its own
    sequence's."""
    model, params = tiny
    a, b, c = _ids(120, 1), _ids(60, 2), _ids(96, 3)
    d = driver(model, params)
    d.sequence(a[:80], chunks_of(80), slot=0)
    d.sequence(b[:24], chunks_of(24), slot=1)
    got = {0: [], 1: [], 2: []}
    for i, start in enumerate(range(0, len(c), CHUNK)):
        rows = d.step(decode=[(0, a[80 + i], 80 + i), (1, b[24 + i], 24 + i)],
                      chunk=(2, start, c[start:start + CHUNK]))
        got[0].append(rows[0][None]), got[1].append(rows[1][None])
        got[2].append(rows[SLOTS:SLOTS + CHUNK])
    n = len(c) // CHUNK
    for slot, seq, lo in ((0, a, 80), (1, b, 24), (2, c, 0)):
        want = reference_logits(params, seq)[lo:lo + (len(c) if slot == 2 else n), :512]
        assert np.abs(np.concatenate(got[slot])[:, :512] - want).max() < TOL, slot


# ---- the linear layer's three forms ---------------------------------------------- #
def test_the_recurrence_the_chunked_form_and_the_quadratic_form_agree():
    rng = np.random.default_rng(0)
    T, H, D = 24, 3, 8
    q, k, v = (rng.normal(size=(T, H, D)).astype(np.float32) for _ in range(3))
    decay = np.asarray([0.9, 0.3, 0.02], np.float32)
    # the recurrence
    state, rec = np.zeros((H, D, D)), []
    for t in range(T):
        state = np.exp(-decay)[:, None, None] * state + k[t][:, :, None] * v[t][:, None, :]
        rec.append(np.einsum("hd,hde->he", q[t], state))
    rec = np.stack(rec)
    # the O(T^2) masked product
    gap = np.arange(T)[:, None] - np.arange(T)[None]
    mask = np.where(gap >= 0, np.exp(-decay[:, None, None] * np.maximum(gap, 0)), 0.0)
    quad = np.einsum("hij,jhd->ihd", np.einsum("ihd,jhd->hij", q, k) * mask, v)
    assert np.abs(rec - quad).max() < 1e-4
    # the chunked form, three chunks of 8 of which the last holds 5 tokens
    s, out = jnp.zeros((H, D, D)), []
    for start, n in ((0, 8), (8, 8), (16, 5)):
        pad = lambda a: jnp.asarray(np.concatenate(
            [a[start:start + n], np.full((8 - n, H, D), 7.0, np.float32)]))
        o, s = hybrid.linear_chunk(pad(q), pad(k), pad(v), s, jnp.asarray(decay),
                                   jnp.arange(8) < n)
        out.append(np.asarray(o)[:n])
    assert np.abs(np.concatenate(out) - rec[:21]).max() < 1e-4
    state21, _ = np.zeros((H, D, D)), None
    for t in range(21):
        state21 = np.exp(-decay)[:, None, None] * state21 + k[t][:, :, None] * v[t][:, None, :]
    assert np.abs(np.asarray(s) - state21).max() < 1e-4


def test_the_decay_reads_the_published_depth():
    s = hybrid.linear_decay(config())
    assert s.shape == (3, 4)                 # the linear layers 4, 5 and 8 of 12
    want = 2.0 ** (-8 * (np.arange(4) + 1) / 4)
    for row, depth in zip(s, (4, 5, 8)):
        assert np.allclose(row, want * (1 - depth / 11 + 1e-5), rtol=1e-6)


# ---- the selection ------------------------------------------------------------------ #
def test_a_selection_wide_enough_to_hold_every_block_equals_dense_attention(tiny):
    """``topk`` 16 blocks hold all 256 positions: past ``dense_len`` the
    selection runs, chooses every block, and the logits are those of a stack
    that attends every key (``dense_len`` 256)."""
    model, params = tiny
    seq = _ids(130, seed=9)
    wide = GPT(config(dict(SPARSE, topk=16)))
    dense = GPT(config(dict(SPARSE, dense_len=256)))
    got = driver(wide, params).sequence(seq, chunks_of(90))
    want = driver(dense, params).sequence(seq, chunks_of(90))
    assert np.abs(got - want).max() < 1e-5
    # and the narrow selection of the other tests is NOT dense attention
    assert np.abs(driver(model, params).sequence(seq, chunks_of(90)) - want).max() > 100 * TOL


def test_keys_attended_follow_the_rows_lengths():
    cfg = config()
    t = np.asarray([0, 15, 63, 64, 79, 80, 200])
    # all keys up to 64; beyond, 3 whole blocks and the query's own up to it
    assert hybrid.keys_attended(cfg, t).tolist() == [1, 16, 64, 49, 64, 49, 57]
    assert hybrid.table_columns(cfg, 16) == 4 and hybrid.keys_a_page(cfg) == 4
    assert hybrid.layer_runs(cfg) == [("sparse", 0, 1), ("linear", 0, 2),
                                      ("sparse", 1, 2), ("linear", 2, 1)]


# ---- through the engine ---------------------------------------------------------------- #
def served(model, params, prompts, new, **serving):
    return served_tokens(model, params, prompts, new, **dict(SERVING, **serving))


reference_tokens = functools.partial(serving_helpers.reference_tokens, reference_logits, vocab=512)


def test_the_engine_serves_the_references_tokens_in_one_program(tiny):
    model, params = tiny
    prompts = [_ids(n, seed=n) for n in (100, 30, 70)]
    (tokens, eng) = served(model, params, prompts, (40, 50, 30))
    assert eng.compiled_programs() == 1
    for p, got in zip(prompts, tokens):
        best, gap = reference_tokens(params, p, got)
        assert got == best and gap == 0.0


def test_the_kernel_walks_the_chosen_pages(kernels, monkeypatch):
    """The same through ``paged_sparse_attention`` (the interpreter here),
    at heads of 128 lanes, which the kernel's gate asks for: rows of (token,
    K/V head) under the tables of the pages they chose."""
    monkeypatch.setitem(REF, "head_dim", 128)
    model = GPT(config(head_dim=128))
    params = model.init_params(jax.random.PRNGKey(1))
    kernels("paged_sparse_attention")
    prompts = [_ids(n, seed=n) for n in (100, 30)]
    (tokens, eng) = served(model, params, prompts, (12, 40))
    assert eng.paged_tile_pages == 4          # the table's four columns, one tile
    for p, got in zip(prompts, tokens):
        assert got == reference_tokens(params, p, got)[0]


def test_a_slot_reused_by_a_new_sequence_starts_from_a_zero_state(tiny):
    """One slot: the second request runs where the first left its states and
    its compressed keys, and is served what it gets on an engine of its own."""
    model, params = tiny
    a, b = _ids(90, seed=5), _ids(75, seed=6)
    (both, eng) = served(model, params, [a, b], (20, 30), max_batch_size=1)
    assert eng.step_count > 0
    (alone, _) = served(model, params, [b], (30,), max_batch_size=1)
    assert both[1] == alone[0] == reference_tokens(params, b, alone[0])[0]


def test_a_preempted_request_resumes_to_the_same_tokens(tiny):
    """An arena too small for three requests to grow together: the youngest
    is preempted, its pages go back, its state is rebuilt by the re-prefill
    (a chunk at position 0 starts from zero), and every request is served the
    tokens it gets alone."""
    model, params = tiny
    prompts = [_ids(n, seed=40 + n) for n in (70, 60, 50)]
    alone = [served(model, params, [p], (40,))[0][0] for p in prompts]
    assert serving_helpers.preempted(model, params, prompts, 40,
                                     **dict(SERVING, num_blocks=17)) == alone


def test_the_steps_stats_are_what_the_rows_lengths_give(tiny):
    model, params = tiny
    eng = deepspeed_tpu.init_serving(model=model, params=params,
                                     config={"serving": SERVING})
    a, b = eng.submit(_ids(70, 1), max_new_tokens=30), eng.submit(_ids(20, 2), max_new_tokens=30)
    per = 3 * 2                                # sparse layers x K/V heads
    seen, reqs = [], (a.request, b.request)
    while not (a.done and b.done):
        before = [r.prefilled for r in reqs]
        st = eng.step()
        seen.append(st)
        if not st["programs"]:
            continue
        # the rows of the program: a decode row at ``prefilled``, a chunk's
        # tokens from ``prefilled`` on
        t = np.asarray([t for r, b0 in zip(reqs, before) for t in range(b0, r.prefilled)])
        assert len(t) == st["decode_batch"] + st["prefill_tokens"]
        assert st["sparse_keys_resident"] == per * int((t + 1).sum())
        assert st["sparse_keys_attended"] == per * int(
            hybrid.keys_attended(model.cfg, t).sum())
        assert st["sparse_rows_dense"] == int((t + 1 <= 64).sum())
        assert st["sparse_rows_selected"] == 3 * int((t + 1 > 64).sum())
        assert st["sparse_rows_selected"] + 3 * st["sparse_rows_dense"] == 3 * len(t)
        assert st["state_bytes"] == 3 * SLOTS * 4 * 16 * 16 * 4
    eng.close()
    assert sum(s["state_slots_reset"] for s in seen if "state_slots_reset" in s) == 2
    checked = [s for s in seen if s.get("sparse_keys_attended")]
    assert any(s["sparse_keys_attended"] < s["sparse_keys_resident"] for s in checked)
    assert any(s["sparse_rows_dense"] for s in checked)
    assert any(s["sparse_rows_selected"] for s in checked)
    assert all("pages_full" in s for s in seen if s["programs"])


def test_a_snapshot_restores_by_recompute(tiny):
    """``snapshot()`` carries no state and no compressed key: ``restore()``
    prefills prompt and tokens so far again, from a zero state."""
    model, params = tiny
    p = _ids(80, seed=8)
    (whole, _) = served(model, params, [p], (30,))
    assert serving_helpers.restored_tokens(model, params, p, 30, 11, **SERVING) == whole[0]


# ---- what is refused, by the mechanism's name ------------------------------------------ #
@pytest.mark.parametrize("knob, mechanism", [
    ({"prefix_cache": True}, "prefix_cache shares full blocks"),
    ({"kv_tiering": True}, "kv_tiering spills"),
])
def test_init_serving_refuses_what_carries_no_state(tiny, knob, mechanism):
    model, params = tiny
    with pytest.raises(ValueError) as e:
        deepspeed_tpu.init_serving(model=model, params=params,
                                   config={"serving": dict(SERVING, **knob)})
    assert mechanism in str(e.value)
    assert "3 linear layers hold a recurrent state" in str(e.value)
    assert "compressed-key cache" in str(e.value)


def test_a_chunk_that_is_not_whole_strides_is_refused(tiny):
    model, params = tiny
    with pytest.raises(ValueError, match="whole strides of 4"):
        deepspeed_tpu.init_serving(model=model, params=params, config={
            "serving": dict(SERVING, prefill_chunk=6)})


@pytest.mark.parametrize("path", ["forward", "generate", "loss"])
def test_the_dense_paths_refuse_the_stack_by_what_they_lack(tiny, path):
    model, params = tiny
    said = serving_helpers.dense_path_refusal(model, params, path, _ids(16, 0))
    assert "chunked linear-attention scan" in said
    assert "init_serving()" in said


def test_the_leaves_are_stacked_by_kind_at_their_own_widths(tiny):
    model, params = tiny
    blocks = params["blocks"]
    assert set(blocks) == {"sparse", "linear"}
    assert blocks["sparse"]["kv_w"].shape == (3, 64, 2 * 2 * 16)    # 2 K/V heads
    assert blocks["linear"]["qkv_w"].shape == (3, 64, 3 * 64)       # 4 of each
    assert "onorm_g" in blocks["linear"] and "onorm_g" not in blocks["sparse"]
    held = sum(a.size for a in jax.tree.leaves(params)) - params["lnf_b"].size
    assert model.num_params() == held
    specs = model.partition_specs()
    assert jax.tree.structure(specs) == jax.tree.structure(
        jax.tree.map(lambda a: 0, params))
    # only the sparse layers own pages, a K/V head a page; a state a slot
    kp, vp = init_arena(model.cfg, 10, 16)
    assert kp.shape == vp.shape == (3, 10 * 2, 16, 16)
    aux = hybrid.init_aux(model.cfg, 10, 16, SLOTS, jnp.float32)
    assert aux["kc"].shape == (3, 10, 4 * 2 * 16)
    assert aux["state"].shape == (3, SLOTS, 4, 16, 16) and aux["state"].dtype == jnp.float32
    assert hybrid.aux_bytes(model.cfg, 10, SLOTS, 4) == (aux["kc"].nbytes, aux["state"].nbytes)


def test_the_five_other_families_keep_their_layouts():
    from deepspeed_tpu.models.gpt import (gpt_config, mistral4_config, olmoe_config,
                                          smallthinker_config)
    small = dict(vocab_size=256, n_positions=64, n_embd=32, n_head=4)
    plain = gpt_config("tiny")
    assert not plain.hybrid and plain.page_groups == (None,)
    assert plain.arena_layout == (2, 1, (64, 64))
    st = smallthinker_config(**small, n_layer=4, n_kv_head=2, head_dim=8,
                             intermediate_size=16, num_experts=4, top_k=2, window=8)
    assert st.page_groups == (None, 8, 8, 8) and st.arena_layout == (1, 4, (16, 16))
    m4 = mistral4_config(**small, n_layer=2, head_dim=16, q_lora_rank=16, kv_lora_rank=32,
                         qk_rope_dim=8, v_head_dim=16, intermediate_size=16,
                         num_experts=4, top_k=2)
    assert m4.arena_layout == (2, 1, (128,))
    assert not olmoe_config(**small, n_layer=2, intermediate_size=16, num_experts=4,
                            top_k=2).hybrid
    assert dataclasses.replace(plain, n_layer=4).mixers == ("softmax",)
