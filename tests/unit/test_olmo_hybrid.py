"""Olmo-Hybrid on the serving path (the ``delta`` and ``full`` mixers of
``models/hybrid.py`` under norms on the sublayers' outputs), at a tiny size
on the CPU in float32, against the plain reference
(``benchmarks/lib/reference_olmo_hybrid.py``): prefill in chunks whose
boundaries fall at every offset of the convolution's reach, then decode
through the pages and the two states; the chunked form against the
token-by-token recurrence; what a slot keeps against one whole-sequence pass;
a slot reused and a request preempted; a write strength past 1; the state's
kernel against its reference; the published parameter count; where the norm
sits; and what ``init_serving`` and the dense paths refuse."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmarks.lib import reference_olmo_hybrid as ref
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.gpt import GPT, olmo_hybrid_config
from deepspeed_tpu.ops.pallas import delta_rule
from deepspeed_tpu.serving.kv_cache import init_arena
from tests.unit import serving_helpers
from tests.unit.serving_helpers import Driver, jitted, served_tokens

TYPES = 2 * (3 * ["linear_attention"] + ["full_attention"])
WIDTHS = dict(vocab_size=512, n_positions=256, n_embd=64, n_head=4, n_kv_head=4,
              head_dim=16, intermediate_size=128, layer_types=TYPES, linear_heads=4,
              linear_key_head_dim=8, linear_value_head_dim=16)
REF = dict(layer_types=TYPES, n_head=4, head_dim=16, linear_heads=4,
           linear_key_head_dim=8, linear_value_head_dim=16, vocab_size=512, q_block=32)
BS, SLOTS, CHUNK, MB = 16, 3, 8, 16
SERVING = dict(max_batch_size=SLOTS, prefill_chunk=CHUNK, block_size=BS,
               num_blocks=64, dtype="float32")
# float32 against float32 at the highest matmul precision on both sides: what
# is left is the order of the sums (the chunked form's solve and the decode
# row's multiplied-out read against the recurrence; pages against one pass),
# which sixteen norms on the sublayers' outputs carry to logits of order 1
# at up to 3e-5 (a nudge of 1e-6 of the embedding moves the REFERENCE's
# logits by 1e-4 at these weights: hidden 64 under a norm on every output is
# that sensitive, and taps or write strengths four times louder ten times
# more).  bf16 in the delta layers' state alone reads 0.05-0.15, in the
# weights 0.25 and more
TOL = 1e-4


@pytest.fixture(scope="module")
def tiny():
    model = GPT(olmo_hybrid_config(**WIDTHS, dtype="float32"))
    return model, model.init_params(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def loud(tiny):
    """The same model with the leaves that seeded weights leave quiet made
    loud: taps of order 1 (at std 0.02 the convolution's sum lies under the L2
    norms' eps), write strengths over the whole of (0, 2) and decays that
    differ by token (at std 0.02 every ``b_t`` and ``a_t`` is near 0), and
    every gain different from 1."""
    model, params = tiny
    rng = np.random.default_rng(7)
    gain = lambda a: jnp.asarray(rng.uniform(0.5, 1.5, a.shape), jnp.float32)
    blocks = {m: dict(leaves) for m, leaves in params["blocks"].items()}
    delta, full = blocks["delta"], blocks["full"]
    delta["conv_w"] = delta["conv_w"] * 40.0
    delta["ba_w"] = delta["ba_w"] * 5.0
    delta["dt_bias"] = jnp.asarray(rng.normal(0, 1.0, delta["dt_bias"].shape), jnp.float32)
    for leaves in (delta, full):
        for name in leaves:
            if name.endswith("_g"):
                leaves[name] = gain(leaves[name])
    return model, dict(params, blocks=blocks, lnf_g=gain(params["lnf_g"]))


def reference_logits(params, seq, **kw):
    ids = np.zeros(-(-len(seq) // 32) * 32, np.int32)
    ids[:len(seq)] = seq
    fn = jitted(ref.olmo_hybrid_logits, **dict(REF, **kw))
    return np.asarray(fn(params, jnp.asarray(ids)))[:len(seq)]


def _ids(n, seed):
    return np.random.default_rng(seed).integers(0, 512, n).astype(np.int32)


# ``round_through=`` rounds the delta layers' state through that type after
# every step (a planted lower precision)
driver = functools.partial(Driver, slots=SLOTS, chunk=CHUNK, block_size=BS,
                           blocks_a_slot=MB, leaves=("delta_state",))


# ---- (a) the served logits against the reference's full forward pass ------------ #
# chunk boundaries at 8, 16, 24 (0 mod 4, the convolution's reach); at 5, 6,
# 7, 15, 18, 26 (1, 2, 3, 3, 2, 2 mod 4); after every one of the first six
CHUNKS = {"whole": (8, 8, 8), "ragged": (5, 1, 1, 8, 3, 8), "single": (1,) * 6}


@pytest.mark.parametrize("weights", ["seeded", "loud"])
@pytest.mark.parametrize("chunks", sorted(CHUNKS))
def test_prefill_then_decode_agree_with_the_reference(tiny, loud, weights, chunks):
    model, params = tiny if weights == "seeded" else loud
    seq = _ids(44, seed=len(chunks))
    got = driver(model, params).sequence(seq, CHUNKS[chunks])
    want = reference_logits(params, seq)
    assert np.abs(got - want).max() < TOL
    assert np.abs(want).max() > 0.5


def test_a_bf16_state_fails_the_tolerance(loud):
    """The planted lower precision: the delta layers' state alone rounded
    through bf16 after every step."""
    model, params = loud
    seq = _ids(44, seed=5)
    got = driver(model, params, round_through=jnp.bfloat16).sequence(seq, CHUNKS["ragged"])
    assert np.abs(got - reference_logits(params, seq)).max() > 5 * TOL


def test_bf16_weights_fail_the_tolerance(loud):
    model, params = loud
    seq = _ids(44, seed=5)
    rounded = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    got = driver(model, rounded).sequence(seq, CHUNKS["whole"])
    assert np.abs(got - reference_logits(params, seq)).max() > 50 * TOL


def test_a_step_with_decode_rows_and_a_chunk_together(loud):
    """Two sequences decode while a third's prompt runs in the chunk rows of
    the same steps, in another slot: every row's logits are its own
    sequence's, and so is every slot's state."""
    model, params = loud
    a, b, c = _ids(60, 1), _ids(40, 2), _ids(40, 3)
    d = driver(model, params)
    d.sequence(a[:30], (8, 8, 8, 6), slot=0)
    d.sequence(b[:11], (8, 3), slot=1)
    got = {0: [], 1: [], 2: []}
    for i, start in enumerate(range(0, len(c), CHUNK)):
        rows = d.step(decode=[(0, a[30 + i], 30 + i), (1, b[11 + i], 11 + i)],
                      chunk=(2, start, c[start:start + CHUNK]))
        got[0].append(rows[0][None]), got[1].append(rows[1][None])
        got[2].append(rows[SLOTS:SLOTS + CHUNK])
    n = len(c) // CHUNK
    for slot, seq, lo in ((0, a, 30), (1, b, 11), (2, c, 0)):
        want = reference_logits(params, seq)[lo:lo + (len(c) if slot == 2 else n)]
        assert np.abs(np.concatenate(got[slot]) - want).max() < TOL, slot


# ---- (b) the chunked form against the recurrence -------------------------------------- #
def _recurrence(q, k, v, g, beta, s_in, n, dtype=np.float64):
    """Step 5 of the specification, a token at a time in float64 (in the
    arguments' own type under another ``dtype``)."""
    S, out = np.asarray(s_in, dtype), []
    for t in range(n):
        S = np.exp(g[t])[:, None, None] * S
        m = np.einsum("hkv,hk->hv", S, k[t])
        S = S + k[t][:, :, None] * (beta[t][:, None] * (v[t] - m))[:, None, :]
        out.append(np.einsum("hkv,hk->hv", S, q[t]))
    return np.stack(out), S


def _tokens(C, H, dk, dv, seed):
    r = np.random.default_rng(seed)
    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)
    return (unit(r.normal(size=(C, H, dk))) / np.sqrt(dk), unit(r.normal(size=(C, H, dk))),
            r.normal(size=(C, H, dv)), -r.uniform(0.0, 0.7, (C, H)), r.uniform(0.0, 2.0, (C, H)),
            r.normal(size=(H, dk, dv)))


@pytest.mark.parametrize("live", [24, 17, 1])
def test_the_chunked_form_is_the_recurrence(live):
    """A chunk entered with a non-zero state whose live length is short of
    the chunk: the solve gives the recurrence's reads and leaves its state;
    the rows past the live length write nothing and decay nothing."""
    q, k, v, g, beta, s_in = _tokens(24, 3, 8, 16, seed=live)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    o, s_out = hybrid.delta_chunk(f32(q), f32(k), f32(v), f32(g), f32(beta), f32(s_in),
                                  jnp.arange(24) < live)
    want_o, want_s = _recurrence(q, k, v, g, beta, s_in, live)
    assert np.abs(np.asarray(o)[:live] - want_o).max() < 2e-5
    assert np.abs(np.asarray(s_out) - want_s).max() < 2e-5
    assert np.abs(want_o).max() > 0.1


@pytest.mark.parametrize("decays", [True, False], ids=["decay", "no-decay-repeated-key"])
@pytest.mark.parametrize("C, live", [(176, 176), (176, 41), (512, 512), (512, 300), (40, 33)])
def test_the_chunked_form_at_the_cells_chunk_lengths(C, live, decays):
    """The two cells' own chunks (176 and 512) and a ragged one, whole and
    short of the chunk, write strengths up to 2: the writes got from
    products (:func:`hybrid.unit_lower_solve`: substitution rows, doubled
    blocks, block rows, the chunk padded to whole blocks) read what the
    recurrence reads.  Without decay nothing is forgotten over the chunk, and
    a third of the tokens share ONE key: the case in which the powers of
    ``L`` grow before they cancel (a finite product in the rows' place is
    wrong in the second digit there).  The tolerance is one the recurrence a
    token at a time in float32 meets with room: at 512 tokens the form's own
    sums of decays are good to 1e-5."""
    q, k, v, g, beta, s_in = _tokens(C, 2, 16, 8, seed=C + live)
    if not decays:
        g, k[::3] = np.zeros_like(g), k[0]
    assert beta.max() > 1.9
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    o, s_out = jax.jit(hybrid.delta_chunk)(f32(q), f32(k), f32(v), f32(g), f32(beta),
                                           f32(s_in), jnp.arange(C) < live)
    want_o, want_s = _recurrence(q, k, v, g, beta, s_in, live)
    single = [np.asarray(a, np.float32) for a in (q, k, v, g, beta, s_in)]
    for got, want in zip(_recurrence(*single, live, np.float32), (want_o, want_s)):
        assert np.abs(got - want).max() < 1e-5
    assert np.abs(np.asarray(o)[:live] - want_o).max() < 3e-5
    assert np.abs(np.asarray(s_out) - want_s).max() < 3e-5
    assert np.abs(want_o).max() > 0.1


def test_no_triangular_solve_is_left_in_a_chunk():
    """The writes of a chunk of 512 lower to products: XLA's triangular
    solve (a custom call on the chip, a row-by-row substitution of 1.4 ms a
    layer; PERF.md § 6, PR 65) is in neither text of the program."""
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    C, H, dk, dv = 512, 2, 16, 8
    lowered = jax.jit(hybrid.delta_chunk).lower(
        shape(C, H, dk), shape(C, H, dk), shape(C, H, dv), shape(C, H), shape(C, H),
        shape(H, dk, dv), jax.ShapeDtypeStruct((C,), jnp.bool_))
    for text in (lowered.as_text(), lowered.as_text(dialect="hlo")):
        assert "dot" in text
        assert "triangular_solve" not in text and "triangular-solve" not in text


# ---- (e) a write strength past 1 ------------------------------------------------------- #
def test_a_repeated_key_under_a_strength_of_two_flips_the_sign():
    """``beta = 2`` and no decay: the transition along the written key has
    the eigenvalue ``1 - beta = -1``.  A key written with ``v`` and written
    again with 0 leaves a state that returns ``-2 v`` for it where it
    returned ``2 v``; at ``beta = 1`` the second write erases it."""
    r = np.random.default_rng(0)
    k = r.normal(size=(1, 2, 8))
    k = np.repeat(k / np.linalg.norm(k, axis=-1, keepdims=True), 2, axis=0)      # twice
    v = np.concatenate([r.normal(size=(1, 2, 16)), np.zeros((1, 2, 16))])
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    for strength, returns in ((2.0, -2.0), (1.0, 0.0)):
        o, s = hybrid.delta_chunk(f32(k), f32(k), f32(v), jnp.zeros((2, 2)),
                                  jnp.full((2, 2), strength), jnp.zeros((2, 8, 16)),
                                  jnp.ones(2, bool))
        assert np.abs(np.asarray(o)[0] - strength * v[0]).max() < 1e-5
        assert np.abs(np.asarray(o)[1] - returns * v[0]).max() < 1e-5
        assert np.abs(np.einsum("hkv,hk->hv", np.asarray(s), k[0]) - returns * v[0]).max() < 1e-5


def test_the_models_strength_reaches_past_one(loud):
    """``linear_allow_neg_eigval`` doubles the sigmoid: with it the served
    logits are the reference's with the factor and not without."""
    model, params = loud
    seq = _ids(24, seed=9)
    got = driver(model, params).sequence(seq, (8, 8, 8))
    assert np.abs(got - reference_logits(params, seq)).max() < TOL
    halved = reference_logits(params, seq, linear_allow_neg_eigval=False)
    assert np.abs(got - halved).max() > 100 * TOL
    single = GPT(dataclasses.replace(model.cfg, delta_neg_eigval=False))
    assert np.abs(driver(single, params).sequence(seq, (8, 8, 8)) - halved).max() < TOL


# ---- the state's kernel ------------------------------------------------------------------ #
@pytest.mark.parametrize("H, dk, dv", [(4, 16, 64), (6, 8, 192), (2, 24, 128)])
def test_the_state_kernel_is_its_reference(kernels, H, dk, dv):
    """``delta_state_update`` through the interpreter against the reference
    beside it and against the specification's five lines: heads two a lane
    group (64 and 192 lanes) and one (128); a row that is not live and the
    other layers' states are left to the bit."""
    assert delta_rule.kernel_shape_ok(H, dk, dv, jnp.float32)
    assert not delta_rule.kernel_shape_ok(4, 8, 16, jnp.float32)      # the tiny preset
    assert not delta_rule.kernel_shape_ok(H, dk, dv, jnp.bfloat16)
    q, k, v, g, beta, _ = _tokens(5, H, dk, dv, seed=H)
    r = np.random.default_rng(1)
    state = jnp.asarray(r.normal(size=(3, 5, dk, H * dv)), jnp.float32)
    live = jnp.asarray([True, True, False, True, True])
    args = [jnp.asarray(a, jnp.float32) for a in (q, k, v, np.exp(g), beta)]
    kernels()
    want_s, want_o = jax.jit(delta_rule.delta_state_update)(state, 1, *args, live)
    # the reference's results stay behind their barrier: beside a serving
    # arena the chip's compiler recomputed what read the state after the
    # update was written in place (PERF.md § 6, PR 47); no CPU run shows it
    assert "optimization_barrier" in str(jax.make_jaxpr(
        delta_rule.delta_state_update)(state, 1, *args, live))
    kernels("delta_state_update")
    got_s, got_o = jax.jit(delta_rule.delta_state_update)(state, 1, *args, live)
    assert np.abs(np.asarray(got_s) - np.asarray(want_s)).max() < 1e-5
    assert np.abs(np.asarray(got_o) - np.asarray(want_o)).max() < 1e-5
    for s in (got_s, want_s):
        assert (s[0] == state[0]).all() and (s[2] == state[2]).all() and (s[1, 2] == state[1, 2]).all()
    heads = lambda s: np.asarray(s, np.float64).reshape(5, dk, H, dv).transpose(0, 2, 1, 3)
    for n in (0, 1, 3, 4):
        o, s = _recurrence(q[n][None], k[n][None], v[n][None], g[n][None], beta[n][None],
                           heads(state[1])[n], 1)
        assert np.abs(np.asarray(got_o)[n] - o[0]).max() < 1e-4
        assert np.abs(heads(got_s[1])[n] - s).max() < 1e-4


def test_the_walk_runs_the_state_kernel_where_its_gate_admits(kernels):
    """A stack whose four delta heads' values are one whole lane tile (4 x
    32): the kernel runs inside the scan over layers, on the stacked states
    in place, and the served logits are the reference's."""
    kw = dict(WIDTHS, linear_value_head_dim=32)
    model = GPT(olmo_hybrid_config(**kw, dtype="float32"))
    assert delta_rule.kernel_shape_ok(4, 8, 32, jnp.float32)
    params = model.init_params(jax.random.PRNGKey(1))
    seq = _ids(20, seed=2)
    kernels("delta_state_update")
    got = driver(model, params).sequence(seq, (8, 5))
    want = reference_logits(params, seq, linear_value_head_dim=32)
    assert np.abs(got - want).max() < TOL


# ---- (c) what the pages and a slot keep ------------------------------------------------- #
def test_a_decode_rows_states_k_and_v_are_one_whole_sequence_pass(loud):
    """Token by token through the states, or all 40 tokens as ONE chunk (the
    convolution shifts along its rows, the delta rule one solve): the same K
    and V in the pages, the same state and the same last three packed rows
    left in the slot."""
    model, params = loud
    seq = _ids(40, seed=11)
    steps = driver(model, params)
    steps.sequence(seq, (5,))                     # 5 prefilled, 35 decode rows
    whole = driver(model, params, chunk=40)
    whole.sequence(seq, (40,))
    pages = slice(1, 1 + 3)                       # slot 0's first three blocks
    for a, b in ((steps.kp, whole.kp), (steps.vp, whole.vp)):
        a, b = np.asarray(a[:, pages]).reshape(2, -1, 64), np.asarray(b[:, pages]).reshape(2, -1, 64)
        assert np.abs(a[:, :40] - b[:, :40]).max() < TOL
        assert np.abs(b[:, :40]).max() > 0.1
    for name, shape in (("delta_state", (6, 8, 64)), ("delta_conv", (6, 3, 128))):
        a, b = (np.asarray(d.aux[name][:, 0]) for d in (steps, whole))
        assert a.shape == shape and np.abs(a - b).max() < TOL and np.abs(b).max() > 0.01, name
    # the convolution's state is the last three packed rows, the oldest
    # first: a token earlier, its last two are the first two
    before = driver(model, params, chunk=40)
    before.sequence(seq[:39], (39,))
    conv = np.asarray(whole.aux["delta_conv"][:, 0])
    assert np.abs(np.asarray(before.aux["delta_conv"][:, 0, 1:]) - conv[:, :2]).max() < TOL
    # the other slots' states were never written
    assert not np.asarray(whole.aux["delta_state"][:, 1:]).any()


def test_the_leaves_are_two_stacks_and_the_states_a_slot(tiny):
    model, params = tiny
    cfg, blocks = model.cfg, params["blocks"]
    assert set(blocks) == {"delta", "full"} and cfg.mixers == 2 * (3 * ("delta",) + ("full",))
    assert cfg.ffns == ("mlp",) * 8 and cfg.norm_after and cfg.qk_norm
    assert hybrid.layer_runs(cfg) == [("delta", 0, 3), ("full", 0, 1), ("delta", 3, 3), ("full", 1, 1)]
    assert not any(kind.rope for kind in cfg.pattern)
    delta, full = blocks["delta"], blocks["full"]
    assert delta["qkv_w"].shape == (6, 64, 2 * 32 + 64) and delta["conv_w"].shape == (6, 4, 128)
    assert delta["gate_w"].shape == (6, 64, 64) and delta["ba_w"].shape == (6, 64, 8)
    assert delta["onorm_g"].shape == (6, 16) and delta["out_w"].shape == (6, 64, 64)
    assert np.allclose(np.exp(np.asarray(delta["a_log"])), 0.02 * np.arange(1, 5))
    assert not np.asarray(delta["dt_bias"]).any()
    assert full["qkv_w"].shape == (2, 64, 192) and full["q_norm_g"].shape == (2, 64)
    assert "lm_head" in params
    specs = model.partition_specs()
    assert jax.tree.structure(specs) == jax.tree.structure(jax.tree.map(lambda a: 0, params))
    # the full layers own plain pages of all K/V heads; the delta layers a
    # float32 state and three packed rows a slot
    assert cfg.arena_layout == (2, 1, (64, 64)) and cfg.page_groups == (None,)
    kp, vp = init_arena(cfg, 10, 16)
    assert kp.shape == vp.shape == (2, 10, 16, 64)
    aux = hybrid.init_aux(cfg, 10, 16, SLOTS, jnp.bfloat16)
    assert set(aux) == {"delta_state", "delta_conv"}
    assert aux["delta_state"].shape == (6, SLOTS, 8, 64) and aux["delta_state"].dtype == jnp.float32
    assert aux["delta_conv"].shape == (6, SLOTS, 3, 128) and aux["delta_conv"].dtype == jnp.bfloat16


# ---- (h) where the norm sits --------------------------------------------------------------- #
def test_the_residual_enters_mixer_and_mlp_as_it_is(loud):
    """``x + norm(f(x))``: the same leaves walked with the norms on the
    sublayers' INPUTS are another model, and a mixer whose output norm's gain
    is 0 adds nothing whatever it computes."""
    model, params = loud
    seq = _ids(24, seed=4)
    want = reference_logits(params, seq)
    assert np.abs(driver(model, params).sequence(seq, (8, 8, 8)) - want).max() < TOL
    pre = GPT(dataclasses.replace(model.cfg, norm_after=False))
    assert np.abs(driver(pre, params).sequence(seq, (8, 8, 8)) - want).max() > 1000 * TOL
    muted = {m: dict(leaves, ln1_g=jnp.zeros_like(leaves["ln1_g"]))
             for m, leaves in params["blocks"].items()}
    loudest = {m: dict(leaves, out_w=leaves["out_w"] * 50.0) for m, leaves in muted.items()}
    a = driver(model, dict(params, blocks=muted)).sequence(seq, (8, 8, 8))
    b = driver(model, dict(params, blocks=loudest)).sequence(seq, (8, 8, 8))
    assert np.abs(a - b).max() < TOL and np.abs(a - want).max() > 1000 * TOL
    assert np.abs(a - reference_logits(dict(params, blocks=muted), seq)).max() < TOL


# ---- through the engine ---------------------------------------------------------------------- #
def served(model, params, prompts, new, **serving):
    return served_tokens(model, params, prompts, new, **dict(SERVING, **serving))


reference_tokens = functools.partial(serving_helpers.reference_tokens, reference_logits)


def test_the_engine_serves_the_references_tokens_in_one_program(loud):
    model, params = loud
    prompts = [_ids(n, seed=n) for n in (50, 13, 29)]
    (tokens, eng) = served(model, params, prompts, (20, 30, 25))
    assert eng.compiled_programs() == 1
    # K and V of 4 heads of 16 a token a full layer; nothing a token in a delta layer
    assert eng.cache_bytes_per_token == 2 * 64 * 4 and eng.chunk_queries_per_row >= 1
    assert eng._k_pages.shape == (2, 64, BS, 64)
    for p, got in zip(prompts, tokens):
        best, gap = reference_tokens(params, p, got)
        assert got == best and gap == 0.0


# ---- (d) a slot reused, a request preempted --------------------------------------------------- #
def test_a_slot_reused_by_a_new_sequence_starts_from_zero_states(loud):
    """One slot: the second request runs where the first left its states,
    and is served what it gets on an engine of its own."""
    model, params = loud
    a, b = _ids(37, seed=5), _ids(22, seed=6)
    (both, eng) = served(model, params, [a, b], (20, 30), max_batch_size=1)
    assert eng.step_count > 0
    (alone, _) = served(model, params, [b], (30,), max_batch_size=1)
    assert both[1] == alone[0] == reference_tokens(params, b, alone[0])[0]


def test_a_preempted_request_resumes_to_the_same_tokens(loud):
    """An arena too small for three requests to grow together: the youngest
    is preempted, its pages go back, its states are rebuilt by the re-prefill
    (a chunk at position 0 starts from zero whatever the slot holds), and
    every request is served the tokens it gets alone; ``state_slots_reset``
    counts the first chunks, ``delta_state_moves`` the states a step moved."""
    model, params = loud
    prompts = [_ids(n, seed=40 + n) for n in (70, 60, 50)]
    alone = [served(model, params, [p], (40,))[0][0] for p in prompts]

    def moves(st):
        assert st["delta_state_moves"] == 6 * (st["decode_batch"] + (st["prefill_tokens"] > 0))
        assert st["delta_state_bytes"] == 6 * SLOTS * 8 * 64 * 4
        assert st["delta_conv_bytes"] == 6 * SLOTS * 3 * 128 * 4
    assert serving_helpers.preempted(model, params, prompts, 40, moves,
                                     **dict(SERVING, num_blocks=17)) == alone


def test_a_snapshot_restores_by_recompute(loud):
    """``snapshot()`` carries no state, no packed row and no K or V:
    ``restore()`` prefills prompt and generated tokens again from zero."""
    model, params = loud
    p = _ids(45, seed=8)
    (whole, _) = served(model, params, [p], (30,))
    assert serving_helpers.restored_tokens(model, params, p, 30, 11, **SERVING) == whole[0]


# ---- (f) the published parameter count ------------------------------------------------------- #
def test_the_published_config_counts_the_issues_parameters():
    mlp, norms = 3 * 3840 * 11008, 2 * 3840
    linear = (2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30 + 4 * 11520 + 60 + 192
              + mlp + norms)
    full = 4 * 3840 * 3840 + 2 * 3840 + mlp + norms
    assert (linear, full) == (215_570_172, 185_809_920)
    types = 8 * (3 * ["linear_attention"] + ["full_attention"])
    whole, held = GPT(olmo_hybrid_config(layer_types=types)), GPT(olmo_hybrid_config(layer_types=types[:16]))
    assert whole.num_params() == 8 * (3 * linear + full) + 2 * 100_352 * 3840 + 3840 == 7_430_870_688
    assert held.num_params() == 4_100_788_944
    shapes = jax.eval_shape(held.init_params, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) - 3840 == 4_100_788_944
    cfg = held.cfg
    assert (cfg.ln_eps, cfg.padded_vocab, cfg.n_positions) == (1e-6, 100_352, 65_536)
    assert cfg.untied_head and cfg.cache_lanes == (3840, 3840) and cfg.arena_layout[0] == 4
    aux = jax.eval_shape(lambda: hybrid.init_aux(cfg, 16, 16, 80, jnp.bfloat16))
    assert aux["delta_state"].shape == (12, 80, 96, 5760)
    assert aux["delta_state"].size * 4 // (12 * 80) == 2_211_840
    assert aux["delta_conv"].shape == (12, 80, 3, 11_520)
    with pytest.raises(AssertionError, match="full beside one of them"):
        olmo_hybrid_config(layer_types=["full_attention"] * 2)


# ---- (g) what is refused, by the mechanism's name ---------------------------------------------- #
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
    assert "6 delta layers hold a recurrent state and a convolution state a slot" in str(e.value)
    assert "no block of K and V carries" in str(e.value)


@pytest.mark.parametrize("path", ["forward", "generate", "loss"])
def test_the_dense_paths_refuse_the_stack_by_what_they_lack(tiny, path):
    model, params = tiny
    said = serving_helpers.dense_path_refusal(model, params, path, _ids(16, 0))
    assert "no chunked delta-rule scan (nor its backward)" in said
    assert "6 delta layers" in said and "init_serving()" in said


def test_a_norm_on_the_output_outside_the_hybrid_walk_is_refused():
    from deepspeed_tpu.models.gpt import llama_config
    with pytest.raises(AssertionError, match="read by the hybrid walk"):
        llama_config(vocab_size=256, n_positions=64, n_embd=32, n_head=4, n_layer=2,
                     norm_after=True)
