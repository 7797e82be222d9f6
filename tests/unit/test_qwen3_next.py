"""Qwen3-Next on the serving path (``models/hybrid.py``: the ``delta`` mixer
with fewer key heads than value heads, the ``full`` mixer with a norm a head,
rope on a quarter of the lanes and an output gate, both over a HELD share of
a softmax bank beside a gated shared expert), at a tiny size on the CPU in
float32, against the plain reference
(``benchmarks/lib/reference_qwen3_next.py``): prefill in chunks then decode
through the pages and the states; the chunked form against the recurrence
with two value heads a key head; where rope reaches; the gate and the head
norm; the shares of the bank adding up to the layer; the published parameter
count; what the engine says of the held bank; what the refusals still
refuse; and that the stacks which share this code lower as they did."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.lib import reference_qwen3_next as ref
from deepspeed_tpu.models import gpt, hybrid
from deepspeed_tpu.models.gpt import (GPT, GPTConfig, keye_vl2_config,
                                      olmo_hybrid_config, qwen3_next_config)
from deepspeed_tpu.serving.kv_cache import init_arena
from tests.unit import serving_helpers
from tests.unit.serving_helpers import Driver, jitted, served_tokens

TYPES = 3 * ["linear_attention"] + ["full_attention"]
WIDTHS = dict(vocab_size=512, n_positions=256, n_embd=64, n_head=4, n_kv_head=2,
              head_dim=16, intermediate_size=160, layer_types=TYPES, linear_heads=4,
              linear_key_heads=2, linear_key_head_dim=8, linear_value_head_dim=16,
              num_experts=16, top_k=4, moe_intermediate_size=32,
              shared_expert_intermediate_size=32)
REF = dict(layer_types=TYPES, n_head=4, n_kv_head=2, head_dim=16, rope_dim=4,
           linear_key_heads=2, linear_heads=4, linear_key_head_dim=8,
           linear_value_head_dim=16, top_k=4, vocab_size=512, q_block=32)
HELD = (0, 8)
BS, SLOTS, CHUNK, MB = 16, 3, 8, 16
SERVING = dict(max_batch_size=SLOTS, prefill_chunk=CHUNK, block_size=BS,
               num_blocks=64, dtype="float32")
# float32 against float32 at the highest matmul precision on both sides: what
# is left is the order of the sums (the chunked form's solve against the
# recurrence, pages against one pass, sorted rows against every expert)
TOL = 2e-5


def _model(**kw):
    return GPT(qwen3_next_config(**dict(WIDTHS, **kw), dtype="float32"))


@pytest.fixture(scope="module")
def tiny():
    model = _model(experts_held=HELD)
    return model, model.init_params(jax.random.PRNGKey(0))


def _loud(params, seed=7):
    """The leaves that seeded weights leave quiet made loud: taps of order 1,
    write strengths over (0, 1) and decays that differ by token, every gain
    different from 1, a router that prefers some experts, a shared gate away
    from a half."""
    rng = np.random.default_rng(seed)
    gain = lambda a: jnp.asarray(rng.uniform(0.5, 1.5, a.shape), jnp.float32)
    blocks = {m: dict(leaves) for m, leaves in params["blocks"].items()}
    delta = blocks["delta"]
    delta["conv_w"] = delta["conv_w"] * 40.0
    delta["ba_w"] = delta["ba_w"] * 5.0
    delta["dt_bias"] = jnp.asarray(rng.normal(0, 1.0, delta["dt_bias"].shape), jnp.float32)
    for leaves in blocks.values():
        leaves["router_w"] = leaves["router_w"] * 20.0
        leaves["shared_gate_w"] = leaves["shared_gate_w"] * 30.0
        for name in leaves:
            if name.endswith("_g"):
                leaves[name] = gain(leaves[name])
    return dict(params, blocks=blocks, lnf_g=gain(params["lnf_g"]))


@pytest.fixture(scope="module")
def loud(tiny):
    model, params = tiny
    return model, _loud(params)


def reference_logits(params, seq, **kw):
    ids = np.zeros(-(-len(seq) // 32) * 32, np.int32)
    ids[:len(seq)] = seq
    fn = jitted(ref.qwen3_next_logits, **dict(REF, experts_held=HELD, **kw))
    return np.asarray(fn(params, jnp.asarray(ids)))[:len(seq)]


def _ids(n, seed):
    return np.random.default_rng(seed).integers(0, 512, n).astype(np.int32)


driver = functools.partial(Driver, slots=SLOTS, chunk=CHUNK, block_size=BS,
                           blocks_a_slot=MB, leaves=("delta_state",))
CHUNKS = {"whole": (8, 8, 8), "ragged": (5, 1, 1, 8, 3, 8), "single": (1,) * 6}


# ---- the served logits against the reference's full forward pass ------------------ #
@pytest.mark.parametrize("weights", ["seeded", "loud"])
@pytest.mark.parametrize("chunks", sorted(CHUNKS))
def test_prefill_then_decode_agree_with_the_reference(tiny, loud, weights, chunks):
    model, params = tiny if weights == "seeded" else loud
    seq = _ids(44, seed=len(chunks))
    got = driver(model, params).sequence(seq, CHUNKS[chunks])
    want = reference_logits(params, seq)
    assert np.abs(got - want).max() < TOL
    assert np.abs(want).max() > 0.1


def test_a_bf16_state_fails_the_tolerance(loud):
    model, params = loud
    seq = _ids(44, seed=5)
    got = driver(model, params, round_through=jnp.bfloat16).sequence(seq, CHUNKS["ragged"])
    assert np.abs(got - reference_logits(params, seq)).max() > 5 * TOL


def test_a_step_with_decode_rows_and_a_chunk_together(loud):
    """Two sequences decode while a third's prompt runs in the chunk rows of
    the same steps: every row's logits are its own sequence's."""
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


# ---- the chunked form against the recurrence, two value heads a key head ---------- #
def _recurrence(q, k, v, g, beta, s_in, n):
    """Step 5 of the specification, a token at a time in float64; ``q`` and
    ``k`` a KEY head, value head ``j`` on key head ``j // (Hv / Hk)``."""
    S, out = np.asarray(s_in, np.float64), []
    of = np.arange(v.shape[1]) // (v.shape[1] // q.shape[1])
    for t in range(n):
        S = np.exp(g[t])[:, None, None] * S
        m = np.einsum("hkv,hk->hv", S, k[t][of])
        S = S + k[t][of][:, :, None] * (beta[t][:, None] * (v[t] - m))[:, None, :]
        out.append(np.einsum("hkv,hk->hv", S, q[t][of]))
    return np.stack(out), S


@pytest.mark.parametrize("live", [24, 17, 1])
def test_the_chunked_form_is_the_recurrence_with_two_value_heads_a_key_head(live):
    r = np.random.default_rng(live)
    C, Hk, Hv, dk, dv = 24, 2, 4, 8, 16
    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)
    q, k = unit(r.normal(size=(C, Hk, dk))) / np.sqrt(dk), unit(r.normal(size=(C, Hk, dk)))
    v, g = r.normal(size=(C, Hv, dv)), -r.uniform(0.0, 0.7, (C, Hv))
    beta, s_in = r.uniform(0.0, 1.0, (C, Hv)), r.normal(size=(Hv, dk, dv))
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    twice = lambda a: jnp.repeat(f32(a), Hv // Hk, axis=1)       # as the mixer hands them
    o, s_out = hybrid.delta_chunk(twice(q), twice(k), f32(v), f32(g), f32(beta), f32(s_in),
                                  jnp.arange(C) < live)
    want_o, want_s = _recurrence(q, k, v, g, beta, s_in, live)
    assert np.abs(np.asarray(o)[:live] - want_o).max() < 2e-5
    assert np.abs(np.asarray(s_out) - want_s).max() < 2e-5
    assert np.abs(want_o).max() > 0.1


def test_a_value_head_reads_its_own_key_head(loud):
    """The reference with the value heads dealt ROUND ROBIN over the key
    heads (``j % Hk``) is another model: the mixer's is ``j // 2``."""
    model, params = loud
    seq = _ids(24, seed=9)
    got = driver(model, params).sequence(seq, (8, 8, 8))
    assert np.abs(got - reference_logits(params, seq)).max() < TOL
    # swap the two middle value heads' lanes of v, z, b, a, the decay's
    # leaves and W_o: heads (0, 1, 2, 3) on key heads (0, 0, 1, 1) become
    # (0, 2, 1, 3) on (0, 1, 0, 1): the same weights dealt round robin
    d = dict(params["blocks"]["delta"])
    order = np.asarray([0, 2, 1, 3])
    lanes = lambda n: (order[:, None] * n + np.arange(n)).reshape(-1)
    d["qkv_w"] = jnp.concatenate([d["qkv_w"][..., :32], d["qkv_w"][..., 32:][..., lanes(16)]], -1)
    d["conv_w"] = jnp.concatenate([d["conv_w"][..., :32], d["conv_w"][..., 32:][..., lanes(16)]], -1)
    d["gate_w"] = d["gate_w"][..., lanes(16)]
    d["ba_w"] = d["ba_w"][..., np.concatenate([order, 4 + order])]
    d["a_log"], d["dt_bias"] = d["a_log"][..., order], d["dt_bias"][..., order]
    d["out_w"] = d["out_w"][:, lanes(16)]
    dealt = dict(params, blocks=dict(params["blocks"], delta=d))
    assert np.abs(driver(model, dealt).sequence(seq, (8, 8, 8)) - got).max() > 100 * TOL


# ---- the full layer: rope's reach, the head norm, the gate ------------------------- #
def test_rope_touches_the_first_quarter_of_a_heads_lanes_and_no_other():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 6, 4, 16)), jnp.float32)
    at = jnp.arange(3, 9)[None]
    cfg = _model().cfg
    assert cfg.rope_dim == 4 and cfg.rope_theta == 1e7
    got = np.asarray(gpt.apply_rope(x, at, cfg.rope_theta, cfg.rope_dim))
    assert (got[..., 4:] == np.asarray(x)[..., 4:]).all()
    assert (got[..., :4] != np.asarray(x)[..., :4]).all()
    # the reference's own rotation, in the pairs (0, 2) and (1, 3)
    want = np.asarray(ref.rope(jnp.pad(x[0], ((3, 0), (0, 0), (0, 0))), 4, 1e7))[3:]
    assert np.abs(got[0] - want).max() < 1e-6
    a, b = np.asarray(x)[0, 1, 0, 0], np.asarray(x)[0, 1, 0, 2]
    assert abs(got[0, 1, 0, 0] - (a * np.cos(4.0) - b * np.sin(4.0))) < 1e-5


def test_the_gate_and_the_head_norm_against_the_reference(loud):
    """The full layer alone, served against the reference's ``full_layer``;
    without the gate, or with the norm's gains left at 1, it is another."""
    model, params = loud
    seq = _ids(24, seed=3)
    want = reference_logits(params, seq)
    assert np.abs(driver(model, params).sequence(seq, (8, 8, 8)) - want).max() < TOL
    full = params["blocks"]["full"]
    assert full["q_norm_g"].shape == full["k_norm_g"].shape == (1, 16)
    assert full["gate_w"].shape == (1, 64, 64)
    for name, other in (("gate_w", jnp.zeros_like(full["gate_w"])),
                        ("q_norm_g", jnp.ones_like(full["q_norm_g"]))):
        changed = dict(params, blocks=dict(params["blocks"], full=dict(full, **{name: other})))
        got = driver(model, changed).sequence(seq, (8, 8, 8))
        assert np.abs(got - want).max() > 100 * TOL, name
        assert np.abs(got - reference_logits(changed, seq)).max() < TOL, name


# ---- the bank: the shares add up ----------------------------------------------------- #
def test_the_shares_add_up_to_the_uncut_layer(loud):
    """One layer's feed-forward over the same rows: the outputs of the
    experts 0..7 and 8..15, the shared expert counted once, sum to the uncut
    reference's layer; and the program's held half is the reference's."""
    model, params = loud
    whole = _model()
    uncut = whole.init_params(jax.random.PRNGKey(3))
    uncut = _loud(uncut, seed=11)
    p = jax.tree.map(lambda a: a[0], uncut["blocks"]["delta"])
    m = jnp.asarray(np.random.default_rng(2).normal(size=(40, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.ffn(p, m, top_k=4, experts_held=None)
        half = lambda lo: dict(p, experts=jax.tree.map(lambda a: a[lo:lo + 8], p["experts"]))
        first = ref.ffn(half(0), m, top_k=4, experts_held=(0, 8))
        second = ref.ffn(half(8), m, top_k=4, experts_held=(8, 8), shared=False)
    assert np.abs(np.asarray(first + second - want)).max() < 1e-5
    assert np.abs(np.asarray(second)).max() > 1e-3 < np.abs(np.asarray(first - second)).max()
    # the program's feed-forward of a held half against the reference's
    for lo, cfg in ((0, model.cfg), (8, dataclasses.replace(model.cfg, moe_experts_held=(8, 8)))):
        step = hybrid._Step(None, jnp.ones(40, bool), None, None, None, None, 0,
                            jnp.float32, None)
        leaves = dict(half(lo), ln2_g=jnp.ones(64))
        bank = jax.tree.map(lambda a: a[None], leaves["experts"])
        with jax.default_matmul_precision("highest"):
            y, _, counts = hybrid.FEED_FORWARDS["moe_softmax"](
                cfg, leaves, bank, 0, m, None, step)
            mine = ref.ffn(leaves, ref._rms(m, 1.0, 1e-6), top_k=4, experts_held=(lo, 8))
        assert np.abs(np.asarray(y - mine)).max() < 1e-5, lo
        assert counts.shape == (16,) and int(counts.sum()) == 40 * 4


# ---- the leaves, the arena, the states ------------------------------------------------- #
def test_the_leaves_are_two_stacks_and_the_states_a_slot(tiny):
    model, params = tiny
    cfg, blocks = model.cfg, params["blocks"]
    assert set(blocks) == {"delta", "full"} and cfg.mixers == 3 * ("delta",) + ("full",)
    assert cfg.ffns == ("moe_softmax",) * 4 and not cfg.norm_after
    assert cfg.qk_norm == "head" and cfg.attn_gate and cfg.moe_shared_gate
    assert hybrid.layer_runs(cfg) == [("delta", 0, 3), ("full", 0, 1)]
    assert all(kind.rope for kind in cfg.pattern)
    delta, full = blocks["delta"], blocks["full"]
    # the packed lanes: 2 x (2 key heads x 8) + 4 value heads x 16
    assert delta["qkv_w"].shape == (3, 64, 2 * 16 + 64) and delta["conv_w"].shape == (3, 4, 96)
    assert delta["gate_w"].shape == (3, 64, 64) and delta["ba_w"].shape == (3, 64, 8)
    assert delta["a_log"].shape == delta["dt_bias"].shape == (3, 4)
    assert full["qkv_w"].shape == (1, 64, (4 + 2 * 2) * 16)
    for leaves, n in ((delta, 3), (full, 1)):
        assert leaves["router_w"].shape == (n, 64, 16)                 # over all 16
        assert leaves["experts"]["wi"].shape == (n, 8, 64, 64)         # the 8 held
        assert leaves["experts"]["wo"].shape == (n, 8, 32, 64)
        assert leaves["shared_fc_w"].shape == (n, 64, 64)
        assert leaves["shared_proj_w"].shape == (n, 32, 64)
        assert leaves["shared_gate_w"].shape == (n, 64, 1)
    specs = model.partition_specs()
    assert jax.tree.structure(specs) == jax.tree.structure(jax.tree.map(lambda a: 0, params))
    # the ONE full layer owns pages of its two K/V heads; a delta layer a
    # float32 state of the VALUE heads and three packed rows a slot
    assert cfg.arena_layout == (1, 1, (32, 32)) and cfg.page_groups == (None,)
    kp, vp = init_arena(cfg, 10, 16)
    assert kp.shape == vp.shape == (1, 10, 16, 32)
    aux = hybrid.init_aux(cfg, 10, 16, SLOTS, jnp.bfloat16)
    assert set(aux) == {"delta_state", "delta_conv"}
    assert aux["delta_state"].shape == (3, SLOTS, 8, 64) and aux["delta_state"].dtype == jnp.float32
    assert aux["delta_conv"].shape == (3, SLOTS, 3, 96) and aux["delta_conv"].dtype == jnp.bfloat16
    assert model.num_params() == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(params)) - 64      # lnf_b


# ---- the published parameter count -------------------------------------------------------- #
def test_the_published_config_counts_the_issues_parameters():
    E = 2048
    delta = E * 8192 + E * 4096 + E * 64 + 4 * 8192 + 64 + 128 + 4096 * E
    full = E * 8192 + E * 1024 + 512 + 4096 * E
    beside = E * 512 + (3 * E * 512 + E) + 2 * E
    expert = 3 * E * 512
    assert (delta, full, beside, expert) == (33_718_464, 27_263_488, 4_200_448, 3_145_728)
    types = 12 * TYPES
    whole = GPT(qwen3_next_config(layer_types=types))
    assert whole.num_params() == (36 * (delta + beside + 512 * expert)
                                  + 12 * (full + beside + 512 * expert)
                                  + 2 * 151_936 * E + E) == 79_674_391_296
    held = GPT(qwen3_next_config(layer_types=TYPES, experts_held=(0, 256),
                                 vocab_size=75_968, vocab_multiple=64))
    assert held.num_params() == 3_677_613_120
    shapes = jax.eval_shape(held.init_params, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) - E == 3_677_613_120
    # the arena's and the states' bytes of the cell's engine: one layer of
    # 2 x 2 x 256 lanes of bf16 a token; 32 slots x 3 layers of state
    kp, vp = jax.eval_shape(lambda: init_arena(held.cfg, 81_920, 16, jnp.bfloat16))
    assert (kp.size + vp.size) * 2 == 1_310_720 * 2048 == 2_684_354_560
    aux = jax.eval_shape(lambda: hybrid.init_aux(held.cfg, 81_920, 16, 32, jnp.bfloat16))
    assert aux["delta_state"].size * 4 == 32 * 3 * 2_097_152
    assert aux["delta_conv"].size * 2 == 32 * 3 * 49_152


# ---- through the engine --------------------------------------------------------------------- #
def served(model, params, prompts, new, **serving):
    return served_tokens(model, params, prompts, new, **dict(SERVING, **serving))


reference_tokens = functools.partial(serving_helpers.reference_tokens, reference_logits)


def test_the_engine_serves_the_references_tokens_in_one_program(loud):
    model, params = loud
    prompts = [_ids(n, seed=n) for n in (50, 13, 29)]
    (tokens, eng) = served(model, params, prompts, (20, 30, 25))
    assert eng.compiled_programs() == 1
    # K and V of 2 heads of 16 a token in the ONE full layer; nothing a token
    # in a delta layer
    assert eng.cache_bytes_per_token == 2 * 32 * 4
    assert eng._k_pages.shape == (1, 64, BS, 32)
    for p, got in zip(prompts, tokens):
        best, gap = reference_tokens(params, p, got)
        assert got == best and gap == 0.0


def test_the_engine_counts_the_held_bank_and_the_states(loud):
    """``serve.stats`` of this stack: the delta layers' moves, and of the
    live rows' assignments (top 4 x rows x 4 layers) those on the 8 experts
    held; the experts touched are counted a layer and of the HELD alone."""
    model, params = loud
    prompts = [_ids(n, seed=40 + n) for n in (70, 60, 50)]
    seen = []

    def each(st):
        assert st["delta_state_moves"] == 3 * (st["decode_batch"] + (st["prefill_tokens"] > 0))
        assert st["delta_state_bytes"] == 3 * SLOTS * 8 * 64 * 4
        if "moe_assignments" in st:       # of the program that landed in this step
            assert st["moe_assignments"] % (4 * 4) == 0
            assert 0 < st["moe_assignments_held"] < st["moe_assignments"]
            assert 0 < st["moe_experts_touched"] <= 4 * 8
            seen.append(st["moe_assignments_held"] / st["moe_assignments"])
    alone = [served(model, params, [p], (40,))[0][0] for p in prompts]
    assert serving_helpers.preempted(model, params, prompts, 40, each,
                                     **dict(SERVING, num_blocks=17)) == alone
    assert seen and 0.2 < np.mean(seen) < 0.8


# ---- what is refused, and what it says of itself ------------------------------------------- #
def test_the_refusals_that_stay_and_the_sentences(tiny):
    model, params = tiny
    cfg = model.cfg
    assert "3 delta layers hold a recurrent state" in hybrid.what_no_block_carries(cfg)
    assert "3 delta layers" in hybrid.what_a_dense_path_lacks(cfg)
    ids = jnp.zeros(8, jnp.int32)
    for path in ("forward", "generate"):
        serving_helpers.dense_path_refusal(model, params, path, ids)
    with pytest.raises(ValueError, match="delta layers hold a recurrent state"):
        served(model, params, [_ids(8, 0)], (2,), prefix_cache=True)
    # the sigmoid router and a router before attention stay refused BY NAME
    with pytest.raises(AssertionError, match="sigmoid router"):
        qwen3_next_config(**WIDTHS, moe_scoring="sigmoid")
    with pytest.raises(AssertionError, match="router before attention"):
        qwen3_next_config(**WIDTHS, moe_router_input="pre_attn")
    with pytest.raises(AssertionError, match="value heads whole groups"):
        qwen3_next_config(**dict(WIDTHS, linear_key_heads=3))
    # the shared expert's gate is the hybrid walk's
    with pytest.raises(AssertionError, match="hybrid walk"):
        GPTConfig(norm="rmsnorm", moe_shared_gate=True)
    with pytest.raises(AssertionError, match="gate of a shared expert"):
        qwen3_next_config(**dict(WIDTHS, shared_expert_intermediate_size=0))


# ---- the stacks that share this code lower as they did ------------------------------------- #
OLMO = dict(vocab_size=512, n_positions=256, n_embd=64, n_head=4, n_kv_head=4,
            head_dim=16, intermediate_size=128,
            layer_types=3 * ["linear_attention"] + ["full_attention"], linear_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=16)
KEYE = dict(vocab_size=512, n_positions=256, n_embd=64, n_layer=2, n_head=4,
            n_kv_head=2, head_dim=16, intermediate_size=32, num_experts=8, top_k=2,
            indexer=(2, 8, 8))
# the first eight logits of position 19 of a seeded sequence, served in
# float32 through chunks of 8 then decode, as the parent commit serves them
# (read there and pinned here: these stacks' leaves, seeds and programs are
# what they were); Olmo-Hybrid's as PR 65 serves them, whose chunk solves its
# writes by products: the same sums in another order, 7e-8 from PR 64's
PINNED = {"olmo": (olmo_hybrid_config, OLMO), "keye": (keye_vl2_config, KEYE)}
WAS = {"olmo": [-0.051973119378089905, 0.4007280468940735, 0.15220005810260773,
                0.1846480667591095, -0.2205587774515152, 0.02701590396463871,
                0.05854756385087967, 0.330745667219162],
       "keye": [-0.0298094991594553, -0.407356321811676, -0.2446649968624115,
                0.093532994389534, 0.04259955883026123, -0.21952801942825317,
                -0.060341011732816696, -0.2109837383031845]}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_the_older_stacks_seeded_logits_are_what_they_were(name):
    build, widths = PINNED[name]
    model = GPT(build(**widths, dtype="float32"))
    params = model.init_params(jax.random.PRNGKey(0))
    seq = _ids(20, seed=1)
    got = driver(model, params).sequence(seq, (8, 8))[19, :8]
    want = np.asarray(WAS[name], np.float32)
    assert (got == want).all(), (got.tolist(), want.tolist())

