"""ZAYA1 on the serving path (the ``cca`` mixer and the ``moe`` feed-forward
of ``models/hybrid.py``), at a tiny size on the CPU in float32, against the
plain reference (``benchmarks/lib/reference_zaya.py``): prefill in chunks
whose boundaries fall everywhere, then decode through the pages and the
state; what a slot keeps against one whole-sequence pass; a slot reused and a
request preempted; the routers' stream; top-1 routing in the step's stats; the
published parameter count; and what ``init_serving`` and the dense paths
refuse."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmarks.lib import reference_zaya as ref
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.gpt import GPT, zaya_config
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.serving.kv_cache import init_arena
from tests.unit import serving_helpers
from tests.unit.serving_helpers import Driver, jitted, served_tokens

WIDTHS = dict(vocab_size=512, n_positions=256, n_embd=64, n_layer=3, n_head=4,
              n_kv_head=2, head_dim=16, intermediate_size=32, num_experts=4,
              router_hidden=16)
REF = dict(n_head=4, n_kv_head=2, head_dim=16, vocab_size=512, q_block=32)
BS, SLOTS, CHUNK, MB = 16, 3, 8, 16
SERVING = dict(max_batch_size=SLOTS, prefill_chunk=CHUNK, block_size=BS,
               num_blocks=64, dtype="float32")
# float32 against float32 at the highest matmul precision on both sides:
# what is left is the order of the sums (pages against one pass, the sorted
# rows of the bank against every expert for every token), 2e-6 of logits of
# 0.3.  bf16 in the slots' states alone reads 2e-4, in the weights 1e-3 and more
TOL = 2e-5


@pytest.fixture(scope="module")
def tiny():
    model = GPT(zaya_config(**WIDTHS, dtype="float32"))
    return model, model.init_params(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def loud(tiny):
    """The same model with the leaves that seeded weights leave quiet made
    loud: convolutions of order 1 (at std 0.02 they add a hundredth to q and
    k), a router whose logits differ by ones (at std 0.02 every expert weighs
    a quarter), key scales, stream scales and balancing biases that differ."""
    model, params = tiny
    rng = np.random.default_rng(7)
    cca = dict(params["blocks"]["cca"])
    for name, scale in (("conv0_w", 25.0), ("conv0_b", 25.0), ("conv1_w", 10.0),
                        ("conv1_b", 25.0), ("router_w1", 15.0), ("router_w2", 15.0),
                        ("router_w3", 30.0)):
        cca[name] = cca[name] * scale
    cca["k_scale_g"] = jnp.asarray(rng.uniform(0.5, 2.0, cca["k_scale_g"].shape), jnp.float32)
    cca["stream_g"] = jnp.asarray(rng.uniform(0.5, 1.5, cca["stream_g"].shape), jnp.float32)
    cca["balance_bias"] = jnp.asarray(rng.normal(0, 0.1, cca["balance_bias"].shape), jnp.float32)
    return model, dict(params, blocks={"cca": cca})


def _padded(seq):
    ids = np.zeros(-(-len(seq) // 32) * 32, np.int32)
    ids[:len(seq)] = seq
    return jnp.asarray(ids)


def reference_logits(params, seq):
    return np.asarray(jitted(ref.zaya_logits, **REF)(params, _padded(seq)))[:len(seq)]


def reference_experts(params, seq):
    hidden = jitted(ref.zaya_hidden, with_experts=True, **REF)
    return np.asarray(hidden(params, _padded(seq))[1])[:, :len(seq)]


def _ids(n, seed):
    return np.random.default_rng(seed).integers(0, 512, n).astype(np.int32)


# the step's expert counts kept; ``round_through=`` rounds the slots'
# convolution states through that type after every step
driver = functools.partial(Driver, slots=SLOTS, chunk=CHUNK, block_size=BS,
                           blocks_a_slot=MB, static={"with_expert_counts": True})


# ---- (a) the served logits against the reference's full forward pass ------------ #
# chunk boundaries at 8, 16, 24 (2, 1, 0 mod 3); at 7, 12, 20, 23, 24; after
# every one of the first six tokens
CHUNKS = {"whole": (8, 8, 8), "ragged": (7, 5, 8, 3, 1), "single": (1,) * 6}


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
    """The planted lower precision: the slots' states alone rounded through
    bf16 after every step (two of a key's three taps and half its value) read
    ten times the tolerance."""
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


# ---- (b) what the pages and a slot keep ------------------------------------------ #
def test_a_decode_rows_k_v_and_state_are_one_whole_sequence_pass(loud):
    """Token by token through the state, or all 40 tokens as ONE chunk (the
    convolutions shifts along its rows, nothing read from a state): the same
    K and V in the pages, the same state left in the slot."""
    model, params = loud
    seq = _ids(40, seed=11)
    steps = driver(model, params)
    steps.sequence(seq, (5,))                     # 5 prefilled, 35 decode rows
    whole = driver(model, params, chunk=40)
    whole.sequence(seq, (40,))
    pages = slice(1, 1 + 3)                       # slot 0's first three blocks
    for a, b in ((steps.kp, whole.kp), (steps.vp, whole.vp)):
        a, b = np.asarray(a[:, pages]).reshape(3, -1, 32), np.asarray(b[:, pages]).reshape(3, -1, 32)
        assert np.abs(a[:, :40] - b[:, :40]).max() < 1e-5
        assert np.abs(b[:, :40]).max() > 0.1
    a, b = (np.asarray(d.aux["cca_state"][:, 0]) for d in (steps, whole))
    assert a.shape == (3, 2 * 96 + 16) and np.abs(a - b).max() < 1e-5
    # the state is [u_{t-1} | u_{t-2} | W_v2 h_{t-1}]: shifted by one token,
    # the first becomes the second
    before = driver(model, params, chunk=40)
    before.sequence(seq[:39], (39,))
    assert np.abs(np.asarray(before.aux["cca_state"][:, 0, :96]) - b[:, 96:192]).max() < 1e-5
    # the second K/V head's value is the PREVIOUS token's: position 0 holds zeros
    v = np.asarray(whole.vp[:, 1]).reshape(3, BS, 2, 16)
    assert np.all(v[:, 0, 1] == 0) and np.abs(v[:, 0, 0]).max() > 0


def test_the_leaves_are_one_stack_and_the_state_a_slot(tiny):
    model, params = tiny
    cfg, cca = model.cfg, params["blocks"]["cca"]
    assert set(params["blocks"]) == {"cca"} and cfg.mixers == ("cca",) * 3
    assert cfg.ffns == ("moe",) * 3 and hybrid.layer_runs(cfg) == [("cca", 0, 3)]
    assert cca["qkv_w"].shape == (3, 64, 96 + 32)       # [q 64 | k 32 | v1 16 | v2 16]
    assert cca["conv0_w"].shape == (3, 2, 96) and cca["conv1_w"].shape == (3, 2, 6, 16, 16)
    assert cca["experts"]["wi"].shape == (3, 4, 64, 64)
    assert cca["stream_g"].shape == (3,) and "lm_head" not in params
    specs = model.partition_specs()
    assert jax.tree.structure(specs) == jax.tree.structure(jax.tree.map(lambda a: 0, params))
    assert specs["blocks"]["cca"]["experts"]["wi"] == jax.sharding.PartitionSpec(
        None, "expert", None, "tensor")
    # plain pages of both K/V heads; a state a slot a layer
    assert cfg.arena_layout == (3, 1, (32, 32)) and cfg.page_groups == (None,)
    kp, vp = init_arena(cfg, 10, 16)
    assert kp.shape == vp.shape == (3, 10, 16, 32)
    aux = hybrid.init_aux(cfg, 10, 16, SLOTS, jnp.bfloat16)
    assert set(aux) == {"cca_state"} and aux["cca_state"].shape == (3, SLOTS, 208)
    assert aux["cca_state"].dtype == jnp.bfloat16


# ---- (d) the routers' stream ------------------------------------------------------- #
def test_a_layers_router_reads_the_stream_of_the_layer_before(loud):
    """With the later layers' stream scale at 0 their routers read their own
    input alone: other logits, and the reference follows."""
    model, params = loud
    seq = _ids(24, seed=3)
    cut = dict(params["blocks"]["cca"])
    cut["stream_g"] = cut["stream_g"].at[1:].set(0.0)
    cut = dict(params, blocks={"cca": cut})
    with_stream = driver(model, params).sequence(seq, (8, 8, 8))
    without = driver(model, cut).sequence(seq, (8, 8, 8))
    assert np.abs(with_stream - without).max() > 100 * TOL
    assert np.abs(without - reference_logits(cut, seq)).max() < TOL
    assert (reference_experts(params, seq)[1:] != reference_experts(cut, seq)[1:]).any()
    assert (reference_experts(params, seq)[0] == reference_experts(cut, seq)[0]).all()


def test_the_stream_router_and_the_biased_choice():
    rng = np.random.default_rng(0)
    h, prev = rng.normal(size=(5, 8)).astype(np.float32), rng.normal(size=(5, 4)).astype(np.float32)
    w_in, w1, w2 = (rng.normal(size=s).astype(np.float32) for s in ((8, 4), (4, 4), (4, 4)))
    w3, g = rng.normal(size=(4, 3)).astype(np.float32), rng.uniform(0.5, 2, 4).astype(np.float32)
    stream, logits = dropless.stream_mlp_logits(
        jnp.asarray(h), jnp.asarray(prev), w_in, jnp.float32(0.7), g, [w1, w2, w3], 1e-5)
    want = h @ w_in + 0.7 * prev
    assert np.abs(np.asarray(stream) - want).max() < 1e-5
    z = want / np.sqrt((want ** 2).mean(-1, keepdims=True) + 1e-5) * g
    gelu = lambda a: np.asarray(jax.nn.gelu(jnp.asarray(a), approximate=True))
    assert np.abs(np.asarray(logits) - gelu(gelu(z @ w1) @ w2) @ w3).max() < 1e-5
    # the bias chooses and never weighs; the weight is not renormalised
    lg = jnp.asarray([[2.0, 1.9, 0.0], [0.0, 3.0, 1.0]])
    probs, weights, experts = dropless.biased_softmax_topk(lg, 1, jnp.asarray([0.0, 0.5, 0.0]))
    assert experts.tolist() == [[1], [1]]
    assert np.allclose(weights[:, 0], np.asarray(probs)[:, 1]) and weights[0, 0] < 0.5
    assert dropless.biased_softmax_topk(lg, 1, jnp.zeros(3))[2].tolist() == [[0], [1]]


# ---- through the engine ------------------------------------------------------------ #
def served(model, params, prompts, new, **serving):
    return served_tokens(model, params, prompts, new, **dict(SERVING, **serving))


reference_tokens = functools.partial(serving_helpers.reference_tokens, reference_logits)


def test_the_engine_serves_the_references_tokens_in_one_program(loud):
    model, params = loud
    prompts = [_ids(n, seed=n) for n in (50, 13, 29)]
    (tokens, eng) = served(model, params, prompts, (20, 30, 25))
    assert eng.compiled_programs() == 1
    assert eng.cache_bytes_per_token == 2 * 32 * 4 and eng.chunk_queries_per_row >= 1
    for p, got in zip(prompts, tokens):
        best, gap = reference_tokens(params, p, got)
        assert got == best and gap == 0.0


# ---- (c) a slot reused, a request preempted ------------------------------------------ #
def test_a_slot_reused_by_a_new_sequence_starts_from_a_zero_state(loud):
    """One slot: the second request runs where the first left its state, and
    is served what it gets on an engine of its own."""
    model, params = loud
    a, b = _ids(37, seed=5), _ids(22, seed=6)
    (both, eng) = served(model, params, [a, b], (20, 30), max_batch_size=1)
    assert eng.step_count > 0
    (alone, _) = served(model, params, [b], (30,), max_batch_size=1)
    assert both[1] == alone[0] == reference_tokens(params, b, alone[0])[0]


def test_a_preempted_request_resumes_to_the_same_tokens(loud):
    """An arena too small for three requests to grow together: the youngest
    is preempted, its pages go back, its state is rebuilt by the re-prefill
    (whatever lies before position 0 is zero by position), and every request
    is served the tokens it gets alone; ``state_slots_reset`` counts the
    first chunks."""
    model, params = loud
    prompts = [_ids(n, seed=40 + n) for n in (70, 60, 50)]
    alone = [served(model, params, [p], (40,))[0][0] for p in prompts]
    assert serving_helpers.preempted(model, params, prompts, 40,
                                     **dict(SERVING, num_blocks=17)) == alone


def test_a_snapshot_restores_by_recompute(loud):
    model, params = loud
    p = _ids(45, seed=8)
    (whole, _) = served(model, params, [p], (30,))
    assert serving_helpers.restored_tokens(model, params, p, 30, 11, **SERVING) == whole[0]


# ---- (e) top-1 in the step's stats --------------------------------------------------- #
def test_the_steps_stats_are_the_rows_routing(loud):
    """Every live row adds ONE assignment a layer; the experts a step's rows
    reach (counted a layer) and the fullest expert over the mean are what
    the reference's routing of those rows gives."""
    model, params = loud
    eng = deepspeed_tpu.init_serving(model=model, params=params,
                                     config={"serving": SERVING})
    a, b = eng.submit(_ids(30, 1), max_new_tokens=12), eng.submit(_ids(9, 2), max_new_tokens=12)
    reqs, seen, routed, land = (a.request, b.request), [], [], eng._land
    # a program's routing comes in with its row: in the step that launched it,
    # or (prompt left behind its chunk: dispatched ahead) behind the next launch
    eng._land = lambda flight: routed.append(land(flight)) or routed[-1]
    while not (a.done and b.done):
        before = [r.prefilled for r in reqs]
        st = eng.step()
        if st["programs"]:
            seen.append((st, [(b0, r.prefilled) for r, b0 in zip(reqs, before)]))
    eng.close()
    assert len(routed) == len(seen) and eng.steps_dispatched_ahead == 5    # six chunks
    chosen = [reference_experts(params, np.concatenate([r.prompt, r.generated]).astype(np.int32))
              for r in reqs]
    for (st, spans), moe in zip(seen, routed):
        rows = np.concatenate([c[:, lo:hi] for c, (lo, hi) in zip(chosen, spans)], axis=1)
        assert rows.shape[1] == st["decode_batch"] + st["prefill_tokens"]
        assert moe["moe_assignments"] == 3 * rows.shape[1]
        by_layer = np.stack([np.bincount(r, minlength=4) for r in rows])
        assert moe["moe_experts_touched"] == int((by_layer > 0).sum())
        counts = by_layer.sum(0)
        assert moe["moe_load_max_over_mean"] == pytest.approx(counts.max() / counts.mean())
        assert st["cca_state_bytes"] == 3 * SLOTS * 208 * 4
        if not st["dispatched_ahead"] and "moe_assignments" in st:
            assert {k: st[k] for k in moe} == moe       # its own row's, in its stats
    assert sum(st["state_slots_reset"] for st, _ in seen) == 2
    assert any(moe["moe_experts_touched"] < 3 * 4 for moe in routed)


# ---- (f) the published parameter count ------------------------------------------------ #
def test_the_published_config_counts_the_issues_parameters():
    outside = (2_097_152 + 524_288 + 2 * 262_144 + 2_097_152 + 3_840 + 328_960
               + 2 + 4_096)
    router = 524_288 + 2 * 65_536 + 4_096 + 273
    layer = outside + router + 16 * 3 * 2048 * 2048
    assert (outside, router, layer) == (5_579_778, 659_729, 207_566_099)
    whole, held = GPT(zaya_config()), GPT(zaya_config(n_layer=20))
    assert whole.num_params() == 40 * layer + 262_272 * 2048 + 2048
    assert held.num_params() == 4_688_457_084
    shapes = jax.eval_shape(held.init_params, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) - 2048 == 4_688_457_084
    cfg = held.cfg
    assert (cfg.rope_dim, cfg.rope_theta, cfg.ln_eps, cfg.padded_vocab) == (64, 5e6, 1e-5, 262_272)
    assert (cfg.moe_top_k, cfg.moe_num_experts, cfg.moe_router_hidden) == (1, 16, 256)
    assert not cfg.untied_head and cfg.cache_lanes == (256, 256)
    with pytest.raises(AssertionError, match="two taps"):
        zaya_config(cca_time0=4)


# ---- (g) what is refused, by the mechanism's name --------------------------------------- #
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
    assert "3 cca layers hold a convolution state a slot" in str(e.value)
    assert "no block of K and V carries" in str(e.value)


@pytest.mark.parametrize("path", ["forward", "generate", "loss"])
def test_the_dense_paths_refuse_the_stack_by_what_they_lack(tiny, path):
    model, params = tiny
    said = serving_helpers.dense_path_refusal(model, params, path, _ids(16, 0))
    assert "convolution over time of the packed q/k latents" in said
    assert "second carry for the router's stream" in said
    assert "init_serving()" in said


def test_a_stream_router_outside_the_hybrid_walk_is_refused():
    from deepspeed_tpu.models.gpt import olmoe_config
    with pytest.raises(AssertionError, match="second carry of the layer walk"):
        olmoe_config(vocab_size=256, n_positions=64, n_embd=32, n_head=4, n_layer=2,
                     intermediate_size=16, num_experts=4, top_k=1,
                     moe_router_hidden=8)
