"""Keye-VL-2.0 on the serving path (the ``indexed`` mixer and the
``moe_softmax`` feed-forward of ``models/hybrid.py``), at a tiny size on the
CPU in float32, against the plain reference
(``benchmarks/lib/reference_keye_vl2.py``): prefill in chunks then decode
through the pages on both sides of ``topk`` and across a page border; plain
causal attention under ``topk`` keys; the exact set with ties, in the decode
rows' form and the chunk rows'; a slot re-bound to a shorter sequence; the
linear softmax router against ``gpt.py``'s periodic path; the engine's tokens
and stats; the published parameter count; and what ``init_serving`` and the
dense paths refuse."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmarks.lib import reference_keye_vl2 as ref
from deepspeed_tpu.models import gpt, hybrid
from deepspeed_tpu.models.gpt import GPT, keye_vl2_config, olmoe_config
from tests.unit import serving_helpers
from tests.unit.serving_helpers import Driver, served_tokens, tiny_engine

TOPK = 24
WIDTHS = dict(vocab_size=512, n_positions=256, n_embd=64, n_layer=3, n_head=4,
              n_kv_head=2, head_dim=16, intermediate_size=32, num_experts=8,
              top_k=2)
REF = dict(n_head=4, n_kv_head=2, head_dim=16, top_k=2, indexer_heads=4,
           indexer_head_dim=8, vocab_size=512, q_block=32)
BS, SLOTS, CHUNK, MB = 16, 3, 8, 8
SERVING = dict(max_batch_size=SLOTS, prefill_chunk=CHUNK, block_size=BS,
               num_blocks=64, max_blocks_per_seq=MB, dtype="float32")
# float32 against float32 at the highest matmul precision on both sides: what
# is left is the order of the sums (the chosen keys gathered against a dense
# row under a mask, the sorted rows of the bank against every expert for every
# token).  bf16 in the index keys alone swaps chosen tokens and reads 1e-3
TOL = 2e-5


def _loud(params, rng):
    """The leaves that seeded weights leave quiet made loud: index scores
    that differ by ones (at std 0.02 every token scores alike and rounding
    picks the set), a bias on the index key's norm, a router whose logits
    differ, queries and keys whose softmax is not flat."""
    ix = dict(params["blocks"]["indexed"])
    ix["index_w"], ix["router_w"], ix["qkv_w"] = (
        ix["index_w"] * 20, ix["router_w"] * 30, ix["qkv_w"] * 5)
    ix["ik_norm_b"] = jnp.asarray(rng.normal(0, 0.3, ix["ik_norm_b"].shape), jnp.float32)
    ix["ik_norm_g"] = jnp.asarray(rng.uniform(0.5, 1.5, ix["ik_norm_g"].shape), jnp.float32)
    ix["q_norm_g"] = jnp.asarray(rng.uniform(0.5, 1.5, ix["q_norm_g"].shape), jnp.float32)
    return dict(params, blocks={"indexed": ix})


def build(topk=TOPK):
    model = GPT(keye_vl2_config(**WIDTHS, indexer=(4, 8, topk), dtype="float32"))
    return model, _loud(model.init_params(jax.random.PRNGKey(0)), np.random.default_rng(3))


@pytest.fixture(scope="module")
def loud():
    return build()


@functools.lru_cache(maxsize=None)
def _reference(topk):
    return jax.jit(functools.partial(ref.keye_vl2_logits, topk=topk, **REF))


def reference_logits(params, seq, topk=TOPK):
    ids = np.zeros(-(-len(seq) // 32) * 32, np.int32)
    ids[:len(seq)] = seq
    return np.asarray(_reference(topk)(params, jnp.asarray(ids)))[:len(seq)]


def _ids(n, seed):
    return np.random.default_rng(seed).integers(0, 512, n).astype(np.int32)


# ``round_through=`` rounds the cached index keys through that type after
# every step (a planted lower precision)
driver = functools.partial(Driver, slots=SLOTS, chunk=CHUNK, block_size=BS,
                           blocks_a_slot=MB, leaves=("ki",))


# ---- (a) the served logits against the reference's full forward pass ---------- #
# the prompt ends under topk (24) and inside the first page (16), the decode
# crosses both; it ends over topk, on a page border; every token decoded from
# the sixth on
CHUNKS = {"under_topk": (8, 5), "over_topk": (8, 8, 8, 8), "ragged": (7, 5, 8, 3, 1),
          "single": (1,) * 6}


@pytest.mark.parametrize("chunks", sorted(CHUNKS))
def test_prefill_then_decode_agree_with_the_reference(loud, chunks):
    model, params = loud
    seq = _ids(70, seed=len(chunks))
    got = driver(model, params).sequence(seq, CHUNKS[chunks])
    want = reference_logits(params, seq)
    assert np.abs(got - want).max() < TOL
    assert np.abs(want).max() > 0.1
    # the selection is at work: attending every key reads other logits
    assert np.abs(want - reference_logits(params, seq, topk=10 ** 6))[TOPK + 8:].max() > 100 * TOL


def test_bf16_index_keys_fail_the_tolerance(loud):
    """The planted lower precision: the cached index keys alone rounded
    through bf16 swap chosen tokens."""
    model, params = loud
    seq = _ids(70, seed=5)
    d = driver(model, params, round_through=jnp.bfloat16)
    assert np.abs(d.sequence(seq, CHUNKS["over_topk"]) - reference_logits(params, seq)).max() > 5 * TOL


def test_a_step_with_decode_rows_and_a_chunk_together(loud):
    """Two sequences decode while a third's prompt runs in the chunk rows of
    the same steps, in another slot: every row's logits are its own
    sequence's."""
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


# ---- (b) under topk keys: plain causal attention -------------------------------- #
def test_under_topk_keys_the_mixer_is_plain_causal_attention():
    """With more room than keys every key is chosen: the logits are those of
    dense causal attention over the same leaves (the reference with a
    ``topk`` no context reaches), whatever the indexer's weights are."""
    model, params = build(topk=128)
    seq = _ids(70, seed=9)
    got = driver(model, params).sequence(seq, CHUNKS["ragged"])
    assert np.abs(got - reference_logits(params, seq, topk=10 ** 6)).max() < TOL
    ix = dict(params["blocks"]["indexed"])
    ix["index_w"] = ix["index_w"][:, ::-1] * 3.0
    other = driver(model, dict(params, blocks={"indexed": ix})).sequence(seq, CHUNKS["ragged"])
    assert np.abs(got - other).max() < TOL


# ---- (c) the chosen set, ties included ------------------------------------------ #
@pytest.mark.parametrize("in_vmem", [False, True], ids=["plain", "in_vmem"])
@pytest.mark.parametrize("n, T, k, G", [(3, 64, 8, 16), (2, 128, 128, 16), (4, 256, 17, 64),
                                        (2, 192, 50, 64), (9, 2176, 48, 64), (5, 5760, 2048, 64)])
def test_the_chosen_set_is_the_references_with_ties(kernels, in_vmem, n, T, k, G):
    """Seeded scores of which a third are whole numbers (ties at the k-th
    place), a row half unseen, a row all alike: the chunk rows' form (a mask)
    and the decode rows' (positions) choose the reference's set, which is a
    stable sort's.  ``in_vmem``: through the kernel where its gate admits the
    shape (2,048 keys or more in whole lane tiles: the last two)."""
    kernels(*["index_select"] * in_vmem)
    assert hybrid.selects_in_vmem(n, T, k) == (in_vmem and T >= 2048)
    rng = np.random.default_rng(T + k)
    s = rng.normal(size=(n, T)).astype(np.float32)
    s[:, ::3] = np.round(s[:, ::3])
    s[0, T // 2:] = -np.inf
    s[1, :] = 1.0
    chosen = jax.jit(lambda s: hybrid.chosen_tokens(s, k, G))(jnp.asarray(s))
    at, real = jax.jit(lambda s: hybrid.chosen_positions(s, k))(jnp.asarray(s))
    chosen, at, real = np.asarray(chosen), np.asarray(at), np.asarray(real)
    assert np.array_equal(chosen, np.asarray(ref.chosen_mask(jnp.asarray(s), k)))
    for r in range(n):
        want = np.argsort(-s[r], kind="stable")[:k]
        want = np.sort(want[s[r][want] > -np.inf])
        assert np.array_equal(np.flatnonzero(chosen[r]), want)
        assert np.array_equal(np.sort(at[r][real[r]]), want) and real[r].sum() == len(want)
    assert real[0].sum() == min(k, T // 2) and np.array_equal(np.flatnonzero(chosen[1]), np.arange(k))


def test_the_index_scores_are_the_references(loud):
    model, _ = loud
    rng = np.random.default_rng(1)
    qi, keys, w = (jnp.asarray(rng.normal(size=s), jnp.float32)
                   for s in ((5, 4, 8), (40, 8), (5, 4)))
    with jax.default_matmul_precision("highest"):
        got = hybrid.index_scores(model.cfg, qi, w, keys[None])
        want = ref.index_scores(qi, keys, w, jnp.full((5,), 39))
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


# ---- (d) a slot re-bound ---------------------------------------------------------- #
def test_a_slot_rebound_to_a_shorter_sequence_scores_no_former_tenant(loud):
    """The second sequence runs in the blocks the first one filled, past its
    own end the first one's index keys, made to score highest: none is
    chosen, because a key after the query's position is never seen."""
    model, params = loud
    long, short = _ids(100, seed=5), _ids(40, seed=6)
    d = driver(model, params)
    d.sequence(long, (8,) * 12)
    d.aux = dict(d.aux, ki=d.aux["ki"] * 1e3)
    got = d.sequence(short, (8, 8, 8))
    assert np.abs(got - reference_logits(params, short)).max() < TOL


# ---- (e) the linear softmax router in the walk ------------------------------------- #
def test_the_softmax_router_routes_as_the_periodic_path_does():
    """OLMoE's tiny preset through both: ``gpt.py:_ffn`` over a layer's
    leaves, and the walk's ``moe_softmax`` feed-forward over the same leaves
    stacked: the same output for the live rows, the same expert counts; and
    renormalised."""
    kw = dict(vocab_size=256, n_positions=64, n_embd=32, n_head=4, n_layer=2,
              intermediate_size=16, num_experts=8, top_k=2, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(6, 32)), jnp.float32)
    live = jnp.asarray([True] * 5 + [False])
    step = hybrid._Step(None, live, None, None, None, None, 0, jnp.float32, None)
    for norm_topk in (False, True):
        cfg = olmoe_config(**kw, moe_norm_topk=norm_topk)
        p = gpt._init_block(cfg, jax.random.PRNGKey(1))
        p["moe"]["gate"]["wg"] = p["moe"]["gate"]["wg"] * 30
        z = gpt.rms_norm(x, p["ln2_g"], eps=cfg.ln_eps)
        want, _, counts = gpt._ffn(cfg, p, z, jnp.float32, live=live)
        leaves = {"ln2_g": p["ln2_g"], "router_w": p["moe"]["gate"]["wg"]}
        bank = jax.tree.map(lambda a: jnp.stack([jnp.zeros_like(a), a]), p["moe"]["experts"])
        got, stream, got_counts = hybrid.FEED_FORWARDS["moe_softmax"](
            cfg, leaves, bank, jnp.int32(1), x, None, step)
        assert stream is None
        # the rows that carry a request: the periodic path's bank computes
        # nothing for the idle one (``dropless_moe(live=)``), the walk's does
        gap = np.abs(np.asarray(got) - np.asarray(want))
        assert gap[:5].max() < 1e-6 and float(np.abs(np.asarray(want))[5].max()) == 0.0
        assert np.array_equal(np.asarray(got_counts), np.asarray(counts))
        assert int(np.asarray(counts).sum()) == 5 * 2


# ---- (f) the engine ------------------------------------------------------------------ #
def served(model, params, prompts, new, **serving):
    return served_tokens(model, params, prompts, new, **dict(SERVING, **serving))


reference_tokens = functools.partial(serving_helpers.reference_tokens, reference_logits)


def test_the_engine_serves_the_references_tokens_in_one_program(loud):
    model, params = loud
    prompts = [_ids(n, seed=n) for n in (50, 13, 29)]
    (tokens, eng) = served(model, params, prompts, (20, 30, 25))
    assert eng.compiled_programs() == 1
    assert eng.cache_bytes_per_token == 2 * 32 * 4
    for p, got in zip(prompts, tokens):
        best, gap = reference_tokens(params, p, got)
        assert got == best and gap == 0.0


def test_a_slot_reused_by_the_engine_serves_what_an_engine_of_its_own_does(loud):
    model, params = loud
    a, b = _ids(90, seed=5), _ids(22, seed=6)
    (both, _) = served(model, params, [a, b], (20, 30), max_batch_size=1)
    (alone, _) = served(model, params, [b], (30,), max_batch_size=1)
    assert both[1] == alone[0] == reference_tokens(params, b, alone[0])[0]


def test_the_steps_stats_count_the_index_keys(loud):
    model, params = loud
    eng = tiny_engine(model, params, **SERVING)
    f = eng.submit(_ids(40, 1), max_new_tokens=6)
    seen = []
    while not f.done:
        st = eng.step()
        if "index_keys_scored" in st:
            seen.append(st)
    assert seen and all(st["index_key_bytes"] == 3 * 64 * BS * 8 * 4 for st in seen)
    first, last = seen[0], seen[-1]
    # the first chunk: positions 0..7 in three layers; a decode row past topk
    assert first["index_keys_scored"] == 3 * 36 == first["indexed_keys_attended"]
    assert last["indexed_keys_attended"] == 3 * TOPK < last["indexed_keys_resident"]
    assert last["index_keys_scored"] == last["indexed_keys_resident"]
    # rows at or under topk keys take them all; a row past it selects in every layer
    assert (first["index_rows_all"], first["index_rows_selected"]) == (8, 0)
    assert (last["index_rows_all"], last["index_rows_selected"]) == (0, 3)


# ---- (g) the published parameter count ------------------------------------------------ #
def test_the_published_config_counts_the_issues_parameters():
    layer = (2048 * (32 + 8) * 128 + 4096 * 2048 + 2 * 128
             + 2048 * (16 * 64 + 64 + 16) + 2 * 64 + 2 * 2048
             + 2048 * 128 + 128 * 3 * 2048 * 768)
    assert layer == 625_381_760
    held = GPT(keye_vl2_config(n_layer=6))
    assert held.num_params() == 6 * layer + 2 * 151_936 * 2048 + 2048 == 4_374_622_464
    assert GPT(keye_vl2_config()).num_params() == 48 * layer + 2 * 151_936 * 2048 + 2048
    cfg = held.cfg
    assert cfg.mixers == ("indexed",) * 6 and cfg.ffns == ("moe_softmax",) * 6
    assert cfg.indexer == gpt.IndexerSpec(16, 64, 2048)
    assert (cfg.rope_theta, cfg.ln_eps, cfg.padded_vocab) == (1e7, 1e-6, 151_936)
    assert (cfg.moe_top_k, cfg.moe_num_experts, cfg.moe_norm_topk) == (8, 128, True)
    assert cfg.untied_head and cfg.cache_lanes == (512, 512)
    assert cfg.arena_layout == (6, 1, (512, 512))
    aux = jax.eval_shape(lambda: hybrid.init_aux(cfg, 100, 64, 8, jnp.bfloat16))
    assert set(aux) == {"ki"} and aux["ki"].shape == (6, 100, 64, 64)


# ---- (h) what is refused, by the mechanism's name --------------------------------------- #
@pytest.mark.parametrize("knob, mechanism", [
    ({"prefix_cache": True}, "prefix_cache shares full blocks"),
    ({"kv_tiering": True}, "kv_tiering spills"),
])
def test_init_serving_refuses_what_carries_no_index_keys(loud, knob, mechanism):
    model, params = loud
    with pytest.raises(ValueError) as e:
        deepspeed_tpu.init_serving(model=model, params=params,
                                   config={"serving": dict(SERVING, **knob)})
    assert mechanism in str(e.value)
    assert "3 indexed layers a cache of index keys the selection scores" in str(e.value)
    assert "no block of K and V carries" in str(e.value)


@pytest.mark.parametrize("path", ["forward", "generate", "loss"])
def test_the_dense_paths_refuse_the_stack_by_what_they_lack(loud, path):
    model, params = loud
    said = serving_helpers.dense_path_refusal(model, params, path, _ids(16, 0))
    assert "no lightning indexer, no cache of index keys" in said
    assert "3 indexed layers" in said and "init_serving()" in said


@pytest.mark.parametrize("kw, said", [
    (dict(moe_scoring="sigmoid"), "sigmoid router"),
    (dict(moe_router_input="pre_attn"), "router before attention"),
])
def test_the_walk_still_refuses_the_routers_it_has_not_written(kw, said):
    with pytest.raises(AssertionError, match=said):
        keye_vl2_config(**WIDTHS, **kw)


@pytest.mark.parametrize("kw, leaf, shape", [
    (dict(moe_shared_experts=1), "shared_fc_w", "E, 2 * I"),
    (dict(moe_experts_held=(0, 4)), "experts", "4"),
])
def test_the_walk_takes_a_shared_expert_and_a_held_share(kw, leaf, shape):
    """What the hybrid walk refused until PR 64 (Qwen3-Next): this stack
    with a shared expert beside the bank, or with a held share of it, builds
    the leaves ``models/hybrid.py:_ffn_shapes`` gives them."""
    cfg = keye_vl2_config(**WIDTHS, **kw)
    shapes = hybrid._leaf_shapes(cfg, "indexed")
    E, I = cfg.n_embd, cfg.moe_expert_hidden or cfg.ffn_dim
    if leaf == "experts":
        assert shapes["experts"]["wi"] == (4, E, 2 * I) and shapes["router_w"] == (E, cfg.moe_num_experts)
    else:
        assert shapes[leaf] == (E, 2 * I) and shapes["shared_proj_w"] == (I, E)
        assert "shared_gate_w" not in shapes


def test_a_chunk_that_is_not_whole_tiles_of_queries_is_refused(loud):
    model, params = loud
    with pytest.raises(ValueError, match="whole tiles of queries that select"):
        deepspeed_tpu.init_serving(model=model, params=params, config={
            "serving": dict(SERVING, prefill_chunk=192)})
