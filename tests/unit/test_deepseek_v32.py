"""DeepSeek-V3.2-Exp on the serving path (latent attention under a lightning
indexer in the periodic walk of ``models/gpt.py``, the selection shared with
the hybrid walk's ``indexed`` mixer, the sigmoid router limited to groups), at
a tiny size on the CPU in float32, against the plain reference
(``benchmarks/lib/reference_deepseek_v32.py``): prefill in chunks then decode
through the latent and the index-key pages on both sides of ``topk``; the
absorbed form against the plain form; the router with groups against a
ten-line numpy router and, at one group, bit for bit today's; the sixteen
shares of an expert layer adding up to the uncut layer; three wrong models the
tolerance must see, each a variant of the REFERENCE held against the logits
of one served program; the engine's tokens and stats; the parameter count;
and what the configuration, ``init_serving`` and the dense paths refuse."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmarks.lib import reference_deepseek_v32 as ref
from deepspeed_tpu.models import gpt
from deepspeed_tpu.models.gpt import GPT, deepseek_v32_config
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas.indexed_attention import (chosen_latent_attention,
                                                        masked_latent_attention)
from tests.unit import serving_helpers
from tests.unit.serving_helpers import Driver, served_tokens

TOPK = 24
# q_lora_rank is the hidden size so that a WRONG indexer can read the layer's
# input through the same matrix (the first control below)
WIDTHS = dict(vocab_size=512, n_positions=256, n_embd=64, n_layer=3, n_head=4,
              head_dim=24, q_lora_rank=64, kv_lora_rank=128, qk_rope_dim=8,
              v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
              num_experts=8, top_k=2, n_group=2, topk_group=1, dense_layers=1,
              rope_yarn=(4.0, 32, 32.0, 1.0, 1.0, 1.0, 0.0))
REF = dict(n_head=4, q_lora_rank=64, kv_lora_rank=128, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4, index_head_dim=16,
           top_k=2, n_routed_experts=8, n_group=2, topk_group=1,
           first_k_dense_replace=1, routed_scaling_factor=2.5, vocab_size=512,
           rope_scaling=dict(factor=4.0, original_max_position_embeddings=32,
                             beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1),
           q_block=32)
BS, SLOTS, CHUNK, MB = 16, 3, 8, 8
SERVING = dict(max_batch_size=SLOTS, prefill_chunk=CHUNK, block_size=BS,
               num_blocks=64, max_blocks_per_seq=MB, dtype="float32")
# float32 against float32 at the highest matmul precision on both sides: what
# is left is the order of the sums (the absorbed form against the plain one,
# the chosen rows gathered against a dense row under a mask, the sorted rows
# of the bank against every expert for every token)
TOL = 2e-5


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _loud(params, rng):
    """The leaves that seeded weights leave quiet made loud: index scores
    that differ by ones (at std 0.02 every token scores alike and rounding
    picks the set), a gain and a bias on the index key's norm, a router whose
    logits differ under a bias that chooses, queries whose softmax is not
    flat."""
    b = dict(params["blocks"])
    b["index_q_w"], b["index_kw_w"], b["q_b_w"] = (
        b["index_q_w"] * 20, b["index_kw_w"] * 20, b["q_b_w"] * 8)
    b["ik_norm_b"] = jnp.asarray(rng.normal(0, 0.3, b["ik_norm_b"].shape), jnp.float32)
    b["ik_norm_g"] = jnp.asarray(rng.uniform(0.5, 1.5, b["ik_norm_g"].shape), jnp.float32)
    gate = dict(b["moe"]["gate"], wg=b["moe"]["gate"]["wg"] * 30)
    gate["bias"] = jnp.asarray(rng.normal(0, 0.2, gate["bias"].shape), jnp.float32)
    b["moe"] = dict(b["moe"], gate=gate)
    return dict(params, blocks=b)


def build(topk=TOPK, held=(0, 4)):
    model = GPT(deepseek_v32_config(**WIDTHS, indexer=(4, 16, topk),
                                    experts_held=held, dtype="float32"))
    return model, _loud(model.init_params(jax.random.PRNGKey(0)), np.random.default_rng(3))


@pytest.fixture(scope="module")
def loud():
    return build()


def reference_logits(params, seq, **other):
    kw = {**REF, "index_topk": TOPK, "experts_held": (0, 4), **other}
    ids = np.zeros(-(-len(seq) // 32) * 32, np.int32)
    ids[:len(seq)] = seq
    fn = serving_helpers.jitted(ref.deepseek_v32_logits, **kw)
    return np.asarray(fn(params, jnp.asarray(ids)))[:len(seq)]


def _ids(n, seed):
    return np.random.default_rng(seed).integers(0, 512, n).astype(np.int32)


driver = functools.partial(Driver, slots=SLOTS, chunk=CHUNK, block_size=BS,
                           blocks_a_slot=MB, leaves=("ki",))

# the prompt ends under topk (24) and inside the first page (16), the decode
# crosses both; it ends over topk, on a page border; ragged chunks; every
# token decoded from the sixth on
CHUNKS = {"under_topk": (8, 5), "over_topk": (8, 8, 8, 8), "ragged": (7, 5, 8, 3, 1),
          "single": (1,) * 6}


@pytest.fixture(scope="module")
def served(loud):
    """One sequence of 70 tokens through the pages, and the reference's
    logits of it: what the three controls below are held against."""
    model, params = loud
    seq = _ids(70, seed=4)
    with jax.default_matmul_precision("highest"):
        return seq, driver(model, params).sequence(seq, CHUNKS["over_topk"])


# ---- (a) the served logits against the reference's full forward pass ---------- #
@pytest.mark.parametrize("chunks", sorted(CHUNKS))
def test_prefill_then_decode_agree_with_the_reference(loud, chunks):
    model, params = loud
    seq = _ids(70, seed=len(chunks))
    got = driver(model, params).sequence(seq, CHUNKS[chunks])
    want = reference_logits(params, seq)
    assert np.abs(got - want).max() < TOL
    assert np.abs(want).max() > 0.1
    # the selection is at work: attending every key reads other logits
    assert np.abs(want - reference_logits(params, seq, index_topk=10 ** 6)
                  )[TOPK + 8:].max() > 100 * TOL


def test_the_references_last_layer_may_run_the_asked_rows_alone(loud):
    """``rows_from`` (what the cell's check hands the reference: where the
    generated positions start) changes nothing from its block on."""
    _, params = loud
    seq = _ids(70, seed=6)
    want = reference_logits(params, seq)
    assert np.abs(reference_logits(params, seq, rows_from=41)[32:] - want[32:]).max() < 1e-6


def test_bf16_index_keys_fail_the_tolerance(loud):
    """The planted lower precision: the cached index keys alone rounded
    through bf16 swap chosen tokens."""
    model, params = loud
    seq = _ids(70, seed=5)
    d = driver(model, params, round_through=jnp.bfloat16)
    assert np.abs(d.sequence(seq, CHUNKS["over_topk"])
                  - reference_logits(params, seq)).max() > 5 * TOL


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


def test_tables_of_topk_positions_or_fewer_attend_every_key():
    """With more room than the tables hold nothing is selected: the step is
    ``paged_mla_attention`` over every key, whatever the indexer's weights."""
    model, params = build(topk=128)
    seq = _ids(70, seed=9)
    got = driver(model, params).sequence(seq, CHUNKS["ragged"])
    assert np.abs(got - reference_logits(params, seq, index_topk=10 ** 6)).max() < TOL
    b = dict(params["blocks"], index_q_w=params["blocks"]["index_q_w"][:, ::-1] * 3.0)
    other = driver(model, dict(params, blocks=b)).sequence(seq, CHUNKS["ragged"])
    assert np.abs(got - other).max() < TOL


# ---- (b) the wrong models, each a variant of the reference ---------------------- #
def _swapped_pairings(monkeypatch):
    half, pairs = ref.rope_half_split, ref.rope_interleaved
    monkeypatch.setattr(ref, "rope_half_split", pairs)
    monkeypatch.setattr(ref, "rope_interleaved", half)
    return {}


WRONG = {"indexer_reads_the_layers_input":
         lambda mp: mp.setattr(ref, "index_query_input", lambda c_q, h: h) or {},
         "rope_pairings_swapped": _swapped_pairings,
         "group_limit_dropped": lambda mp: dict(n_group=1, topk_group=1)}


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_a_wrong_model_breaks_the_tolerance(loud, served, monkeypatch, wrong):
    """ONE served program's logits against the reference and against a
    reference that is another model: the tolerance holds the first and must
    see the second."""
    _, params = loud
    seq, got = served
    assert np.abs(got - reference_logits(params, seq)).max() < TOL
    other = WRONG[wrong](monkeypatch)
    monkeypatch.setattr(serving_helpers, "_JITTED", {})     # the variant compiles anew
    assert np.abs(got - reference_logits(params, seq, **other)).max() > 100 * TOL


# ---- (c) the absorbed form against the plain form ------------------------------- #
def test_the_absorbed_form_is_the_plain_form():
    """A query's 4 heads over the rows it chose: each head's own key and value
    made from the latent (``_latent_plain_qkv``) against the query moved into
    the cached vector's lanes and the value read from the same row."""
    model, params = build()
    cfg, rng = model.cfg, np.random.default_rng(0)
    p = jax.tree.map(lambda a: a[1], {k: v for k, v in params["blocks"].items()
                                      if k not in ("lead", "moe")})
    n, K, H, R, dr = 5, 12, cfg.n_head, cfg.kv_lora_rank, cfg.qk_rope_dim
    h = jnp.asarray(rng.normal(0, 1, (n, 1, cfg.n_embd)), jnp.float32)
    hk = jnp.asarray(rng.normal(0, 1, (n, K, cfg.n_embd)), jnp.float32)
    pos = jnp.asarray(rng.integers(40, 80, (n, 1)))
    q, _ = gpt._latent_project(cfg, p, h, jnp.float32, pos)
    _, rows = gpt._latent_project(cfg, p, hk, jnp.float32, jnp.arange(K)[None] + 3)
    real = jnp.asarray(rng.random((n, K)) < 0.8).at[:, 0].set(True)
    _, k, v = gpt._latent_plain_qkv(cfg, p, q, rows, jnp.float32)
    s = jnp.einsum("nhd,nkhd->nhk", q[:, 0], k) / np.sqrt(cfg.head_dim)
    a = jax.nn.softmax(jnp.where(real[:, None], s, -1e30), axis=-1)
    plain = jnp.einsum("nhk,nkhd->nhd", a, v)
    w_uk, w_uv = gpt._latent_up(cfg, p, jnp.float32)
    absorbed_q = jnp.concatenate([jnp.einsum("nhd,rhd->nhr", q[:, 0, :, :-dr], w_uk),
                                  q[:, 0, :, -dr:]], axis=-1)
    o = chosen_latent_attention(absorbed_q, rows, real, scale=cfg.head_dim ** -0.5,
                                value_lanes=R)
    assert np.abs(jnp.einsum("nhr,rhd->nhd", o, w_uv) - plain).max() < 1e-5
    assert np.abs(plain).max() > 1e-3


@pytest.mark.parametrize("path", ["reference", "kernel"])
def test_the_masked_pass_is_the_gathered_rows(kernels, path):
    """A prompt chunk's form against a decode row's, through the public name
    on both of its paths: every head's own keys and values made from the
    latent and attended under the selection's mask (the reference: two groups
    of heads and two tiles of queries, the limits made small; the kernel
    through the interpreter: two grid steps of four heads over three tiles of
    keys), and the same queries absorbed over the rows gathered at the chosen
    positions with the output brought up through W_UV; a query that chose
    fewer than the others among them."""
    from deepspeed_tpu.ops.pallas import indexed_attention as ia
    rng = np.random.default_rng(1)
    C, H, dn, dr, dv, R, T, K = 32, 4, 12, 8, 10, 24, 96, 12
    if path == "kernel":
        kernels(ia.LATENT_KERNEL)
        H, dn, dr, dv, T = 8, 128, 64, 128, 3 * 640
        assert ia.latent_kernel_shape_ok(C, H, dn, dr, dv, T, jnp.float32)
    f = lambda *shape: jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
    q, w_uk, w_uv = f(C, H, dn + dr), f(R, H, dn), f(R, H, dv)
    c = jnp.pad(f(T, R + dr), ((0, 0), (0, 8)))           # the pages' lanes past the key
    at = np.stack([rng.choice(T, K, replace=False) for _ in range(C)])
    real = (rng.random((C, K)) < 0.8) | (np.arange(K) == 0)
    chosen = np.zeros((C, T), bool)
    np.put_along_axis(chosen, at, real, axis=1)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ia, "_MASKED_STEP_QUERIES", 16)
        mp.setattr(ia, "_MASKED_STEP_SCORES", 16 * 2 * T)
        got = jax.jit(lambda *a: masked_latent_attention(*a, scale=0.1))(
            q, c, jnp.asarray(chosen), jnp.int32(T - 1), w_uk, w_uv)
    absorbed = jnp.concatenate([jnp.einsum("chd,rhd->chr", q[..., :dn], w_uk), q[..., dn:]], -1)
    want = jnp.einsum("chr,rhd->chd", chosen_latent_attention(
        absorbed, c[at][..., :R + dr], jnp.asarray(real), scale=0.1, value_lanes=R), w_uv)
    # float32's own rounding, of outputs that reach past 10 at 128 lanes a head
    assert got.shape == (C, H, dv)
    assert np.abs(got - want).max() < 2e-5 * max(1.0, float(np.abs(want).max()))
    assert np.abs(want).max() > 0.1


@pytest.mark.parametrize("chunks", ["over_topk", "ragged"])
def test_a_chunk_over_its_extent_is_the_chunk_over_its_table(loud, monkeypatch, chunks):
    """A prompt chunk scores, selects among and attends the least eighth of
    its table that holds it (``gpt.CHUNK_EXTENTS``: here a page an extent, the
    first of them under ``topk``): the logits of a step compiled for the whole
    table alone."""
    model, params = loud
    seq = _ids(70, seed=7)
    got = driver(model, params).sequence(seq, CHUNKS[chunks])
    monkeypatch.setattr(gpt, "CHUNK_EXTENTS", 1)
    monkeypatch.setattr(serving_helpers, "_STEPS", {})      # the step compiles anew
    whole = driver(model, params).sequence(seq, CHUNKS[chunks])
    assert np.abs(got - whole).max() < TOL and np.abs(whole).max() > 0.1


# ---- (d) the router limited to groups ------------------------------------------- #
def _numpy_router(logits, bias, k, n_group, topk_group, scale):
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    b = s + bias
    T, E = b.shape
    group = np.sort(b.reshape(T, n_group, -1), axis=-1)[..., -2:].sum(-1)
    kept = np.argsort(-group, axis=-1, kind="stable")[:, :topk_group]
    mask = np.zeros((T, n_group), bool)
    np.put_along_axis(mask, kept, True, axis=1)
    b = np.where(np.repeat(mask, E // n_group, axis=1), b, -np.inf)
    experts = np.argsort(-b, axis=-1, kind="stable")[:, :k]
    w = np.take_along_axis(s, experts, axis=1)
    return experts, scale * w / w.sum(-1, keepdims=True)


@pytest.mark.parametrize("E, k, n_group, topk_group", [(8, 2, 2, 1), (256, 8, 8, 4),
                                                       (64, 6, 4, 3), (32, 4, 8, 2)])
def test_the_router_with_groups_is_the_numpy_router(E, k, n_group, topk_group):
    rng = np.random.default_rng(E + k)
    logits = rng.normal(0, 2, (97, E)).astype(np.float32)
    bias = rng.normal(0, 0.3, E).astype(np.float32)
    _, weights, experts = dropless.sigmoid_topk(
        jnp.asarray(logits), k, jnp.asarray(bias), True, 2.5, n_group, topk_group)
    want_e, want_w = _numpy_router(logits, bias, k, n_group, topk_group, 2.5)
    assert (np.sort(np.asarray(experts), -1) == np.sort(want_e, -1)).all()
    assert np.abs(np.sort(np.asarray(weights), -1) - np.sort(want_w, -1)).max() < 1e-5
    # every chosen expert lies in one of topk_group groups
    assert (np.asarray([len(set(r // (E // n_group))) for r in np.asarray(experts)])
            <= topk_group).all()


@pytest.mark.parametrize("renormalise, scale", [(True, 1.0), (True, 2.448), (False, 1.0)])
def test_one_group_is_bit_for_bit_the_router_without_groups(renormalise, scale):
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(0, 2, (64, 128)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.3, 128), jnp.float32)
    was = dropless.sigmoid_topk(logits, 4, bias, renormalise, scale)
    now = dropless.sigmoid_topk(logits, 4, bias, renormalise, scale, 1, 1)
    whole = dropless.sigmoid_topk(logits, 4, bias, renormalise, scale, 4, 4)
    for a, b, c in zip(was, now, whole):
        assert (np.asarray(a) == np.asarray(b)).all() and (np.asarray(a) == np.asarray(c)).all()


# ---- (e) the shares of an expert layer add up to the layer ---------------------- #
@pytest.mark.parametrize("shares", [2, 4, 8])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(shares):
    """Every chip's part of an expert layer (its held experts' weighted
    outputs, beside the shared expert every chip computes), the shared expert
    counted once, is the uncut layer's feed-forward: the reference's router
    and every expert for every token."""
    model, params = build(held=None)
    cfg = model.cfg
    moe = jax.tree.map(lambda a: a[0], params["blocks"]["moe"])
    z = jnp.asarray(np.random.default_rng(shares).normal(0, 1, (40, cfg.n_embd)), jnp.float32)
    per = cfg.moe_num_experts // shares
    total = 0.0
    shared = ref._swiglu(z, moe["shared"]["wi"], moe["shared"]["wo"])
    for s in range(shares):
        part = deepseek_v32_config(**WIDTHS, indexer=(4, 16, TOPK), dtype="float32",
                                   experts_held=(s * per, per))
        bank = jax.tree.map(lambda a: a[s * per:(s + 1) * per], moe["experts"])
        y, _, counts = gpt._ffn(part, {"moe": dict(moe, experts=bank)}, z, jnp.float32)
        total = total + (y - shared)
        assert int(counts.sum()) == 40 * cfg.moe_top_k
    weight = ref.routed_weights(moe["gate"], z, top_k=2, n_group=2, topk_group=1, scale=2.5)
    want = shared + sum(weight[:, e:e + 1] * ref._swiglu(
        z, moe["experts"]["wi"][e], moe["experts"]["wo"][e]) for e in range(8))
    assert np.abs(total + shared - want).max() < TOL
    assert np.abs(want - shared).max() > 1e-3


# ---- (f) the engine ----------------------------------------------------------------- #
def test_the_engine_serves_the_references_tokens_and_counts_its_keys(loud):
    model, params = loud
    prompts, new = [_ids(40, 11), _ids(9, 12)], [20, 30]
    tokens, eng = served_tokens(model, params, prompts, new, **SERVING)
    for prompt, got in zip(prompts, tokens):
        best, gap = serving_helpers.reference_tokens(
            lambda p, seq: reference_logits(p, seq), params, prompt, got, vocab=512)
        assert gap < TOL and list(got) == best
    assert eng._aux["ki"].shape == (3, SERVING["num_blocks"], BS, 16)
    eng.submit(_ids(30, 13), max_new_tokens=2)
    st = eng.step()
    while not st["programs"]:
        st = eng.step()
    # a chunk of 8 at positions 0..7 in each of 3 layers
    assert st["index_keys_scored"] == st["indexed_keys_resident"] == 3 * 36
    assert st["indexed_keys_attended"] == 3 * 36
    assert st["index_key_bytes"] == eng._aux["ki"].nbytes
    assert (st["index_rows_all"], st["index_rows_selected"]) == (8, 0)
    # the chunk's branch: the first page of eight (``gpt.CHUNK_EXTENTS``), and
    # on the CPU the reference walks every key of it
    assert st["chunk_keys_extent"] == st["chunk_keys_passed"] == 3 * BS
    seen = [st]
    while len(seen) < 4:
        st = eng.step()
        seen += [st] * bool(st.get("chunk_keys_extent"))
    # chunks of 8 at positions 8..15, 16..23, 24..29: a page, then two
    assert [s["chunk_keys_extent"] for s in seen] == [3 * BS, 3 * BS, 6 * BS, 6 * BS]
    while eng.sched.has_work:
        eng.step()


# ---- the serving tree: a latent layer's projections as the engine keeps them --------- #
@pytest.mark.parametrize("chunks", ["over_topk", "single"])
@pytest.mark.parametrize("indexed", [True, False], ids=["indexed", "every_key"])
def test_the_serving_tree_serves_the_canonical_trees_logits(indexed, chunks):
    """``paged_step`` over the tree the engine keeps (the five projections
    transposed, :func:`gpt.serving_params`) against the canonical tree, a
    prompt in chunks then decode rows, with the selection at work and with a
    ``topk`` no table reaches: the same tokens, the same logits.  Not to the
    bit on the CPU, whose dot sums a transposed operand's products in another
    order (2e-7 here); the file's tolerance."""
    model, params = build() if indexed else build(topk=10 ** 6)
    tree, relaid = model.serving_params(params)
    assert sorted(relaid) == sorted(gpt.SERVING_LEAVES)
    assert not set(gpt.SERVING_LEAVES) & set(tree["blocks"])
    seq = _ids(70, seed=7)
    got = driver(model, tree).sequence(seq, CHUNKS[chunks])
    want = driver(model, params).sequence(seq, CHUNKS[chunks])
    assert (got.argmax(-1) == want.argmax(-1)).all()
    assert np.abs(got - want).max() < TOL and np.abs(want).max() > 0.1


def test_the_engine_holds_each_relaid_leaf_once(loud):
    """The engine's tree holds the five projections of the three layers
    transposed and none of them canonical, as many bytes as the caller's
    leaves; every other leaf is the array it was given; and weights assigned
    to a kept engine go through the same relay."""
    model, params = loud
    _, eng = served_tokens(model, params, [_ids(9, 12)], [3], **SERVING)
    blocks = eng.params["blocks"]
    assert eng.relaid_leaves == 5 and not set(gpt.SERVING_LEAVES) & set(blocks)
    assert eng.relaid_bytes == sum(params["blocks"][k].nbytes for k in gpt.SERVING_LEAVES)
    for name, relaid in gpt.SERVING_LEAVES.items():
        np.testing.assert_array_equal(blocks[relaid], params["blocks"][name].swapaxes(-1, -2))
    assert blocks["q_a_w"] is params["blocks"]["q_a_w"] and eng.params["wte"] is params["wte"]
    eng.params = jax.tree.map(lambda a: a * 2, params)
    assert eng.relaid_leaves == 5 and "q_b_w" not in eng.params["blocks"]
    np.testing.assert_array_equal(eng.params["blocks"]["q_b_t"],
                                  2 * params["blocks"]["q_b_w"].swapaxes(-1, -2))


def test_an_int8_injected_latent_stack_still_serves(loud):
    """A projection injected as int8 (``module_inject/quantization.py``) is
    left where it is (its scales run along the output channels) beside the
    others relaid, and the engine serves what the canonical int8 tree serves
    by hand."""
    from deepspeed_tpu.module_inject.quantization import quantize_block_params
    model, params = loud
    injected = quantize_block_params(params, keys=("q_b_w", "kv_b_w", "index_q_w", "out_w"))
    prompt = _ids(30, 14)
    (tokens,), eng = served_tokens(model, injected, [prompt], [6], **SERVING)
    assert eng.relaid_leaves == 2 and "q8" in eng.params["blocks"]["q_b_w"]
    seq = np.concatenate([prompt, tokens]).astype(np.int32)
    want = driver(model, injected).sequence(seq, (8, 8, 8, 6))
    assert list(tokens) == want[len(prompt) - 1:len(seq) - 1].argmax(-1).tolist()


def test_the_parameter_count_is_the_trees(loud):
    model, params = loud
    zeros = sum(a.size for k, a in params["blocks"].items() if k in ("ln1_b", "ln2_b", "out_b"))
    held = sum(a.size for a in jax.tree.leaves(params)) - zeros - params["lnf_b"].size
    assert model.num_params() == held
    E, Rq, ix = 64, 64, model.cfg.indexer
    assert params["blocks"]["index_q_w"].shape == (3, Rq, ix.heads * ix.head_dim)
    assert params["blocks"]["index_kw_w"].shape == (3, E, ix.head_dim + ix.heads)


# ---- (g) what is refused ------------------------------------------------------------ #
@pytest.mark.parametrize("path", ["forward", "generate", "loss"])
def test_the_dense_paths_refuse_the_stack_by_mechanism(loud, path):
    model, params = loud
    said = serving_helpers.dense_path_refusal(model, params, path, _ids(12, 1))
    assert "lightning indexer" in said and "init_serving" in said


@pytest.mark.parametrize("mechanism", ["prefix_cache", "kv_tiering"])
def test_init_serving_refuses_what_no_block_of_the_cache_carries(loud, mechanism):
    model, params = loud
    with pytest.raises(ValueError, match=mechanism):
        deepspeed_tpu.init_serving(model=model, params=params, config={
            "serving": dict(SERVING, **{mechanism: True})})


def test_a_chunk_that_is_no_whole_tiles_of_queries_is_refused(loud):
    model, params = loud
    with pytest.raises(ValueError, match="whole tiles"):
        deepspeed_tpu.init_serving(model=model, params=params, config={
            "serving": dict(SERVING, prefill_chunk=192)})


@pytest.mark.parametrize("wrong, said", [
    (dict(layer_pattern=(gpt.LayerKind(32, True),)), "window"),
    (dict(moe_n_group=3), "whole groups"),
    (dict(moe_topk_group=5), "moe_topk_group"),
    (dict(moe_scoring="softmax"), "sigmoid router")])
def test_the_configuration_refuses_what_is_not_written(wrong, said):
    with pytest.raises(AssertionError, match=said):
        deepseek_v32_config(**WIDTHS, indexer=(4, 16, TOPK), **wrong)


def test_an_indexer_over_k_and_v_heads_is_the_hybrid_walks():
    with pytest.raises(AssertionError, match="LATENT"):
        gpt.llama_config(vocab_size=512, n_embd=64, n_layer=2, n_head=4, indexer=(4, 16, TOPK))
