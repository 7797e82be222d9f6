"""The Mistral 4 layer (latent attention, a sigmoid router over experts of
which the bank holds a share, a shared expert) through the program's normal
paths against the plain reference (``benchmarks/lib/reference_mistral4.py``),
at a tiny size with seeded weights on the CPU: 4 heads of 16 + 16 key lanes
and 24 value lanes (a value as wide as no key), a latent of 128 + 16 in an
arena of 256 lanes, YaRN over 32 original positions so that every sequence
here crosses them, 8 experts with 2 a token.

Tolerances.  Program and reference both compute in float32 under
``default_matmul_precision("highest")`` and differ only in the order of
their sums (the absorbed form against every head's own keys and values,
experts in sorted groups against one by one, keys in pages against whole),
which at these sizes is a few 1e-7 of logit (as ``tests/unit/test_olmoe.py``
and ``test_smallthinker.py`` found).  ``TOL`` is 2e-5, and each of these is
held to miss it fifty times over below, on the dense path and through the
engine: bf16, softmax scoring, weights not renormalised, rope in halves, no
YaRN, the softmax without its YaRN scale, no scale of the query by its
position.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmarks.lib import reference_mistral4 as ref
from deepspeed_tpu.models import gpt
from deepspeed_tpu.models.gpt import GPT, YarnRope, mistral4_config
from tests.unit.paged_bank import PATHS, bank_in_place_equals_bank_sliced
from tests.unit.serving_helpers import Driver, jitted, served_logits, served_tokens
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas import decode_attention as da

TOL = 2e-5
V, N, K = 500, 8, 2
YARN = (16.0, 32, 32.0, 1.0, 1.0, 1.0, 0.1)
ROPE = dict(rope_theta=10000, factor=16.0, original_max_position_embeddings=32,
            beta_fast=32.0, beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0,
            llama_4_scaling_beta=0.1)
REF = dict(n_head=4, q_lora_rank=48, kv_lora_rank=128, qk_nope_head_dim=16,
           qk_rope_head_dim=16, v_head_dim=24, top_k=K, n_routed_experts=N,
           vocab_size=V, rope_parameters=ROPE)
SLOTS, CHUNK = 3, 8
SERVING = {"block_size": 8, "num_blocks": 40, "max_batch_size": SLOTS,
           "prefill_chunk": CHUNK, "dtype": "float32"}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def tiny_config(**overrides):
    kw = dict(vocab_size=V, n_positions=4096, n_embd=64, n_layer=2, n_head=4,
              head_dim=32, q_lora_rank=48, kv_lora_rank=128, qk_rope_dim=16,
              v_head_dim=24, intermediate_size=32, num_experts=N, top_k=K,
              rope_yarn=YARN, dtype=jnp.float32, moe_aux_coeff=0.0)
    kw.update(overrides)
    return mistral4_config(**kw)


def lively(params, seed=0):
    """Norm weights moved off 1, a livelier router than std 0.02 gives at
    hidden 64 and a score-correction bias that changes who is chosen, so
    that each is seen."""
    blocks = dict(params["blocks"])
    for i, name in enumerate(("ln1_g", "ln2_g", "q_a_norm_g", "kv_a_norm_g")):
        blocks[name] = 1.0 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(seed + 10 + i), blocks[name].shape)
    gate = blocks["moe"]["gate"]
    blocks["moe"] = dict(blocks["moe"], gate={
        "wg": gate["wg"] * 20,
        "bias": 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 30),
                                        gate["bias"].shape)})
    return dict(params, blocks=blocks,
                lnf_g=1.0 + 0.2 * jax.random.normal(jax.random.PRNGKey(seed + 20), (64,)))


@pytest.fixture(scope="module")
def tiny():
    model = GPT(tiny_config())
    return model, lively(model.init_params(jax.random.PRNGKey(0)))


def _ids(n, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, V)


def mistral4_logits(params, ids, **kw):
    """The reference's forward pass, compiled once a set of its keywords."""
    return jitted(ref.mistral4_logits, **kw)(params, ids)


@pytest.fixture(scope="module")
def want70(tiny):
    """The reference's logits of the 70 tokens every dense-path case compares
    against, once a module."""
    with jax.default_matmul_precision("highest"):
        return mistral4_logits(tiny[1], _ids(70), **REF)


# what a wrong model is: each moves the logits by far more than TOL
WRONG = {
    "bf16": dict(dtype=jnp.bfloat16),
    "softmax_scoring": dict(moe_scoring="softmax"),
    "no_renormalisation": dict(moe_norm_topk=False),
    "rope_in_halves": dict(rope_interleaved=False),
    "no_yarn_frequencies": dict(rope_yarn=(1.0, 32, 32.0, 1.0, 1.0, 1.0, 0.1)),
    "softmax_without_its_yarn_scale": dict(rope_yarn=YARN[:4] + (0.0, 0.0, 0.1)),
    "query_not_scaled_by_position": dict(rope_yarn=YARN[:6] + (0.0,)),
}


def test_config_is_the_published_layer():
    cfg = mistral4_config()
    assert (cfg.n_embd, cfg.n_layer, cfg.n_head, cfg.head_dim, cfg.attn_dim) == (
        4096, 36, 32, 128, 4096)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.v_head_dim) == (
        1024, 256, 64, 128)
    assert cfg.rope_yarn == YarnRope(128.0, 8192, 32.0, 1.0, 1.0, 1.0, 0.1)
    assert cfg.rope_interleaved and cfg.rope_theta == 10000.0
    assert (cfg.moe_num_experts, cfg.moe_top_k, cfg.ffn_dim, cfg.moe_shared_experts) == (
        128, 4, 2048, 1)
    assert cfg.moe_router == "dropless" and cfg.moe_scoring == "sigmoid"
    assert cfg.moe_norm_topk
    assert cfg.moe_experts_held is None
    assert (cfg.norm, cfg.mlp_type, cfg.ln_eps) == ("rmsnorm", "swiglu", 1e-6)
    assert cfg.untied_head and not cfg.use_bias
    assert cfg.padded_vocab == cfg.vocab_size == 131072 and cfg.n_positions == 1048576
    # THE cache spec: 256 + 64 numbers in ONE array of whole lane tiles;
    # the families with K and V keep theirs
    assert cfg.cache_lanes == (384,)
    assert gpt.olmoe_config().cache_lanes == (2048, 2048)
    assert gpt.smallthinker_config().cache_lanes == (512, 512)
    # ISSUE 33's arithmetic: a layer outside its routed experts, one expert,
    # the whole model, and one chip's share of five layers
    one = GPT(dataclasses.replace(cfg, n_layer=1))
    layer = GPT(dataclasses.replace(cfg, n_layer=2)).num_params() - one.num_params()
    assert layer - 128 * 25_165_824 == 53_749_120
    assert GPT(cfg).num_params() == 36 * layer + 2 * 131072 * 4096 + 4096
    assert GPT(cfg).num_params() // 10 ** 9 == 118                  # "119B"
    share = mistral4_config(n_layer=5, vocab_size=32768, experts_held=(0, 32))
    assert GPT(share).num_params() == 4_563_716_992                 # 9.13 GB in bf16
    shapes = jax.eval_shape(GPT(share).init_params, jax.random.PRNGKey(0))["blocks"]
    assert "qkv_w" not in shapes
    assert shapes["q_a_w"].shape == (5, 4096, 1024)
    assert shapes["q_b_w"].shape == (5, 1024, 32 * 128)
    assert shapes["kv_a_w"].shape == (5, 4096, 256 + 64)
    assert shapes["kv_b_w"].shape == (5, 256, 32 * (64 + 128))
    assert shapes["out_w"].shape == (5, 4096, 4096)
    assert shapes["moe"]["gate"]["wg"].shape == (5, 4096, 128)      # the router whole
    assert shapes["moe"]["experts"]["wi"].shape == (5, 32, 4096, 4096)
    assert shapes["moe"]["experts"]["wo"].shape == (5, 32, 2048, 4096)
    assert shapes["moe"]["shared"]["wi"].shape == (5, 4096, 4096)
    with pytest.raises(AssertionError, match="moe_experts_held"):
        mistral4_config(experts_held=(100, 32))


def test_partition_specs_match_the_parameter_tree():
    model = GPT(tiny_config(experts_held=(2, 4)))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    specs = model.partition_specs()
    assert jax.tree.structure(shapes) == jax.tree.structure(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    for a, s in zip(jax.tree.leaves(shapes), jax.tree.leaves(
            specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))):
        assert len(s) <= a.ndim


# ---- rope and the two scales, by hand ---------------------------------------- #
def test_yarn_frequencies_by_hand():
    """The published block: 64 rope lanes, theta 10,000, factor 128 over
    8,192 positions, beta 32 and 1.  The pair that makes ``t`` turns in 8,192
    positions is ``64 ln(8192 / (2 pi t)) / (2 ln 10000)``: 12.88 for 32
    turns, 24.92 for 1, so pairs 0..12 keep their frequency, pairs 25..31
    turn 128 times slower, and pair 18 is (18 - 12) / 13 along the ramp."""
    yarn = YarnRope(128.0, 8192, 32.0, 1.0, 1.0, 1.0, 0.1)
    got = np.asarray(gpt.yarn_inv_freq(64, 10000.0, yarn), np.float64)
    plain = 10000.0 ** (-np.arange(32) / 32)
    assert math.floor(64 * math.log(8192 / (2 * math.pi * 32)) / (2 * math.log(1e4))) == 12
    assert math.ceil(64 * math.log(8192 / (2 * math.pi * 1)) / (2 * math.log(1e4))) == 25
    np.testing.assert_allclose(got[:13], plain[:13], rtol=1e-6)
    np.testing.assert_allclose(got[25:], plain[25:] / 128, rtol=1e-6)
    at = 6 / 13
    np.testing.assert_allclose(got[18], plain[18] * (1 - at) + plain[18] / 128 * at, rtol=1e-6)
    np.testing.assert_allclose(got, ref.yarn_inv_freq(64, 10000, 128, 8192, 32, 1), rtol=1e-6)


def test_both_scales_by_hand():
    """``m = 0.1 ln 128 + 1 = 1.4852``; the softmax scale is ``128^-0.5 m^2``;
    the query of position ``p`` is scaled by ``1 + 0.1 ln(1 + p // 8192)``:
    1 below 8,192, 1.0693 to 16,383.  The program folds both into q: a
    query all of whose products are 1 scores ``m^2`` times that."""
    assert gpt.yarn_mscale(128.0, 1.0) == pytest.approx(1.485203, abs=1e-6)
    assert ref.mscale(128.0, 1.0) == gpt.yarn_mscale(128.0, 1.0)
    assert 1 + 0.1 * math.log(2) == pytest.approx(1.069315, abs=1e-6)
    cfg = mistral4_config(vocab_size=V, n_embd=64, n_layer=1, n_head=2, head_dim=32,
                          q_lora_rank=16, kv_lora_rank=128, qk_rope_dim=16,
                          v_head_dim=32, intermediate_size=32, num_experts=4,
                          top_k=1, dtype=jnp.float32)
    p = jax.tree.map(lambda a: a[0], GPT(cfg).init_params(jax.random.PRNGKey(0))["blocks"])
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 3, 64))
    at = lambda pos: gpt._latent_project(cfg, p, h, jnp.float32, jnp.asarray(pos))[0]
    # the lanes without position: scaled and nothing else
    early, late, later = at([0, 1, 8191]), at([8192, 8193, 16383]), at([16384] * 3)
    nope = lambda q: np.asarray(q[..., :16])
    np.testing.assert_allclose(nope(late), nope(early) * (1 + 0.1 * math.log(2)), rtol=1e-5)
    np.testing.assert_allclose(nope(later), nope(early) * (1 + 0.1 * math.log(3)), rtol=1e-5)
    plain = dataclasses.replace(cfg, rope_yarn=None)
    unscaled = gpt._latent_project(plain, p, h, jnp.float32, jnp.asarray([0, 1, 8191]))[0]
    np.testing.assert_allclose(nope(early), nope(unscaled) * 1.485203 ** 2, rtol=1e-5)


def test_rope_turns_the_pairs_in_place():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 1, 16))
    yarn = YarnRope(*YARN)
    got = gpt.apply_rope(x, jnp.arange(5) + 40, interleaved=True, yarn=yarn)[0, :, 0]
    inv = ref.yarn_inv_freq(16, 10000, 16.0, 32, 32.0, 1.0)
    ang = (np.arange(5) + 40)[:, None] * inv[None]
    want = np.asarray(x[0, :, 0]).copy()
    even, odd = want[:, 0::2].copy(), want[:, 1::2].copy()
    want[:, 0::2] = even * np.cos(ang) - odd * np.sin(ang)
    want[:, 1::2] = even * np.sin(ang) + odd * np.cos(ang)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


# ---- the router and the share of a bank ------------------------------------- #
def test_sigmoid_top_k_with_renormalisation_by_hand():
    logits = jnp.log(jnp.asarray([[1.0, 3.0, 1 / 3.0, 9.0, 1.0]]))   # scores 1/2 3/4 1/4 9/10 1/2
    probs, w, e = dropless.sigmoid_topk(logits, 2)
    assert e.tolist() == [[3, 1]]
    np.testing.assert_allclose(np.asarray(w), [[0.9 / 1.65, 0.75 / 1.65]], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(probs).sum(), 1.0, rtol=1e-6)
    _, w, _ = dropless.sigmoid_topk(logits, 2, renormalise=False)
    np.testing.assert_allclose(np.asarray(w), [[0.9, 0.75]], rtol=1e-6)
    # the bias chooses and does not weigh: expert 2 in, at its own score
    bias = jnp.asarray([0.0, 0.0, 0.6, 0.0, 0.0])
    _, w, e = dropless.sigmoid_topk(logits, 2, bias)
    assert e.tolist() == [[3, 2]]
    np.testing.assert_allclose(np.asarray(w), [[0.9 / 1.15, 0.25 / 1.15]], rtol=1e-6)


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """A layer of 8 experts cut four ways, two experts a chip: the routed
    parts of the four banks and the shared expert, counted ONCE, are the
    uncut layer's feed-forward; and each share is what the reference makes
    of it."""
    whole_cfg = tiny_config(n_layer=1)
    whole = lively(GPT(whole_cfg).init_params(jax.random.PRNGKey(5)))
    p = jax.tree.map(lambda a: a[0], whole["blocks"])
    z = jax.random.normal(jax.random.PRNGKey(6), (37, 64))
    uncut, _, counts = gpt._ffn(whole_cfg, p, z, jnp.float32)
    assert int(counts.sum()) == 37 * K
    shared = gpt._mlp(whole_cfg, {"fc_w": p["moe"]["shared"]["wi"],
                                  "proj_w": p["moe"]["shared"]["wo"]}, z, jnp.float32)
    parts = []
    for first in range(0, N, 2):
        cfg = tiny_config(n_layer=1, experts_held=(first, 2))
        held = dict(p, moe=dict(p["moe"], experts=jax.tree.map(
            lambda a: a[first:first + 2], p["moe"]["experts"])))
        y, _, c = gpt._ffn(cfg, held, z, jnp.float32)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(counts))
        parts.append(y - shared)
    assert float(jnp.abs(sum(parts) + shared - uncut).max()) < 1e-6
    assert all(float(jnp.abs(part).max()) > 1e-3 for part in parts)


@pytest.mark.parametrize("held", [None, (2, 4), (6, 2)])
def test_forward_logits_equal_the_reference(held):
    model = GPT(tiny_config(experts_held=held))
    params = lively(model.init_params(jax.random.PRNGKey(0)))
    ids = _ids(70)
    want = mistral4_logits(params, ids, experts_held=held, **REF)
    got = model.forward_logits(params, ids[None])[0, :, :V]
    assert float(jnp.abs(got - want).max()) < TOL
    some = mistral4_logits(params, ids, lo=30, hi=37, experts_held=held, **REF)
    np.testing.assert_array_equal(np.asarray(some), np.asarray(want[30:37]))
    blocked = mistral4_logits(params, ids[:64], experts_held=held, q_block=16, **REF)
    assert float(jnp.abs(blocked - want[:64]).max()) < TOL


@pytest.mark.parametrize("wrong", list(WRONG))
def test_the_tolerance_refuses_a_wrong_model_on_the_dense_path(tiny, want70, wrong):
    _, params = tiny
    ids, want = _ids(70), want70
    got = GPT(tiny_config(**WRONG[wrong])).forward_logits(params, ids[None])[0, :, :V]
    gap = float(jnp.abs(got.astype(jnp.float32) - want).max())
    assert gap > 50 * TOL, gap


def test_generate_on_the_dense_cache_serves_the_forward_pass():
    """``generate()`` keeps every head's own K and V (the plain form): its
    greedy tokens are the forward pass's."""
    model = GPT(tiny_config(v_head_dim=32, n_positions=128))
    params = lively(model.init_params(jax.random.PRNGKey(2)))
    ids = _ids(40, seed=9)[None]
    out = model.generate(params, ids, 12)
    lg = model.forward_logits(params, out)[0, 39:-1, :V]
    np.testing.assert_array_equal(np.asarray(out[0, 40:]), np.asarray(lg.argmax(-1)))


# ---- the absorbed form and its kernel ---------------------------------------- #
def _absorbed_inputs(cfg, p, h, positions, W):
    q, cache = gpt._latent_project(cfg, p, h, jnp.float32, positions)
    w_uk, w_uv = gpt._latent_up(cfg, p, jnp.float32)
    dr = cfg.qk_rope_dim
    q_abs = jnp.concatenate([jnp.einsum("bshd,rhd->bshr", q[..., :-dr], w_uk),
                             q[..., -dr:]], -1)
    pad = lambda a: jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, W - a.shape[-1]),))
    return q, cache, pad(q_abs), pad(cache), w_uv


def test_absorbed_attention_equals_plain_attention(tiny):
    """One layer's attention both ways on one sequence: every head's own
    key and value from the latent through the causal einsum, against the
    queries moved into the latent's space over the cached vectors in pages."""
    from deepspeed_tpu.ops.attention import reference_attention
    model, params = tiny
    cfg = model.cfg
    p = jax.tree.map(lambda a: a[1], params["blocks"])
    S, BS, W = 48, 8, 256
    h = jax.random.normal(jax.random.PRNGKey(4), (1, S, 64))
    q, cache, q_abs, cached, w_uv = _absorbed_inputs(cfg, p, h, jnp.arange(S), W)
    plain = reference_attention(*gpt._latent_plain_qkv(cfg, p, q, cache, jnp.float32),
                                causal=True)                        # [1, S, H, dv]
    # the sequence's pages in a shuffled arena; every position a row of its own
    order = np.random.default_rng(0).permutation(np.arange(1, 1 + S // BS))
    pages = jnp.zeros((1 + S // BS, BS, W)).at[order].set(cached[0].reshape(-1, BS, W))
    tables = jnp.broadcast_to(jnp.asarray(order, jnp.int32), (S, S // BS))
    o_lat = da.paged_mla_attention_reference(
        q_abs[0][:, None], pages, tables, jnp.arange(S), scale=32 ** -0.5,
        value_lanes=128)                                            # [S, 1, H, R]
    absorbed = jnp.einsum("shr,rhd->shd", o_lat[:, 0], w_uv)
    assert float(jnp.abs(absorbed - plain[0]).max()) < 1e-6


@pytest.mark.parametrize("Sq", [1, 3, 16])
def test_paged_mla_kernel_equals_the_gather_reference(kernels, Sq):
    """The kernel through the interpreter against the gather reference: 5
    heads (rows padded to the sublane tile), pages of 16 in tiles of 32
    (``_MLA_TILE_ROWS`` 512), rows whose context ends inside the first page,
    at a page's edge, past a tile and in the table's last page, an idle row
    of trash, over layer 1 of a two-layer arena; a query a row, three, and
    the 16 a row of the serve cell's prompt chunk holds (its queries then
    span pages, and from 511 + 9 the tile's edge; 255 + 9 an attend step's)."""
    kernels("paged_mla_attention")
    B, H, W, R, BS, MB, NB = 6, 5, 256, 128, 16, 40, 64
    rng = np.random.default_rng(1)
    arena = jnp.asarray(rng.standard_normal((2, NB, BS, W)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, Sq, H, W)), jnp.float32)
    lengths = jnp.asarray([3, 15, 255 + 9, 511 + 9, MB * BS - Sq, 0], jnp.int32)
    tables = jnp.asarray(np.stack([rng.permutation(np.arange(1, NB))[:MB]
                                   for _ in range(B)]), jnp.int32).at[5].set(0)
    assert da.latent_plan(W, R, H, BS, MB, 0, jnp.float32, 0.17).tile_pages == 32
    assert da.latent_plan(384, 256, 32, 16, 1024, 384, jnp.bfloat16,
                          0.17).chunk_queries == 16
    got = jax.jit(lambda *a: da.paged_mla_attention(*a, scale=0.17, value_lanes=R))(
        q, arena, jnp.int32(1), tables, lengths)
    want = da.paged_mla_attention_reference(q, arena[1], tables, lengths, scale=0.17,
                                            value_lanes=R)
    assert got.shape == (B, Sq, H, R)
    assert float(jnp.abs(got - want).max()) < 2e-5
    # the gate: the cached vector unpadded is not whole lane tiles
    assert not da.mla_kernel_shape_ok(144, 128, BS, jnp.float32)
    assert da.mla_kernel_shape_ok(256, 128, BS, jnp.float32)


# ---- through the engine ----------------------------------------------------- #
@pytest.fixture(scope="module")
def served():
    cfg = tiny_config(experts_held=(2, 4))
    params = lively(GPT(cfg).init_params(jax.random.PRNGKey(0)))
    prompt = list(map(int, _ids(21, seed=6)))
    with jax.default_matmul_precision("highest"):
        tokens, got, stats, eng = served_logits(cfg, params, prompt, 30, SERVING, V)
        return cfg, params, prompt, tokens, got, stats, (eng._k_pages, eng._v_pages)


def test_prefill_in_chunks_then_decode_equals_the_reference(served):
    """Three prompt chunks (the last short) and thirty decode steps, past the
    32 original positions of this YaRN: the logits of every position, not the
    tokens, against the reference's one full forward pass; half the experts
    held."""
    cfg, params, prompt, tokens, got, stats, (arena, none) = served
    seq = jnp.asarray(prompt + tokens)
    want = mistral4_logits(params, seq, experts_held=(2, 4), **REF)
    assert got.shape == (len(seq) - 1, V)
    assert float(np.abs(got - np.asarray(want[:-1])).max()) < TOL
    assert sum(s["prefill_tokens"] > 0 for s in stats) == 3
    # the cache spec's ONE array: 128 + 16 numbers a token in 256 lanes
    assert none is None and arena.shape == (2, 40, 8, 256)
    assert stats[-1]["paged_tile_pages"] == 0          # the CPU takes the reference
    # a decode step's one live row: K assignments a layer, those on the
    # held experts counted beside them
    last = stats[-1]
    assert last["moe_assignments"] == K * 2
    assert 0 <= last["moe_assignments_held"] <= last["moe_assignments"]
    assert sum(s.get("moe_assignments_held", 0) for s in stats) > 0


@pytest.mark.parametrize("wrong", ["bf16", "no_renormalisation", "rope_in_halves",
                                   "softmax_without_its_yarn_scale"])
def test_the_tolerance_refuses_a_wrong_model_on_the_served_path(served, wrong):
    """The sequence the right engine served goes through a wrong one as a
    prompt (every position a row of a chunk, through the pages)."""
    cfg, params, prompt, tokens = served[:4]
    seq = prompt + tokens
    want = mistral4_logits(params, jnp.asarray(seq), experts_held=(2, 4), **REF)
    kw = dict(WRONG[wrong], experts_held=(2, 4))
    serving = dict(SERVING, dtype="bfloat16") if kw.pop("dtype", None) else SERVING
    _, got, _, _ = served_logits(tiny_config(**kw), params, seq, 1, serving, V)
    gap = float(np.abs(got.astype(np.float32) - np.asarray(want)).max())
    assert gap > 50 * TOL, gap


def test_the_engine_on_the_kernel_serves_the_reference_paths_logits(kernels):
    """The program with ``paged_mla_attention`` through the interpreter
    against the program on the gather reference: prompt chunks and decode
    rows, contexts over several pages."""
    cfg = tiny_config(v_head_dim=32)
    params = lively(GPT(cfg).init_params(jax.random.PRNGKey(1)))
    prompt = list(map(int, _ids(19, seed=8)))
    want_tokens, want, _, _ = served_logits(cfg, params, prompt, 9, SERVING, V)
    kernels("paged_mla_attention")
    # a new engine: ``tile_runs_pct`` below is read off a new allocator's runs
    tokens, got, stats, _ = served_logits(cfg, params, prompt, 9, SERVING, V, new_engine=True)
    assert stats[0]["paged_tile_pages"] == 64 and tokens == want_tokens
    assert float(np.abs(got - want).max()) < TOL
    # 39 pages hold no run of 64, and the reference path lays none
    assert stats[-2]["tile_runs_pct"] == 0.0


@pytest.mark.parametrize("num_blocks", [40, 11])
def test_the_engine_lays_runs_of_a_tile_and_the_kernel_fetches_them(
        kernels, monkeypatch, num_blocks):
    """Tiles of 4 pages (the rule's constant lowered for the size of the
    test): the allocator the engine builds grows a table in aligned runs of
    the kernel's tile, the step's flags send those tiles through the ONE
    copy, the stat counts them, and the logits are the reference path's.  In
    an arena of 11 blocks the last tiles find no whole run, take loose blocks
    and go page by page in the same program."""
    cfg = tiny_config(v_head_dim=32)
    params = lively(GPT(cfg).init_params(jax.random.PRNGKey(1)))
    prompt = list(map(int, _ids(53, seed=8)))
    serving = dict(SERVING, num_blocks=num_blocks)
    want_tokens, want, _, _ = served_logits(cfg, params, prompt, 20, serving, V)
    kernels("paged_mla_attention")
    monkeypatch.setattr(da, "_MLA_TILE_ROWS", 32)
    # a new engine: where a NEW allocator lays its runs is the claim, and the
    # constant lowered here is read when an engine is built and traced
    tokens, got, stats, eng = served_logits(cfg, params, prompt, 20, serving, V,
                                            new_engine=True)
    assert eng.paged_tile_pages == eng.alloc.run_blocks == 4
    assert tokens == want_tokens and float(np.abs(got - want).max()) < TOL
    # before the last step frees them, 72 tokens are 9 pages: two whole
    # tiles and a short one
    assert stats[-2]["tile_runs_pct"] == pytest.approx(
        100 * (2 if num_blocks == 40 else 1) / 3)


@pytest.mark.parametrize("chunks", [(8, 8, 8, 8), (7, 5, 8, 3, 1), (1,)],
                         ids=["whole", "ragged", "single"])
def test_the_serving_tree_serves_the_canonical_trees_logits(tiny, chunks):
    """``paged_step`` over the tree the engine keeps (``q_b_w``, ``kv_b_w``
    and ``kv_a_w`` transposed, :func:`gpt.serving_params`) against the
    canonical tree, a prompt in chunks then decode rows: the same tokens,
    the same logits.  Not to the bit on the CPU, whose dot sums a transposed
    operand's products in another order; the file's tolerance."""
    model, params = tiny
    tree, relaid = model.serving_params(params)
    assert sorted(relaid) == ["kv_a_w", "kv_b_w", "q_b_w"]
    assert sorted(k for k in tree["blocks"] if k.endswith("_t")) == [
        "kv_a_t", "kv_b_t", "q_b_t"]
    seq = np.asarray(_ids(60, seed=9))
    by_hand = lambda weights: Driver(model, weights, slots=SLOTS, chunk=CHUNK, block_size=8,
                                     blocks_a_slot=10).sequence(seq, chunks)
    got, want = by_hand(tree), by_hand(params)
    assert (got.argmax(-1) == want.argmax(-1)).all()
    assert np.abs(got - want).max() < TOL and np.abs(want).max() > 0.1


def test_the_engine_holds_each_relaid_leaf_once(tiny):
    model, params = tiny
    _, eng = served_tokens(model, params, [list(map(int, _ids(9, seed=5)))], [3], **SERVING)
    names = ("q_b_w", "kv_b_w", "kv_a_w")
    assert eng.relaid_leaves == 3 and not set(names) & set(eng.params["blocks"])
    assert eng.relaid_bytes == sum(params["blocks"][k].nbytes for k in names)
    assert eng.params["blocks"]["q_a_w"] is params["blocks"]["q_a_w"]


def test_under_a_mesh_a_relaid_leaf_keeps_its_spec_transposed(tiny):
    """``q_b_w`` and ``kv_b_w`` make heads and are column-parallel
    (``gpt_partition_specs``): relaid ``[L, N, K]`` they are sharded over the
    rows that were their columns; ``kv_a_w``, shared by all heads, stays
    whole; an unplaced leaf goes where the compiler puts it."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    model, params = tiny
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tensor",))
    specs = model.partition_specs()["blocks"]
    blocks = dict(params["blocks"])
    for name in ("q_b_w", "kv_b_w"):
        assert specs[name] == P(None, None, "tensor")
        blocks[name] = jax.device_put(blocks[name], NamedSharding(mesh, specs[name]))
    blocks["kv_a_w"] = jax.device_put(blocks["kv_a_w"], NamedSharding(mesh, P()))
    tree, _ = model.serving_params(dict(params, blocks=blocks))
    for name in ("q_b_t", "kv_b_t"):
        assert tree["blocks"][name].sharding == NamedSharding(mesh, P(None, "tensor", None))
    assert tree["blocks"]["kv_a_t"].sharding.is_fully_replicated
    np.testing.assert_array_equal(tree["blocks"]["q_b_t"],
                                  params["blocks"]["q_b_w"].swapaxes(-1, -2))


def test_arena_bytes_are_the_cache_specs():
    from deepspeed_tpu.serving.kv_cache import arena_bytes, init_arena
    cfg = tiny_config()
    arena, none = init_arena(cfg, 10, 8, jnp.bfloat16)
    assert none is None and arena.nbytes == arena_bytes(cfg, 10, 8) == 2 * 10 * 8 * 256 * 2
    k, v = init_arena(gpt.gpt_config("tiny"), 10, 8, jnp.bfloat16)
    assert k.shape == v.shape == (2, 10, 8, 64)
    assert k.nbytes + v.nbytes == arena_bytes(gpt.gpt_config("tiny"), 10, 8)
    # the published model: 640 B cached, 768 B held a token a layer
    assert arena_bytes(mistral4_config(n_layer=5), 50000, 16) == 3_072_000_000


def test_a_latent_cache_refuses_tiering_and_the_prefix_cache(tiny):
    model, params = tiny
    for knob in ("kv_tiering", "prefix_cache"):
        with pytest.raises(ValueError, match="latent cache"):
            deepspeed_tpu.init_serving(model=model, params=params, config={
                "serving": dict(SERVING, **{knob: True})})


@pytest.mark.parametrize("path", PATHS)
def test_the_paged_step_reads_the_held_bank_in_place(path, kernels, monkeypatch):
    """A period of one layer with ``held`` experts (the stack is ``[3, 4, K,
    N]`` of 8 experts, and most assignments lie in no group), at widths the
    kernel takes: against the step with each layer's bank sliced out by
    hand, bit for bit; the counts are of all 8 experts."""
    cfg = tiny_config(n_embd=128, intermediate_size=128, n_layer=3,
                      experts_held=(2, 4))
    params = GPT(cfg).init_params(jax.random.PRNGKey(2))
    assert params["blocks"]["moe"]["experts"]["wi"].shape == (3, 4, 128, 256)
    gate = params["blocks"]["moe"]["gate"]
    params["blocks"]["moe"] = dict(params["blocks"]["moe"],
                                   gate=dict(gate, wg=gate["wg"] * 20))
    bank_in_place_equals_bank_sliced(cfg, params, path, kernels, monkeypatch)
