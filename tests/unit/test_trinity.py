"""Trinity (``afmoe``) through the program's normal paths against the plain
reference (``benchmarks/lib/reference_trinity.py``), at a tiny size with
seeded weights on the CPU: one leading dense layer and seven expert layers,
two whole periods of (window with rope x 3, full without rope), 4 query heads
on 2 K/V heads of a width that is not ``n_embd // n_head``, a window shorter
than every sequence here, 16 experts with 4 a token beside a shared one.

Tolerances.  Program and reference both compute in float32 under
``default_matmul_precision("highest")`` and differ only in the order of
their sums (fused projections, experts in sorted groups against one by one,
keys in pages against whole), which at these sizes is a few 1e-6 of logit (a
norm on every sublayer's output passes a difference on undamped; as
``tests/unit/test_olmoe.py`` and ``test_smallthinker.py`` found theirs).
``TOL`` is 5e-5, and each of these is held to miss it fifty times over below,
on the dense path and through the engine: bf16, the output gate, the norm a
head on q and k, ``route_scale`` and the norms on the sublayers' outputs each
left out, rope on the full layers, the embedding not scaled.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import reference_trinity
from deepspeed_tpu.models import gpt
from deepspeed_tpu.models.gpt import GPT, LayerKind, trinity_config
from tests.unit.paged_bank import PATHS, bank_in_place_equals_bank_sliced
from tests.unit.serving_helpers import jitted, served_logits

TOL = 5e-5
V, W, LAYERS, N, K = 500, 16, 8, 16, 4
TYPES = ["sliding_attention"] * 3 + ["full_attention"]
REF = dict(n_head=4, n_kv_head=2, head_dim=24, top_k=K, num_experts=N,
           layer_types=TYPES * 2, window=W, num_dense_layers=1,
           route_scale=2.448, vocab_size=V)
SLOTS, CHUNK = 3, 8
SERVING = {"block_size": 4, "num_blocks": 40, "max_batch_size": SLOTS,
           "prefill_chunk": CHUNK, "dtype": "float32"}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def tiny_config(**overrides):
    kw = dict(vocab_size=V, n_positions=128, n_embd=64, n_layer=LAYERS, n_head=4,
              n_kv_head=2, head_dim=24, intermediate_size=96,
              moe_intermediate_size=32, num_experts=N, top_k=K, dense_layers=1,
              window=W, dtype=jnp.float32, moe_aux_coeff=0.0)
    kw.update(overrides)
    return trinity_config(**kw)


def lively(params, seed=0):
    """Norm gains moved off 1, a livelier router than std 0.02 gives at
    hidden 64 and a bias that changes who is chosen, so that each is seen."""
    blocks = dict(params["blocks"])
    for i, name in enumerate(("ln1_g", "ln2_g", "post_attn_g", "post_mlp_g",
                              "q_norm_g", "k_norm_g")):
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


def trinity_logits(params, ids, **kw):
    """The reference's forward pass, compiled once a set of its keywords."""
    return jitted(reference_trinity.trinity_logits, **kw)(params, ids)


@pytest.fixture(scope="module")
def want48(tiny):
    """The reference's logits of the 48 tokens every dense-path case compares
    against, once a module."""
    with jax.default_matmul_precision("highest"):
        return trinity_logits(tiny[1], _ids(48), **REF)


# what a wrong model is: each moves the logits by far more than TOL
WRONG = {
    "bf16": dict(dtype=jnp.bfloat16),
    "no_output_gate": dict(attn_gate=False),
    "no_norm_a_head": dict(qk_norm=False),
    "no_route_scale": dict(moe_route_scale=1.0),
    "no_norms_on_the_outputs": dict(norm_sandwich=False),
    "rope_on_the_full_layers": dict(layer_pattern=4 * (LayerKind(W, True),)),
    "embedding_not_scaled": dict(scale_emb=1.0),
}


def test_config_is_the_published_layer():
    cfg = trinity_config()
    assert (cfg.n_embd, cfg.n_layer, cfg.n_head, cfg.kv_heads, cfg.head_dim) == (
        3072, 60, 48, 8, 128)
    assert cfg.attn_dim == 6144 and cfg.ffn_dim == 12288
    assert (cfg.moe_num_experts, cfg.moe_top_k, cfg.moe_expert_hidden,
            cfg.moe_shared_experts, cfg.moe_dense_layers) == (256, 4, 3072, 1, 6)
    assert cfg.moe_router == "dropless" and cfg.moe_scoring == "sigmoid"
    assert cfg.moe_norm_topk and cfg.moe_route_scale == 2.448
    assert cfg.pattern == 3 * (LayerKind(4096, True),) + (LayerKind(None, False),)
    assert cfg.page_groups == (4096, 4096, 4096, None)
    assert cfg.qk_norm == "head" and cfg.attn_gate and cfg.norm_sandwich
    assert cfg.scale_emb == math.sqrt(3072)
    # seeded gains: 1, but for the norm on attention's output (depth-scaled)
    assert cfg.post_attn_gain == 1 / math.sqrt(60) and cfg.published_layers == 60
    assert trinity_config(n_layer=8, dense_layers=1).post_attn_gain == 1 / math.sqrt(60)
    seeded = GPT(trinity_config(vocab_size=64, n_embd=32, n_layer=4, n_head=2, n_kv_head=1,
                                head_dim=16, intermediate_size=16, moe_intermediate_size=8,
                                num_experts=4, top_k=2, dense_layers=1)
                 ).init_params(jax.random.PRNGKey(0))["blocks"]
    assert float(seeded["post_attn_g"].max()) == pytest.approx(60 ** -0.5)
    assert float(seeded["post_mlp_g"].min()) == float(seeded["ln1_g"].min()) == 1.0
    assert (cfg.norm, cfg.mlp_type, cfg.ln_eps, cfg.rope_theta) == (
        "rmsnorm", "swiglu", 1e-5, 10000.0)
    assert cfg.untied_head and not cfg.use_bias
    assert cfg.padded_vocab == cfg.vocab_size == 200192 and cfg.n_positions == 262144
    # ISSUE 55's arithmetic: attention a layer, the dense layer, an expert
    # layer of the share of 16, and the cell's 1 + 7 layers
    share = dict(vocab_size=25024, vocab_multiple=64, experts_held=(0, 16))
    count = lambda **kw: GPT(trinity_config(**share, **kw)).num_params()
    ends = 2 * 25024 * 3072 + 3072
    expert_layer = (count(n_layer=8, dense_layers=1) - count(n_layer=4, dense_layers=1)) // 4
    assert expert_layer == 545_010_176
    assert expert_layer - 256 * 3073 - 17 * 3 * 3072 ** 2 == 62_927_104   # attention
    assert count(n_layer=8, dense_layers=1) == ends + 176_173_312 + 7 * expert_layer \
        == 4_144_995_072                                             # 8.29 GB in bf16
    assert GPT(cfg).num_params() // 10 ** 9 == 398                   # "400B"
    shapes = jax.eval_shape(GPT(trinity_config(n_layer=8, dense_layers=1, **share))
                            .init_params, jax.random.PRNGKey(0))
    blocks = shapes["blocks"]
    assert blocks["qkv_w"].shape == (8, 3072, 48 * 128 + 2 * 8 * 128)
    assert blocks["gate_w"].shape == (8, 3072, 6144)
    assert blocks["q_norm_g"].shape == blocks["k_norm_g"].shape == (8, 128)
    assert blocks["post_attn_g"].shape == blocks["post_mlp_g"].shape == (8, 3072)
    assert blocks["lead"]["fc_w"].shape == (1, 3072, 2 * 12288)
    assert blocks["moe"]["gate"]["wg"].shape == (7, 3072, 256)       # the router whole
    assert blocks["moe"]["experts"]["wi"].shape == (7, 16, 3072, 6144)
    assert blocks["moe"]["experts"]["wo"].shape == (7, 16, 3072, 3072)
    assert blocks["moe"]["shared"]["wi"].shape == (7, 3072, 6144)
    # and the leaves a bias-free RMSNorm model never reads: two shifts, the
    # fused projection's and the output's bias a layer, the last norm's shift
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 4_144_995_072 + 8 * (
        2 * 3072 + 8192 + 3072) + 3072
    with pytest.raises(AssertionError, match="whole periods"):
        trinity_config(n_layer=6)
    with pytest.raises(AssertionError, match="moe_dense_layers"):
        trinity_config(n_layer=8, dense_layers=8)


def test_partition_specs_match_the_parameter_tree():
    model = GPT(tiny_config(experts_held=(2, 4)))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    specs = model.partition_specs()
    is_spec = lambda s: isinstance(s, jax.sharding.PartitionSpec)
    assert jax.tree.structure(shapes) == jax.tree.structure(specs, is_leaf=is_spec)
    for a, s in zip(jax.tree.leaves(shapes), jax.tree.leaves(specs, is_leaf=is_spec)):
        assert len(s) <= a.ndim


def test_sigmoid_top_k_times_the_route_scale_by_hand():
    from deepspeed_tpu.moe import dropless
    logits = jnp.log(jnp.asarray([[1.0, 3.0, 1 / 3.0, 9.0, 1.0]]))   # scores 1/2 3/4 1/4 9/10 1/2
    _, w, e = dropless.sigmoid_topk(logits, 2, scale=2.448)
    assert e.tolist() == [[3, 1]]
    np.testing.assert_allclose(np.asarray(w), [[2.448 * 0.9 / 1.65, 2.448 * 0.75 / 1.65]],
                               rtol=1e-6)
    _, one, _ = dropless.sigmoid_topk(logits, 2)
    np.testing.assert_allclose(np.asarray(w), 2.448 * np.asarray(one), rtol=1e-6)


def test_the_sixteen_shares_of_a_layer_add_up_to_the_uncut_layer():
    """A layer of 16 experts cut sixteen ways, an expert a chip: the routed
    parts of the sixteen banks and the shared expert, counted ONCE, are the
    uncut layer's feed-forward, which ``test_forward_logits_equal_the_
    reference`` holds to the uncut reference."""
    whole_cfg = tiny_config()
    whole = lively(GPT(whole_cfg).init_params(jax.random.PRNGKey(5)))
    p = gpt._layer_of(whole_cfg, whole["blocks"], 3)
    z = jax.random.normal(jax.random.PRNGKey(6), (37, 64))
    uncut, _, counts = gpt._ffn(whole_cfg, p, z, jnp.float32)
    assert int(counts.sum()) == 37 * K
    shared = gpt._mlp(whole_cfg, {"fc_w": p["moe"]["shared"]["wi"],
                                  "proj_w": p["moe"]["shared"]["wo"]}, z, jnp.float32)
    parts = []
    for first in range(N):
        cfg = tiny_config(experts_held=(first, 1))
        held = dict(p, moe=dict(p["moe"], experts=jax.tree.map(
            lambda a: a[first:first + 1], p["moe"]["experts"])))
        y, _, c = gpt._ffn(cfg, held, z, jnp.float32)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(counts))
        parts.append(y - shared)
    assert float(jnp.abs(sum(parts) + shared - uncut).max()) < 1e-5
    assert sum(float(jnp.abs(part).max()) > 1e-3 for part in parts) >= N // 2
    # the dense lead's layer holds no bank and counts nothing
    lead = gpt._layer_of(whole_cfg, whole["blocks"], 0)
    assert "moe" not in lead and lead["fc_w"].shape == (64, 2 * 96)
    assert gpt._ffn(whole_cfg, lead, z, jnp.float32)[2] is None


@pytest.mark.parametrize("held", [None, (4, 8), (15, 1)])
def test_forward_logits_equal_the_reference(held):
    model = GPT(tiny_config(experts_held=held))
    params = lively(model.init_params(jax.random.PRNGKey(0)))
    ids = _ids(48)
    want = trinity_logits(params, ids, experts_held=held, **REF)
    got = model.forward_logits(params, ids[None])[0, :, :V]
    assert float(jnp.abs(got - want).max()) < TOL
    # a range of positions is those rows of the whole; blocks of queries
    # change nothing
    some = trinity_logits(params, ids, lo=30, hi=37, experts_held=held, **REF)
    np.testing.assert_array_equal(np.asarray(some), np.asarray(want[30:37]))
    blocked = trinity_logits(params, ids, experts_held=held, q_block=16, **REF)
    assert float(jnp.abs(blocked - want).max()) < TOL


@pytest.mark.parametrize("wrong", list(WRONG))
def test_the_tolerance_refuses_a_wrong_model_on_the_dense_path(tiny, want48, wrong):
    _, params = tiny
    ids, want = _ids(48), want48
    got = GPT(tiny_config(**WRONG[wrong])).forward_logits(params, ids[None])[0, :, :V]
    gap = float(jnp.abs(got.astype(jnp.float32) - want).max())
    assert gap > 50 * TOL, gap


def test_the_training_paths_that_scan_refuse_the_stack_by_name(tiny):
    model, params = tiny
    remat = GPT(dataclasses.replace(model.cfg, remat=True))
    with pytest.raises(AssertionError, match="dense lead"):
        remat.forward_logits(params, _ids(8)[None])
    with pytest.raises(AssertionError, match="dense lead"):
        model.generate(params, _ids(8)[None], 2)


@pytest.fixture(scope="module")
def served(tiny):
    model, params = tiny
    prompt = list(map(int, _ids(21, seed=6)))
    with jax.default_matmul_precision("highest"):
        return (prompt, *served_logits(model.cfg, params, prompt, 20, SERVING, V)[:3])


def test_prefill_in_chunks_then_decode_past_the_window_equals_the_reference(
        tiny, served):
    """Three prompt chunks (the last short) and twenty decode steps, to
    position 40 of a window of 16 whose ring of 6 blocks of 4 has wrapped:
    the logits of every position, not the tokens, against the reference's
    one full forward pass; the dense lead and two periods."""
    _, params = tiny
    prompt, tokens, got, stats = served
    seq = jnp.asarray(prompt + tokens)
    want = trinity_logits(params, seq, **REF)
    assert got.shape == (len(seq) - 1, V)
    assert float(np.abs(got - np.asarray(want[:-1])).max()) < TOL
    assert sum(s["prefill_tokens"] > 0 for s in stats) == 3
    # the window groups gave back what no later query sees: the last step
    # but one has 38 tokens resident and writes the 39th (10 blocks of 4);
    # its query at 38 sees the keys from 23, in block 5
    last = stats[-2]
    assert last["pages_full"] == 10 and last["pages_window"] == 3 * (10 - 5)
    assert last["pages_given_back"] == 3 * 5
    # what the live rows' routing says of itself: 4 a token a layer over the
    # SEVEN expert layers, none for the dense lead
    routed = [s for s in stats if "moe_assignments" in s]
    assert routed and all(s["moe_assignments"] % (7 * K) == 0 for s in routed)
    assert all(s["moe_assignments_held"] == s["moe_assignments"] for s in routed)


@pytest.mark.parametrize("wrong", ["bf16", "no_output_gate", "no_norm_a_head",
                                   "no_route_scale", "no_norms_on_the_outputs"])
def test_the_tolerance_refuses_a_wrong_model_on_the_served_path(tiny, served, wrong):
    """The sequence the right engine served goes through a wrong one as a
    prompt (every position a row of a chunk, through the pages)."""
    _, params = tiny
    prompt, tokens, _, _ = served
    seq = prompt + tokens
    want = trinity_logits(params, jnp.asarray(seq), **REF)
    kw = dict(WRONG[wrong])
    serving = dict(SERVING, dtype="bfloat16") if kw.pop("dtype", None) else SERVING
    _, got, _, _ = served_logits(tiny_config(**kw), params, seq, 1, serving, V)
    gap = float(np.abs(got.astype(np.float32) - np.asarray(want)).max())
    assert gap > 50 * TOL, gap


def test_a_held_share_is_served_as_the_reference_makes_it(tiny):
    """Four of the sixteen experts held: the engine's logits are the
    reference's given the same share, and the step counts the assignments
    that fell on experts that are here."""
    cfg = tiny_config(experts_held=(4, 4))
    params = lively(GPT(cfg).init_params(jax.random.PRNGKey(0)))
    prompt = list(map(int, _ids(19, seed=8)))
    tokens, got, stats, _ = served_logits(cfg, params, prompt, 6, SERVING, V)
    want = trinity_logits(params, jnp.asarray(prompt + tokens), experts_held=(4, 4), **REF)
    assert float(np.abs(got - np.asarray(want[:-1])).max()) < TOL
    routed = [s for s in stats if "moe_assignments" in s]
    assert 0 < sum(s["moe_assignments_held"] for s in routed) < sum(
        s["moe_assignments"] for s in routed)


@pytest.mark.parametrize("path", PATHS)
def test_the_paged_step_reads_the_bank_in_place(path, kernels, monkeypatch):
    """A dense lead before two periods of four, at widths the kernel takes:
    expert layer ``l - 1`` of the stacked bank read where it lies (the three
    walked before the scan, the four the scan walks) against the step with
    each layer's bank sliced out by hand, bit for bit."""
    cfg = tiny_config(n_embd=128, intermediate_size=256, moe_intermediate_size=128,
                      num_experts=8, top_k=2)
    assert len(cfg.pattern) == 4 and cfg.n_layer == 8 and cfg.moe_dense_layers == 1
    params = GPT(cfg).init_params(jax.random.PRNGKey(2))
    params["blocks"]["moe"]["gate"]["wg"] = params["blocks"]["moe"]["gate"]["wg"] * 20
    bank_in_place_equals_bank_sliced(cfg, params, path, kernels, monkeypatch)


@pytest.mark.parametrize("held", [None, (2, 3)])
def test_the_bank_computes_nothing_for_a_row_without_a_request(held, monkeypatch):
    """``dropless_moe(live=)``: the live rows' results are what they are
    without it, an idle row's is zero, and the groups the bank is handed
    hold the live rows' assignments alone (the idle rows of a serve step all
    carry one token and so choose the same experts: hundreds of rows a step
    for nobody, and which of them a held share holds is the seed's)."""
    from deepspeed_tpu.moe import dropless
    T, M, E_, k = 12, 16, 6, 2
    x = jax.random.normal(jax.random.PRNGKey(0), (T, M))
    w = jax.random.normal(jax.random.PRNGKey(1), (held[1] if held else E_, M, M))
    experts = jax.random.randint(jax.random.PRNGKey(2), (T, k), 0, E_)
    weights = jnp.full((T, k), 0.5)
    live = jnp.arange(T) % 3 != 1
    seen = []
    real = dropless.grouped_matmul
    monkeypatch.setattr(dropless, "grouped_matmul", lambda a, b, sizes, layer: (
        seen.append(np.asarray(sizes)), real(a, b, sizes, layer))[1])
    fn = lambda rows, matmul, pick: matmul(rows, w)
    whole = dropless.dropless_moe(x, weights, experts, E_, fn, held=held)
    masked = dropless.dropless_moe(x, weights, experts, E_, fn, held=held, live=live)
    np.testing.assert_allclose(np.asarray(masked[live]), np.asarray(whole[live]), atol=1e-6)
    assert float(jnp.abs(masked[~live]).max()) == 0.0 and float(jnp.abs(whole[~live]).max()) > 0
    first, count = held or (0, E_)
    want = np.bincount(np.asarray(experts[live]).reshape(-1), minlength=E_)[first:first + count]
    np.testing.assert_array_equal(seen[1], want)
    assert seen[0].sum() > seen[1].sum()
