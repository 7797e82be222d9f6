"""SmallThinker through the program's normal paths against the plain
reference (``benchmarks/lib/reference_smallthinker.py``), at a tiny size with
seeded weights on the CPU: two whole periods of (full without rope, window
with rope x 3), 4 query heads on 2 K/V heads of a width that is not
``n_embd // n_head``, a window shorter than every sequence here.

Tolerances.  Program and reference both compute in float32 under
``default_matmul_precision("highest")`` and differ only in the order of
their sums (fused projections, experts in sorted groups against one by one,
keys in pages against whole), which at these sizes is under 1e-6 of logit
(as ``tests/unit/test_olmoe.py`` found).  ``TOL`` is 2e-5, and each of these
is held to miss it fifty times over below, on the dense path and through
the engine: bf16 weights, router weights not renormalised over the chosen,
rope on the full layers, a window off by one either way, a router that
reads the MLP's input.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmarks.lib import reference_smallthinker
from deepspeed_tpu.models.gpt import GPT, LayerKind, smallthinker_config
from tests.unit.paged_bank import PATHS, bank_in_place_equals_bank_sliced
from tests.unit.serving_helpers import jitted, served_logits, tiny_engine

TOL = 2e-5
V, W, LAYERS = 500, 16, 8
REF = dict(n_head=4, n_kv_head=2, head_dim=24, top_k=3, vocab_size=V,
           rope_layout=[0, 1, 1, 1] * 2, sliding_window_layout=[0, 1, 1, 1] * 2,
           window=W)
SLOTS, CHUNK = 3, 8
SERVING = {"block_size": 4, "num_blocks": 40, "max_batch_size": SLOTS,
           "prefill_chunk": CHUNK, "dtype": "float32"}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def tiny_config(**overrides):
    kw = dict(vocab_size=V, n_positions=128, n_embd=64, n_layer=LAYERS, n_head=4,
              n_kv_head=2, head_dim=24, intermediate_size=32, num_experts=8,
              top_k=3, window=W, dtype=jnp.float32, moe_aux_coeff=0.0)
    kw.update(overrides)
    return smallthinker_config(**kw)


@pytest.fixture(scope="module")
def tiny():
    """Norm weights moved off 1 and a livelier router than std 0.02 gives
    at hidden 64, so that each is seen."""
    model = GPT(tiny_config())
    params = model.init_params(jax.random.PRNGKey(0))
    for i, name in enumerate(("ln1_g", "ln2_g")):
        leaf = params["blocks"][name]
        params["blocks"][name] = 1.0 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(10 + i), leaf.shape)
    params["lnf_g"] = 1.0 + 0.2 * jax.random.normal(jax.random.PRNGKey(20), (64,))
    params["blocks"]["moe"]["gate"]["wg"] = params["blocks"]["moe"]["gate"]["wg"] * 20
    return model, params


def _ids(n, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, V)


def smallthinker_logits(params, ids, **kw):
    """The reference's forward pass, compiled once a set of its keywords."""
    return jitted(reference_smallthinker.smallthinker_logits, **kw)(params, ids)


@pytest.fixture(scope="module")
def want40(tiny):
    """The reference's logits of the 40 tokens every dense-path case compares
    against, once a module."""
    with jax.default_matmul_precision("highest"):
        return smallthinker_logits(tiny[1], _ids(40), **REF)


# what a wrong model is: each moves the logits by far more than TOL
WRONG = {
    "bf16": dict(dtype=jnp.bfloat16),
    "no_renormalisation": dict(moe_norm_topk=False),
    "rope_on_the_full_layers": dict(layer_pattern=(LayerKind(None, True),)
                                    + 3 * (LayerKind(W, True),)),
    "window_one_short": dict(window=W - 1),
    "window_one_long": dict(window=W + 1),
    "router_after_attention": dict(moe_router_input="post_attn"),
}


def test_config_is_the_published_layer():
    cfg = smallthinker_config()
    assert (cfg.n_embd, cfg.n_layer, cfg.n_head, cfg.kv_heads, cfg.head_dim) == (
        2560, 52, 28, 4, 128)
    assert cfg.n_embd // cfg.n_head != cfg.head_dim and cfg.attn_dim == 3584
    assert (cfg.moe_num_experts, cfg.moe_top_k, cfg.ffn_dim) == (64, 6, 768)
    assert cfg.moe_router == "dropless" and cfg.moe_norm_topk
    assert cfg.moe_router_input == "pre_attn" and cfg.glu_activation == "relu"
    assert cfg.pattern == (LayerKind(None, False),) + 3 * (LayerKind(4096, True),)
    assert (cfg.norm, cfg.mlp_type, cfg.ln_eps, cfg.rope_theta) == (
        "rmsnorm", "swiglu", 1e-6, 1.5e6)
    assert cfg.untied_head and not cfg.use_bias
    assert cfg.padded_vocab == cfg.vocab_size == 151936 and cfg.n_positions == 16384
    whole, one = GPT(cfg), GPT(dataclasses.replace(cfg, n_layer=4))
    # 20.97 M attention + 0.16 M router + 377.5 M bank + two norms a layer
    assert (whole.num_params() - one.num_params()) // 48 == 398_627_840
    assert whole.num_params() == 21_506_562_560                   # 21.5 G
    shapes = jax.eval_shape(one.init_params, jax.random.PRNGKey(0))["blocks"]
    assert shapes["qkv_w"].shape == (4, 2560, 28 * 128 + 2 * 4 * 128)
    assert shapes["out_w"].shape == (4, 3584, 2560)
    assert shapes["moe"]["experts"]["wi"].shape == (4, 64, 2560, 1536)
    # and the leaves a bias-free RMSNorm model never reads (two shifts, two biases)
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 4 * (
        398_627_840 + 2 * 2560 + 4608 + 2560)
    with pytest.raises(AssertionError, match="whole periods"):
        smallthinker_config(n_layer=6)


def test_forward_logits_equal_the_reference(tiny, want40):
    model, params = tiny
    ids, want = _ids(40), want40
    got = model.forward_logits(params, ids[None])[0, :, :V]
    assert float(jnp.abs(got - want).max()) < TOL
    # a range of positions is those rows of the whole
    some = smallthinker_logits(params, ids, lo=30, hi=37, **REF)
    np.testing.assert_array_equal(np.asarray(some), np.asarray(want[30:37]))


@pytest.mark.parametrize("wrong", list(WRONG))
def test_the_tolerance_refuses_a_wrong_model_on_the_dense_path(tiny, want40, wrong):
    _, params = tiny
    ids, want = _ids(40), want40
    got = GPT(tiny_config(**WRONG[wrong])).forward_logits(params, ids[None])[0, :, :V]
    gap = float(jnp.abs(got.astype(jnp.float32) - want).max())
    assert gap > 50 * TOL, gap


@pytest.fixture(scope="module")
def served(tiny):
    model, params = tiny
    prompt = list(map(int, _ids(21, seed=6)))
    with jax.default_matmul_precision("highest"):
        return (prompt, *served_logits(model.cfg, params, prompt, 20, SERVING, V)[:3])


def test_prefill_in_chunks_then_decode_past_the_window_equals_the_reference(
        tiny, served):
    """Three prompt chunks (the last short) and twenty decode steps, to
    position 40 of a window of 16: the logits of every position, not the
    tokens, against the reference's one full forward pass."""
    _, params = tiny
    prompt, tokens, got, stats = served
    seq = jnp.asarray(prompt + tokens)
    want = smallthinker_logits(params, seq, **REF)
    assert got.shape == (len(seq) - 1, V)
    assert float(np.abs(got - np.asarray(want[:-1])).max()) < TOL
    assert sum(s["prefill_tokens"] > 0 for s in stats) == 3
    # the window groups gave back what no later query sees: the last step
    # but one has 38 tokens resident and writes the 39th (10 blocks of 4);
    # its query at 38 sees the keys from 23, in block 5
    last = stats[-2]
    assert last["pages_full"] == 10 and last["pages_window"] == 3 * (10 - 5)
    assert last["pages_given_back"] == 3 * 5
    assert last["blocks_in_use"] == -(-(10 + 15) // 4)      # of ALL layers


@pytest.mark.parametrize("wrong", ["bf16", "window_one_short", "rope_on_the_full_layers",
                                   "no_renormalisation"])
def test_the_tolerance_refuses_a_wrong_model_on_the_served_path(tiny, served, wrong):
    """The sequence the right engine served goes through a wrong one as a
    prompt (every position a row of a chunk, through the pages)."""
    _, params = tiny
    prompt, tokens, _, _ = served
    seq = prompt + tokens
    want = smallthinker_logits(params, jnp.asarray(seq), **REF)
    kw = dict(WRONG[wrong])
    serving = dict(SERVING, dtype="bfloat16") if kw.pop("dtype", None) else SERVING
    _, got, _, _ = served_logits(tiny_config(**kw), params, seq, 1, serving, V)
    gap = float(np.abs(got.astype(np.float32) - np.asarray(want)).max())
    assert gap > 50 * TOL, gap


def test_the_engine_on_the_kernel_serves_the_reference_paths_logits(tiny, kernels):
    """Heads of 128 lanes (what the kernel takes), 2 query heads on 1 K/V
    head, pages of 8: the program with ``paged_gqa_attention`` through the
    interpreter against the program on the gather reference, past the
    window, prompt chunks and decode rows."""
    cfg = tiny_config(n_head=2, n_kv_head=1, head_dim=128, n_layer=4)
    params = GPT(cfg).init_params(jax.random.PRNGKey(1))
    prompt = list(map(int, _ids(19, seed=8)))
    serving = dict(SERVING, block_size=8)
    want_tokens, want, _, _ = served_logits(cfg, params, prompt, 9, serving, V)
    kernels("paged_gqa_attention")
    tokens, got, stats, _ = served_logits(cfg, params, prompt, 9, serving, V)
    assert stats[0]["paged_tile_pages"] == 16 and tokens == want_tokens
    assert float(np.abs(got - want).max()) < TOL


def test_preemption_and_resume_keep_the_pages_consistent(tiny):
    """An arena too small for three long requests: the youngest is preempted
    while the others grow, resumes by recompute, and every request's tokens
    are those it gets alone; the allocator's books hold at every step."""
    model, params = tiny
    prompts = [list(map(int, _ids(n, seed=30 + n))) for n in (30, 26, 22)]
    alone = [tiny_engine(model, params, **SERVING).submit(p, max_new_tokens=30).result()
             for p in prompts]
    # an engine of its own (``preemptions`` is read as a total), of 13 blocks
    # of all layers = 52 pages: one request at 60 tokens holds 15 + 3 x 5 =
    # 30, three cannot grow together
    eng = deepspeed_tpu.init_serving(model=model, params=params, config={
        "serving": dict(SERVING, num_blocks=13)})
    futures = [eng.submit(p, max_new_tokens=30) for p in prompts]
    peak = 0
    while not all(f.done for f in futures):
        st = eng.step()
        eng.alloc.check_consistent()
        peak = max(peak, st["pages_full"] + st["pages_window"])
    assert st["preemptions"] >= 1 and peak <= 51
    assert [f.token_ids for f in futures] == alone
    assert eng.alloc.pages_full == eng.alloc.pages_window == 0
    eng.close()


def test_sharing_and_spilling_refuse_several_tables_a_sequence(tiny):
    model, params = tiny
    for knob in ("prefix_cache", "kv_tiering"):
        with pytest.raises(ValueError, match="layer pattern"):
            deepspeed_tpu.init_serving(model=model, params=params, config={
                "serving": dict(SERVING, **{knob: True})})


@pytest.mark.parametrize("path", PATHS)
def test_the_paged_step_reads_the_bank_in_place(path, kernels, monkeypatch):
    """A period of FOUR layers (the scan over two periods, whose body takes
    every other leaf out of the stack itself), at widths the kernel takes:
    layer ``period * 4 + j`` of the stacked bank read where it lies against
    the step with each layer's bank sliced out by hand, bit for bit."""
    cfg = tiny_config(n_embd=128, intermediate_size=128)
    assert len(cfg.pattern) == 4 and cfg.n_layer == 8
    params = GPT(cfg).init_params(jax.random.PRNGKey(2))
    params["blocks"]["moe"]["gate"]["wg"] = params["blocks"]["moe"]["gate"]["wg"] * 20
    bank_in_place_equals_bank_sliced(cfg, params, path, kernels, monkeypatch)
