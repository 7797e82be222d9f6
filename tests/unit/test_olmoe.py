"""OLMoE through the program's normal paths against the plain reference
(``benchmarks/lib/reference_olmoe.py``), at a tiny size with seeded weights
on the CPU: forward, loss and gradients, prefill in chunks then decode
through ``ServingEngine``, and what rows without a request may change.

Tolerances.  Program and reference both compute in float32 under
``default_matmul_precision("highest")`` and differ only in the order of
their sums (fused projections, experts in sorted groups against one by
one), which at these sizes is under 1e-6 of logit (seen: 2e-7).  ``TOL`` is
2e-5: a hundred times that, and a hundredth of what a wrong model gives:
computing in bf16 moves the logits by 4e-3 and normalising q and k per head
(after the split) by 2e-2, and both are held to FAIL it below.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmarks.lib import reference_olmoe
from benchmarks.lib.reference_olmoe import olmoe_loss_sum
from deepspeed_tpu.models import gpt as gpt_lib
from deepspeed_tpu.models.gpt import GPT, olmoe_config
from tests.unit.paged_bank import PATHS, bank_in_place_equals_bank_sliced
from tests.unit.serving_helpers import jitted

TOL = 2e-5
V, H = 500, 4
REF = dict(n_head=H, vocab_size=V, top_k=2)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def tiny():
    """8 experts of 32, top 2, hidden 64, 2 layers, 4 heads of 16; norm
    weights moved off 1 so that each norm's weight is seen."""
    cfg = olmoe_config(vocab_size=V, n_positions=128, n_embd=64, n_layer=2,
                       n_head=H, intermediate_size=32, num_experts=8, top_k=2,
                       dtype=jnp.float32, moe_aux_coeff=0.0)
    model = GPT(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    for i, name in enumerate(("ln1_g", "ln2_g", "q_norm_g", "k_norm_g")):
        leaf = params["blocks"][name]
        params["blocks"][name] = 1.0 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(10 + i), leaf.shape)
    params["lnf_g"] = 1.0 + 0.2 * jax.random.normal(jax.random.PRNGKey(20), (64,))
    # a livelier router than std 0.02 gives at hidden 64
    params["blocks"]["moe"]["gate"]["wg"] = params["blocks"]["moe"]["gate"]["wg"] * 20
    return model, params


def _ids(n, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, V)


def olmoe_logits(params, ids, **kw):
    """The reference's forward pass, compiled once a set of its keywords."""
    return jitted(reference_olmoe.olmoe_logits, **kw)(params, ids)


@pytest.fixture(scope="module")
def want40(tiny):
    """The reference's logits of the 40 tokens both dense-path tests compare
    against, once a module."""
    with jax.default_matmul_precision("highest"):
        return olmoe_logits(tiny[1], _ids(40), **REF)


def test_config_is_the_published_layer():
    cfg = olmoe_config()
    assert (cfg.n_embd, cfg.n_layer, cfg.n_head, cfg.head_dim) == (2048, 16, 16, 128)
    assert (cfg.moe_num_experts, cfg.moe_top_k, cfg.ffn_dim) == (64, 8, 1024)
    assert cfg.moe_router == "dropless" and cfg.qk_norm and not cfg.use_bias
    assert (cfg.norm, cfg.mlp_type, cfg.position_encoding) == ("rmsnorm", "swiglu", "rope")
    assert cfg.untied_head and cfg.padded_vocab == cfg.vocab_size == 50304
    model = GPT(cfg)
    assert model.num_params() == 6_919_161_856          # 6.92 G
    assert model.num_params(active=True) == 1_282_017_280     # 1.28 G a token
    shapes = jax.eval_shape(GPT(dataclasses.replace(cfg, n_layer=1)).init_params,
                            jax.random.PRNGKey(0))["blocks"]
    assert shapes["moe"]["experts"]["wi"].shape == (1, 64, 2048, 2048)
    assert shapes["moe"]["experts"]["wo"].shape == (1, 64, 1024, 2048)
    assert set(shapes["moe"]["experts"]) == {"wi", "wo"}        # no bias
    assert shapes["q_norm_g"].shape == shapes["k_norm_g"].shape == (1, 2048)


def test_top_k_follows_the_router():
    with pytest.raises(AssertionError, match="gshard"):
        gpt_lib.GPTConfig(moe_num_experts=8, moe_top_k=3)
    with pytest.raises(AssertionError, match="dropless"):
        gpt_lib.GPTConfig(moe_num_experts=8, moe_top_k=9, moe_router="dropless")
    gpt_lib.GPTConfig(moe_num_experts=8, moe_top_k=8, moe_router="dropless")


def test_forward_logits_equal_the_reference(tiny, want40):
    model, params = tiny
    ids, want = _ids(40), want40
    got = model.forward_logits(params, ids[None])[0, :, :V]
    assert float(jnp.abs(got - want).max()) < TOL


def test_the_tolerance_refuses_bf16_and_a_norm_after_the_split(tiny, want40, monkeypatch):
    model, params = tiny
    ids, want = _ids(40), want40
    low = GPT(dataclasses.replace(model.cfg, dtype=jnp.bfloat16))
    gap = float(jnp.abs(low.forward_logits(params, ids[None])[0, :, :V] - want).max())
    assert gap > 50 * TOL, gap

    real_norm, real_project = gpt_lib.rms_norm, gpt_lib._project_qkv

    def per_head(x, g, eps=1e-5):        # RMSNorm over each head's 16 lanes
        B, S, W = x.shape
        return real_norm(x.reshape(B, S, H, W // H), jnp.ones(()), eps).reshape(B, S, W) * g

    def project(*args):                  # q and k are its only rms_norm calls
        with monkeypatch.context() as m:
            m.setattr(gpt_lib, "rms_norm", per_head)
            return real_project(*args)
    monkeypatch.setattr(gpt_lib, "_project_qkv", project)
    gap = float(jnp.abs(model.forward_logits(params, ids[None])[0, :, :V] - want).max())
    assert gap > 50 * TOL, gap


def test_loss_and_gradients_equal_the_reference(tiny):
    model, params = tiny
    ids, labels = _ids(33, seed=4), _ids(33, seed=5)
    mean = lambda p: olmoe_loss_sum(p, ids, labels, **REF) / ids.shape[0]
    want, want_g = jax.value_and_grad(mean)(params)
    got, got_g = jax.value_and_grad(
        lambda p: model(p, (ids[None], labels[None]), None, True))(params)
    assert float(abs(got - want)) < TOL
    flat_w = dict(jax.tree_util.tree_leaves_with_path(want_g))
    checked = 0
    for path, g in jax.tree_util.tree_leaves_with_path(got_g):
        name = jax.tree_util.keystr(path)
        if name.endswith(("_b']", "wpe']")):       # leaves a bias-free model never reads
            assert not np.asarray(g).any(), name
            continue
        np.testing.assert_allclose(np.asarray(g), np.asarray(flat_w[path]),
                                   atol=TOL, rtol=1e-3, err_msg=name)
        assert np.asarray(g).any(), name
        checked += 1
    assert checked == 12       # wte, lm_head, lnf_g and nine leaves a block


def test_aux_loss_is_the_coefficient_times_the_balance_term(tiny):
    model, params = tiny
    ids, labels = _ids(33, seed=4), _ids(33, seed=5)
    with_aux = GPT(dataclasses.replace(model.cfg, moe_aux_coeff=0.5))
    batch = (ids[None], labels[None])
    _, aux = gpt_lib.gpt_forward(model.cfg, params, ids[None], with_aux=True)
    assert float(aux) > 1.9          # two layers, each at least 1.0 (even)
    np.testing.assert_allclose(float(with_aux(params, batch, None, True)),
                               float(model(params, batch, None, True)) + 0.5 * float(aux),
                               rtol=1e-6)


SERVING = {"block_size": 8, "num_blocks": 64, "max_batch_size": 4,
           "prefill_chunk": 8, "dtype": "float32"}


def test_prefill_in_chunks_then_decode_through_the_engine(tiny):
    """Three prompt chunks (the last short), then the second request's chunk
    in one program with the first's decode row, then both decode beside two
    idle slots: every served token is the reference's best by its full
    forward pass, teacher-forced, within ``TOL`` of logit."""
    model, params = tiny
    eng = deepspeed_tpu.init_serving(model=model, params=params,
                                     config={"serving": SERVING})
    prompts = [list(map(int, _ids(19, seed=6))), list(map(int, _ids(5, seed=7)))]
    futures = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, (12, 7))]
    stats, assigned, land = [], [], eng._land
    # a program's counts come in with its row, in the step that launched it
    # or (prompt left behind its chunk: dispatched ahead) in the next
    eng._land = lambda flight: (land(flight), assigned.append(
        int(eng._expert_counts.sum())))[0]
    while not all(f.done for f in futures):
        stats.append(eng.step())
    assert futures[0].request.prefill_chunks == 3
    assert [s["dispatched_ahead"] for s in stats[:5]] == [0, 1, 1, 1, 0]
    # the one program's counts cover every row that carries a request (the
    # decode rows and the chunk's tokens: k assignments a layer each) and no
    # idle slot, no row past the chunk's tokens
    k, layers = model.cfg.moe_top_k, model.cfg.n_layer
    assert assigned == [(s["decode_batch"] + s["prefill_tokens"]) * k * layers
                        for s in stats]
    assert any(s["decode_batch"] and 0 < s["prefill_tokens"] < 8 for s in stats)
    # the expert counts came back in the token row's fetch: still the one
    # program, with one int32 array (and the arena) as its result
    assert eng.compiled_programs() == 1
    rows = 4 + 8                                  # slots + the chunk's rows
    out = jax.eval_shape(eng._raw_step_fn, eng.params,
                         jnp.zeros((eng._layout.packed_size,), jnp.int32),
                         eng._previous, eng._k_pages, eng._v_pages,
                         eng._tables)[0]
    assert out.shape == (rows + 8,) and out.dtype == jnp.int32
    # no latent projection: the engine relaid nothing and its tree is the
    # caller's, leaf for leaf the same arrays
    assert (eng.relaid_leaves, eng.relaid_bytes) == (0, 0)
    assert all(a is b for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(params)))
    for prompt, f in zip(prompts, futures):
        seq = jnp.asarray(prompt + f.result())
        logits = olmoe_logits(params, seq, **REF)
        for t in range(len(prompt) - 1, len(seq) - 1):
            gap = float(logits[t].max() - logits[t, seq[t + 1]])
            assert gap < TOL, (t, gap)
    assert "moe_load_max_over_mean" not in stats[0], "no row came in yet"
    assert all(s["programs"] == 1 and 1.0 <= s["moe_load_max_over_mean"] <= 8.0
               and 1 <= s["moe_experts_touched"] <= 8 for s in stats[1:])
    eng.close()


def _paged(step, cfg, params, ids, positions, live):
    """One ``gpt_paged_step`` (``step``, jitted) over an empty arena: row
    ``b`` writes to block ``b + 1`` where ``live``, to the trash block 0
    where not."""
    B, S = ids.shape
    arena = jnp.zeros((cfg.n_layer, 8, 8, cfg.n_embd), jnp.float32)
    tables = np.zeros((B, 16), np.int32)
    tables[:, 0] = np.arange(1, B + 1) * live.any(axis=1)
    wb = (np.arange(1, B + 1)[:, None] * live).astype(np.int32)
    wo = (np.arange(S)[None] * live).astype(np.int32)
    logits, _, _, counts = step(
        params, jnp.asarray(ids), jnp.asarray(positions, jnp.int32), arena, arena,
        jnp.asarray(tables), jnp.asarray(wb), jnp.asarray(wo))
    return logits, counts


@pytest.mark.parametrize("router", ["dropless", "gshard"])
def test_rows_without_a_request_and_a_routers_capacity(tiny, router):
    """Idle decode slots and the padding of a prompt chunk run through the
    experts like live rows.  Under the dropless router they change no live
    row's logits and no count; under a router with a capacity they take a
    live token's place in its expert (so ``init_serving`` refuses it)."""
    model, params = tiny
    if router == "gshard":
        cfg = dataclasses.replace(model.cfg, moe_router="gshard", moe_top_k=1,
                                  moe_eval_capacity_factor=0.25, moe_min_capacity=1)
        model = GPT(cfg)
    step = jax.jit(functools.partial(model.paged_step, with_expert_counts=True))
    # decode: slot 3 is live, slots 0..2 idle with one token id or another
    live = np.asarray([[False], [False], [False], [True]])
    runs = [_paged(step, model.cfg, params, np.asarray([[pad], [pad], [pad], [7]]),
                   np.zeros(4), live) for pad in range(0, 40)]
    same = [bool(jnp.array_equal(runs[0][0][3], r[0][3])) for r in runs[1:]]
    # a prompt chunk of 8 with 3 live tokens and padding of one id or another
    live_c = np.arange(8)[None] < 3
    chunk = lambda pad: np.asarray([[11, 12, 13] + [pad] * 5])
    chunks = [_paged(step, model.cfg, params, chunk(pad), np.zeros(1), live_c)
              for pad in range(0, 40)]
    same += [bool(jnp.array_equal(chunks[0][0][0, :3], c[0][0, :3])) for c in chunks[1:]]
    if router == "dropless":
        assert all(same)
        # counts: live rows only, k a live token a layer
        assert int(runs[0][1].sum()) == 1 * 2 * 2 and int(chunks[0][1].sum()) == 3 * 2 * 2
        assert all(bool(jnp.array_equal(runs[0][1], r[1])) for r in runs)
    else:
        assert not all(same)
        with pytest.raises(ValueError, match="dropless"):
            deepspeed_tpu.init_serving(model=model, params=params,
                                       config={"serving": SERVING})


@pytest.mark.parametrize("path", PATHS)
def test_the_paged_step_reads_the_bank_in_place(path, kernels, monkeypatch):
    """A period of ONE layer (the plain scan over three layers), at widths
    the kernel takes: the step that hands ``grouped_matmul`` the stacked
    bank and the layer's index against the step with each layer's bank
    sliced out by hand, bit for bit."""
    cfg = olmoe_config(vocab_size=V, n_positions=128, n_embd=128, n_layer=3,
                       n_head=H, intermediate_size=128, num_experts=8, top_k=2,
                       dtype=jnp.float32, moe_aux_coeff=0.0)
    params = GPT(cfg).init_params(jax.random.PRNGKey(2))
    params["blocks"]["moe"]["gate"]["wg"] = params["blocks"]["moe"]["gate"]["wg"] * 20
    bank_in_place_equals_bank_sliced(cfg, params, path, kernels, monkeypatch)
