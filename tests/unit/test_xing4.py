"""The Xing4.0 stack (the DeepSeek-V3 line's layer, latent attention over
every cached key and a whole expert bank behind a dense lead, on FOUR
RESIDUAL STREAMS a token mixed by manifold-constrained hyper-connections round
both sublayers) through the paged step and the engine against the plain
reference (``benchmarks/lib/reference_xing4.py``), at a tiny size with seeded
weights on the CPU: 4 heads of 16 + 8 key lanes and 16 value lanes, hidden 64
in 4 streams, a dense layer and two layers of 8 experts with 2 a token, YaRN
over 32 original positions so that every sequence here crosses them.

Tolerances.  Program and reference both compute in float32 under
``default_matmul_precision("highest")`` and differ only in the order of their
sums, a few 1e-7 of logit at these sizes; ``TOL`` is 2e-5 (the issue asks
1e-4), and each of a Sinkhorn-Knopp projection stopped early, another eps,
another clamp and ``Hpost`` without its factor 2 is held to miss it fifty
times over.  The stacks that share the walk (Mistral-Small-4, DeepSeek-V3.2)
lower to the text they lowered to before the streams were written, and give
the logits they gave.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import reference_xing4 as ref
from benchmarks.lib.reference_mistral4 import _rms
from deepspeed_tpu.models import gpt
from deepspeed_tpu.models.gpt import GPT, HyperSpec, xing4_config
from tests.unit import serving_helpers
from tests.unit.serving_helpers import Driver, dense_path_refusal, served_logits, served_tokens

TOL = 2e-5
V = 512
WIDTHS = dict(vocab_size=V, n_positions=4096, n_embd=64, n_layer=3, n_head=4, head_dim=24,
              q_lora_rank=32, kv_lora_rank=128, qk_rope_dim=8, v_head_dim=16,
              intermediate_size=96, moe_intermediate_size=32, num_experts=8, top_k=2,
              dense_layers=1, rope_yarn=(4.0, 32, 32.0, 1.0, 1.0, 1.0, 0.0))
REF = dict(n_head=4, q_lora_rank=32, kv_lora_rank=128, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=16, top_k=2, n_routed_experts=8,
           first_k_dense_replace=1, routed_scaling_factor=2.0, vocab_size=V,
           rope_theta=10000, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
           mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30, q_block=32)
ROPE = dict(factor=4.0, original_max_position_embeddings=32, beta_fast=32.0,
            beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
SLOTS, CHUNK, BS, MB = 3, 8, 8, 10
SERVING = {"block_size": BS, "num_blocks": 40, "max_batch_size": SLOTS,
           "prefill_chunk": CHUNK, "dtype": "float32"}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _loud(params, rng):
    """Norm gains off 1, a router that prefers some experts with a bias that
    changes who is chosen, and every map's ``b`` moved by a deviation of 0.5,
    so that each is seen."""
    blocks = dict(params["blocks"])
    for name in ("ln1_g", "ln2_g", "q_a_norm_g", "kv_a_norm_g"):
        blocks[name] = jnp.asarray(rng.uniform(0.7, 1.3, blocks[name].shape), jnp.float32)
    for sub in ("attn", "mlp"):
        b = blocks[f"hc_{sub}_b"]
        blocks[f"hc_{sub}_b"] = b + jnp.asarray(rng.normal(0, 0.5, b.shape), jnp.float32)
    gate = blocks["moe"]["gate"]
    blocks["moe"] = dict(blocks["moe"], gate={
        "wg": gate["wg"] * 20,
        "bias": jnp.asarray(rng.normal(0, 0.3, gate["bias"].shape), jnp.float32)})
    return dict(params, blocks=blocks,
                lnf_g=jnp.asarray(rng.uniform(0.7, 1.3, params["lnf_g"].shape), jnp.float32))


@pytest.fixture(scope="module")
def loud():
    model = GPT(xing4_config(**WIDTHS, dtype="float32"))
    return model, _loud(model.init_params(jax.random.PRNGKey(0)), np.random.default_rng(3))


def reference_logits(params, seq, **other):
    ids = np.zeros(-(-len(seq) // 32) * 32, np.int32)
    ids[:len(seq)] = seq
    kw = {**REF, **other}
    fn = serving_helpers.jitted(lambda p, i, **k: ref.xing4_logits(p, i, rope_scaling=ROPE, **k),
                                **kw)
    return np.asarray(fn(params, jnp.asarray(ids)))[:len(seq)]


def _ids(n, seed):
    return np.random.default_rng(seed).integers(0, V, n).astype(np.int32)


driver = lambda model, params: Driver(model, params, slots=SLOTS, chunk=CHUNK,
                                      block_size=BS, blocks_a_slot=MB)
# whole chunks that end on a page border; ragged chunks; every token decoded
# from the second on (the decode rows alone from the first step)
CHUNKS = {"whole": (8, 8, 8, 8), "ragged": (7, 5, 8, 3, 1), "single": (1,)}


@pytest.fixture(scope="module")
def served(loud):
    """One sequence of 60 tokens through the pages: what the wrong models
    below are held against."""
    model, params = loud
    seq = _ids(60, seed=4)
    with jax.default_matmul_precision("highest"):
        return seq, driver(model, params).sequence(seq, CHUNKS["whole"])


# ---- (a) the served logits against the reference's full forward pass ---------- #
@pytest.mark.parametrize("chunks", sorted(CHUNKS))
def test_prefill_then_decode_agree_with_the_reference(loud, chunks):
    model, params = loud
    seq = _ids(60, seed=len(chunks))
    got = driver(model, params).sequence(seq, CHUNKS[chunks])
    want = reference_logits(params, seq)
    assert np.abs(got - want).max() < TOL
    assert np.abs(want).max() > 0.1


def test_a_step_with_decode_rows_and_a_chunk_together(loud):
    """Two slots decode while a third's prompt chunk runs in the same step:
    every live row is the reference's, whichever body of the walk ran it."""
    model, params = loud
    d = driver(model, params)
    a, b, c = _ids(20, 7), _ids(13, 8), _ids(24, 9)
    for slot, seq in ((0, a), (1, b)):
        for start in range(0, len(seq) - 1, CHUNK):
            d.step(chunk=(slot, start, seq[start:min(start + CHUNK, len(seq) - 1)]))
    rows = d.step(decode=[(0, a[-1], len(a) - 1), (1, b[-1], len(b) - 1)],
                  chunk=(2, 0, c[:CHUNK]))
    assert np.abs(rows[0] - reference_logits(params, a)[-1]).max() < TOL
    assert np.abs(rows[1] - reference_logits(params, b)[-1]).max() < TOL
    assert np.abs(rows[SLOTS:SLOTS + CHUNK] - reference_logits(params, c)[:CHUNK]).max() < TOL


WRONG = {"sinkhorn_stopped_after_one": lambda mp: dict(hc_sinkhorn_iters=1),
         "another_eps": lambda mp: dict(hc_eps=1e-2),
         "clamped_at_half": lambda mp: dict(mhc_h_res_clamp_max=0.5),
         "hpost_without_its_two": lambda mp: mp.setattr(
             ref, "stream_maps", lambda *a, _real=ref.stream_maps, **kw: (
                 lambda pre, post, res: (pre, 0.5 * post, res))(*_real(*a, **kw))) or {}}


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
    assert np.abs(got - reference_logits(params, seq, **other)).max() > 50 * TOL


# ---- (b) the maps --------------------------------------------------------------- #
def _logits_of_maps(rng, tokens, n=4):
    """``Hres~`` of ``tokens`` tokens as the seeding makes them: the
    diagonal ahead by 2, a deviation of 1 a token."""
    return (2.0 * np.eye(n)[None] + rng.normal(0, 1.0, (tokens, n, n))).astype(np.float32)


def test_hres_is_doubly_stochastic_within_the_iterations_error():
    m = np.exp(_logits_of_maps(np.random.default_rng(0), 256))
    once = np.asarray(gpt.sinkhorn_knopp(jnp.asarray(m.transpose(1, 2, 0)), 1, 1e-6))
    done = np.asarray(gpt.sinkhorn_knopp(jnp.asarray(m.transpose(1, 2, 0)), 20, 1e-6))
    long = np.asarray(gpt.sinkhorn_knopp(jnp.asarray(m.transpose(1, 2, 0)), 200, 1e-6))
    assert (done > 0).all()
    columns = lambda a: np.abs(a.sum(axis=0) - 1)
    for a in (once, done, long):
        assert np.abs(a.sum(axis=1) - 1).max() < 1e-5          # rows: the last pass
    # columns: the iteration's error, which the 20 the model runs leave at a
    # hundredth for the slowest of 256 matrices and at 1e-4 for the median
    assert columns(once).max() > 0.05 and columns(long).max() < 1e-5
    assert columns(done).max() < 0.02 and np.median(columns(done).max(axis=0)) < 1e-4


@pytest.mark.parametrize("iters", [1, 5, 20])
def test_the_programs_sinkhorn_is_the_references_loop(iters):
    m = np.exp(_logits_of_maps(np.random.default_rng(iters), 64))
    got = np.asarray(gpt.sinkhorn_knopp(jnp.asarray(m.transpose(1, 2, 0)), iters, 1e-6))
    want = np.asarray(jax.vmap(lambda a: ref.sinkhorn(a, iters, 1e-6))(jnp.asarray(m)))
    assert np.abs(got.transpose(2, 0, 1) - want).max() < 1e-6


@pytest.mark.parametrize("sub", ["attn", "mlp"])
def test_hyper_read_gives_the_references_maps(loud, sub):
    model, params = loud
    rng = np.random.default_rng(11)
    p = jax.tree.map(lambda a: a[1], {k: v for k, v in params["blocks"].items()
                                      if k.startswith("hc_")})
    X = jnp.asarray(rng.normal(0, 1.0, (5, 1, 4, 64)), jnp.float32)
    u, (hres, hpost) = gpt.hyper_read(model.cfg, p, sub, X, jnp.float32)
    leaves = [p[f"hc_{sub}_{leaf}"] for leaf in ("phi", "b", "alpha")]
    pre, post, res = jax.vmap(lambda Xt: ref.stream_maps(
        Xt, *leaves, iters=20, eps=1e-6, clamp=(-30, 30)))(X[:, 0])
    assert hres.shape == (5, 1, 4, 4) and hpost.shape == (5, 1, 4)
    assert np.abs(np.asarray(hres[:, 0]) - np.asarray(res)).max() < 1e-6
    assert np.abs(np.asarray(hpost[:, 0]) - np.asarray(post)).max() < 1e-6
    assert np.abs(np.asarray(u[:, 0]) - np.einsum("tn,tnc->tc", pre, X[:, 0])).max() < 1e-5
    # the token's own part is at work: the maps differ from token to token
    assert np.asarray(hres).std(axis=0).max() > 0.02
    f = jnp.asarray(rng.normal(0, 1.0, (5, 1, 64)), jnp.float32)
    want = np.einsum("tij,tjc->tic", res, X[:, 0]) + np.einsum("ti,tc->tic", post, f[:, 0])
    assert np.abs(np.asarray(gpt.hyper_write(X, (hres, hpost), f))[:, 0] - want).max() < 1e-5


@pytest.mark.parametrize("sub", ["attn", "mlp"])
def test_the_maps_are_float32_whatever_the_streams_type(loud, sub):
    """bf16 streams and bf16 leaves, as the cell serves them: the maps come
    out float32 and are the reference's over the same rounded values to
    float32's own error; computed in bf16 they stand a thousand times
    further off (what the cell's limit on the first sublayer's maps
    refuses)."""
    model, params = loud
    bf16 = lambda a: a.astype(jnp.bfloat16)
    p = jax.tree.map(lambda a: bf16(a[1]), {k: v for k, v in params["blocks"].items()
                                            if k.startswith(f"hc_{sub}_")})
    X = bf16(jnp.asarray(np.random.default_rng(13).normal(0, 1.0, (9, 1, 4, 64))))
    got = gpt.hyper_maps(model.cfg, p, sub, X)
    want = jax.vmap(lambda Xt: ref.stream_maps(
        Xt, *(p[f"hc_{sub}_{leaf}"].astype(jnp.float32) for leaf in ("phi", "b", "alpha")),
        iters=20, eps=1e-6, clamp=(-30, 30)))(X[:, 0].astype(jnp.float32))
    low = gpt.hyper_maps(model.cfg, p, sub, X, compute=jnp.bfloat16)
    for g, w, l in zip(got, want, low):
        assert g.dtype == jnp.float32 and l.dtype == jnp.bfloat16
        assert np.abs(np.asarray(g[:, 0]) - np.asarray(w)).max() < 1e-5
        assert np.abs(np.asarray(l[:, 0], np.float32) - np.asarray(w)).max() > 1e-3


def test_the_seeded_maps_read_evenly_write_once_and_stay_near_the_identity():
    """What ``_init_hyper`` seeds, with ``phi`` taken out: an even read, a
    unit write and 0.71 on ``Hres``'s diagonal; and with it, a deviation of
    about 1 in every logit a token."""
    cfg = xing4_config(**WIDTHS, dtype="float32")
    p = gpt._init_hyper(cfg, jax.random.PRNGKey(1), "attn")
    assert p["hc_attn_phi"].shape == (4 * 64, 24) and p["hc_attn_b"].shape == (24,)
    X = jnp.asarray(np.random.default_rng(2).normal(0, 1.0, (512, 1, 4, 64)), jnp.float32)
    quiet = dict(p, hc_attn_phi=jnp.zeros_like(p["hc_attn_phi"]))
    u, (hres, hpost) = gpt.hyper_read(cfg, quiet, "attn", X, jnp.float32)
    assert np.abs(np.asarray(u) - np.asarray(X).mean(axis=2)).max() < 1e-5
    assert np.abs(np.asarray(hpost) - 1.0).max() < 1e-6
    assert np.abs(np.asarray(hres)[..., np.arange(4), np.arange(4)] - 0.711).max() < 1e-3
    raw = np.asarray(p["hc_attn_alpha"])[0] * (
        np.asarray(X).reshape(512, -1) @ np.asarray(p["hc_attn_phi"]))
    assert 0.8 < raw.std() < 1.2


# ---- (c) the streams' entry and exit ---------------------------------------------- #
def test_the_streams_enter_as_copies_and_leave_as_their_sum(loud):
    """With every sublayer's write shut (``Hpost = 2 sigmoid(-1e9) = 0``) the
    streams stay the four copies of the embedding they entered as (``Hres``'s
    rows sum to 1), and the logits are the head of the norm of their SUM."""
    model, params = loud
    blocks = dict(params["blocks"])
    for sub in ("attn", "mlp"):
        blocks[f"hc_{sub}_b"] = blocks[f"hc_{sub}_b"].at[:, 4:8].set(-1e9)
        blocks[f"hc_{sub}_alpha"] = blocks[f"hc_{sub}_alpha"].at[:, 1].set(0.0)
    shut = dict(params, blocks=blocks)
    seq = _ids(12, 5)
    got = driver(model, shut).sequence(seq, (8, 3))
    e = 4.0 * np.asarray(params["wte"])[seq]
    want = np.asarray(_rms(jnp.asarray(e), params["lnf_g"], 1e-6)) @ np.asarray(
        params["lm_head"]).T
    assert np.abs(got - want[:, :got.shape[1]]).max() < TOL
    assert np.abs(got - reference_logits(shut, seq)).max() < TOL


# ---- (d) through the engine --------------------------------------------------------- #
def test_the_engine_serves_the_references_tokens(loud):
    model, params = loud
    prompt = _ids(29, 6)
    tokens, got, stats, _ = served_logits(model.cfg, params, prompt, 6, SERVING, vocab=V)
    seq = np.concatenate([prompt, tokens]).astype(np.int32)
    want = reference_logits(params, seq)
    assert np.abs(got - want[:len(got)]).max() < TOL
    assert tokens == want[len(prompt) - 1:len(seq) - 1].argmax(-1).tolist()
    assert sum(s["prefill_tokens"] for s in stats) == len(prompt)


@pytest.mark.parametrize("chunks", sorted(CHUNKS))
def test_the_serving_tree_serves_the_canonical_trees_logits(loud, chunks):
    """``paged_step`` over the tree the engine keeps (``q_b_w``, ``kv_b_w``
    and ``kv_a_w`` transposed, :func:`gpt.serving_params`) against the
    canonical tree, four streams a token, a prompt in chunks then decode
    rows: the same tokens, the same logits.  Not to the bit on the CPU, whose
    dot sums a transposed operand's products in another order; the file's
    tolerance."""
    model, params = loud
    tree, relaid = model.serving_params(params)
    assert sorted(relaid) == ["kv_a_w", "kv_b_w", "q_b_w"]
    seq = _ids(60, seed=8)
    got = driver(model, tree).sequence(seq, CHUNKS[chunks])
    want = driver(model, params).sequence(seq, CHUNKS[chunks])
    assert (got.argmax(-1) == want.argmax(-1)).all()
    assert np.abs(got - want).max() < TOL and np.abs(want).max() > 0.1


def test_the_engine_holds_each_relaid_leaf_once(loud):
    model, params = loud
    _, eng = served_tokens(model, params, [_ids(9, 5)], [3], **SERVING)
    names = ("q_b_w", "kv_b_w", "kv_a_w")
    assert eng.relaid_leaves == 3 and not set(names) & set(eng.params["blocks"])
    assert eng.relaid_bytes == sum(params["blocks"][k].nbytes for k in names)
    assert eng.params["blocks"]["hc_attn_phi"] is params["blocks"]["hc_attn_phi"]


def test_the_parameter_count_is_the_trees(loud):
    model, params = loud
    zeros = sum(a.size for k, a in params["blocks"].items() if k in ("ln1_b", "ln2_b", "out_b"))
    held = sum(a.size for a in jax.tree.leaves(params)) - zeros - params["lnf_b"].size
    assert model.num_params() == held
    assert params["blocks"]["hc_attn_phi"].shape == (3, 4 * 64, 24)
    assert params["blocks"]["hc_mlp_alpha"].shape == (3, 3)
    specs = model.partition_specs()["blocks"]
    assert jax.tree.structure(specs) == jax.tree.structure(
        jax.tree.map(lambda a: 0, params["blocks"]))


# ---- (e) what is refused -------------------------------------------------------------- #
@pytest.mark.parametrize("path", ["forward", "generate", "loss"])
def test_the_dense_paths_refuse_the_stack_by_mechanism(loud, path):
    model, params = loud
    said = dense_path_refusal(model, params, path, _ids(12, 1))
    assert "4 streams" in said and "Sinkhorn-Knopp" in said and "init_serving" in said


def test_the_pipeline_engines_block_refuses_the_streams():
    layer = gpt.GPTBlockLayer(gpt.llama_config(n_embd=64, n_layer=2, n_head=4, hyper=(4,)))
    with pytest.raises(NotImplementedError, match="4 streams"):
        layer({}, jnp.zeros((1, 4, 64)))


@pytest.mark.parametrize("wrong", [
    dict(hyper=(1,)), dict(hyper=(4, 0)), dict(block_type="parallel"),
    dict(scan_layers=False, dense_layers=0),
    dict(layer_pattern=tuple(gpt.LayerKind(None, True, "linear") for _ in range(3)))])
def test_the_configuration_refuses_what_is_not_written(wrong):
    with pytest.raises(AssertionError):
        xing4_config(**{**WIDTHS, **wrong})


def test_the_builder_is_deepseeks_without_indexer_or_groups():
    cfg = xing4_config()
    assert cfg.hyper == HyperSpec(4, 20, 1e-6, -30.0, 30.0) and cfg.indexer is None
    assert (cfg.n_embd, cfg.n_layer, cfg.n_head, cfg.head_dim) == (3584, 40, 32, 192)
    assert (cfg.moe_num_experts, cfg.moe_top_k, cfg.moe_dense_layers) == (64, 4, 2)
    assert (cfg.moe_n_group, cfg.moe_topk_group, cfg.moe_route_scale) == (1, 1, 2.0)
    assert cfg.rope_yarn.factor == 64.0 and cfg.cache_lanes == (640,)
    assert gpt.deepseek_v32_config(n_layer=4, vocab_size=512).hyper is None


# ---- (f) the stacks that share the walk ------------------------------------------------ #
SHARED = {
    "mistral4": (lambda: gpt.mistral4_config(
        vocab_size=500, n_positions=4096, n_embd=64, n_layer=2, n_head=4, head_dim=32,
        q_lora_rank=48, kv_lora_rank=128, qk_rope_dim=16, v_head_dim=24,
        intermediate_size=32, num_experts=8, top_k=2,
        rope_yarn=(16.0, 32, 32.0, 1.0, 1.0, 1.0, 0.1), dtype=jnp.float32),
                 [0.11630982905626297, -0.02822587825357914, 0.10883866250514984]),
    "deepseek_v32": (lambda: gpt.deepseek_v32_config(
        vocab_size=500, n_positions=4096, n_embd=64, n_layer=3, n_head=4, head_dim=24,
        q_lora_rank=32, kv_lora_rank=128, qk_rope_dim=8, v_head_dim=16,
        intermediate_size=96, moe_intermediate_size=32, num_experts=8, top_k=2, n_group=2,
        topk_group=1, dense_layers=1, indexer=(4, 16, 24), experts_held=(0, 4),
        rope_yarn=(4.0, 32, 32.0, 1.0, 1.0, 1.0, 0.0), dtype=jnp.float32),
                     [0.0578722320497036, -0.12913422286510468, 0.08265924453735352])}


@pytest.mark.parametrize("stack", sorted(SHARED))
def test_a_stack_without_streams_is_the_program_it_was(stack):
    """Tiny seeded Mistral-Small-4 and DeepSeek-V3.2 steps: three of their
    logits as the parent commit ``e76fa13`` served them, before the streams
    were written, to the bit on the machine that wrote them (another CPU's
    vector width may reorder a sum: 1e-6)."""
    make, logits = SHARED[stack]
    model = GPT(make())
    params = model.init_params(jax.random.PRNGKey(0))
    with jax.default_matmul_precision(None):
        d = Driver(model, params, slots=2, chunk=8, block_size=8, blocks_a_slot=6)
        got = d.sequence(np.random.default_rng(5).integers(0, 500, 30).astype(np.int32),
                         (8, 8, 5))
    assert np.abs(got[[3, 20, 29], 7] - np.asarray(logits)).max() < 1e-6
