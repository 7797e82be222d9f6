"""GPT model family tests: forward shapes, loss sanity, TP/ZeRO-3 sharded
training on the 8-device CPU mesh, the two layouts of the blocks and the two
walks of a stacked layout (``layer_walk``): equal, and each where the rule
puts it."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import gpt as gpt_lib
from deepspeed_tpu.models.gpt import (GPT, LayerKind, gpt_config, gpt_forward,
                                      gpt_loss, init_gpt_params)
from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.parallel.mesh import MeshSpec


def tiny_cfg(**kw):
    base = dict(attn_impl="reference")
    base.update(kw)
    return gpt_config("tiny", **base)


def test_forward_shape_and_loss():
    cfg = tiny_cfg()
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    ids = jnp.zeros((2, 32), jnp.int32)
    logits = gpt_forward(cfg, params, ids)
    assert logits.shape == (2, 32, cfg.padded_vocab)
    loss = gpt_loss(cfg, params, ids, ids, train=False)
    # near-uniform at init → loss ≈ ln(vocab)
    assert 0.5 * np.log(cfg.vocab_size) < float(loss) < 2.0 * np.log(cfg.vocab_size)


def test_scan_matches_unrolled():
    cfg_s = tiny_cfg(scan_layers=True, dtype=jnp.float32)
    cfg_u = tiny_cfg(scan_layers=False, dtype=jnp.float32)
    ps = init_gpt_params(cfg_s, jax.random.PRNGKey(1))
    # restack scanned params into the unrolled layout
    pu = dict(ps)
    pu["blocks"] = {f"h{i}": jax.tree.map(lambda x: x[i], ps["blocks"])
                    for i in range(cfg_s.n_layer)}
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, cfg_s.vocab_size)
    a = gpt_forward(cfg_s, ps, ids)
    b = gpt_forward(cfg_u, pu, ids)
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("stage", [0, 3])
def test_gpt_trains_with_tp_and_zero(stage):
    """TP=2 × fsdp=2 × data=2 mesh; loss must go down on a memorization task."""
    spec = MeshSpec(data=2, fsdp=2, tensor=2, device_count=8)
    mesh = spec.build(jax.devices()[:8])
    cfg = tiny_cfg(n_embd=64, n_head=2, n_layer=2, vocab_size=256)
    model = GPT(cfg)
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adam", "params": {"lr": 3e-3}},
        "zero_optimization": {"stage": stage},
        "bf16": {"enabled": True},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config, mesh=mesh)
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 8, 32), 0, cfg.vocab_size)
    losses = [float(engine.train_batch(batch=(ids, ids))) for _ in range(8)]
    assert losses[-1] < losses[0] * 0.8, f"no learning: {losses}"


def test_remat_matches():
    # float32: remat is also the other WALK of the layers (``layer_walk``), and
    # in bf16 two programs that fuse differently round differently
    cfg_a = tiny_cfg(remat=False, dtype=jnp.float32)
    cfg_b = tiny_cfg(remat=True, dtype=jnp.float32)
    p = init_gpt_params(cfg_a, jax.random.PRNGKey(3))
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, cfg_a.vocab_size)

    ga = jax.grad(lambda p: gpt_loss(cfg_a, p, ids, ids, train=False))(p)
    gb = jax.grad(lambda p: gpt_loss(cfg_b, p, ids, ids, train=False))(p)
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


# --------------------------------------------------------------------------- #
# The two walks of the stacked layers (``gpt.layer_walk``)
# --------------------------------------------------------------------------- #
WALK_CASES = {
    "plain": (dict(), dict()),
    "dropout": (dict(dropout=0.1), dict()),
    # theta 0: the last block is dropped for certain, the first by a coin
    "pld": (dict(), dict(pld_theta=jnp.float32(0.0))),
    "random_ltd": (dict(ltd_keep=8), dict()),
    "two_kinds": (dict(n_layer=4, layer_pattern=(LayerKind(None, True),
                                                 LayerKind(4, True))), dict()),
    "moe_with_aux": (dict(moe_num_experts=4, moe_top_k=1), dict()),
}


def _layer_whiles(lowered) -> int:
    """The ``while`` ops of a lowered program whose name stack ends in the
    scope ``blocks`` (``transpose(jvp(blocks))/while``): the walk's loops."""
    stacks = re.findall(r'loc\("([^"]*)/while"', lowered.as_text(debug_info=True))
    last = [s.split("/")[-1] for s in stacks]
    return sum(c[c.rfind("(") + 1:].split(")")[0] == "blocks" for c in last)


@pytest.mark.parametrize("case", list(WALK_CASES))
def test_the_two_walks_give_one_loss_and_one_gradient(case, monkeypatch):
    """float32 on the CPU: the loss to the bit, every gradient leaf within
    1e-6 (seen: 6e-8; the two programs sum a layer's gradient into the stack
    in another order).  The scan side is the rule's own function answering
    "scan", as it does under remat: the configuration stays the same."""
    over, kw = WALK_CASES[case]
    cfg = tiny_cfg(dtype=jnp.float32, **over)
    assert gpt_lib.layer_walk(cfg) == "unrolled"
    p = init_gpt_params(cfg, jax.random.PRNGKey(3))
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, cfg.vocab_size)

    def side():
        step = jax.jit(jax.value_and_grad(lambda p: gpt_loss(
            cfg, p, ids, ids, rng=jax.random.PRNGKey(5), train=True, **kw)))
        return step(p), _layer_whiles(step.lower(p))

    (loss_u, grad_u), whiles_u = side()
    monkeypatch.setattr(gpt_lib, "layer_walk", lambda cfg: "scan")
    (loss_s, grad_s), whiles_s = side()
    assert (whiles_u, whiles_s) == (0, 2)       # the forward's loop and its transpose
    assert float(loss_u) == float(loss_s)
    if case == "pld":
        assert float(loss_u) != float(gpt_loss(cfg, p, ids, ids, train=False))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grad_u),
                            jax.tree.leaves(grad_s)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=0,
                                   err_msg=jax.tree_util.keystr(path))


def _train_engine(remat=False, **zero):
    model = GPT(tiny_cfg(n_layer=4, dtype=jnp.float32, remat=remat))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": zero or {"stage": 0}}, seed=7)
    return engine


def _fused(remat):
    engine = _train_engine(remat)
    ids = np.zeros((1, 8, 16), np.int32)
    engine.train_batch(batch=(ids, ids))
    carry = (engine.state.params, engine.state.opt_state, engine.state.scaler,
             engine.state.skipped)
    return engine, "fused", engine._fused_step.lower(
        carry, jax.tree.map(jnp.asarray, (ids, ids)), engine._rng)


def _layered():
    engine = _train_engine(stage=3, overlap_comm=True)
    ids = np.zeros((8, 16), np.int32)
    engine.backward(engine.forward(ids, ids))
    return engine, "layered", engine._layered_step.lower(
        engine.state.params, engine._place_batch((ids, ids)), engine._rng,
        engine.state.scaler.scale)


def _paged():
    from deepspeed_tpu.serving.kv_cache import init_arena
    cfg = tiny_cfg(n_layer=4, dtype=jnp.float32)
    model, rows, BS, MB = GPT(cfg), 4, 8, 4
    shape = jax.ShapeDtypeStruct
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    kp, vp = jax.eval_shape(lambda: init_arena(cfg, 16, BS, dtype=jnp.float32))
    ints = lambda *s: shape(s, jnp.int32)
    return None, None, jax.jit(lambda *a: model.paged_step(*a, chunk=2)).lower(
        params, ints(rows, 1), ints(rows), kp, vp, (ints(rows, MB),),
        (ints(rows, 1),), ints(rows, 1))


@pytest.mark.parametrize("program,walk,whiles", [
    (lambda: _fused(remat=False), "unrolled", 0),
    (lambda: _fused(remat=True), "scan", 2),
    (_layered, "scan", 2),
    (_paged, None, None),
], ids=["fused", "fused_remat", "layered_prefetch", "paged_serve_step"])
def test_the_walk_is_unrolled_where_the_rule_says_and_nowhere_else(program, walk, whiles):
    """Read from the lowered step's text: without remat on unsharded
    parameters no ``while`` stands under ``blocks``; with remat, and where
    the layered prefetch hands the forward its parameters a layer at a time,
    the forward's loop and its transpose do; and the engine's own record
    (``layer_walks``, what ``_built`` read from the traced step) says the
    same.  The paged serve step is another function and keeps its one scan
    over the layers, under no ``blocks`` scope."""
    engine, name, lowered = program()
    if engine is None:
        assert _layer_whiles(lowered) == 0
        assert lowered.as_text().count("stablehlo.while") == 1
        return
    assert _layer_whiles(lowered) == whiles
    assert engine.layer_walks[name] == {"layer_walk": walk, "layer_whiles": whiles}
    engine.close()
    mesh_lib.reset_mesh()
