"""Fused Pallas Adam/AdamW vs the optax chain.

Parity contract (``ops/pallas/fused_optim.py``): BITWISE fp32 equality
jit-to-jit — the kernel replays the exact optax 0.2.x op sequence, and
every path the engine takes is jitted, so the honest comparison is
compiled-vs-compiled (eager optax differs from ANY compiled form by FMA
contraction, which is a property of compilation, not of this kernel).
The kernel has one caller, the NVMe leaf-streamed walk; a compiled step's
Adam is the optax chain whatever the selection rule answers.  Covers the
update composed as the walk composes it, the chain matcher, the config spec
gate, that no compiled step program holds the kernel, the walk (offload
on/off, checkpoint rollback-resync), and the no-retrace invariant."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import deepspeed_tpu
from deepspeed_tpu.models.simple import SimpleModel
from deepspeed_tpu.ops.pallas import fused_optim
from deepspeed_tpu.parallel import mesh as mesh_lib


def make_tree(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"w": jax.random.normal(ks[0], (17, 9), jnp.float32),
            "b": jax.random.normal(ks[1], (8,), jnp.float32),
            "s": jax.random.normal(ks[2], (), jnp.float32)}


def assert_tree_equal(a, b, msg=""):
    for pa, pb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb),
                                      err_msg=msg)


def assert_tree_close(a, b, msg=""):
    """Ulp-tight, for the walk against the whole-tree offload step: both
    contain the same unscale/clip prelude, but the compiler fuses it into a
    different consumer (pallas call vs optax tail) and may FMA-contract it
    differently — a ~1-ulp wobble on the grads entering the update.  The
    kernel itself is bitwise vs jitted optax (see
    ``test_tree_update_bitwise_vs_optax``)."""
    for pa, pb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                   rtol=2e-6, atol=1e-8, err_msg=msg)


# --------------------------------------------------------------------------- #
# kernel vs optax, jit-to-jit
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("variant", ["adamw_static", "adamw_sched",
                                     "adam_nowd"])
def test_tree_update_bitwise_vs_optax(variant):
    if variant == "adamw_static":
        lr, wd = 1e-3, 0.01
        tx = optax.adamw(learning_rate=lr, weight_decay=wd)
        spec = fused_optim.spec_from_config(
            "adamw", {"weight_decay": wd}, lr)
    elif variant == "adamw_sched":
        lr = optax.exponential_decay(1e-3, transition_steps=2,
                                     decay_rate=0.5)
        wd = 0.01
        tx = optax.adamw(learning_rate=lr, weight_decay=wd)
        spec = fused_optim.spec_from_config(
            "adamw", {"weight_decay": wd}, lr)
    else:
        lr = 1e-3
        tx = optax.adam(learning_rate=lr)
        spec = fused_optim.spec_from_config("adam", {}, lr)
    assert spec is not None

    params = make_tree()
    state_ref = state_fused = tx.init(params)
    p_ref = p_fused = params

    @jax.jit
    def unfused(p, s, g):
        u, s2 = tx.update(g, s, p)
        return jax.tree.map(lambda pp, uu: (pp + uu).astype(pp.dtype),
                            p, u), s2

    @jax.jit
    def fused(p, s, g):
        # composed as the NVMe walk composes it (``_fused_offload_step``):
        # the step's scalars once, the kernel a leaf, the counts beside it
        adam_idx, sched_idx = fused_optim.match_adam_chain(s)
        adam = s[adam_idx]
        sc = s[sched_idx].count if sched_idx is not None else None
        scal = jnp.stack([jnp.float32(1.0), jnp.float32(1.0),
                          *fused_optim.step_scalars(spec, adam.count, sc)])
        out = {k: fused_optim.fused_leaf_update(
                   p[k], g[k], adam.mu[k], adam.nu[k], scal, b1=spec["b1"],
                   b2=spec["b2"], eps=spec["eps"], wd=spec["wd"]) for k in p}
        incr = fused_optim._safe_int32_increment
        s2 = list(s)
        s2[adam_idx] = type(adam)(count=incr(adam.count),
                                  mu={k: out[k][1] for k in p},
                                  nu={k: out[k][2] for k in p})
        if sched_idx is not None:
            s2[sched_idx] = type(s[sched_idx])(count=incr(sc))
        return {k: out[k][0] for k in p}, tuple(s2)

    for step in range(4):
        g = make_tree(seed=10 + step)
        p_ref, state_ref = unfused(p_ref, state_ref, g)
        p_fused, state_fused = fused(p_fused, state_fused, g)
        assert_tree_equal(p_ref, p_fused, f"params diverged at step {step}")
        assert_tree_equal(state_ref, state_fused,
                          f"opt state diverged at step {step}")


def test_leaf_update_scalars_fold_unscale_and_clip():
    """The kernel's [inv, clip] SMEM scalars must reproduce the unfused
    ``(g * inv) * factor`` preprocessing.  Tolerance is a few ulp, not
    bitwise: folding the scaling INTO the kernel changes which products
    the compiler may FMA-contract relative to a separate tree.map pass
    (the engine-level tests compare like-shaped programs and stay exact)."""
    spec = fused_optim.spec_from_config("adamw", {"weight_decay": 0.01},
                                        1e-3)
    tx = optax.adamw(learning_rate=1e-3, weight_decay=0.01)
    params = make_tree()
    state = tx.init(params)
    g_raw = make_tree(seed=42)
    inv, factor = jnp.float32(1.0 / 1024.0), jnp.float32(0.37)

    @jax.jit
    def unfused(p, s, g):
        g = jax.tree.map(lambda x: (x.astype(jnp.float32) * inv) * factor, g)
        u, s2 = tx.update(g, s, p)
        return jax.tree.map(lambda pp, uu: (pp + uu).astype(pp.dtype),
                            p, u), s2

    adam = state[0]
    neg_lr, bc1, bc2 = fused_optim.step_scalars(spec, adam.count)
    scal = jnp.stack([inv, factor, neg_lr, bc1, bc2])

    @jax.jit
    def fused_leaf(p, g, mu, nu):
        return fused_optim.fused_leaf_update(
            p, g, mu, nu, scal, b1=spec["b1"], b2=spec["b2"],
            eps=spec["eps"], wd=spec["wd"])

    p_ref, _ = unfused(params, state, g_raw)
    for key in params:
        np_, _, _ = fused_leaf(params[key], g_raw[key],
                               adam.mu[key], adam.nu[key])
        np.testing.assert_allclose(np.asarray(np_),
                                   np.asarray(p_ref[key]),
                                   atol=1e-8, rtol=1e-6,
                                   err_msg=f"leaf {key}")


# --------------------------------------------------------------------------- #
# gates
# --------------------------------------------------------------------------- #
def test_match_adam_chain():
    p = make_tree()
    assert fused_optim.match_adam_chain(
        optax.adamw(1e-3).init(p)) == (0, None)
    sched = optax.exponential_decay(1e-3, 2, 0.5)
    adam_idx, sched_idx = fused_optim.match_adam_chain(
        optax.adamw(sched).init(p))
    assert adam_idx == 0 and sched_idx is not None
    # stateful non-adam links must refuse
    assert fused_optim.match_adam_chain(
        optax.sgd(1e-2, momentum=0.9).init(p)) is None
    assert fused_optim.match_adam_chain(optax.sgd(1e-2).init(p)) is None
    assert fused_optim.match_adam_chain(jnp.zeros((3,))) is None


def test_spec_from_config():
    assert fused_optim.spec_from_config("lamb", {}, 1e-3) is None
    # L2 mode (decay feeds the moments) is different math: refuse
    assert fused_optim.spec_from_config(
        "adam", {"adam_w_mode": False, "weight_decay": 0.01}, 1e-3) is None
    spec = fused_optim.spec_from_config(
        "fusedadam", {"betas": (0.8, 0.99), "eps": 1e-6,
                      "weight_decay": 0.05}, 1e-3)
    assert spec == {"b1": 0.8, "b2": 0.99, "eps": 1e-6, "wd": 0.05,
                    "lr": 1e-3}


# --------------------------------------------------------------------------- #
# engine e2e (single-device mesh: the gate's supported regime)
# --------------------------------------------------------------------------- #
HIDDEN = 32


def one_device_engine(config, seed=11):
    spec = mesh_lib.MeshSpec(device_count=1)
    mesh = spec.build(jax.devices()[:1])
    model = SimpleModel(hidden_dim=HIDDEN, nlayers=2)
    params = model.init_params(jax.random.PRNGKey(0), batch_size=2)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=config, mesh=mesh,
        seed=seed)
    return engine


def batch(step):
    rng = np.random.default_rng(100 + step)
    x = rng.standard_normal((8, HIDDEN)).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.int32)
    return x, y


def run_engine(kernels, fused, config, n=3, hooks=None):
    kernels("fused_adam") if fused else kernels()
    try:
        engine = one_device_engine(config)
        assert engine._fused_opt_active() == fused
        for i in range(n):
            x, y = batch(i)
            loss = engine.forward(x, y)
            engine.backward(loss)
            engine.step()
            if hooks:
                hooks(engine, i)
        return engine
    finally:
        mesh_lib.reset_mesh()


def adamw_config(**zero_over):
    return {"train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 1e-2, "weight_decay": 0.01}},
            "gradient_clipping": 1.0,
            "zero_optimization": {"stage": 3, "param_shard_min_size": 0,
                                  **zero_over}}


class TestEngineParity:

    @pytest.mark.parametrize("mode,zero_over", [
        ("exact", {}),
        ("qwZ", {"zero_quantized_weights": True}),
        ("qgZ", {"zero_quantized_gradients": True}),
        ("hpZ", {"zero_hpz_partition_size": 2}),
    ])
    def test_fused_matches_unfused(self, kernels, mode, zero_over):
        """The gate reaches no compiled step: the same parameters, to the
        bit, after 3 steps under every compression config, whichever way
        the selection rule answers for ``fused_adam``."""
        cfg = adamw_config(**zero_over)
        e_off = run_engine(kernels, fused=False, config=cfg)
        e_on = run_engine(kernels, fused=True, config=cfg)
        assert_tree_equal(e_off.state.params, e_on.state.params,
                          f"params diverged under {mode}")
        assert_tree_equal(e_off.state.opt_state, e_on.state.opt_state,
                          f"opt state diverged under {mode}")

    @pytest.mark.parametrize("variant", ["adamw_static", "adamw_sched",
                                         "adam"])
    def test_no_step_program_holds_the_kernel(self, kernels, variant):
        """Where the rule says kernels run and the mesh is one device, the
        fused train step and the apply step are still the optax chain: no
        ``pallas_call`` in either (the model brings none of its own)."""
        cfg = {"train_micro_batch_size_per_gpu": 8, "gradient_clipping": 1.0,
               "optimizer": {"type": "Adam" if variant == "adam" else "AdamW",
                             "params": {"lr": 1e-2}}}
        if variant != "adam":
            cfg["optimizer"]["params"]["weight_decay"] = 0.01
        if variant == "adamw_sched":
            cfg["scheduler"] = {"type": "WarmupLR",
                                "params": {"warmup_min_lr": 0.0,
                                           "warmup_max_lr": 1e-2,
                                           "warmup_num_steps": 4}}
        kernels("fused_adam")
        try:
            engine = one_device_engine(cfg)
            assert engine._fused_opt_active()
            assert callable(engine._fused_opt_spec["lr"]) == (
                variant == "adamw_sched")
            st = engine.state
            carry = (st.params, st.opt_state, st.scaler, st.skipped)
            x, y = batch(0)
            programs = {
                "fused": (engine._build_fused_step(),
                          (carry, (x[None], y[None]), jax.random.PRNGKey(0))),
                "apply": (engine._build_apply_step(),
                          (st.params, st.opt_state, st.params, st.scaler,
                           st.skipped))}
            for name, (fn, args) in programs.items():
                text = str(jax.make_jaxpr(lambda *a: fn(*a))(*args))
                assert "pallas_call" not in text, name
        finally:
            mesh_lib.reset_mesh()

    def test_gate_rejects_multi_device_mesh(self, kernels):
        kernels("fused_adam")
        model = SimpleModel(hidden_dim=HIDDEN, nlayers=2)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model,
            model_parameters=model.init_params(jax.random.PRNGKey(0),
                                               batch_size=2),
            config={"train_batch_size": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}}})
        assert engine.mesh.size > 1
        assert not engine._fused_opt_active()


def offload_config(tmp_path):
    cfg = adamw_config()
    cfg["zero_optimization"]["offload_optimizer"] = {
        "device": "nvme", "nvme_path": str(tmp_path)}
    return cfg


def swapped_state(engine):
    return engine.optimizer_swapper.swap_in()


@pytest.mark.usefixtures("offload_on_device")
class TestOffloadWalk:

    def test_walk_matches_unfused_offload(self, kernels, tmp_path):
        """The leaf-streamed NVMe walk vs the whole-tree-materializing
        unfused offload step: ulp-tight params AND moments on disk,
        with the state never resident after a step."""
        ready = []

        def check(engine, i):
            assert engine.state.opt_state is None   # swapped back out
            ready.append(engine._fused_offload_walk_ready())

        e_off = run_engine(kernels, fused=False,
                           config=offload_config(tmp_path / "off"))
        e_on = run_engine(kernels, fused=True,
                          config=offload_config(tmp_path / "on"),
                          hooks=check)
        assert all(ready), "fused walk was not active for every step"
        assert_tree_close(e_off.state.params, e_on.state.params,
                          "params diverged (offload walk)")
        assert_tree_close(swapped_state(e_off), swapped_state(e_on),
                          "NVMe-resident moments diverged")

    def test_rollback_resync(self, kernels, tmp_path):
        """Checkpoint save → further steps → load (the PR 5 rollback): the
        loader re-persists the swapped state, and the fused walk must read
        the restored moments — matching an unfused engine driven
        through the identical sequence."""
        def run(fused, sub):
            kernels("fused_adam") if fused else kernels()
            try:
                engine = one_device_engine(
                    offload_config(tmp_path / sub / "nvme"))
                for i in range(2):
                    x, y = batch(i)
                    loss = engine.forward(x, y)
                    engine.backward(loss)
                    engine.step()
                engine.save_checkpoint(str(tmp_path / sub / "ck"))
                for i in range(2, 4):   # the abandoned trajectory
                    x, y = batch(i)
                    loss = engine.forward(x, y)
                    engine.backward(loss)
                    engine.step()
                engine.load_checkpoint(str(tmp_path / sub / "ck"))
                for i in range(4, 6):   # resumed from the rollback point
                    x, y = batch(i)
                    loss = engine.forward(x, y)
                    engine.backward(loss)
                    engine.step()
                return engine
            finally:
                mesh_lib.reset_mesh()

        e_off = run(False, "off")
        e_on = run(True, "on")
        assert_tree_close(e_off.state.params, e_on.state.params,
                          "params diverged after rollback-resync")
        assert_tree_close(swapped_state(e_off), swapped_state(e_on),
                          "moments diverged after rollback-resync")

    def test_no_new_traced_programs_per_step(self, kernels, tmp_path):
        """The per-leaf jits must be traced once per leaf shape, not per
        step — a retrace per step would re-introduce the dispatch cost
        the fusion exists to remove."""
        sizes = {}

        def record(engine, i):
            if i == 1:
                sizes.update({
                    "leaf": engine._fused_leaf_jit._cache_size(),
                    "prelude": engine._fused_prelude_jit._cache_size(),
                    "scalars": engine._fused_scalars_jit._cache_size(),
                    "incr": engine._fused_incr_jit._cache_size()})

        engine = run_engine(kernels, fused=True,
                            config=offload_config(tmp_path), n=5,
                            hooks=record)
        assert sizes["prelude"] == 1 and sizes["scalars"] == 1
        assert engine._fused_leaf_jit._cache_size() == sizes["leaf"]
        assert engine._fused_prelude_jit._cache_size() == 1
        assert engine._fused_scalars_jit._cache_size() == 1
        assert engine._fused_incr_jit._cache_size() == sizes["incr"]
