"""Beyond-HBM offload, end to end: a toy model whose plain stage-3 step
is REFUSED under a simulated HBM budget (``HBMBudgetError`` at init, not
an OOM mid-step) trains once the tiered offload engine is on — with
bitwise parity against the fully-in-HBM run, a passing
``tools/offload_audit.py`` gate over the run's telemetry, rollback
coherence of the NVMe tier across checkpoint load, and the extended
whole-tree-transfer lint."""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.gpt import GPT, GPTConfig
from deepspeed_tpu.runtime.offload import HBMBudgetError

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CFG = dict(vocab_size=128, n_positions=32, n_embd=64, n_layer=4, n_head=4,
           dtype=jnp.float32, attn_impl="reference")
IDS = np.random.default_rng(0).integers(0, 128, (8, 32)).astype(np.int32)

# between the offloaded layer-window peak (~0.9 MiB for this toy on 8
# devices) and the plain gathered stage-3 peak (~1.2 MiB): plain refuses,
# the window fits
BUDGET = int(1.1 * (1 << 20))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO_ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _engine(telemetry_path=None, **zero_over):
    model = GPT(GPTConfig(**CFG))
    config = {"train_batch_size": 8,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": 3, **zero_over}}
    if telemetry_path:
        config["telemetry"] = {"enabled": True, "jsonl_path": telemetry_path}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(jax.random.key(0)),
        config=config, seed=7)
    return engine


def _steps(engine, n=3):
    losses = []
    for _ in range(n):
        loss = engine.forward(IDS, IDS)
        engine.backward(loss)
        engine.step()
        losses.append(float(np.asarray(loss)))
    return losses


@pytest.mark.usefixtures("offload_on_device")
class TestBeyondHBMProof:
    def test_plain_refused_offload_trains_with_parity_and_audit(self, tmp_path):
        # 1) the budget refuses the plain stage-3 step at init
        with pytest.raises(HBMBudgetError, match="offload_param"):
            _engine(hbm_budget_bytes=BUDGET)

        # 2) the same budget trains with the tiered offload engine on
        tele = str(tmp_path / "telemetry.jsonl")
        off = _engine(telemetry_path=tele, hbm_budget_bytes=BUDGET,
                      offload_param={"device": "nvme",
                                     "nvme_path": str(tmp_path / "nvme"),
                                     "max_in_cpu": 0},
                      offload_optimizer={"device": "nvme",
                                         "nvme_path": str(tmp_path / "nvme")})
        assert off._residency_plan is not None
        assert not off._residency_plan.fits_plain
        assert off._residency_plan.fits_window
        r_off = _steps(off)

        # 3) numeric parity against the fully-in-HBM layered run
        hbm = _engine(overlap_comm=True)
        r_hbm = _steps(hbm)
        assert r_off == r_hbm
        for a, b in zip(jax.tree.leaves(jax.device_get(off.state.params)),
                        jax.tree.leaves(jax.device_get(hbm.state.params))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        # 4) the audit gate passes over the run's telemetry
        off.telemetry.close()
        audit_mod = _load_tool("offload_audit")
        assert audit_mod.main([tele, "--max-stall-frac", "1.0"]) == 0
        staged, _, err = audit_mod.load_records(tele)
        assert err is None
        report = audit_mod.audit(staged, {})
        assert report["bytes_written"] > 0      # params + optimizer staged

    def test_env_budget_override_refuses(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DST_HBM_BUDGET_BYTES", str(BUDGET))
        with pytest.raises(HBMBudgetError):
            _engine()

    def test_budget_too_small_even_for_window(self, tmp_path):
        with pytest.raises(HBMBudgetError, match="window"):
            _engine(hbm_budget_bytes=1 << 10,
                    offload_param={"device": "nvme",
                                   "nvme_path": str(tmp_path / "nvme")})


@pytest.mark.usefixtures("offload_on_device")
class TestOffloadComposesWithCompression:
    """The Frontier-recipe composition: the offload prefetch ring under
    the ZeRO++ wire formats (qwZ quantized gathers, qgZ hierarchical
    reduce-scatter, hpZ secondary shards) — staging must not perturb the
    compressed numerics (bitwise vs the same variant fully in HBM)."""

    VARIANTS = {
        "qwz_int8": {"zero_quantized_weights": True},
        "qgz": {"zero_quantized_gradients": True},
        "hpz": {"zero_quantized_weights": True,
                "zero_quantized_gradients": True,
                "zero_hpz_partition_size": 4},
    }

    @pytest.mark.parametrize("variant", sorted(VARIANTS))
    def test_variant_parity_under_offload(self, tmp_path, variant):
        over = self.VARIANTS[variant]
        off = _engine(offload_param={"device": "nvme",
                                     "nvme_path": str(tmp_path / "nvme")},
                      **over)
        hbm = _engine(overlap_comm=True, **over)
        r_off = _steps(off, n=2)
        r_hbm = _steps(hbm, n=2)
        assert r_off == r_hbm
        for a, b in zip(jax.tree.leaves(jax.device_get(off.state.params)),
                        jax.tree.leaves(jax.device_get(hbm.state.params))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.usefixtures("offload_on_device")
class TestRollbackCoherence:
    def test_nvme_tier_resynced_after_checkpoint_load(self, tmp_path):
        """Chunks staged from an abandoned trajectory must never be read
        back: after load_checkpoint the param tier is re-persisted from
        the restored params and training continues in lockstep with an
        uninterrupted reference run."""
        nvme = str(tmp_path / "nvme")
        ckpt = str(tmp_path / "ckpt")
        off = _engine(offload_param={"device": "nvme", "nvme_path": nvme},
                      offload_optimizer={"device": "nvme", "nvme_path": nvme})
        ref = _engine(overlap_comm=True)
        _steps(off, n=2)
        _steps(ref, n=2)
        off.save_checkpoint(ckpt, tag="t2")
        _steps(off, n=2)                      # the abandoned trajectory
        off.load_checkpoint(ckpt, tag="t2")   # rollback -> _resync_offload_state
        r_off = _steps(off, n=2)
        r_ref = _steps(ref, n=2)
        assert r_off == r_ref
        # the re-persisted tier serves reads: a fresh swap-in round-trips
        off.param_swapper.store.drain()
        assert off.param_swapper.stats()["bytes_written"] > 0


class TestTransferLint:
    """The extended ``tools/check_overlap_structure.py``: whole-tree
    host→device transfers inside the layered scopes are violations; the
    per-slice staging site in ``comm/compression/layered.py`` is outside
    every checked scope."""

    def test_repo_is_clean(self):
        lint = _load_tool("check_overlap_structure")
        assert lint.check_files() == []

    def test_detects_whole_tree_transfer(self, tmp_path):
        lint = _load_tool("check_overlap_structure")
        bad = tmp_path / "bad.py"
        bad.write_text(
            "import jax\n"
            "def _build_layered_step(tree):\n"
            "    return jax.device_put(tree, None)\n")
        out = lint.check_files([(str(bad), "_build_layered_step")])
        assert len(out) == 1 and "host-to-device transfer" in out[0]

    def test_pragma_sanctions_transfer(self, tmp_path):
        lint = _load_tool("check_overlap_structure")
        ok = tmp_path / "ok.py"
        ok.write_text(
            "import jax\n"
            "def _build_layered_step(tree):\n"
            "    return jax.device_put(tree, None)  # offload-transfer ok\n")
        assert lint.check_files([(str(ok), "_build_layered_step")]) == []

    def test_gather_lint_still_fires(self, tmp_path):
        lint = _load_tool("check_overlap_structure")
        bad = tmp_path / "bad.py"
        bad.write_text(
            "from jax import lax\n"
            "def _build_layered_step(x):\n"
            "    return lax.all_gather(x, 'fsdp')\n")
        out = lint.check_files([(str(bad), "_build_layered_step")])
        assert len(out) == 1 and "gather primitive" in out[0]
