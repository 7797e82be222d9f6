"""Pinned-host regather (``engine._device_view``): host-tier leaves are
copied into device memory inside the compiled step and stream back to the
host tier through out_shardings — the XLA host-offload idiom the ZeRO-
Offload path rides.  The memory-kind move itself needs hardware with a
``pinned_host`` space (TPU); those tests skip on CPU, where the
``offload_on_device`` fixture keeps device placement explicitly and the
no-retrace discipline is covered instead."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.runtime.engine import DeepSpeedEngine


def _mesh():
    return Mesh(np.array(jax.devices()), ("x",))


def _pinned_host_supported():
    try:
        s = NamedSharding(_mesh(), P(), memory_kind="pinned_host")
        jax.jit(lambda: jnp.zeros((8,), jnp.float32), out_shardings=s)()
        return True
    except Exception:   # noqa: BLE001 — backend capability probe
        return False


class TestDeviceView:
    def test_passthrough_without_pinned_host(self):
        """Default-kind leaves come back untouched — the view must not
        insert copies for state that already lives on device."""
        mesh = _mesh()
        s = NamedSharding(mesh, P())
        tree = {"w": jax.device_put(jnp.arange(8.0), s)}
        out = DeepSpeedEngine._device_view(None, tree, {"w": s})
        assert out["w"] is tree["w"]

    def test_non_sharding_leaves_pass_through(self):
        tree = {"w": jnp.arange(4.0)}
        out = DeepSpeedEngine._device_view(None, tree, {"w": object()})
        assert out["w"] is tree["w"]

    @pytest.mark.skipif(not _pinned_host_supported(),
                        reason="backend has no pinned_host memory space")
    def test_pinned_host_roundtrip_residency_no_retrace(self):
        """Host-tier leaves: device view inside jit, result streamed back
        to pinned_host by out_shardings, and ONE compiled program serves
        repeated calls (a retrace would hide a sharding/memory-kind leak
        in the carry)."""
        mesh = _mesh()
        host = NamedSharding(mesh, P(), memory_kind="pinned_host")
        x = jax.device_put(np.arange(16.0, dtype=np.float32), host)
        assert x.sharding.memory_kind == "pinned_host"

        def step(t):
            v = DeepSpeedEngine._device_view(None, t, {"w": host})
            return {"w": v["w"] * 2.0}

        f = jax.jit(step, out_shardings={"w": host})
        y = f({"w": x})
        np.testing.assert_array_equal(np.asarray(y["w"]),
                                      np.arange(16.0) * 2)
        # round-trip residency: the updated leaf landed back on the host tier
        assert y["w"].sharding.memory_kind == "pinned_host"
        y = f(y)
        y = f(y)
        assert f._cache_size() == 1


@pytest.mark.usefixtures("offload_on_device")
class TestOffloadParamCpuFallback:
    """With the host tier explicitly kept in device memory
    (``offload_on_device``) training must be untouched (bitwise) and the
    layered step offload implies must not retrace."""

    def _engine(self, **zero_over):
        from deepspeed_tpu.models.gpt import GPT, GPTConfig
        cfg = GPTConfig(vocab_size=128, n_positions=32, n_embd=64, n_layer=4,
                        n_head=4, dtype=jnp.float32, attn_impl="reference")
        model = GPT(cfg)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=model.init_params(jax.random.key(0)),
            config={"train_batch_size": 8,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 3, **zero_over}},
            seed=7)
        return engine

    def _steps(self, engine, n=3):
        ids = np.random.default_rng(0).integers(0, 128, (8, 32)).astype(np.int32)
        losses = []
        for _ in range(n):
            loss = engine.forward(ids, ids)
            engine.backward(loss)
            engine.step()
            losses.append(float(np.asarray(loss)))
        return losses

    def test_roundtrip_parity_and_no_retrace(self):
        plain = self._engine(overlap_comm=True)
        offl = self._engine(offload_param={"device": "cpu"})
        assert offl._cc["offload"] is True
        r_plain = self._steps(plain)
        r_off = self._steps(offl)
        assert r_plain == r_off
        for a, b in zip(jax.tree.leaves(jax.device_get(plain.state.params)),
                        jax.tree.leaves(jax.device_get(offl.state.params))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # offload_param implied the layered schedule; one program serves it
        assert offl._cc["layered"] is True
        assert offl._layered_step._cache_size() == 1
