"""Native async I/O + ZeRO-Infinity swap tests (reference
``tests/unit/ops/aio/test_aio.py`` + ``runtime/swap_tensor`` coverage)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.ops.aio import AIOHandle, AsyncIOBuilder
from deepspeed_tpu.runtime.swap_tensor import (AsyncPartitionedParameterSwapper,
                                               AsyncTensorSwapper,
                                               PartitionedOptimizerSwapper,
                                               get_aio_config)


@pytest.fixture(scope="module")
def handle():
    assert AsyncIOBuilder().is_compatible(), "g++ toolchain required"
    return AIOHandle(num_threads=4)


class TestAIOHandle:
    def test_sync_roundtrip(self, handle, tmp_path):
        x = np.random.default_rng(0).standard_normal(1 << 16).astype(np.float32)
        p = str(tmp_path / "a.bin")
        handle.pwrite(x, p)
        y = np.zeros_like(x)
        handle.pread(y, p)
        np.testing.assert_array_equal(x, y)

    def test_async_overlap_and_wait(self, handle, tmp_path):
        xs = [np.full((1 << 14,), i, np.float32) for i in range(8)]
        ids = [handle.async_pwrite(x, str(tmp_path / f"w{i}.bin"))
               for i, x in enumerate(xs)]
        assert handle.wait() == len(ids)
        z = np.zeros((1 << 14,), np.float32)
        rid = handle.async_pread(z, str(tmp_path / "w5.bin"))
        handle.wait(rid)
        np.testing.assert_array_equal(z, xs[5])

    def test_offsets(self, handle, tmp_path):
        p = str(tmp_path / "off.bin")
        a = np.arange(1024, dtype=np.int64)
        handle.pwrite(a, p)
        part = np.zeros(256, np.int64)
        handle.pread(part, p, offset=256 * 8)
        np.testing.assert_array_equal(part, a[256:512])

    def test_read_error_raises(self, handle, tmp_path):
        with pytest.raises(OSError):
            handle.pread(np.zeros(8, np.float32), str(tmp_path / "missing.bin"))

    def test_builder_surface(self):
        b = AsyncIOBuilder()
        assert b.is_compatible()
        assert b.load() is not None
        assert os.path.exists(b.so_path())

    def test_rebuild_decided_by_source_hash(self, handle, tmp_path, monkeypatch):
        """A copy of the tree keeps no mtimes: the build is reused only when
        the source hash recorded beside it matches, rebuilt otherwise."""
        from deepspeed_tpu.ops.aio import aio_handle as ah
        monkeypatch.setattr(ah, "_BUILD_DIR", str(tmp_path))
        monkeypatch.setattr(ah, "_SO", str(tmp_path / "libdst_aio.so"))
        monkeypatch.setattr(ah, "_SO_HASH", str(tmp_path / "libdst_aio.so.sha256"))
        ah._build_if_stale()                       # missing -> built
        good = (tmp_path / "libdst_aio.so.sha256").read_text()
        built = os.stat(ah._SO).st_ino
        ah._build_if_stale()                       # same hash -> reused
        assert os.stat(ah._SO).st_ino == built
        (tmp_path / "libdst_aio.so.sha256").write_text("stale")
        ah._build_if_stale()                       # hash differs -> rebuilt
        assert os.stat(ah._SO).st_ino != built
        assert (tmp_path / "libdst_aio.so.sha256").read_text() == good


class TestSwappers:
    def test_async_tensor_swapper(self, tmp_path):
        sw = AsyncTensorSwapper(swap_folder=str(tmp_path))
        x = np.random.default_rng(1).standard_normal((64, 64)).astype(np.float32)
        sw.swap_out("t0", x)
        sw.synchronize()
        back = sw.swap_in("t0", x.shape, x.dtype)
        np.testing.assert_array_equal(back, x)
        assert sw.bytes_swapped == x.nbytes

    def test_partitioned_param_swapper_tree(self, tmp_path):
        sw = AsyncPartitionedParameterSwapper(str(tmp_path))
        tree = {"a": jnp.arange(16, dtype=jnp.float32).reshape(4, 4),
                "b": {"c": jnp.ones((8,), jnp.bfloat16)}}
        sw.swap_out_tree(tree)
        template = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
        sw.prefetch_tree(template)
        back = sw.swap_in_tree(template)
        np.testing.assert_array_equal(back["a"], np.asarray(tree["a"]))
        np.testing.assert_array_equal(np.asarray(back["b"]["c"], np.float32),
                                      np.ones((8,), np.float32))

    def test_optimizer_swapper_roundtrip(self, tmp_path):
        sw = PartitionedOptimizerSwapper(str(tmp_path))
        state = {"mu": jnp.arange(32, dtype=jnp.float32),
                 "nu": jnp.ones((4, 8), jnp.float32)}
        sw.swap_out(state)
        assert sw.is_swapped and sw.swapped_bytes() > 0
        sw.prefetch()
        back = sw.swap_in()
        np.testing.assert_array_equal(back["mu"], np.asarray(state["mu"]))

    def test_aio_config_defaults(self):
        cfg = get_aio_config({"aio": {"thread_count": 9}})
        assert cfg["thread_count"] == 9
        assert cfg["block_size"] == 1 << 20


@pytest.mark.usefixtures("offload_on_device")
class TestZeroInfinityEngine:
    def test_nvme_offload_training(self, tmp_path):
        """offload_optimizer.device='nvme': state lives on disk between
        steps and training still optimizes."""
        from deepspeed_tpu.models.simple import SimpleModel
        model = SimpleModel(hidden_dim=32)
        engine, *_ = deepspeed_tpu.initialize(
            model=model, model_parameters=model.init_params(jax.random.key(0)),
            config={"train_batch_size": 8,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                    "zero_optimization": {
                        "stage": 1,
                        "offload_optimizer": {"device": "nvme",
                                              "nvme_path": str(tmp_path)}}})
        assert engine.optimizer_swapper is not None
        assert engine.state.opt_state is None            # on disk, not HBM
        rng = np.random.default_rng(0)
        x = rng.standard_normal((8, 32)).astype(np.float32)
        y = (x.sum(axis=1) > 0).astype(np.int32)
        losses = []
        for _ in range(5):
            loss = engine.forward(x, y)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
            assert engine.state.opt_state is None        # swapped back out
        assert losses[-1] < losses[0]
        assert engine.optimizer_swapper.swapped_bytes() > 0
        # checkpointing materializes the swapped state transparently
        engine.save_checkpoint(str(tmp_path / "ck"))
        e2_model = SimpleModel(hidden_dim=32)
        engine2, *_ = deepspeed_tpu.initialize(
            model=e2_model,
            model_parameters=e2_model.init_params(jax.random.key(0)),
            config={"train_batch_size": 8,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})
        engine2.load_checkpoint(str(tmp_path / "ck"))
        assert engine2.global_steps == 5


@pytest.mark.usefixtures("offload_on_device")
class TestOffloadOptimizerConfigHonored:
    def test_pipeline_write_and_buffer_count_flow_through(self, tmp_path):
        """The engine must build the optimizer swapper from the user's
        offload_optimizer block, not hardcoded values."""
        from deepspeed_tpu.models.simple import SimpleModel

        def mk(extra):
            model = SimpleModel(hidden_dim=16)
            oc = {"device": "nvme", "nvme_path": str(tmp_path)}
            oc.update(extra)
            engine, *_ = deepspeed_tpu.initialize(
                model=model,
                model_parameters=model.init_params(jax.random.key(0)),
                config={"train_batch_size": 8,
                        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                        "zero_optimization": {"stage": 1,
                                              "offload_optimizer": oc}})
            return engine.optimizer_swapper

        sw = mk({"pipeline_write": True, "buffer_count": 3})
        assert sw._pipeline_write is True
        assert sw._swapper.pool._bounce.budget == \
            3 * sw._swapper.pool._bounce.buffer_size
        # config default: synchronous writeback
        assert mk({})._pipeline_write is False


@pytest.mark.usefixtures("offload_on_device")
class TestNvmeCheckpointResume:
    def test_load_checkpoint_with_nvme_offload(self, tmp_path):
        """Resuming a ZeRO-Infinity run: the restore target must come from
        the swapped state and the restored state goes back to NVMe."""
        from deepspeed_tpu.models.simple import SimpleModel

        def mk(nvme_dir):
            model = SimpleModel(hidden_dim=32)
            engine, *_ = deepspeed_tpu.initialize(
                model=model, model_parameters=model.init_params(jax.random.key(0)),
                config={"train_batch_size": 8,
                        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                        "zero_optimization": {
                            "offload_optimizer": {"device": "nvme",
                                                  "nvme_path": str(nvme_dir)}}})
            return engine

        engine = mk(tmp_path / "n1")
        rng = np.random.default_rng(0)
        x = rng.standard_normal((8, 32)).astype(np.float32)
        y = np.zeros((8,), np.int32)
        loss = engine.forward(x, y); engine.backward(loss); engine.step()
        engine.save_checkpoint(str(tmp_path / "ck"))
        engine2 = mk(tmp_path / "n2")
        path, _ = engine2.load_checkpoint(str(tmp_path / "ck"))
        assert path is not None
        assert engine2.state.opt_state is None        # back on NVMe
        # and the restored optimizer state is the trained one
        restored = engine2._opt_state_view()
        orig = engine._opt_state_view()
        a = jax.tree.leaves(restored)
        b = jax.tree.leaves(orig)
        for x1, x2 in zip(a, b):
            np.testing.assert_allclose(np.asarray(x1), np.asarray(x2))
