"""Compressed (1-bit) gradient allreduce tests.

Reference coverage model: ``tests/onebit/`` (NCCL/MPI compressed-comm
correctness + the 1,243-line ``onebit/test_onebit.py`` optimizer suite).
Here: the collective itself (sign/scale parity, error-feedback
convergence, padding), the wire-byte accounting, and the engine
integration (warmup → compressed switch, convergence, comms logging).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.runtime.comm.compressed import (
    CompressionState, compressed_allreduce, compressed_bytes,
    init_compression_state, padded_size)


def _mesh():
    return Mesh(np.array(jax.devices()[:8]).reshape(8), ("data",))


def _run(xs, we, se, mesh):
    def f(x, we, se):
        out, st = compressed_allreduce(x[0], CompressionState(we[0], se[0]), "data")
        return out[None], st.worker_error[None], st.server_error[None]

    g = jax.jit(jax.shard_map(f, mesh=mesh,
                                   in_specs=(P("data"), P("data"), P("data")),
                                   out_specs=(P("data"), P("data"), P("data")),
                                   check_vma=False))
    return g(xs, we, se)


class TestCompressedAllreduce:
    @pytest.mark.parametrize("n", [1024, 1000])   # padded and unpadded sizes
    def test_sign_structure_and_agreement(self, n):
        mesh = _mesh()
        rng = np.random.default_rng(0)
        xs = rng.standard_normal((8, n)).astype(np.float32)
        we, se = init_compression_state(n, 8)
        WE, SE = np.tile(we, (8, 1)), np.tile(se, (8, 1))
        out, _, _ = _run(xs, WE, SE, mesh)
        out = np.asarray(out)
        # every device reconstructs the identical result
        for d in range(1, 8):
            np.testing.assert_array_equal(out[0], out[d])
        # the result is sign*scale per server chunk: per-chunk |values| const
        chunk = padded_size(n, 8) // 8
        flat = np.zeros(padded_size(n, 8), np.float32)
        flat[:n] = out[0]
        mags = np.abs(flat.reshape(8, chunk))
        for c in range(8):
            vals = np.unique(np.round(mags[c], 6))
            assert len(vals) <= 2   # one scale (and possibly 0 padding)

    def test_error_feedback_converges_to_mean(self):
        mesh = _mesh()
        n = 512
        rng = np.random.default_rng(1)
        xs = rng.standard_normal((8, n)).astype(np.float32)
        exact = xs.mean(0)
        we, se = init_compression_state(n, 8)
        WE, SE = np.tile(we, (8, 1)), np.tile(se, (8, 1))
        iters = 300

        # the whole error-feedback loop as ONE scanned program (the
        # python-loop version re-dispatched 300 times on one CPU core)
        def f(x, we, se):
            def step(carry, _):
                we, se, acc = carry
                out, st = compressed_allreduce(x[0],
                                               CompressionState(we, se),
                                               "data")
                return (st.worker_error, st.server_error, acc + out), None

            init = (we[0], se[0], jnp.zeros_like(x[0]))
            (_, _, acc), _ = jax.lax.scan(step, init, None, length=iters)
            return acc[None]

        g = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P("data"), P("data"), P("data")),
            out_specs=P("data"), check_vma=False))
        acc = np.asarray(g(xs, WE, SE))[0]
        err = np.abs(acc / iters - exact).max() / (np.abs(exact).max() + 1e-9)
        assert err < 0.05            # compensated compression is unbiased

    def test_wire_bytes_beat_fp32(self):
        n, world = 1_000_000, 8
        fp32_ring = 2 * (world - 1) / world * n * 4   # ring allreduce bytes
        assert compressed_bytes(n, world) < fp32_ring / 3


class TestEngineOnebit:
    def _engine(self, freeze_step, gas=1, lr=3e-3):
        from deepspeed_tpu.models.simple import SimpleModel
        model = SimpleModel(hidden_dim=64)
        params = model.init_params(jax.random.key(0))
        engine, *_ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config={"train_batch_size": 8 * gas,
                    "gradient_accumulation_steps": gas,
                    "optimizer": {"type": "OneBitAdam",
                                  "params": {"lr": lr,
                                             "freeze_step": freeze_step}},
                    "comms_logger": {"enabled": True, "verbose": False}})
        return engine

    def _data(self):
        rng = np.random.default_rng(2)
        x = rng.standard_normal((8, 64)).astype(np.float32)
        y = (x.sum(axis=1) > 0).astype(np.int32)
        return x, y

    def test_compressed_switch_and_convergence(self):
        # freeze once the variance is established (the reference's contract:
        # freeze_step is a sizeable fraction of training, not a handful of
        # steps) and use the documented smaller 1-bit-phase lr
        engine = self._engine(freeze_step=20)
        assert engine._onebit_comm is not None
        x, y = self._data()
        losses = []
        for i in range(40):
            assert engine._onebit_active() == (i >= 20)
            loss = engine.forward(x, y)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        assert all(np.isfinite(losses))
        # warmup optimizes exactly; the compressed phase keeps the loss well
        # below the start (sign noise gives a floor, not divergence)
        assert losses[19] < losses[0]
        assert np.mean(losses[-10:]) < losses[0] * 0.8
        assert min(losses[20:]) < losses[19]
        assert engine._onebit_errors is not None
        # error feedback is live (buffers non-zero after compression steps)
        assert float(jnp.abs(engine._onebit_errors[0]).sum()) > 0

    def test_comms_logger_records_compressed_bytes(self):
        engine = self._engine(freeze_step=1)
        x, y = self._data()
        for _ in range(3):
            loss = engine.forward(x, y)
            engine.backward(loss)
            engine.step()
        entry = engine.comms_logger.comms_dict.get("compressed_allreduce")
        assert entry, "compressed allreduce not logged"
        (size, (count, _lat)), = entry.items()
        n = engine._onebit_n
        assert size == compressed_bytes(n, 8)
        assert size < n * 4                     # beats one fp32 buffer
        assert count >= 2

    def test_gas_accumulates_locally(self):
        engine = self._engine(freeze_step=0, gas=2)
        x, y = self._data()
        for _ in range(2):
            for _ in range(2):
                loss = engine.forward(x, y)
                engine.backward(loss)
            engine.step()
            assert np.isfinite(float(loss))

    def test_warmup_matches_exact_adam(self):
        """Before freeze_step the onebit path must be exact Adam."""
        def losses(opt):
            from deepspeed_tpu.models.simple import SimpleModel
            model = SimpleModel(hidden_dim=64)
            params = model.init_params(jax.random.key(0))
            engine, *_ = deepspeed_tpu.initialize(
                model=model, model_parameters=params,
                config={"train_batch_size": 8, "optimizer": opt})
            x, y = self._data()
            out = []
            for _ in range(3):
                l = engine.forward(x, y)
                engine.backward(l)
                engine.step()
                out.append(float(l))
            return out

        a = losses({"type": "OneBitAdam",
                    "params": {"lr": 1e-2, "freeze_step": 100}})
        b = losses({"type": "Adam", "params": {"lr": 1e-2}})
        np.testing.assert_allclose(a, b, rtol=1e-5)


class TestReviewFixes:
    def test_train_batch_routes_through_compression(self):
        """train_batch must not feed raw grads to the post-freeze optimizer."""
        engine = self._engine_helper(freeze_step=1, gas=2)
        x, y = _data_helper()
        batch = (np.stack([x, x]), np.stack([y, y]))    # [gas, micro, ...]
        for _ in range(3):
            loss = engine.train_batch(batch=batch)
            assert np.isfinite(float(loss))
        # the compressed exchange actually ran
        entry = engine.comms_logger.comms_dict.get("compressed_allreduce")
        assert entry and list(entry.values())[0][0] >= 2

    @staticmethod
    def _engine_helper(freeze_step, gas=1):
        import deepspeed_tpu
        from deepspeed_tpu.models.simple import SimpleModel
        model = SimpleModel(hidden_dim=64)
        params = model.init_params(jax.random.key(0))
        engine, *_ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config={"train_batch_size": 8 * gas,
                    "gradient_accumulation_steps": gas,
                    "optimizer": {"type": "OneBitAdam",
                                  "params": {"lr": 3e-3,
                                             "freeze_step": freeze_step}},
                    "comms_logger": {"enabled": True}})
        return engine


def _data_helper():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((8, 64)).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.int32)
    return x, y
