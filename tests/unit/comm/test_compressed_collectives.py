"""qwZ / qgZ / hpZ collectives vs their exact ``jax.lax`` equivalents on
the 8-device virtual CPU mesh — single-axis and the 2(slow)x4(fast)
(data, fsdp) split hpZ keys off."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.compression import hpz, qgz, qwz
from deepspeed_tpu.comm.compression.core import quantization_error_bound


def _mesh1():
    return Mesh(np.array(jax.devices()[:8]).reshape(8), ("fsdp",))


def _mesh2():
    return Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("data", "fsdp"))


def _run(mesh, axes, body, xs, out_spec=P()):
    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(axes),),
                                    out_specs=out_spec, check_vma=False))
    return np.asarray(fn(xs))


class TestQwz:
    @pytest.mark.parametrize("mesh_fn,axes", [(_mesh1, ("fsdp",)),
                                              (_mesh2, ("data", "fsdp"))])
    def test_parity_with_exact_all_gather(self, mesh_fn, axes):
        rng = np.random.default_rng(0)
        n = 1024
        xs = rng.standard_normal((8, n)).astype(np.float32)

        got = _run(mesh_fn(), axes,
                   lambda x: qwz.quantized_all_gather(x[0], axes, dim=0,
                                                      bits=8, block_size=256),
                   xs)
        full = xs.reshape(-1)          # device-major order == mesh order
        assert got.shape == full.shape
        bound = np.concatenate(
            [quantization_error_bound(xs[d], 8, 256) for d in range(8)])
        assert (np.abs(got - full) <= bound).all()

    def test_exact_when_codes_representable(self):
        # every block spans [0, 255] → scale 1 → integer codes round-trip
        # exactly → the quantized gather must EQUAL the exact one
        rng = np.random.default_rng(1)
        xs = rng.integers(0, 256, (8, 512)).astype(np.float32)
        xs[:, 0::256], xs[:, 1::256] = 0.0, 255.0
        axes = ("fsdp",)
        got = _run(_mesh1(), axes,
                   lambda x: qwz.quantized_all_gather(x[0], axes, dim=0,
                                                      bits=8, block_size=256),
                   xs)
        np.testing.assert_array_equal(got, xs.reshape(-1))

    def test_merge_dim1(self):
        """Gather along a non-leading dim matches tiled lax.all_gather."""
        rng = np.random.default_rng(2)
        xs = rng.integers(0, 256, (8, 4, 64)).astype(np.float32)
        xs[..., 0], xs[..., 1] = 0.0, 255.0      # exact-representable blocks
        axes = ("fsdp",)

        def body(x):
            q = qwz.quantized_all_gather(x[0], axes, dim=1, bits=8,
                                         block_size=64)
            e = jax.lax.all_gather(x[0], "fsdp", axis=1, tiled=True)
            return q, e

        mesh = _mesh1()
        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("fsdp"),),
                                        out_specs=(P(), P()), check_vma=False))
        got, exact = map(np.asarray, fn(xs))
        np.testing.assert_array_equal(got, exact)

    def test_accounting_ratio(self):
        n, w = 1 << 20, 8
        ratio = qwz.logical_bytes(n, w) / qwz.wire_bytes(n, w, bits=8,
                                                         block_size=256)
        assert ratio > 3.8
        assert qwz.logical_bytes(n, w) == (w - 1) * n * 4


class TestQgz:
    def test_exact_baseline_matches_psum_scatter(self):
        rng = np.random.default_rng(3)
        xs = rng.standard_normal((8, 1024)).astype(np.float32)

        def body(x):
            h = qgz.hierarchical_reduce_scatter(x[0], 0, ("fsdp",), bits=None,
                                                mean=False)
            e = jax.lax.psum_scatter(x[0], "fsdp", scatter_dimension=0,
                                     tiled=True)
            return h[None], e[None]

        mesh = _mesh1()
        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("fsdp"),),
                                        out_specs=(P("fsdp"), P("fsdp")),
                                        check_vma=False))
        h, e = map(np.asarray, fn(xs))
        np.testing.assert_allclose(h, e, rtol=1e-6, atol=1e-5)

    @pytest.mark.parametrize("mesh_fn,axes", [(_mesh1, ("fsdp",)),
                                              (_mesh2, ("data", "fsdp"))])
    def test_quantized_mean_close_to_exact(self, mesh_fn, axes):
        rng = np.random.default_rng(4)
        xs = rng.standard_normal((8, 1024)).astype(np.float32)
        exact = xs.mean(0).reshape(8, 128)

        def body(x):
            return qgz.hierarchical_reduce_scatter(
                x[0], 0, axes, bits=8, block_size=128, mean=True)[None]

        got = _run(mesh_fn(), axes, body, xs, out_spec=P(axes))
        # only (at most) the slow hop is lossy; per-element step of the
        # averaged rows bounds the error loosely
        assert got.shape == (8, 128)
        assert np.abs(got.reshape(8, -1) - exact).max() < 0.05
        assert np.corrcoef(got.reshape(-1), exact.reshape(-1))[0, 1] > 0.999

    def test_indivisible_raises(self):
        with pytest.raises(AssertionError):
            _run(_mesh1(), ("fsdp",),
                 lambda x: qgz.hierarchical_reduce_scatter(
                     x[0], 0, ("fsdp",), bits=8)[None],
                 np.zeros((8, 1004), np.float32), out_spec=P("fsdp"))

    def test_accounting(self):
        n = 1 << 20
        # single quantized hop
        r1 = qgz.logical_bytes(n, 8) / qgz.wire_bytes(n, (8,), bits=8,
                                                      block_size=256)
        assert r1 > 3.8
        # hierarchical: fast fp32 hop dominates → lower but still < exact
        w2 = qgz.wire_bytes(n, (2, 4), bits=8, block_size=256)
        assert w2 < qgz.wire_bytes(n, (2, 4), bits=None)


class TestHpz:
    def test_gather_and_regather_parity(self):
        rng = np.random.default_rng(5)
        xs = rng.standard_normal((8, 256)).astype(np.float32)
        axes = ("data", "fsdp")

        def body(x):
            full, sec = hpz.hierarchical_gather(x[0], 0, axes,
                                                checkpoint_fast=False)
            again = hpz.fast_regather(sec, 0, "fsdp", w_slow=2)
            exact = jax.lax.all_gather(x[0], axes, axis=0, tiled=True)
            return full, sec, again, exact

        mesh = _mesh2()
        # sec is sharded over fsdp at dim 0: spec P("fsdp")
        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(axes),),
            out_specs=(P(), P("fsdp"), P(), P()), check_vma=False))
        full, sec, again, exact = map(np.asarray, fn(xs))
        # bf16 secondary: full gather is within bf16 cast error
        assert np.abs(full - exact).max() <= np.abs(exact).max() * 2 ** -8
        # the reuse path reproduces the refresh path EXACTLY
        np.testing.assert_array_equal(again, full)
        assert sec.shape == exact.shape     # replicated view of fsdp shards

    def test_quantized_secondary(self):
        rng = np.random.default_rng(6)
        xs = rng.standard_normal((8, 512)).astype(np.float32)
        axes = ("data", "fsdp")

        def body(x):
            full, sec = hpz.hierarchical_gather(
                x[0], 0, axes, quantize_bits=8, block_size=256,
                checkpoint_fast=False)
            return full, hpz.fast_regather(sec, 0, "fsdp", w_slow=2)

        mesh = _mesh2()
        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(axes),),
            out_specs=(P(), P()), check_vma=False))
        full, again = map(np.asarray, fn(xs))
        assert np.abs(full - xs.reshape(-1)).max() < 0.05
        np.testing.assert_array_equal(again, full)

    def test_accounting(self):
        n = 1 << 16
        # a reuse gather moves no slow-axis bytes at all, and bf16 beats
        # the fp32 full-world gather standard ZeRO-3 would run
        assert hpz.reuse_wire_bytes(n, w_slow=2, w_fast=4) < \
            hpz.refresh_wire_bytes(n, w_slow=2, w_fast=4)
        assert hpz.reuse_wire_bytes(n, w_slow=2, w_fast=4) < \
            hpz.logical_bytes(n, w_slow=2, w_fast=4)
