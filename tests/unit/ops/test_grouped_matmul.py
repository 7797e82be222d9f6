"""The grouped-matmul kernel (``ops/pallas/grouped_matmul.py``) through the
Pallas interpreter on the CPU, against ``jax.lax.ragged_dot``; what the
chip's compiler accepts is in ``test_chip_compile.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import grouped_matmul as gm


def _sizes(case, A, G, rng):
    if case == "all_on_one":
        sizes = np.zeros(G, np.int32)
        sizes[2] = A
        return sizes
    cuts = np.sort(rng.integers(0, A + 1, G - 1))
    sizes = np.diff(np.concatenate([[0], cuts, [A]])).astype(np.int32)
    if case == "empty_groups":          # one in the middle, and the last
        sizes[5] += sizes[4]
        sizes[-2] += sizes[-1]
        sizes[4] = sizes[-1] = 0
    if case == "tile_aligned":          # every border on a row tile's border
        sizes = np.zeros(G, np.int32)
        sizes[:A // 128] = 128
    return sizes


@pytest.mark.parametrize("case", ["random", "all_on_one", "empty_groups", "tile_aligned"])
@pytest.mark.parametrize("A,G,K,N", [(256, 8, 128, 256), (384, 16, 256, 128)])
def test_kernel_equals_ragged_dot(case, A, G, K, N):
    rng = np.random.default_rng(A + G)
    sizes = jnp.asarray(_sizes(case, A, G, rng))
    assert int(sizes.sum()) == A
    lhs = jnp.asarray(rng.standard_normal((A, K)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((G, K, N)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        assert gm.kernel_shape_ok(A, K, N, lhs.dtype)
        got = jax.jit(gm._grouped)(lhs, rhs, sizes)
        want = jax.lax.ragged_dot(lhs, rhs, sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-5)


def test_visits_walk_every_group_with_rows_once_a_tile():
    sizes = jnp.asarray([100, 0, 156, 128, 0, 128], jnp.int32)       # A = 512
    offsets, group, tile, n = gm.visits(sizes, 512, 128)
    np.testing.assert_array_equal(np.asarray(offsets), [0, 100, 100, 256, 384, 384, 512])
    assert int(n[0]) == 5
    # group 0 in tile 0; group 2 in tiles 0 and 1; groups 3 and 5 a tile each;
    # the list is padded to 4 + 6 - 1 with its last real visit
    np.testing.assert_array_equal(np.asarray(group), [0, 2, 2, 3, 5, 5, 5, 5, 5])
    np.testing.assert_array_equal(np.asarray(tile), [0, 0, 1, 2, 3, 3, 3, 3, 3])


def test_gradients_are_ragged_dots():
    rng = np.random.default_rng(3)
    A, G, K, N = 256, 8, 128, 128
    sizes = jnp.asarray(_sizes("empty_groups", A, G, rng))
    lhs = jnp.asarray(rng.standard_normal((A, K)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((G, K, N)), jnp.float32)
    target = jnp.asarray(rng.standard_normal((A, N)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        loss = lambda f: lambda a, w: jnp.sum(f(a, w, sizes) * target)
        got = jax.grad(loss(gm._grouped), argnums=(0, 1))(lhs, rhs)
        want = jax.grad(loss(jax.lax.ragged_dot), argnums=(0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4, rtol=1e-5)
    assert not np.asarray(got[1][4]).any()          # an empty group's matrix


def test_the_gate(kernels):
    """For shapes the kernel does not take the call is ``ragged_dot``, even
    where the rule says kernels run (the rule's own three states are in
    ``test_kernel_selection.py``)."""
    assert gm.kernel_shape_ok(1024, 2048, 2048, jnp.bfloat16)
    assert gm._column_tile(2048, 2048, 2) == 2048 and gm._column_tile(2048, 4096, 2) == 2048
    assert not gm.kernel_shape_ok(1000, 2048, 2048, jnp.bfloat16)    # no whole row tiles
    assert not gm.kernel_shape_ok(1024, 2048, 2000, jnp.bfloat16)
    assert not gm.kernel_shape_ok(1024, 2048, 2048, jnp.int8)
    kernels("grouped_matmul")
    sizes = jnp.asarray([3, 4], jnp.int32)
    out = gm.grouped_matmul(jnp.ones((7, 8)), jnp.ones((2, 8, 4)), sizes)   # refused shape
    np.testing.assert_array_equal(np.asarray(out), np.full((7, 4), 8.0))
