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


# the three served banks' proportions (groups; gate|up ``K x N``), cut to a
# CPU's size: OLMoE's square matrix, SmallThinker's 5 : 3, and Mistral's 32
# held experts with most rows in NO group (behind the last)
BANKS = {"olmoe": (64, 256, 256, 1.0), "smallthinker": (64, 640, 384, 1.0),
         "mistral_held": (32, 512, 512, 0.3)}


def _fresh():
    """A new jitted function: the selection rule is read while a program is
    traced, and one jitted ``grouped_matmul`` would keep the first side's."""
    return jax.jit(lambda *a: gm.grouped_matmul(*a))


def _stack(bank, L=3, A=256, dtype=jnp.float32):
    G, K, N, share = BANKS[bank]
    rng = np.random.default_rng(G + K)
    in_groups = int(A * share)
    sizes = _sizes("empty_groups", in_groups, G, rng)
    assert int(sizes.sum()) == in_groups
    lhs = jnp.asarray(rng.standard_normal((A, K)), dtype)
    rhs = jnp.asarray(rng.standard_normal((L, G, K, N)), dtype)
    return lhs, rhs, jnp.asarray(sizes), in_groups


@pytest.mark.parametrize("path", ["kernel", "ragged_dot"])
@pytest.mark.parametrize("bank", list(BANKS))
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_a_layer_of_the_stack_equals_the_layer_sliced(kernels, monkeypatch,
                                                       layer, bank, path):
    """``grouped_matmul(lhs, stack, sizes, layer)`` against
    ``grouped_matmul(lhs, stack[layer], sizes)``: the same tiles multiplied,
    read from another address, so bit for bit; the kernel is handed the
    four-dimensional stack and a traced layer, never a slice of it."""
    kernels(*(["grouped_matmul"] if path == "kernel" else []))
    lhs, rhs, sizes, in_groups = _stack(bank)
    handed = []
    real = gm._call
    monkeypatch.setattr(gm, "_call", lambda a, w, s, l: handed.append(w.shape)
                        or real(a, w, s, l))
    got = _fresh()(lhs, rhs, sizes, jnp.int32(layer))
    want = _fresh()(lhs, rhs[layer], sizes)
    assert handed == ([rhs.shape, (1, *rhs.shape[1:])] if path == "kernel" else [])
    # rows behind the last group are whatever the output buffer held
    np.testing.assert_array_equal(np.asarray(got[:in_groups]),
                                  np.asarray(want[:in_groups]))
    other = _fresh()(lhs, rhs[(layer + 1) % 3], sizes)
    assert not np.array_equal(np.asarray(got[:in_groups]), np.asarray(other[:in_groups]))


def _converts_of(fn, *args):
    """Shapes of what the program converts to another type."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "convert_element_type":
                found.append(eqn.outvars[0].aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("leaf", ["float32_under_bf16_rows", "int8_injected"])
def test_a_stack_the_kernel_cannot_read_is_indexed_then_converted(kernels, leaf):
    """A bank of another type than the rows', or an int8-injected leaf
    (``{"q8", "scale"}``): the kernel cannot read the stack as it is stored,
    so the layer is indexed first and converted ALONE; nothing of the
    stack's four dimensions is ever converted."""
    from deepspeed_tpu.module_inject.quantization import (dequantize_weight,
                                                          quantize_weight)
    kernels("grouped_matmul")
    lhs, rhs, sizes, _ = _stack("olmoe")
    lhs = lhs.astype(jnp.bfloat16)
    if leaf == "int8_injected":
        rhs = quantize_weight(rhs)
        one_layer = lambda l: dequantize_weight(jax.tree.map(lambda a: a[l], rhs),
                                                jnp.bfloat16)
    else:
        one_layer = lambda l: rhs[l].astype(jnp.bfloat16)
    converted = _converts_of(gm.grouped_matmul, lhs, rhs, sizes, jnp.int32(1))
    assert converted and all(len(shape) < 4 for shape in converted), converted
    got = _fresh()(lhs, rhs, sizes, jnp.int32(1))
    want = _fresh()(lhs, one_layer(1), sizes)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("path", ["kernel", "ragged_dot"])
def test_the_stacked_form_has_no_gradient_on_the_kernel(kernels, path):
    """The stacked form is the inference paths': on the kernel its gradient
    would be as large as the stack, and differentiating it raises an error
    that names the sliced form.  (Where ``ragged_dot`` runs the layer is
    indexed in the open, and JAX differentiates that as it does any index.)"""
    kernels(*(["grouped_matmul"] if path == "kernel" else []))
    lhs, rhs, sizes, _ = _stack("olmoe")
    loss = lambda a, w: jnp.sum(gm.grouped_matmul(a, w, sizes, jnp.int32(1)))
    if path == "kernel":
        with pytest.raises(NotImplementedError, match="sliced form"):
            jax.grad(loss, argnums=(0, 1))(lhs, rhs)
    else:
        da, dw = jax.grad(loss, argnums=(0, 1))(lhs, rhs)
        assert not np.asarray(dw[0]).any() and np.asarray(dw[1]).any()
