"""An indexed layer's selection with the rows' scores held in VMEM
(``ops/pallas/index_select.py`` through the Pallas interpreter) against the
bisection in plain ``jax.numpy`` (``models/hybrid.py:chosen_tokens`` on the
CPU) and the model's reference (``benchmarks/lib/reference_keye_vl2.py:
chosen_mask``): the same mask to the entry at seeded scores with ties planted
AT the k-th place, a row half unseen, a row all alike, a row with fewer than
``k`` scores above -inf, ``k`` the whole row, rows that are not whole sublane
tiles, a tile of more rows than one grid step holds, and keys of 1, 45 and 360
lane tiles; the positions by rank behind it (``chosen_positions``: rising,
``real`` the count, the stable sort's set); and the shape gate."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.lib import reference_keye_vl2 as ref
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.ops.pallas import index_select as ix

# (rows, keys, k): 1, 45 and 360 lane tiles of keys; rows of a part of a
# sublane tile, of one, of two and a part (bf16's tile) and past a grid step's
SHAPES = [(3, 128, 8), (8, 128, 128), (5, 5760, 2048), (12, 5760, 5760), (8, 5760, 17),
          (19, 640, 50), (40, 384, 64), (4, 46080, 2048)]


def scores(n, T, k, seed=0):
    """Seeded scores, a third of them whole numbers (ties at the k-th place
    and everywhere else; no -0, which a sort calls +0's equal and the bit
    patterns do not); row 0 half unseen, row 1 all alike, row 2 with ``k //
    2`` seen, and the k-th place of the last row planted: four scores of
    one value there, two of them inside the set."""
    rng = np.random.default_rng(seed + T + k)
    s = rng.normal(size=(n, T)).astype(np.float32) * 3
    s[:, ::3] = np.round(s[:, ::3]) + 0.0
    s[0, T // 2:] = -np.inf
    s[1, :] = 1.0
    s[2, k // 2:] = -np.inf
    if 2 <= k <= T - 2:
        order = np.argsort(-s[-1], kind="stable")
        s[-1, order[k - 2:k + 2]] = s[-1, order[k - 2]]
    return s


def stable_set(row, k):
    want = np.argsort(-row, kind="stable")[:k]
    return np.sort(want[row[want] > -np.inf])


@pytest.fixture
def both(kernels):
    """-> ``forms(fn, s)``: ``fn`` jitted under the rule as it stands on the
    CPU (the references) and with the kernel chosen (which its gate admits
    at 2,048 keys or more); a new function each, because a trace is kept by
    the function traced."""
    def forms(fn, s):
        kernels()
        plain = jax.jit(lambda s: fn(s))(jnp.asarray(s))
        kernels(ix.KERNEL)
        return plain, jax.jit(lambda s: fn(s))(jnp.asarray(s))
    return forms


@pytest.mark.parametrize("n, T, k", SHAPES)
def test_the_mask_is_the_bisections_and_the_references(both, n, T, k):
    """The kernel itself at every shape (rows short of what its gate admits
    too), and through ``chosen_tokens`` where the gate admits the shape."""
    s = scores(n, T, k)
    assert hybrid.selects_in_vmem(n, T, k) is False      # the CPU takes the plain form
    plain, gated = both(lambda s: hybrid.chosen_tokens(s, k, 128), s)
    assert hybrid.selects_in_vmem(n, T, k) == (T >= 2048)
    got = np.asarray(jax.jit(lambda s: ix.index_select(s, k)[0] != 0)(jnp.asarray(s)))
    assert got.dtype == np.bool_ == np.asarray(gated).dtype
    assert np.array_equal(got, np.asarray(plain)) and np.array_equal(got, np.asarray(gated))
    assert np.array_equal(got, np.asarray(ref.chosen_mask(jnp.asarray(s), k)))
    for r in range(n):
        assert np.array_equal(np.flatnonzero(got[r]), stable_set(s[r], k))
    assert got[0].sum() == min(k, T // 2) and got[2].sum() == k // 2
    assert np.array_equal(np.flatnonzero(got[1]), np.arange(k))


@pytest.mark.parametrize("n, T, k", SHAPES)
def test_the_kernel_counts_what_it_chose(kernels, n, T, k):
    kernels(ix.KERNEL)
    s = scores(n, T, k, seed=1)
    mask, count = jax.jit(lambda s: ix.index_select(s, k))(jnp.asarray(s))
    assert mask.shape == (n, T) and count.shape == (n,)
    assert mask.dtype == (jnp.bfloat16 if ix.row_tile(n) % 16 == 0 else jnp.float32)
    mask = np.asarray(mask.astype(jnp.float32))
    assert set(np.unique(mask)) <= {0.0, 1.0}
    assert np.array_equal(mask.sum(axis=1), np.asarray(count))
    assert np.array_equal(np.asarray(count), [len(stable_set(row, k)) for row in s])


@pytest.mark.parametrize("n, T, k", SHAPES[:-1] + [(3, 192, 50), (3, 64, 8), (9, 2176, 300)])
def test_the_positions_are_the_masks_by_rank(both, n, T, k):
    """Rising, ``real`` as many as were chosen, the stable sort's set; behind
    the kernel (5,760 and 2,176 keys) and behind the plain form (every shape,
    keys that are not whole lane tiles too)."""
    s = scores(n, T, k, seed=2)
    for at, real in both(lambda s: hybrid.chosen_positions(s, k), s):
        at, real = np.asarray(at), np.asarray(real)
        assert at.shape == real.shape == (n, k) and at.dtype == np.int32
        assert (0 <= at).all() and (at < T).all()
        for r in range(n):
            want = stable_set(s[r], k)
            assert real[r].sum() == len(want) and real[r, :len(want)].all()
            assert np.array_equal(at[r][real[r]], want)         # rising, and the set


def test_a_tile_without_a_tie_takes_no_second_bisection(kernels):
    """Distinct scores: the cut among the equal ones is not looked for (the
    branch is the kernel's own), and the mask is still the set."""
    kernels(ix.KERNEL)
    rng = np.random.default_rng(7)
    s = rng.permutation(8 * 640).reshape(8, 640).astype(np.float32)
    got = np.asarray(jax.jit(lambda s: ix.index_select(s, 100)[0])(jnp.asarray(s))) != 0
    for r in range(8):
        assert np.array_equal(np.flatnonzero(got[r]), stable_set(s[r], 100))


def test_the_order_is_the_float32s_across_signs_and_magnitudes(kernels):
    kernels(ix.KERNEL)
    row = np.asarray([-np.inf, -3e38, -1.0, -1e-38, -1e-45, 0.0, 1e-45, 1e-38, 1.0, 3e38,
                      np.inf] + [-np.inf] * 117, np.float32)
    s = np.stack([np.roll(row, i) for i in range(8)])
    for k in (1, 3, 6, 10, 11):
        got = np.asarray(jax.jit(lambda s: ix.index_select(s, k)[0])(jnp.asarray(s))) != 0
        for r in range(8):
            assert np.array_equal(np.flatnonzero(got[r]), stable_set(s[r], k)), (k, r)


def test_the_gate():
    assert ix.kernel_shape_ok(8, 46080, 2048) and ix.kernel_shape_ok(128, 46080, 2048)
    assert ix.kernel_shape_ok(32, 5760, 2048) and ix.kernel_shape_ok(3, 2048, 2048)
    assert not ix.kernel_shape_ok(8, 2112, 8)        # not whole lane tiles
    assert not ix.kernel_shape_ok(1024, 768, 64)     # rows too short to pay for a pass
    assert not ix.kernel_shape_ok(8, 2048, 2049)     # more than the row holds
    assert not ix.kernel_shape_ok(32, 128 * 1024, 2048)    # a grid step past its VMEM
    assert ix.row_tile(3) == 8 and ix.row_tile(12) == 16 and ix.row_tile(512) == 32
