"""Which ``k`` of a row's scores are largest, with the row held in VMEM (the
selection of an indexed layer, ``models/hybrid.py:chosen_tokens``: a query
scores every key before it and attends the 2,048 whose scores are highest).

The ``k``-th largest score of a row is found by bisection over the scores'
bit patterns.  In XLA every pass is fusions of its own that read the scores
from HBM again (sixteen passes over a tile of 128 queries x 46,080 keys, 23.6
MB), and the decode rows, whose positions a gather wants, sorted instead.
Here a grid step holds a tile of rows' scores ``[rows, T]`` (``T`` on the
lanes) as integers in the scores' order and runs the WHOLE bisection there: a
pass is one compare and one add a vector register and one sum across the
lanes, a bit of the answer a pass.  Of equal scores the lower positions are
chosen, never one at -inf: where a tile holds a row with a tie AT the k-th
place a second bisection, over the position, finds where among the equal
ones the set ends.  It writes the mask and, a row, how many it chose.

What has been shown: the mask against ``hybrid.chosen_tokens``' XLA form and
the model's reference through the Pallas interpreter
(``tests/unit/ops/test_index_select.py``) and ahead-of-time compiles for v5e
at the served shapes (``tests/unit/ops/test_chip_compile.py``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import pallas as _pallas

KERNEL = "index_select"
LANES = 128
INT_MIN = -(1 << 31)
# -inf as :func:`sortable` gives it: what is at or under it is never chosen
NEG_INF_KEY = INT_MIN + 0x007FFFFF
# rows a grid step holds: 32 x 46,080 keys are 5.9 MB, twice for the pipeline.
# Measured on v5e at 128 x 46,080 (PERF.md section 6, PR 63): 8 rows a step
# 0.33 ms, 16 rows 0.23, 32 rows 0.20: a pass is a cycle a vector register
_ROW_TILE = 32
# lane tiles a turn of the counting loop takes (its sum is a tree): 16 read
# 0.20 ms there, 8 read 0.21
_LANE_TILES_A_TURN = 16
# the fewest keys of a row: a pass costs about 100 cycles whatever it counts (a
# sum across the lanes, a turn of the loop), which 6 lane tiles of work do not
# cover: 1,024 rows of 768 scores read 0.13 ms here and 0.065 in XLA
_MIN_KEYS = 2048
# a grid step's scores twice, its mask twice, and room: of the chip's 128 MiB
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024
_VMEM_BLOCK_BYTES = 8 * 1024 * 1024


def sortable(x):
    """float32 -> int32 in the same order (-inf lowest but for NaNs of the
    sign bit; -0 under +0)."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)


def row_tile(n: int) -> int:
    """Rows a grid step holds of ``n``: whole sublane tiles, all of a few rows."""
    return min(_ROW_TILE, -(-n // 8) * 8)


def kernel_shape_ok(n: int, T: int, k: int) -> bool:
    """What :func:`_kernel` takes, and is faster at than the fusions it
    replaces: keys in whole lane tiles, :data:`_MIN_KEYS` of them or more, a
    grid step's rows within its share of VMEM."""
    return (T % LANES == 0 and T >= _MIN_KEYS and 1 <= k <= T
            and row_tile(n) * T * 4 <= _VMEM_BLOCK_BYTES)


def _turn(T: int) -> int:
    """Lanes a turn of the counting loop takes: the most whole lane tiles, at
    most :data:`_LANE_TILES_A_TURN`, that divide ``T``."""
    tiles = T // LANES
    return LANES * max(c for c in range(1, _LANE_TILES_A_TURN + 1) if tiles % c == 0)


def _kernel(x_ref, o_ref, n_ref, *, k, n):
    """``x_ref [R, T]`` int32: the rows' scores as :func:`sortable` gives
    them, the last grid step's past the ``n``-th whatever was there; ``o_ref
    [R, T]``: 1 where chosen; ``n_ref [R, 128]`` int32: how many a row chose,
    in every lane."""
    R, T = x_ref.shape
    W = _turn(T)

    def count(hit):
        """How many of a row's lanes ``hit(x [R, W], first lane)`` says: ``[R, 1]``."""
        def turn(i, acc):
            at = pl.multiple_of(i * W, W)
            ones = jnp.where(hit(x_ref[:, pl.ds(at, W)], at), 1, 0)
            parts = [ones[:, j:j + LANES] for j in range(0, W, LANES)]
            while len(parts) > 1:
                parts = [a + b for a, b in zip(parts[::2], parts[1::2])] + parts[len(parts) & ~1:]
            return acc + parts[0]
        acc = jax.lax.fori_loop(0, T // W, turn, jnp.zeros((R, LANES), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    def bit_of_kth(i, carry):
        # ``kth`` in the unsigned order's bits, the compare in the signed
        # one; ``at_least``: how many of the row are at or above ``kth``
        kth, at_least = carry
        cand = kth | (jnp.int32(1) << (31 - i))
        floor = cand ^ INT_MIN
        found = count(lambda x, _: x >= floor)
        return jnp.where(found >= k, cand, kth), jnp.where(found >= k, found, at_least)

    kth, at_least = jax.lax.fori_loop(
        0, 32, bit_of_kth, (jnp.zeros((R, 1), jnp.int32), jnp.full((R, 1), T, jnp.int32)))
    kth = kth ^ INT_MIN
    above = count(lambda x, _: x > kth)
    wanted = k - above                                   # of the equal ones, the first
    some = kth > NEG_INF_KEY
    lane = lambda x, at: at + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)

    def cut_among_equal():
        """The position of the ``wanted``-th of a row's scores equal to its
        k-th: the most positions with fewer than ``wanted`` of them under it."""
        def bit_of_cut(i, cut):
            cand = cut | (jnp.int32(1) << (bits - 1 - i))
            under = count(lambda x, at: (x == kth) & (lane(x, at) < cand))
            return jnp.where(under < wanted, cand, cut)
        bits = max(T - 1, 1).bit_length()
        return jax.lax.fori_loop(0, bits, bit_of_cut, jnp.zeros((R, 1), jnp.int32))

    row = pl.program_id(0) * R + jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
    tied = jnp.max(jnp.where((row < n) & some & (at_least > k), 1, 0)) > 0
    cut = jax.lax.cond(tied, cut_among_equal, lambda: jnp.full((R, 1), T, jnp.int32))

    def write(i, _):
        at = pl.multiple_of(i * W, W)
        x = x_ref[:, pl.ds(at, W)]
        chosen = ((x > kth) | ((x == kth) & (lane(x, at) <= cut))) & (x > NEG_INF_KEY)
        o_ref[:, pl.ds(at, W)] = jnp.where(chosen, 1.0, 0.0).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, T // W, write, 0)
    n_ref[...] = jnp.broadcast_to(jnp.where(some, k, above), (R, LANES))


def index_select(scores, k: int):
    """``scores [n, T]`` float32 -> (which ``k`` of a row are largest ``[n,
    T]``, 1 where chosen and 0 elsewhere, in bfloat16 (float32 from a grid
    step of a single sublane tile); how many a row chose ``[n]`` int32).  Of
    equal scores the lower positions; never one at -inf, so a row with fewer
    than ``k`` scores above it chooses them all.  The caller has asked
    :func:`kernel_shape_ok`."""
    n, T = scores.shape
    R = row_tile(n)
    mask, count = pl.pallas_call(
        functools.partial(_kernel, k=k, n=n),
        grid=(pl.cdiv(n, R),),
        in_specs=[pl.BlockSpec((R, T), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((R, T), lambda i: (i, 0)),
                   pl.BlockSpec((R, LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, T), jnp.bfloat16 if R % 16 == 0 else jnp.float32),
                   jax.ShapeDtypeStruct((n, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_pallas.interpret(),
        name=KERNEL,
    )(sortable(scores))
    return mask, count[:, 0]
