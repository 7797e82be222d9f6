"""Grouped matrix multiplication for an expert bank:
``rows [A, K]``, sorted by group, times ``w [G, K, N]`` — row ``r`` by the
matrix of the group it lies in, ``group_sizes [G]`` rows a group.

``jax.lax.ragged_dot`` computes the same and is the path everywhere but on
one TPU chip.  At a server's shapes (OLMoE: 1,024 rows over 64 experts, 16 a
group) the work is READING the bank, and the chip's ``ragged_dot`` took 2.7
times the time the bank's bytes need (PERF.md § 6, PR 27).  This kernel
streams the bank once, in tiles of whole columns:

* the rows are cut into tiles of ``tm``; a VISIT is a (row tile, group) pair
  in which the group has rows, and the visits are walked in row order (at
  most ``A/tm + G - 1`` of them; after JAX's ``pallas.ops.tpu.megablox``);
* the grid is (column tile, visit): a visit multiplies its whole row tile
  ``[tm, K]`` by the group's ``[K, tn]`` and stores only the group's rows.
  Consecutive visits of one group share the weight block and consecutive
  visits of one row tile the output block, so Pallas fetches each weight
  tile once and writes each output tile once;
* ``K`` is never cut: a weight tile is ``K x tn``, as large as
  ``_WEIGHT_TILE_BYTES`` allows, and the two in flight are the kernel's VMEM.

A server keeps its layers' banks STACKED, ``[L, G, K, N]``, and a Pallas
call that is handed a slice of that gets a copy of it: every layer's bank
written once more and read once more a step, more device time than the
bank's matmuls (PERF.md § 6, PR 38).  So the kernel takes the stack and the
layer's index (a fifth prefetched scalar; the weight's block index is
``(layer, group, 0, column tile)``) and each tile's DMA starts where the
layer lies.  It is ONE kernel: a bank of one layer is the stack of one.

The backward pass is ``ragged_dot``'s own (a ``custom_vjp`` round the
forward kernel): training shapes are compute bound and XLA's is fine there.
Training differentiates a bank a layer at a time (its layer scan hands each
layer its slice and collects the slice's gradient), so only the sliced form
has a gradient; the stacked form refuses one.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import pallas as _pallas

_ROW_TILE = 128
# one weight tile (K x tn); two are in flight.  8 MiB at K = 2048 in bf16 is
# the whole width of OLMoE's gate|up matrix (on the chip 3% faster than 4 MiB)
_WEIGHT_TILE_BYTES = 8 * 1024 * 1024
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def _column_tile(K: int, N: int, itemsize: int) -> int:
    """The widest multiple of 128 that divides ``N`` whose ``K x tn`` tile
    fits ``_WEIGHT_TILE_BYTES``; 0 where there is none."""
    for tn in range(N, 0, -128):
        if N % tn == 0 and K * tn * itemsize <= _WEIGHT_TILE_BYTES:
            return tn
    return 0


def kernel_shape_ok(A: int, K: int, N: int, dtype) -> bool:
    """What the kernel takes: whole row tiles, lane-aligned ``K`` and ``N``,
    bf16 or float32, and a weight tile that fits."""
    dtype = np.dtype(dtype)
    return (A % _ROW_TILE == 0 and K % 128 == 0 and N % 128 == 0
            and dtype in (np.dtype(jnp.bfloat16), np.dtype(np.float32))
            and _column_tile(K, N, dtype.itemsize) > 0)


def rows_to_whole_tiles(A: int, K: int, dtype) -> int:
    """Rows a caller adds behind its ``A`` sorted rows (in no group) so that
    :func:`grouped_matmul` takes the kernel and not ``ragged_dot``: up to
    the next whole row tile, where the kernel would run at all."""
    pad = -A % _ROW_TILE
    if pad and _pallas.use_kernel("grouped_matmul") and _pallas.single_device() \
            and kernel_shape_ok(A + pad, K, 128, dtype):
        return pad
    return 0


def visits(group_sizes, A: int, tm: int):
    """The kernel's walk: (group offsets ``[G+1]``, group of each visit,
    row tile of each visit, number of visits ``[1]``), the two lists
    ``A/tm + G - 1`` long and padded with their last real entry."""
    G = group_sizes.shape[0]
    n_max = A // tm + G - 1
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes
    first = starts // tm
    # tiles a group has rows in: first .. (end-1)//tm; none when empty
    n_tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    before = jnp.cumsum(n_tiles) - n_tiles          # visits of earlier groups
    total = jnp.sum(n_tiles)
    v = jnp.minimum(jnp.arange(n_max), jnp.maximum(total - 1, 0))
    group = jnp.searchsorted(jnp.cumsum(n_tiles), v, side="right").astype(jnp.int32)
    group = jnp.minimum(group, G - 1)
    tile = first[group] + (v - before[group])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets, group, tile.astype(jnp.int32),
            total.astype(jnp.int32).reshape(1))


def _kernel(off_ref, grp_ref, tile_ref, n_ref, layer_ref, lhs_ref, rhs_ref,
            out_ref, *, tm):
    v = pl.program_id(1)

    @pl.when(v < n_ref[0])
    def _():
        g, t = grp_ref[v], tile_ref[v]
        acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                      preferred_element_type=jnp.float32)          # [tm, tn]
        row = t * tm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        mine = (row >= off_ref[g]) & (row < off_ref[g + 1])
        # the first visit of a row tile starts from zeros, later ones from
        # what the groups before left in the resident output block
        first = (v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != t)
        keep = jnp.logical_and(jnp.logical_not(mine), jnp.logical_not(first))
        out = jnp.where(mine, acc.astype(out_ref.dtype),
                        jnp.zeros(acc.shape, out_ref.dtype))
        out_ref[...] = jnp.where(keep, out_ref[...], out)


def _call(lhs, rhs, group_sizes, layer):
    """``rhs [L, G, K, N]`` and ``layer [1]`` int32: a weight tile's DMA
    starts at the layer's offset in the stack."""
    A, K = lhs.shape
    _, G, _, N = rhs.shape
    tm = _ROW_TILE
    tn = _column_tile(K, N, lhs.dtype.itemsize)
    meta = visits(group_sizes, A, tm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(N // tn, A // tm + G - 1),
        in_specs=[
            pl.BlockSpec((tm, K),
                         lambda n, v, off, grp, tile, cnt, lay: (tile[v], 0)),
            pl.BlockSpec((None, None, K, tn),
                         lambda n, v, off, grp, tile, cnt, lay:
                         (lay[0], grp[v], 0, n)),
        ],
        out_specs=pl.BlockSpec(
            (tm, tn), lambda n, v, off, grp, tile, cnt, lay: (tile[v], n)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((A, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_pallas.interpret(),
        name="grouped_matmul",
    )(*meta, layer, lhs, rhs)


@jax.custom_vjp
def _grouped(lhs, rhs, group_sizes):
    # a bank of one layer is the stack of one, at layer 0
    return _call(lhs, rhs[None], group_sizes, jnp.zeros((1,), jnp.int32))


def _grouped_fwd(lhs, rhs, group_sizes):
    return _grouped(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _grouped_bwd(res, dy):
    lhs, rhs, group_sizes = res
    _, vjp = jax.vjp(lambda a, w: jax.lax.ragged_dot(a, w, group_sizes), lhs, rhs)
    return (*vjp(dy), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


@jax.custom_vjp
def _grouped_in_stack(lhs, rhs, group_sizes, layer):
    return _call(lhs, rhs, group_sizes, layer)


def _no_stacked_gradient(*_):
    raise NotImplementedError(
        "grouped_matmul(..., layer=) reads one layer of a stacked bank in "
        "place and is the inference paths' form: its gradient would be as "
        "large as the whole stack.  To differentiate, slice the layer "
        "(rhs[layer]) and call the sliced form, grouped_matmul(lhs, "
        "rhs[layer], group_sizes)")


_grouped_in_stack.defvjp(_no_stacked_gradient, _no_stacked_gradient)


def grouped_matmul(lhs, rhs, group_sizes, layer=None):
    """``lhs [A, K]`` (rows sorted by group, ``sum(group_sizes) == A``) times
    ``rhs [G, K, N]`` -> ``[A, N]`` in ``lhs``'s type: the kernel on one TPU
    chip where :func:`kernel_shape_ok` admits the shapes, else
    ``jax.lax.ragged_dot`` (a mesh shards the bank over ``expert``, which
    the kernel does not).

    With ``layer`` (an int32 scalar, traced or not) ``rhs`` is the STACK
    ``[L, G, K, N]`` and the product is with ``rhs[layer]``: the kernel reads
    that layer's tiles where they lie, and the program holds no copy of the
    layer.  Where the kernel does not run, or cannot read the leaf as it is
    stored (a bank of another type than the rows', an int8-injected leaf
    ``{"q8", "scale"}``), the layer is indexed here and converted alone,
    never the stack.  The stacked form has no gradient (an error says so)."""
    from deepspeed_tpu.module_inject.quantization import (dequantize_weight,
                                                          is_quantized_leaf)
    A, K = lhs.shape
    quantized = is_quantized_leaf(rhs)
    N = (rhs["q8"] if quantized else rhs).shape[-1]
    kernel = (_pallas.use_kernel("grouped_matmul") and _pallas.single_device()
              and kernel_shape_ok(A, K, N, lhs.dtype))
    if layer is not None:
        if kernel and not quantized and rhs.dtype == lhs.dtype:
            return _grouped_in_stack(lhs, rhs, group_sizes.astype(jnp.int32),
                                     jnp.asarray(layer, jnp.int32).reshape(1))
        rhs = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, layer, 0, keepdims=False), rhs)
    rhs = dequantize_weight(rhs, lhs.dtype) if quantized else rhs.astype(lhs.dtype)
    if kernel:
        return _grouped(lhs, rhs, group_sizes.astype(jnp.int32))
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)
