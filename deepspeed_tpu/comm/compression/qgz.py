"""qgZ — hierarchical quantized gradient reduce-scatter (ZeRO++ §4.3).

The gradient reduce-scatter is split by mesh topology: reduction along the
FAST (innermost, intra-host ICI) axes stays exact fp32 ``psum_scatter``;
the remaining hop along the SLOW (outermost, inter-host DCN) axis travels
as blockwise-quantized codes through an all-to-all — each slow-axis peer
quantizes the sub-chunk it is about to hand off, the receiver dequantizes
and finishes the sum in fp32.  Unlike a naive "quantize the allreduce"
this never accumulates *in* low precision: every partial sum is fp32, only
the wire format is quantized — the property that lets qgZ skip error
feedback (one rounding per hop, not a compounding series).

On a single-axis mesh (the 8-device CPU test mesh, or a one-host TPU slice
where ZeRO folds all data parallelism into ``fsdp``) there is no fast/slow
split: the whole reduce-scatter is the quantized all-to-all hop.

Layout contract: for a dim partitioned over ``axes`` MAJOR → MINOR, device
(i_0, .., i_k) must end up with chunk index ``i_0·W_1·..·W_k + .. + i_k``
(the partition-spec order).  The dim is therefore viewed as
``(W_0, .., W_k, chunk)`` and each stage scatters its own axis' sub-dim —
stage order cannot produce a transposed layout by construction.
"""

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.comm.compression import core


def quantized_reduce_scatter_1d(y: jax.Array, axis: str, pos: int,
                                bits: int = 8, block_size: int = 256) -> jax.Array:
    """Reduce over mesh ``axis`` and scatter ``y``'s dim ``pos`` (whose size
    equals the axis size) with a quantized all-to-all: peer ``j`` receives
    everyone's quantized slice ``j``, dequantizes, and sums in fp32.
    Returns ``y`` with dim ``pos`` reduced to size 1.
    """
    w = lax.axis_size(axis)
    z = jnp.moveaxis(y, pos, 0)                       # [w, ...rest]
    rest_shape = z.shape[1:]
    m = math.prod(rest_shape) if rest_shape else 1
    z = z.reshape(w, m).astype(jnp.float32)
    q = core.quantize_blockwise(z, bits=bits, block_size=block_size)
    # row j of every peer → peer j (the compressed.py exchange pattern)
    theirs = core.QuantizedBlocks(
        lax.all_to_all(q.data, axis, split_axis=0, concat_axis=0),
        lax.all_to_all(q.scale, axis, split_axis=0, concat_axis=0),
        lax.all_to_all(q.zero, axis, split_axis=0, concat_axis=0))
    mine = core.dequantize_blockwise(theirs, m, bits=bits).sum(axis=0)
    return jnp.moveaxis(mine.reshape((1,) + rest_shape), 0, pos)


def hierarchical_reduce_scatter(g: jax.Array, dim: int, axes: Sequence[str],
                                bits: Optional[int] = 8, block_size: int = 256,
                                mean: bool = True) -> jax.Array:
    """Reduce ``g`` over ``axes`` (major → minor) and keep this device's
    chunk of dim ``dim`` in partition-spec order.

    ``bits=None`` runs the same two-level schedule exactly (fp32 both hops)
    — the apples-to-apples baseline for parity tests and for configs with
    ``zero_quantized_gradients`` off.  ``mean=True`` divides by the total
    reduction world (the data-parallel gradient mean).
    """
    from deepspeed_tpu.comm.comm import compressed_op_span

    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    sizes = [lax.axis_size(a) for a in axes]
    world = 1
    for s in sizes:
        world *= s
    assert g.shape[dim] % world == 0, (
        f"dim {dim} (size {g.shape[dim]}) not divisible by axes product {world}")
    chunk = g.shape[dim] // world

    with compressed_op_span(
            "qgz_reduce_scatter",
            logical_bytes=logical_bytes(g.size, world),
            wire_bytes=wire_bytes(g.size, sizes, bits, block_size),
            group=axes):
        pre = g.shape[:dim]
        post = g.shape[dim + 1:]
        y = g.reshape(pre + tuple(sizes) + (chunk,) + post).astype(jnp.float32)
        if mean:
            y = y / world
        # fast/minor stages: exact fp32, innermost first
        for i in range(len(axes) - 1, 0, -1):
            y = lax.psum_scatter(y, axes[i], scatter_dimension=len(pre) + i,
                                 tiled=True)
        # slow/major hop: quantized (or exact when bits is None)
        if bits is None:
            y = lax.psum_scatter(y, axes[0], scatter_dimension=len(pre),
                                 tiled=True)
        else:
            y = quantized_reduce_scatter_1d(y, axes[0], len(pre),
                                            bits=bits, block_size=block_size)
    return y.reshape(pre + (chunk,) + post)


# --------------------------------------------------------------------------- #
# Byte accounting (per device, receive-side)
# --------------------------------------------------------------------------- #
def wire_bytes(n: int, axes_sizes: Sequence[int], bits: Optional[int] = 8,
               block_size: int = 256) -> int:
    """Bytes received per device across both levels for an n-element leaf:
    fp32 ring psum_scatter per fast stage, then the quantized all-to-all
    over the slow axis (or fp32 when bits is None)."""
    total = 0
    n_cur = n
    for w in reversed(list(axes_sizes[1:])):
        total += (w - 1) * n_cur // w * 4
        n_cur //= w
    w0 = axes_sizes[0]
    if bits is None:
        total += (w0 - 1) * n_cur // w0 * 4
    else:
        total += (w0 - 1) * core.quantized_nbytes(n_cur // w0, bits, block_size)
    return total


def logical_bytes(n: int, world: int, itemsize: int = 4) -> int:
    """The flat single-level fp32 reduce-scatter the standard ZeRO-3 path
    would run: ring receive of (world-1)/world of the tensor."""
    return (world - 1) * (n // world) * itemsize
