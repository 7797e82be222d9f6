"""hpZ — hierarchical partitioning / secondary weight sharding (ZeRO++ §4.2).

ZeRO-3 shards each parameter across the FULL data-parallel world, so every
forward *and* backward all-gather crosses the slow inter-host axis.  hpZ
trades memory for bandwidth: after the one unavoidable slow-axis hop, each
host keeps a *secondary shard* — the parameter partitioned only over the
fast intra-host axis, in a compact dtype (bf16 by default).  Re-gathers
within the same parameter-freshness window (micro-steps of one gradient
accumulation boundary) then touch only the fast axis.

Two entry points mirror the two programs the engine builds:

* ``hierarchical_gather``  — the refresh path: slow-axis hop (quantized when
  qwZ is on, else a ``secondary_dtype`` cast) + fast-axis regather.  Returns
  the full tensor AND the secondary shard to persist.
* ``fast_regather``        — the reuse path: fast-axis all-gather of a
  persisted secondary shard.  No slow-axis traffic at all.

Layout: a dim sharded over ``(slow, fast)`` major→minor has global chunk
index ``i_slow·W_fast + i_fast``.  The slow gather therefore concatenates
W_slow *interleaved stripes*, and the fast regather must merge its W_fast
members one level *inside* the slow grouping — the (W_slow, W_fast, chunk)
moveaxis below, not a plain leading-dim merge.
"""

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.comm.compression import core, qwz


def fast_regather(secondary: jax.Array, dim: int, fast_axis: str,
                  w_slow: int, out_dtype=jnp.float32) -> jax.Array:
    """All-gather a persisted secondary shard over the fast axis only.

    ``secondary``'s ``dim`` holds ``w_slow`` stripes of this device's fast
    chunk back to back; each gathered member must slot in at position
    (slow_stripe, member) of the full dim.
    """
    w_fast = lax.axis_size(fast_axis)
    parts = lax.all_gather(secondary.astype(out_dtype), fast_axis,
                           axis=0, tiled=False)      # [Wf, ..., Ws*g, ...]
    shape = parts.shape
    g = shape[1 + dim] // w_slow
    parts = parts.reshape(shape[:1 + dim] + (w_slow, g) + shape[2 + dim:])
    parts = jnp.moveaxis(parts, 0, 1 + dim)          # [..., Ws, Wf, g, ...]
    return parts.reshape(shape[1:1 + dim] + (w_slow * w_fast * g,)
                         + shape[2 + dim:])


def slow_gather_secondary(x: jax.Array, dim: int, axes: Sequence[str],
                          quantize_bits: Optional[int] = None,
                          block_size: int = 256,
                          secondary_dtype=jnp.bfloat16) -> jax.Array:
    """Just the slow-axis hop: gather this device's primary shard into the
    fast-axis-only secondary shard (dim becomes W_slow interleaved stripes
    of the local chunk, in ``secondary_dtype``).

    Shared by :func:`hierarchical_gather` (bulk refresh) and the layered
    step's standalone secondary-refresh program, which builds the stacked
    secondary once per parameter-freshness window while the per-block
    ``fast_regather`` runs inside the scan (``compression/layered.py``).
    The slow hop treats every other dim — including a leading stacked
    layer dim — as batch, so a slice of the stacked secondary equals the
    secondary of the slice.
    """
    from deepspeed_tpu.comm.comm import compressed_op_span

    slow = axes[0]
    w_slow = lax.axis_size(slow)
    m = x.size
    if quantize_bits is not None:
        return qwz.quantized_all_gather(
            x, (slow,), dim=dim, bits=quantize_bits, block_size=block_size,
            out_dtype=secondary_dtype)
    wire = qwz.logical_bytes(m, w_slow, jnp.dtype(secondary_dtype).itemsize)
    with compressed_op_span(
            "hpz_secondary_gather",
            logical_bytes=qwz.logical_bytes(m, w_slow),
            wire_bytes=wire, group=(slow,)):
        return qwz.merge_at_dim(
            lax.all_gather(x.astype(secondary_dtype), slow,
                           axis=0, tiled=False), dim)


def hierarchical_gather(x: jax.Array, dim: int, axes: Sequence[str],
                        quantize_bits: Optional[int] = None,
                        block_size: int = 256,
                        secondary_dtype=jnp.bfloat16,
                        out_dtype=jnp.float32,
                        checkpoint_fast: bool = True
                        ) -> Tuple[jax.Array, jax.Array]:
    """Gather ``x`` (the primary shard, dim partitioned over ``axes``
    major→minor = (slow, fast)) into the full tensor, returning
    ``(full, secondary)`` where ``secondary`` is the fast-axis-only shard
    to persist for ``fast_regather``.

    The slow hop uses qwZ quantization when ``quantize_bits`` is set,
    otherwise a plain all-gather of the ``secondary_dtype`` cast (still a
    2x wire saving vs fp32).  The fast regather is wrapped in
    ``jax.checkpoint`` so the full weights are rematerialized rather than
    saved for backward — hpZ's memory story depends on only the secondary
    shard being live between fwd and bwd.
    """
    from deepspeed_tpu.comm.comm import compressed_op_span

    slow, fast = axes
    w_slow = lax.axis_size(slow)

    # dim now Ws*g: the fast-axis shard of the full dim
    secondary = slow_gather_secondary(x, dim, axes, quantize_bits=quantize_bits,
                                      block_size=block_size,
                                      secondary_dtype=secondary_dtype)

    def _fast(sec):
        w_fast = lax.axis_size(fast)
        with compressed_op_span(
                "hpz_fast_all_gather",
                logical_bytes=qwz.logical_bytes(
                    sec.size, w_fast, jnp.dtype(secondary_dtype).itemsize),
                wire_bytes=qwz.logical_bytes(
                    sec.size, w_fast, jnp.dtype(secondary_dtype).itemsize),
                group=(fast,)):
            return fast_regather(sec, dim, fast, w_slow, out_dtype=out_dtype)

    if checkpoint_fast:
        _fast = jax.checkpoint(_fast)
    return _fast(secondary), secondary


# --------------------------------------------------------------------------- #
# Byte accounting (per device, receive-side)
# --------------------------------------------------------------------------- #
def refresh_wire_bytes(shard_elems: int, w_slow: int, w_fast: int,
                       quantize_bits: Optional[int] = None,
                       block_size: int = 256,
                       secondary_itemsize: int = 2) -> int:
    """Slow hop (quantized or secondary-dtype cast) + fast regather."""
    if quantize_bits is not None:
        slow = qwz.wire_bytes(shard_elems, w_slow, quantize_bits, block_size)
    else:
        slow = (w_slow - 1) * shard_elems * secondary_itemsize
    fast = (w_fast - 1) * shard_elems * w_slow * secondary_itemsize
    return slow + fast


def reuse_wire_bytes(shard_elems: int, w_slow: int, w_fast: int,
                     secondary_itemsize: int = 2) -> int:
    """A reuse-path gather: fast axis only, secondary dtype."""
    return (w_fast - 1) * shard_elems * w_slow * secondary_itemsize


def logical_bytes(shard_elems: int, w_slow: int, w_fast: int,
                  itemsize: int = 4) -> int:
    """The flat fp32 all-gather over the full world that standard ZeRO-3
    would run for the same primary shard."""
    world = w_slow * w_fast
    return (world - 1) * shard_elems * itemsize
