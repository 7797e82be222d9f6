"""Layered ZeRO-3 — per-block gather/reduce-scatter inside the layer scan.

The bulk stage-3 step (``engine._build_cc_step``) all-gathers the entire
parameter tree before the first matmul and reduce-scatters every gradient
after the last one: zero overlap, peak memory = the full unsharded tree.
This module provides the pieces that express T3's fused track-and-trigger
(arXiv 2401.16677) as *program structure* instead:

* the stacked per-block params (``params["blocks"]``, leading dim = layer)
  flow through ``lax.scan`` **still sharded**;
* the scan carry holds a ring of ``prefetch_depth`` already-gathered block
  slices — iteration *i* computes with ring head *i* while issuing the
  gather for block ``i + depth`` (double buffering for ``depth=1``), so
  XLA's async collective start/done pairs hide under block *i*'s matmuls;
* each slice gather is a ``jax.custom_vjp`` whose backward rule is the
  hierarchical (optionally quantized) reduce-scatter of that block's
  gradient — the scan transpose then reduce-scatters block *i*'s grads as
  soon as its backward slice completes, instead of holding all of them.

The per-leaf forward/backward rules preserve the ZeRO++ wire formats
(qwZ quantized gather, qgZ hierarchical reduce-scatter, hpZ fast-axis
regather of a persisted secondary shard) bit-for-bit against the bulk
path: quantization blocks never straddle a layer boundary as long as the
per-layer shard is a multiple of the quantization block size, and every
other op involved (cast, psum_scatter, stripe merge) is elementwise in
the layer dim.

Models discover the layered mode through a threading-local context (the
``mesh.manual_sharding`` pattern): the engine wraps the loss call in
``block_prefetch_scope(pf)`` and the model's scan branch asks
``current_prefetch()`` — no signature plumbing, and models traced outside
the scope keep their exact current program.
"""

import contextlib
import threading
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.comm.compression import hpz as hpz_mod
from deepspeed_tpu.comm.compression import qgz, qwz

_scope = threading.local()


def _stage_to_device(x):
    """Per-slice host→HBM stage for offloaded (``pinned_host``) block
    leaves — the device half of the offload prefetch ring.

    Issued inside the slice-gather ``custom_vjp`` *impl*, so it rides the
    same double-buffered ring as the collective: the transfer for block
    ``i + depth`` is in flight while block ``i`` computes, and the
    backward rule is untouched (cotangents stay in device memory with the
    gradient accumulator).  Whole-tree host→device transfers inside the
    scan body are exactly what ``tools/check_overlap_structure.py`` lints
    against; this per-slice form is the sanctioned site.  On the CPU
    backend a put to device memory is already the identity, which keeps
    layered-vs-bulk parity bitwise.
    """
    return jax.device_put(x, jax.memory.Space.Device)


@contextlib.contextmanager
def block_prefetch_scope(pf: "LayeredPrefetch"):
    """Announce the layered step to model code traced inside (trace-time
    only — wrap the loss-function call, like ``mesh.manual_sharding``)."""
    prev = getattr(_scope, "pf", None)
    _scope.pf = pf
    try:
        yield
    finally:
        _scope.pf = prev


def current_prefetch() -> Optional["LayeredPrefetch"]:
    """The active :class:`LayeredPrefetch`, or None outside a layered step
    (models then keep their stock scan over pre-gathered params)."""
    return getattr(_scope, "pf", None)


def _slice_tree(tree, i):
    """Layer ``i``'s slice of a stacked (leading-dim = layer) pytree."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i, axis=0, keepdims=False), tree)


# --------------------------------------------------------------------------- #
# Per-leaf slice gathers (custom_vjp: fwd = gather, bwd = reduce-scatter)
# --------------------------------------------------------------------------- #
def _reduce_slice(ct, d, axes, qg_bits, block):
    """The backward rule shared by every sharded-leaf gather: block *i*'s
    gradient cotangent reduce-scattered back to the ZeRO layout the moment
    the scan transpose produces it — same call the bulk ``reduce_grads``
    makes on the stacked gradient (elementwise in the layer dim)."""
    return qgz.hierarchical_reduce_scatter(ct, d, axes, bits=qg_bits,
                                           block_size=block, mean=True)


def _replicated_gather(group, stage=False):
    """Replicated leaf (below the shard threshold): identity forward,
    gradient-mean backward — the bulk path's ``pmean`` per leaf."""
    def impl(x):
        return _stage_to_device(x) if stage else x

    @jax.custom_vjp
    def gather(x):
        return impl(x)

    def fwd(x):
        return impl(x), None

    def bwd(_, ct):
        return (lax.pmean(ct, group),)

    gather.defvjp(fwd, bwd)
    return gather


def _sharded_gather(d, axes, group, qw_bits, qg_bits, block, stage=False):
    """Sharded leaf, primary-shard gather: exact tiled all-gather, or the
    qwZ blockwise-quantized wire format when ``qw_bits`` is set.  With
    ``stage`` the host-resident shard slice is moved into device memory
    first, so the wire carries device-side bytes."""
    if qw_bits is not None:
        def impl(x):
            if stage:
                x = _stage_to_device(x)
            return qwz.quantized_all_gather(x, axes, dim=d, bits=qw_bits,
                                            block_size=block)
    else:
        def impl(x):
            if stage:
                x = _stage_to_device(x)
            return lax.all_gather(x, group, axis=d, tiled=True)

    @jax.custom_vjp
    def gather(x):
        return impl(x)

    def fwd(x):
        return impl(x), None

    def bwd(_, ct):
        return (_reduce_slice(ct, d, axes, qg_bits, block),)

    gather.defvjp(fwd, bwd)
    return gather


def _hpz_gather(d, axes, sizes, group, qg_bits, block, reuse, stage=False):
    """hpZ leaf: forward regathers the persisted secondary shard over the
    fast axis only (both refresh and reuse — the refresh-path full tensor
    *is* the fast regather of the just-built secondary, see
    ``hpz.hierarchical_gather``); backward reduce-scatters into the
    *primary* layout and sends a zero cotangent to the secondary.

    Replicated leaves (``d is None``) keep the bulk asymmetry: refresh
    computes with the exact fp32 primary, reuse with the secondary-dtype
    round trip.
    """
    if d is None:
        def impl(p, s):
            out = s.astype(jnp.float32) if reuse else p
            return _stage_to_device(out) if stage else out

        def bwd(s, ct):
            return lax.pmean(ct, group), jnp.zeros_like(s)
    else:
        def impl(p, s):
            # the hpZ secondary shard is the gathered-from copy: under
            # offload it is the host-resident one, staged per slice
            if stage:
                s = _stage_to_device(s)
            return hpz_mod.fast_regather(s, d, axes[1], w_slow=sizes[0])

        def bwd(s, ct):
            return (_reduce_slice(ct, d, axes, qg_bits, block),
                    jnp.zeros_like(s))

    @jax.custom_vjp
    def gather(p, s):
        return impl(p, s)

    def fwd(p, s):
        return impl(p, s), s

    gather.defvjp(fwd, bwd)
    return gather


# --------------------------------------------------------------------------- #
# The prefetch object the engine hands to the model
# --------------------------------------------------------------------------- #
class LayeredPrefetch:
    """Per-slice gather plan for one layered step.

    ``plan`` is a pytree matching ONE block slice, each leaf the dim its
    shard occupies in the slice (stacked dim minus the layer dim) or None
    for replicated leaves.  ``gather_block(blocks, i)`` slices layer ``i``
    out of the stacked (sharded) blocks tree, gathers every leaf through
    its custom-vjp rule and casts to the compute dtype — producing exactly
    the block-params tree the model's scan body already consumes.
    """

    def __init__(self, plan, cc: dict, compute_dtype,
                 hpz: bool = False, reuse: bool = False,
                 depth: int = 1, offload: bool = False):
        axes, sizes = cc["axes"], cc["sizes"]
        group = axes if len(axes) > 1 else axes[0]
        qw, qg, block = cc["qw_bits"], cc["qg_bits"], cc["block"]
        self.hpz = hpz
        self.depth = max(1, int(depth))
        self.compute_dtype = compute_dtype
        self.offload = bool(offload)

        def leaf_fn(d):
            if hpz:
                return _hpz_gather(d, axes, sizes, group, qg, block, reuse,
                                   stage=self.offload)
            if d is None:
                return _replicated_gather(group, stage=self.offload)
            return _sharded_gather(d, axes, group, qw, qg, block,
                                   stage=self.offload)

        # callables are pytree leaves: the fns tree mirrors one block slice
        self.fns = jax.tree.map(leaf_fn, plan,
                                is_leaf=lambda x: x is None or isinstance(x, int))

    def clamped_depth(self, n_layer: int) -> int:
        """Never prefetch past the last block: with ``depth >= n_layer``
        the ring would just re-gather block L-1 (clamped index) with zero
        cotangents — wire for nothing."""
        return max(1, min(self.depth, max(1, n_layer - 1)))

    def gather_block(self, blocks, i):
        """Gather layer ``i``: slice → per-leaf custom-vjp gather → cast.

        ``blocks`` is the tree the engine placed at ``params["blocks"]``:
        the sharded stacked leaves, or ``{"p": primary, "s": secondary}``
        under hpZ.  The cast to the compute dtype happens *outside* the
        custom-vjp boundary so its transpose (cotangent back to fp32) sits
        exactly where the bulk path's whole-tree cast puts it.
        """
        if self.hpz:
            p = _slice_tree(blocks["p"], i)
            s = _slice_tree(blocks["s"], i)
            out = jax.tree.map(lambda fn, a, b: fn(a, b), self.fns, p, s)
        else:
            sl = _slice_tree(blocks, i)
            out = jax.tree.map(lambda fn, a: fn(a), self.fns, sl)
        return jax.tree.map(lambda a: a.astype(self.compute_dtype), out)
