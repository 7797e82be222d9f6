"""Sequence/context parallelism — first-class here, absent in the reference.

The reference snapshot (v0.8.3) predates DeepSpeed-Ulysses and has no
SP/CP implementation (SURVEY.md §5.7); its long-sequence answer was
block-sparse attention.  This module fills the gap with the two standard
TPU-native schemes over the ``seq`` mesh axis:

* **Ulysses-style all-to-all** (`ulysses_attention`): activations arrive
  sequence-sharded ``[B, S/sp, H, D]``; re-shard to head-sharded
  ``[B, S, H/sp, D]`` for exact attention, then back.  Expressed purely as
  sharding constraints — XLA inserts the two all-to-alls (this is the
  idiomatic SPMD formulation; DeepSpeed-Ulysses codes the a2a by hand).

* **Ring attention** (`ring_attention`): KV blocks rotate around the
  ``seq`` ICI ring via ``ppermute`` while each device keeps its Q shard.
  The per-hop body is the **Pallas flash kernel**
  (``ops/pallas/flash_attention.flash_block_fwd``) — O(block) memory, MXU
  tiles, fp32 online softmax — and hop outputs are merged by their
  log-sum-exp, so nothing ever materializes an ``[Sl, Sl]`` score tensor.
  Under ``causal=True`` hops whose KV block lies entirely in the future are
  **skipped** (``lax.cond``): the ring computes sp(sp+1)/2 of sp^2 score
  blocks, matching flash's causal block skipping.  The backward pass is a
  custom VJP that re-rotates KV with dK/dV accumulators riding alongside
  (one extra ppermute pair per hop) and evaluates the flash backward
  kernels against the *final* merged lse — exact gradients with O(S/sp)
  memory and no stored probabilities.

Both keep the framework-wide attention signature
``fn(q, k, v, *, causal, bias=None, alibi=None) -> out`` with
``[batch, seq, heads, head_dim]``.  ALiBi goes through ``alibi`` (per-head
slopes, [H]): the flash kernel synthesizes ``slope * (k_pos - q_pos)`` from
*local* iotas, and the per-hop global-offset term ``slope * (src - idx) *
Sl`` — constant over a hop's score block — is folded into that hop's lse
(softmax is shift-invariant per hop; the constant re-enters through the
merge).  O(H) memory, so BLOOM-style models train sequence-parallel at any
length.  A dense ``bias`` (rel-pos etc.) is also supported: its Q rows are
sharded with the local shard and KV-block columns are dynamic-sliced per
hop (O(Hb·S/sp·S) per device — inherent to a dense O(S^2) bias the caller
already materialized; prefer ``alibi``).  Both bias forms are constants
under differentiation, the framework-wide kernel-path contract
(``ops/attention.py`` module docstring).
"""

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.parallel import mesh as mesh_lib

NEG_INF = -1e30


_constrain = mesh_lib.constrain


def ulysses_attention(q, k, v, *, causal: bool = True, bias=None, alibi=None,
                      inner: Optional[Callable] = None):
    """All-to-all head/sequence re-sharding attention (DeepSpeed-Ulysses
    scheme, built after the reference's era).  Requires ``heads % sp == 0``
    for q AND for the (grouped) KV head count.  Uneven KV heads (with even
    q heads) are expanded to full head count so the a2a shards evenly —
    O(S · H) KV memory, the documented trade; uneven q heads reroute to
    ring attention (sequence-sharded, never expands) unless the caller
    pinned an ``inner`` kernel."""
    from deepspeed_tpu.ops.attention import (reference_attention,
                                             expand_kv_heads, canonical_bias)
    caller_inner = inner is not None
    inner = inner or reference_attention
    if mesh_lib.has_mesh() and not mesh_lib.in_manual_mode():
        mesh = mesh_lib.get_mesh()
        head_div = int(mesh.shape["seq"] * mesh.shape["tensor"])
        H, Hkv = q.shape[2], k.shape[2]
        if head_div > 1 and H % head_div == 0 and Hkv % head_div:
            # grouped KV with too few heads for the a2a head sharding:
            # expand to full head count so the re-shard stays even (memory
            # cost documented; ring is the alternative that never expands)
            k, v = expand_kv_heads(q, k, v)
        elif head_div > 1 and H % head_div and not caller_inner:
            # q heads themselves can't be head-sharded: ring shards the
            # sequence axis instead.  Only reroute on the default inner —
            # an explicit caller kernel keeps the (GSPMD-padded) a2a path.
            return ring_attention(q, k, v, causal=causal, bias=bias,
                                  alibi=alibi)
    B = mesh_lib.BATCH_AXES
    # seq-sharded on entry (the transformer keeps activations seq-sharded);
    # heads keep their Megatron 'tensor' sharding throughout
    q, k, v = (_constrain(x, B, "seq", "tensor", None) for x in (q, k, v))
    # a2a: full sequence, heads split over seq x tensor
    q, k, v = (_constrain(x, B, None, ("seq", "tensor"), None) for x in (q, k, v))
    bias = canonical_bias(bias)
    if bias is not None and bias.shape[1] > 1:
        # per-head bias follows the head sharding; the inner kernel slices it
        bias = _constrain(bias, None, ("seq", "tensor"), None, None)
    o = inner(q, k, v, causal=causal, bias=bias, alibi=alibi)
    # a2a back to seq-sharded
    return _constrain(o, B, "seq", "tensor", None)


# --------------------------------------------------------------------------- #
# Ring attention: flash-kernel hop body + lse merge, custom VJP
# --------------------------------------------------------------------------- #
def _hop_bias(bias, src, Sl):
    """Dynamic-slice the in-flight KV block's columns out of the local
    dense-bias slice [Bb, Hb, Sl, S]."""
    if bias is None:
        return None
    return jax.lax.dynamic_slice_in_dim(bias, src * Sl, Sl, axis=3)


def _alibi_shift(slopes, src, idx, Sl):
    """Per-head constant ALiBi term for a whole hop block, to add to an
    lse [B, H, Sl]:
    slope * (k_global - q_global) = slope*(src - idx)*Sl + local part."""
    return (slopes[None, :, None]
            * ((src - idx) * Sl).astype(jnp.float32))


def _ring_fwd_impl(q, k, v, bias, slopes, causal, sp, scale, blk):
    """[B, H, Sl, D] local shards inside shard_map.  Returns (o, lse
    [B, H, Sl], the flash kernels' own shape for row statistics).

    Hop 0 (the diagonal block — the only one needing a causal kernel) is
    peeled; hops 1..sp-1 run in a single rolled ``fori_loop`` so the flash
    kernel is traced once, not O(sp) times."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_block_fwd
    idx = jax.lax.axis_index("seq")
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    B, H, Sl, D = q.shape

    def hop(j, kc, vc, hop_causal):
        src = (idx - j) % sp
        o_j, lse_j = flash_block_fwd(q, kc, vc, _hop_bias(bias, src, Sl),
                                     slopes, causal=hop_causal, scale=scale,
                                     bq=blk, bk=blk)
        if slopes is not None:
            lse_j = lse_j + _alibi_shift(slopes, src, idx, Sl)
        return o_j.astype(jnp.float32), lse_j

    o, lse = hop(0, k, v, causal)

    def body(j, carry):
        o, lse, kc, vc = carry
        kc = jax.lax.ppermute(kc, "seq", perm)
        vc = jax.lax.ppermute(vc, "seq", perm)
        if causal:
            # hop j's block is fully in the future for devices idx < j:
            # skip the kernel entirely (sp(sp+1)/2 of sp^2 blocks computed)
            o_j, lse_j = jax.lax.cond(
                idx >= j,
                lambda kv: hop(j, kv[0], kv[1], False),
                lambda kv: (jnp.zeros((B, H, Sl, D), jnp.float32),
                            jnp.full((B, H, Sl), NEG_INF, jnp.float32)),
                (kc, vc))
        else:
            o_j, lse_j = hop(j, kc, vc, False)
        lse_new = jnp.logaddexp(lse, lse_j)
        o = (o * jnp.exp(lse - lse_new)[..., None]
             + o_j * jnp.exp(lse_j - lse_new)[..., None])
        return o, lse_new, kc, vc

    o, lse, _, _ = jax.lax.fori_loop(1, sp, body, (o, lse, k, v))
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _ring_flash(q, k, v, bias, slopes, causal, sp, scale, blk):
    o, _ = _ring_fwd_impl(q, k, v, bias, slopes, causal, sp, scale, blk)
    return o


def _ring_flash_vjp_fwd(q, k, v, bias, slopes, causal, sp, scale, blk):
    o, lse = _ring_fwd_impl(q, k, v, bias, slopes, causal, sp, scale, blk)
    return o, (q, k, v, bias, slopes, o, lse)


def _ring_flash_vjp_bwd(causal, sp, scale, blk, res, do):
    """Distributed flash backward: KV re-rotates with dK/dV accumulators
    riding alongside; each hop runs the flash backward kernels against the
    final merged lse.  kc/vc rotate at hop START (j>=1, mirroring the
    forward — the last hop's blocks are dead after compute); dk/dv rotate
    at hop END every hop, so after sp ppermutes the accumulators are home —
    holding the full dK/dV for the device's own block.  Hop 0 is peeled
    (causal kernel); hops 1..sp-1 are a rolled ``fori_loop``."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_block_bwd
    q, k, v, bias, slopes, o, lse = res
    idx = jax.lax.axis_index("seq")
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    B, H, Sl, D = q.shape
    Hkv = k.shape[1]
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)                                     # [B,H,Sl]

    def hop_bwd(j, kc, vc, hop_causal):
        src = (idx - j) % sp
        lse_adj = lse
        if slopes is not None:   # undo the per-hop global-offset fold
            lse_adj = lse - _alibi_shift(slopes, src, idx, Sl)
        dq_j, dk_j, dv_j = flash_block_bwd(
            q, kc, vc, do, lse_adj, delta, _hop_bias(bias, src, Sl), slopes,
            causal=hop_causal, scale=scale, bq=blk, bk=blk)
        return (dq_j.astype(jnp.float32), dk_j.astype(jnp.float32),
                dv_j.astype(jnp.float32))

    zq = lambda: jnp.zeros((B, H, Sl, D), jnp.float32)
    zkv = lambda: jnp.zeros((B, Hkv, Sl, D), jnp.float32)
    dq, dk, dv = hop_bwd(0, k, v, causal)
    dk = jax.lax.ppermute(dk, "seq", perm)
    dv = jax.lax.ppermute(dv, "seq", perm)

    def body(j, carry):
        dq, dk, dv, kc, vc = carry
        kc = jax.lax.ppermute(kc, "seq", perm)
        vc = jax.lax.ppermute(vc, "seq", perm)
        if causal:
            dq_j, dk_j, dv_j = jax.lax.cond(
                idx >= j, lambda kv: hop_bwd(j, kv[0], kv[1], False),
                lambda kv: (zq(), zkv(), zkv()), (kc, vc))
        else:
            dq_j, dk_j, dv_j = hop_bwd(j, kc, vc, False)
        dk = jax.lax.ppermute(dk + dk_j, "seq", perm)
        dv = jax.lax.ppermute(dv + dv_j, "seq", perm)
        return dq + dq_j, dk, dv, kc, vc

    dq, dk, dv, _, _ = jax.lax.fori_loop(1, sp, body, (dq, dk, dv, k, v))
    db = None if bias is None else jnp.zeros_like(bias)
    da = None if slopes is None else jnp.zeros_like(slopes)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            db, da)


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def ring_attention(q, k, v, *, causal: bool = True, bias=None, alibi=None):
    """Ring attention over the ``seq`` mesh axis (Liu et al. 2023 scheme,
    pipelined KV ppermute, Pallas flash hop body).  Falls back to plain
    attention when sp == 1.  Grouped KV circulates at its native head
    count [B, Hkv, Sl, D] — the flash kernels index grouped KV via their
    BlockSpecs, so ppermute traffic and per-device KV memory stay
    O(S/sp · Hkv), never expanded."""
    from deepspeed_tpu.ops.attention import reference_attention, canonical_bias
    if not mesh_lib.has_mesh() or mesh_lib.in_manual_mode():
        return reference_attention(q, k, v, causal=causal, bias=bias, alibi=alibi)
    mesh = mesh_lib.get_mesh()
    sp = int(mesh.shape["seq"])
    if sp == 1:
        return reference_attention(q, k, v, causal=causal, bias=bias, alibi=alibi)
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    Sl = S // sp
    # largest flash block that tiles the local shard (128 when it divides;
    # any divisor keeps the O(Sl·blk) kernel memory bound — only truly
    # degenerate shards fall back to the dense path)
    blk = next((b for b in range(min(128, Sl), 0, -1) if Sl % b == 0), 1)
    if S % sp or H % Hkv or blk < 8:
        return reference_attention(q, k, v, causal=causal, bias=bias, alibi=alibi)
    scale = 1.0 / np.sqrt(D)
    slopes = None if alibi is None else jnp.asarray(alibi, jnp.float32).reshape(H)
    bias = canonical_bias(bias)

    # full-manual shard_map (the Pallas call has no SPMD partitioning rule):
    # batch over data/fsdp/expert, heads over tensor, sequence manual over
    # the ring axis — replicate any dim the shapes can't split evenly.
    batch_axes = mesh_lib.BATCH_AXES
    batch_div = int(np.prod([mesh.shape[a] for a in batch_axes]))
    tp = int(mesh.shape["tensor"])
    b_ax = batch_axes if batch_div > 1 and B % batch_div == 0 else None
    h_ax = ("tensor" if tp > 1 and H % tp == 0 and Hkv % tp == 0 else None)
    spec = PartitionSpec(b_ax, "seq", h_ax, None)
    in_specs = [spec, spec, spec]
    args = [q, k, v]
    if bias is not None:
        if bias.shape[3] != S:      # columns must be sliceable per hop
            bias = jnp.broadcast_to(bias, bias.shape[:3] + (S,))
        bias = bias.astype(jnp.float32)
        in_specs.append(PartitionSpec(
            b_ax if bias.shape[0] > 1 else None,
            h_ax if bias.shape[1] > 1 else None,
            "seq" if bias.shape[2] == S else None, None))
        args.append(bias)
    if slopes is not None:
        in_specs.append(PartitionSpec(h_ax))
        args.append(slopes)
    nb, ns = bias is not None, slopes is not None

    def body(q, k, v, *rest):
        b = rest[0] if nb else None
        sl = rest[-1] if ns else None
        # [B, Sl, H, D] -> kernel layout [B, H, Sl, D]
        qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        if b is not None and b.shape[2] == 1:
            # kernel BlockSpecs index q-rows; expand a broadcast row dim
            b = jnp.broadcast_to(b, b.shape[:2] + (qt.shape[2], b.shape[3]))
        with mesh_lib.manual_sharding():
            o = _ring_flash(qt, kt, vt, b, sl, causal, sp, scale, blk)
        return o.transpose(0, 2, 1, 3)

    fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=spec, check_vma=False)
    return fn(*args)
