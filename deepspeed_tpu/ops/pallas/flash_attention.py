"""Pallas TPU flash attention (training fast path, forward + backward).

TPU-native replacement for the reference's fused attention CUDA kernels
(``csrc/transformer/softmax_kernels.cu`` and the strided-batch-gemm pipeline
of ``csrc/transformer/ds_transformer_cuda.cpp``).  Online-softmax tiling:
O(S) memory, MXU-shaped [128, head_dim] tiles, fp32 accumulation, bf16
operands.

Capabilities beyond the round-3 kernel:

* **Grouped-query attention** — K/V may carry ``Hkv < H`` heads
  (``H % Hkv == 0``).  The kernel maps query head ``h`` onto KV head
  ``h // (H//Hkv)`` via the BlockSpec index map, so grouped K/V are never
  materialized at full head count (the reference expands on the host;
  round 3 expanded in ``models/gpt.py:_expand_kv`` — both pay HBM for it).
  The backward dK/dV kernel grids over *KV* heads and accumulates the
  group's query heads in-register.

* **In-kernel ALiBi** — ``alibi`` takes the per-head slopes (an [H] vector,
  O(H) memory) and the kernel computes ``slope * (k_pos - q_pos)`` from
  iotas on the VPU: zero HBM traffic for the bias, so BLOOM-style models
  ride the flash path at any sequence length.  The reference bakes alibi
  into its softmax kernel the same way
  (``csrc/transformer/inference/csrc/softmax.cu``).

* **Additive logit bias** — an optional dense ``bias`` operand
  broadcastable to ``[B, H, S, S]`` (relative-position bias and other
  non-ALiBi biases), added to the scaled scores before the online softmax.
  Inherently O(S^2) HBM (the caller materialized it); prefer ``alibi``
  when the bias is ALiBi-shaped.  Both bias forms are CONSTANTS under
  differentiation: gradients flow to q/k/v but not to the bias (a learned
  T5-style bias would need an O(S^2) dbias output that defeats flash
  memory scaling).

Layout convention here is [batch, heads, seq, head_dim]; the public wrapper
(`flash_attention`) takes the framework-wide [batch, seq, heads, head_dim].

Mosaic layout notes (learned the hard way — round 1 shipped an lse output
of shape [B, H, S] with block (1, 1, bq), which Mosaic rejects because the
second-to-last block dim (1) is neither a multiple of the sublane tile nor
equal to H): every operand/result carries the row-statistics (lse, delta)
as [B, H, S, 1] so the trailing two block dims (bq, 1) are (sublane-multiple,
full-dim) — always legal.

SPMD: ``pallas_call`` has no partitioning rule, so the public wrapper runs
the kernel under ``shard_map`` over the batch (data/fsdp/expert) and head
(seq × tensor) mesh axes whenever a global mesh is active.  Putting the
``seq`` axis on the HEAD dim (sequence replicated inside the kernel) makes
the wrapper itself the Ulysses all-to-all: activations arriving
sequence-sharded are re-sharded by jit to head-sharded full-sequence form,
the exact re-shard ``parallel/sequence.py:ulysses_attention`` expresses as
sharding constraints.  Ring attention (O(S/sp) memory) remains the explicit
alternative for sequences too long to replicate per-device.

On the ``cpu`` platform (``ops.pallas.interpret``) the same kernels run
through the Pallas interpreter so CPU CI validates them against the jnp
reference — the
analogue of the reference's kernel-vs-HF-modeling parity tests
(``tests/unit/ops/accelerators/test_accelerator_forward.py``).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops import pallas as _pallas

NEG_INF = -1e30

_PARALLEL3 = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))


#: (q_shape, reason-class) combos already warned about — the demotion is
#: per-call, the telemetry warning one-shot so a training loop doesn't
#: log once per step
_FALLBACK_WARNED = set()


def _fallback_warn_once(shape, reason: str) -> None:
    key = (tuple(shape), reason.split(":")[0])
    if key in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add(key)
    from deepspeed_tpu.utils.logging import logger
    logger.warning("flash_attention %s: %s — demoting to reference "
                   "attention (further occurrences silenced)", tuple(shape),
                   reason)


def _block_sizes(S: int, bq: Optional[int], bk: Optional[int]):
    """Default blocks: largest divisor of S up to 256 (q) / 512 (k) —
    measured on v5e (r5): (256, 512) beats (128, 128) ~2.3x end-to-end at
    S=512 (fewer online-softmax rescales, larger MXU tiles) and also wins
    at S=1024 over (256, 1024).

    Requested sizes (the caller's) are CLAMPED to the largest divisor of S at
    most the request — never asserted on — so an odd S degrades to a
    smaller block or to the reference fallback instead of crashing.  For
    S below the cap this yields the full-S block, which is always a legal
    Mosaic tile (the round-1 ``(1, 1, 128)`` cliff came from divisor
    hunting down to sub-sublane blocks like bq=1 at small prime S)."""
    def fit(req: Optional[int], cap: int) -> int:
        b = min(req or cap, cap, S)
        while S % b:
            b -= 1
        return b
    return fit(bq, 256), fit(bk, 512)


def _blocks_lowerable(S: int, bq: int, bk: int) -> bool:
    """Mosaic tiling: a block's second-to-last dim must be a sublane
    multiple (8 for fp32) or span the full extent.  The last dim is the
    head extent D, which is always the full dim, so only bq/bk gate."""
    return all(b == S or b % 8 == 0 for b in (bq, bk))


def _bias_spec_qrows(bias, bq, S):
    """BlockSpec for a [Bb, Hb, S, S] bias on the (b, h, i)-gridded kernels
    (q-block rows, full-S columns), honoring batch/head broadcast."""
    bsel = (lambda b: b) if bias.shape[0] > 1 else (lambda b: 0)
    hsel = (lambda h: h) if bias.shape[1] > 1 else (lambda h: 0)
    return pl.BlockSpec((1, 1, bq, S), lambda b, h, i: (bsel(b), hsel(h), i, 0))


def _bias_spec_kcols(bias, group, bk, S):
    """BlockSpec for the dKV kernel's (b, h_kv, j) grid: full-S q rows,
    KV-block columns, the query-head group stacked in dim 1 (or broadcast)."""
    bsel = (lambda b: b) if bias.shape[0] > 1 else (lambda b: 0)
    if bias.shape[1] > 1:
        return pl.BlockSpec((1, group, S, bk), lambda b, h, j: (bsel(b), h, 0, j))
    return pl.BlockSpec((1, 1, S, bk), lambda b, h, j: (bsel(b), 0, 0, j))


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #
def _fwd_kernel(*refs, scale, causal, bq, bk, S, has_bias, has_alibi):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    n = 3
    b_ref = refs[n] if has_bias else None
    n += has_bias
    a_ref = refs[n] if has_alibi else None
    n += has_alibi
    o_ref, lse_ref = refs[n:]
    qi = pl.program_id(2)
    # operands stay in their storage dtype (bf16): the MXU runs bf16 x bf16
    # with f32 accumulation (preferred_element_type) at full rate — casting
    # inputs to f32 first would drop matmul throughput ~8x on v5e
    q = q_ref[0, 0]                       # [bq, D]
    D = q.shape[-1]
    slope = a_ref[pl.program_id(1)] if has_alibi else None

    if causal:
        num_kb = pl.cdiv((qi + 1) * bq, bk)
    else:
        num_kb = S // bk

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(j * bk, bk), :]   # [bk, D]
        v = v_ref[0, 0, pl.ds(j * bk, bk), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if has_bias:
            s = s + b_ref[0, 0, :, pl.ds(j * bk, bk)].astype(jnp.float32)
        if causal or has_alibi:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if has_alibi:   # slope * (k_pos - q_pos), computed on the VPU
            s = s + slope * (cols - rows).astype(jnp.float32)
        if causal:
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_kb, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)        # [bq, 1]


def _fwd(q, k, v, bias, slopes, *, causal, scale, bq=None, bk=None):
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    bq, bk = _block_sizes(S, bq, bk)
    grid = (B, H, S // bq)
    kv_spec = pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h // group, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
        kv_spec, kv_spec,
    ]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(_bias_spec_qrows(bias, bq, S))
        args.append(bias)
    if slopes is not None:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM))
        args.append(slopes)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                          S=S, has_bias=bias is not None,
                          has_alibi=slopes is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ],
        compiler_params=_PARALLEL3,
        interpret=_pallas.interpret(),
        name="flash_fwd",
    )(*args)
    return o, lse


# --------------------------------------------------------------------------- #
# Backward
# --------------------------------------------------------------------------- #
def _bwd_dq_kernel(*refs, scale, causal, bq, bk, S, has_bias, has_alibi):
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    n = 6
    b_ref = refs[n] if has_bias else None
    n += has_bias
    a_ref = refs[n] if has_alibi else None
    n += has_alibi
    dq_ref = refs[n]
    qi = pl.program_id(2)
    q = q_ref[0, 0]                       # storage dtype: bf16 MXU operands
    do = do_ref[0, 0]
    lse = lse_ref[0, 0]                   # [bq, 1]
    delta = delta_ref[0, 0]               # [bq, 1]
    D = q.shape[-1]
    slope = a_ref[pl.program_id(1)] if has_alibi else None

    num_kb = pl.cdiv((qi + 1) * bq, bk) if causal else S // bk

    def body(j, dq):
        k = k_ref[0, 0, pl.ds(j * bk, bk), :]
        v = v_ref[0, 0, pl.ds(j * bk, bk), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_bias:
            s = s + b_ref[0, 0, :, pl.ds(j * bk, bk)].astype(jnp.float32)
        if causal or has_alibi:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if has_alibi:
            s = s + slope * (cols - rows).astype(jnp.float32)
        if causal:
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)                                   # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, num_kb, body, jnp.zeros((bq, D), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, bq, bk, S, group, has_bias,
                    bias_per_head, has_alibi):
    """Grid (B, Hkv, S//bk): one KV block per step, accumulating dK/dV over
    the ``group`` query heads that attend to this KV head."""
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    n = 6
    b_ref = refs[n] if has_bias else None
    n += has_bias
    a_ref = refs[n] if has_alibi else None
    n += has_alibi
    dk_ref, dv_ref = refs[n:]
    ki = pl.program_id(2)
    # program_id must bind at kernel top level (not inside the fori_loop
    # body, where interpret mode can't re-associate it with the grid)
    hk = pl.program_id(1)
    k = k_ref[0, 0]                       # storage dtype: bf16 MXU operands
    v = v_ref[0, 0]
    D = k.shape[-1]
    num_qb = S // bq
    start_qb = (ki * bk) // bq if causal else 0

    dk = jnp.zeros((bk, D), jnp.float32)
    dv = jnp.zeros((bk, D), jnp.float32)
    for g in range(group):      # static unroll over the query-head group
        slope = a_ref[hk * group + g] if has_alibi else None

        def body(i, carry, g=g, slope=slope):
            dk, dv = carry
            q = q_ref[0, g, pl.ds(i * bq, bq), :]
            do = do_ref[0, g, pl.ds(i * bq, bq), :]
            lse = lse_ref[0, g, pl.ds(i * bq, bq), :]       # [bq, 1]
            delta = delta_ref[0, g, pl.ds(i * bq, bq), :]   # [bq, 1]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if has_bias:
                gb = g if bias_per_head else 0
                s = s + b_ref[0, gb, pl.ds(i * bq, bq), :].astype(jnp.float32)
            if causal or has_alibi:
                rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            if has_alibi:
                s = s + slope * (cols - rows).astype(jnp.float32)
            if causal:
                s = jnp.where(rows >= cols, s, NEG_INF)
            p = jnp.exp(s - lse)                                    # [bq, bk]
            pc = p.astype(do.dtype)
            dv = dv + jax.lax.dot_general(pc, do, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta) * scale).astype(q.dtype)         # [bq, bk]
            dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
            return dk, dv

        dk, dv = jax.lax.fori_loop(start_qb, num_qb, body, (dk, dv))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def flash_block_bwd(q, k, v, do, lse, delta, bias=None, slopes=None, *,
                    causal, scale, bq=None, bk=None):
    """Backward kernels against an EXTERNAL softmax normalizer: ``lse`` is
    the (global) log-sum-exp [B, H, S, 1] and ``delta = sum(do * o)``
    [B, H, S, 1].  Returns (dq, dk, dv).  This is the flash backward body —
    exposed separately so ring attention (``parallel/sequence.py``) can use
    it per KV hop with the final merged lse, which makes the distributed
    backward exact without storing per-hop probabilities."""
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    bq_, bk_ = _block_sizes(S, bq, bk)

    qspec = pl.BlockSpec((1, 1, bq_, D), lambda b, h, i: (b, h, i, 0))
    kv_full = pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h // group, 0, 0))
    vec_q = pl.BlockSpec((1, 1, bq_, 1), lambda b, h, i: (b, h, i, 0))

    dq_in = [q, k, v, do, lse, delta]
    dq_specs = [qspec, kv_full, kv_full, qspec, vec_q, vec_q]
    if bias is not None:
        dq_in.append(bias)
        dq_specs.append(_bias_spec_qrows(bias, bq_, S))
    if slopes is not None:
        dq_in.append(slopes)
        dq_specs.append(pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, bq=bq_,
                          bk=bk_, S=S, has_bias=bias is not None,
                          has_alibi=slopes is not None),
        grid=(B, H, S // bq_),
        in_specs=dq_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        compiler_params=_PARALLEL3,
        interpret=_pallas.interpret(),
        name="flash_bwd_dq",
    )(*dq_in)

    # dK/dV: grid over KV heads; q/do/lse/delta delivered group-at-a-time
    kspec = pl.BlockSpec((1, 1, bk_, D), lambda b, h, j: (b, h, j, 0))
    q_grp = pl.BlockSpec((1, group, S, D), lambda b, h, j: (b, h, 0, 0))
    vec_grp = pl.BlockSpec((1, group, S, 1), lambda b, h, j: (b, h, 0, 0))
    dkv_in = [q, k, v, do, lse, delta]
    dkv_specs = [q_grp, kspec, kspec, q_grp, vec_grp, vec_grp]
    bias_per_head = bias is not None and bias.shape[1] > 1
    if bias is not None:
        dkv_in.append(bias)
        dkv_specs.append(_bias_spec_kcols(bias, group, bk_, S))
    if slopes is not None:
        dkv_in.append(slopes)
        dkv_specs.append(pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, bq=bq_,
                          bk=bk_, S=S, group=group, has_bias=bias is not None,
                          bias_per_head=bias_per_head,
                          has_alibi=slopes is not None),
        grid=(B, Hkv, S // bk_),
        in_specs=dkv_specs,
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, S, D), k.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, S, D), v.dtype)],
        compiler_params=_PARALLEL3,
        interpret=_pallas.interpret(),
        name="flash_bwd_dkv",
    )(*dkv_in)
    return dq, dk, dv


# [B, H, S, D] forward returning (o, lse) — the ring-attention hop body.
flash_block_fwd = _fwd


def _bwd(causal, scale, bq, bk, res, do):
    q, k, v, bias, slopes, o, lse = res
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)                     # [B,H,S,1]
    dq, dk, dv = flash_block_bwd(q, k, v, do, lse, delta, bias, slopes,
                                 causal=causal, scale=scale, bq=bq, bk=bk)
    # both bias forms are constants under differentiation (module docstring)
    db = None if bias is None else jnp.zeros_like(bias)
    da = None if slopes is None else jnp.zeros_like(slopes)
    return dq, dk, dv, db, da


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(q, k, v, bias, slopes, causal, scale, bq, bk):
    o, _ = _fwd(q, k, v, bias, slopes, causal=causal, scale=scale, bq=bq, bk=bk)
    return o


def _flash_fwd(q, k, v, bias, slopes, causal, scale, bq, bk):
    o, lse = _fwd(q, k, v, bias, slopes, causal=causal, scale=scale, bq=bq, bk=bk)
    # named for remat: without these tags every jax.checkpoint policy
    # replays the whole forward kernel in the backward pass just to
    # rebuild (o, lse) — ~25% extra attention time for O(B·S·H·D) memory
    # (profiled r5: two identical fwd custom-calls per step under
    # dots_saveable).  checkpointing.checkpoint_policy() saves these names.
    from jax.ad_checkpoint import checkpoint_name
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, bias, slopes, o, lse)


_flash.defvjp(_flash_fwd, _bwd)


def _flash_bshd(q, k, v, bias, slopes, causal, scale, bq, bk):
    """[B,S,H,D] wrapper around the [B,H,S,D] kernel (grouped-KV aware)."""
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    o = _flash(qt, kt, vt, bias, slopes, causal, scale, bq, bk)
    return o.transpose(0, 2, 1, 3)


def flash_attention(q, k, v, *, causal: bool = True, bias=None, alibi=None,
                    block_q: Optional[int] = None, block_k: Optional[int] = None):
    """[batch, seq, heads, head_dim] flash attention (differentiable).

    ``k``/``v`` may carry fewer heads than ``q`` (GQA/MQA; ``H % Hkv == 0``)
    — the kernel indexes grouped KV directly, no host-side expansion.
    ``alibi`` is the per-head slope vector [H]; the kernel synthesizes the
    ALiBi bias from iotas (O(H) memory).  ``bias`` is a dense additive
    logit bias broadcastable to [B, H, S, S].  Both are constants under
    differentiation.

    Under an active mesh the kernel runs inside ``shard_map`` with batch
    sharded over the data/fsdp/expert axes and heads over seq × tensor
    (sequence-sharded inputs are thereby Ulysses-re-sharded to full-seq,
    split-head form before the kernel — see module docstring)."""
    from deepspeed_tpu.ops.attention import canonical_bias
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    block_q, block_k = _block_sizes(S, block_q, block_k)
    if not _blocks_lowerable(S, block_q, block_k) or H % Hkv != 0:
        # e.g. S=1000: largest divisor ≤256 is 250 — neither a sublane
        # multiple nor full-S, so the tile can't lower; take the jnp path
        _fallback_warn_once(q.shape, f"blocks ({block_q},{block_k}) for "
                            f"S={S} are not lowerable")
        from deepspeed_tpu.ops.attention import reference_attention
        return reference_attention(q, k, v, causal=causal, bias=bias, alibi=alibi)
    scale = 1.0 / np.sqrt(D)
    bias = canonical_bias(bias)
    if bias is not None:
        bias = jnp.broadcast_to(
            bias, (bias.shape[0], bias.shape[1], S, S)).astype(jnp.float32)
    slopes = None
    if alibi is not None:
        slopes = jnp.asarray(alibi, jnp.float32).reshape(H)

    from deepspeed_tpu.parallel import mesh as mesh_lib
    if mesh_lib.has_mesh() and not mesh_lib.in_manual_mode():
        mesh = mesh_lib.get_mesh()
        batch_div = int(np.prod([mesh.shape[a] for a in mesh_lib.BATCH_AXES]))
        head_div = int(mesh.shape["tensor"] * mesh.shape["seq"])
        if batch_div > 1 or head_div > 1:
            if B % batch_div != 0 or H % head_div != 0 or Hkv % head_div != 0:
                # a bare pallas_call has no SPMD partitioning rule; on shapes
                # the shard_map can't split, use the jnp path XLA can shard
                _fallback_warn_once(q.shape, f"mesh ({batch_div},{head_div}) "
                                    f"does not divide B={B}, H={H}, Hkv={Hkv}")
                from deepspeed_tpu.ops.attention import reference_attention
                return reference_attention(q, k, v, causal=causal, bias=bias,
                                           alibi=alibi)
            spec = P(mesh_lib.BATCH_AXES, None, ("seq", "tensor"), None)
            in_specs = [spec, spec, spec]
            args = [q, k, v]
            if bias is not None:
                in_specs.append(P(mesh_lib.BATCH_AXES if bias.shape[0] > 1 else None,
                                  ("seq", "tensor") if bias.shape[1] > 1 else None,
                                  None, None))
                args.append(bias)
            if slopes is not None:
                in_specs.append(P(("seq", "tensor")))
                args.append(slopes)
            nb, ns = bias is not None, slopes is not None

            def inner(q, k, v, *rest):
                b = rest[0] if nb else None
                sl = rest[-1] if ns else None
                return _flash_bshd(q, k, v, b, sl, causal, scale, block_q, block_k)

            return jax.shard_map(inner, mesh=mesh, in_specs=tuple(in_specs),
                                 out_specs=spec, check_vma=False)(*args)
    return _flash_bshd(q, k, v, bias, slopes, causal, scale, block_q, block_k)
