"""Pallas TPU flash attention (training fast path, forward + backward).

TPU-native replacement for the reference's fused attention CUDA kernels
(``csrc/transformer/softmax_kernels.cu`` and the strided-batch-gemm pipeline
of ``csrc/transformer/ds_transformer_cuda.cpp``).  Online-softmax tiling:
O(S) memory, score tiles of up to [512, 512], fp32 accumulation, bf16
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

Mosaic layout notes.  All three kernels form a tile's scores KEYS-MAJOR,
``k q^T`` [bk, bq]: keys along sublanes, queries along lanes.  A query's
statistics (running max, sum, lse, delta) are then a ROW [1, bq]: a
reduction over keys is plain vector maxima and sums down the tile, and a
statistic applies to the tile as a sublane broadcast.  The other way round,
[bq, bk] with statistics [bq, 1], a value filled one lane of a 128-lane
register (the forward carried 96 such registers from tile to tile, spilled),
every tile paid two cross-lane reductions and two lane broadcasts a row
group, and ``lse``/``delta`` went through HBM as ``[B, H, S, 1]``, which the
(8, 128) tiling pads to 128 lanes a value: 48 MB a layer at (8, 12, 1024)
where the values are 393 KB.  The products with the probabilities keep the
tile where it is, as the MXU's stationary operand, and stream the D = 64
rows of ``v^T``, ``k^T``, ``do^T`` or ``q^T`` through it (``_through_tile``), so
``o``, ``dq``, ``dk`` and ``dv`` accumulate as [D, block], dense in lanes,
and are transposed once a grid step on their way out; streaming the tile's
rows instead fills D = 64 of the array's 128 columns.  Outside the kernels
the statistics are ``[B, H, S]`` float32; a kernel takes them as
``[B, H, S // bq, 1, bq]`` (a free reshape), whose block ``(1, 1, 1, 1, bq)``
ends in two full dims and so is legal for every bq (round 1 shipped
``[B, H, S]`` with block (1, 1, bq), which Mosaic rejects: the
second-to-last block dim (1) is neither a multiple of the sublane tile nor
equal to H), and the dkv kernel picks q block ``i``'s row by a dynamic index
on a major dim.  A dense bias is handed over transposed, ``[Bb, Hb, Sk,
Sq]``, to lie the way the scores do.

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
import math
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


#: The largest block, default or requested, along q and along k.  Four
#: [512, 512] float32 tiles live at once are 4 MiB of the 16 MiB a kernel may
#: use of a v5e's VMEM.
_BLOCK_CAP = 512


def _block_sizes(S: int, bq: Optional[int], bk: Optional[int]):
    """(block_q, block_k): the largest divisors of S up to ``_BLOCK_CAP``,
    the same pair for the forward, the dq and the dkv kernel, causal or not.

    Measured on a TPU v5e (``device_kind`` "TPU v5 lite") with
    ``tools/flash_kernel_bench.py`` at the two train cells' shapes, causal
    bf16, each kernel's own time on the device in ms a call (PR 52):

    ==========  =====================  =====================
    (bq, bk)    (8, 12, 1024, 64)      (8, 25, 1024, 64)
                fwd / dq / dkv         fwd / dq / dkv
    ==========  =====================  =====================
    (128, 128)  1.296 / 1.227 / 1.564  2.723 / 2.582 / 3.355
    (256, 256)  0.570 / 0.540 / 0.643  1.201 / 1.136 / 1.430
    (256, 512)  0.514 / 0.487 / 0.544  1.086 / 1.028 / 1.128
    (512, 256)  0.394 / 0.444 / 0.494  0.829 / 0.930 / 1.095
    (512, 512)  0.313 / 0.377 / 0.428  0.660 / 0.789 / 0.901
    ==========  =====================  =====================

    (20.9 / 26.0 / 30.5% of ``benchmarks/lib/arith.flash_call``'s bound at 12
    heads; not causal 0.398 / 0.485 / 0.543 ms, 32.8 / 40.4 / 48.2%, and
    the same order of the pairs; the kernels this file held before, at
    their (256, 512): 0.420 / 0.452 / 0.806 ms, 15.6 / 21.7 / 16.2%.)  A
    causal tile the diagonal crosses is computed whole and masked, so at
    S = 1024 the pair (512, 512) computes 1.50 x the triangle's scores where
    (256, 256) computes 1.25 x, and still wins by 1.4-1.8 x: in the
    compiler's bundle dump (PERF.md § 6) a (512, 512) tile takes 1,345 /
    1,638 / 1,803 cycles of which its matmuls hold the MXUs 768 / 1,280 /
    1,536, a (256, 256) tile 736 / 679 / 824 of which 192 / 320 / 384: some
    300-550 cycles a tile go to filling and draining the MXUs whatever the
    tile's size (one tile's work does not overlap the next's), and a grid
    step costs another 390-630.

    Requested sizes (the caller's) are CLAMPED to the largest divisor of S at
    most the request — never asserted on — so an odd S degrades to a
    smaller block or to the reference fallback instead of crashing.  For
    S below the cap this yields the full-S block, which is always a legal
    Mosaic tile (the round-1 ``(1, 1, 128)`` cliff came from divisor
    hunting down to sub-sublane blocks like bq=1 at small prime S)."""
    def fit(req: Optional[int]) -> int:
        b = min(req or _BLOCK_CAP, _BLOCK_CAP, S)
        while S % b:
            b -= 1
        return b
    return fit(bq), fit(bk)


def _blocks_lowerable(S: int, bq: int, bk: int, dense_bias: bool = False) -> bool:
    """Mosaic tiling: a block's second-to-last dim must be a sublane
    multiple (8 for fp32) or span the full extent.  The last dim is the
    head extent D, which is always the full dim, so only bq/bk gate; a dense
    bias is blocked along q in its LAST dim, which wants whole 128-lane
    tiles of it."""
    if dense_bias and not (bq == S or bq % 128 == 0):
        return False
    return all(b == S or b % 8 == 0 for b in (bq, bk))


def _scale_folds(scale: float) -> bool:
    """Whether ``scale`` is a power of two, so that ``q * scale`` in q's own
    dtype is exact (1/sqrt(64) = 2**-3) and the scores need no multiply."""
    return math.frexp(scale)[0] == 0.5


def _stat_spec(bq):
    """One q block's row of a [B, H, S // bq, 1, bq] statistic on a
    (b, h, i) grid."""
    return pl.BlockSpec((1, 1, 1, 1, bq), lambda b, h, i: (b, h, i, 0, 0))


def _stat_rows(x, b):
    """[B, H, S] row statistics (lse, delta) as the kernels take them:
    [B, H, S // b, 1, b], one block's values a lane-major row (module
    docstring, Mosaic layout notes)."""
    B, H, S = x.shape
    return x.reshape(B, H, S // b, 1, b)


def _bias_spec_qcols(bias_t, bq, S):
    """BlockSpec for a [Bb, Hb, Sk, Sq] bias on the (b, h, i)-gridded kernels
    (all key rows, the q block's columns), honoring batch/head broadcast."""
    bsel = (lambda b: b) if bias_t.shape[0] > 1 else (lambda b: 0)
    hsel = (lambda h: h) if bias_t.shape[1] > 1 else (lambda h: 0)
    return pl.BlockSpec((1, 1, S, bq), lambda b, h, i: (bsel(b), hsel(h), 0, i))


def _bias_spec_krows(bias_t, group, bk, S):
    """BlockSpec for the dKV kernel's (b, h_kv, j) grid: the KV block's rows,
    all q columns, the query-head group stacked in dim 1 (or broadcast)."""
    bsel = (lambda b: b) if bias_t.shape[0] > 1 else (lambda b: 0)
    if bias_t.shape[1] > 1:
        return pl.BlockSpec((1, group, bk, S), lambda b, h, j: (bsel(b), h, j, 0))
    return pl.BlockSpec((1, 1, bk, S), lambda b, h, j: (bsel(b), 0, j, 0))


def _optional_refs(refs, n, has_bias, has_alibi):
    """(bias ref, slopes ref, the refs after them) of a kernel whose first
    ``n`` refs are its fixed inputs."""
    b_ref = refs[n] if has_bias else None
    n += has_bias
    a_ref = refs[n] if has_alibi else None
    n += has_alibi
    return b_ref, a_ref, refs[n:]


def _optional_operands(args, specs, bias, bias_spec, slopes):
    """Appends the dense bias and the ALiBi slopes a call has.  The bias
    [Bb, Hb, Sq, Sk] is handed over keys-major, [Bb, Hb, Sk, Sq], so that a
    tile of it lies the way the scores do; ``bias_spec`` blocks that."""
    if bias is not None:
        bias_t = jnp.swapaxes(bias, 2, 3)
        args.append(bias_t)
        specs.append(bias_spec(bias_t))
    if slopes is not None:
        args.append(slopes)
        specs.append(pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM))


def _tile_scores(k, q, first_key, first_query, *, scale, fold, bias, slope,
                 masked):
    """The [bk, bq] float32 logits of one tile, KEYS ALONG SUBLANES and
    queries along lanes (module docstring, Mosaic layout notes): ``k q^T``
    scaled (``fold``: q carries the scale already), plus the dense bias tile
    and the ALiBi term, the entries above the diagonal at NEG_INF when
    ``masked``: every tile of a causal call, the ones wholly under the
    diagonal too (a loop of their own without the iotas, the compare and
    the select saved 13 / 8 / 0 of the 1,342 / 1,636 / 1,803 cycles a
    (512, 512) tile takes in the three kernels, and the second loop's
    carries cost a grid step 32 / 68 / 270: PERF.md § 6, PR 52)."""
    st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if not fold:
        st = st * scale
    if bias is not None:
        st = st + bias.astype(jnp.float32)
    if masked or slope is not None:
        keys = first_key + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
        queries = first_query + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
    if slope is not None:   # slope * (k_pos - q_pos), computed on the VPU
        st = st + slope * (keys - queries).astype(jnp.float32)
    if masked:
        st = jnp.where(queries >= keys, st, NEG_INF)
    return st


def _through_tile(x, tile, over):
    """``x^T tile`` (``over`` 0, [D, bq]) or ``x^T tile^T`` (``over`` 1,
    [D, bk]) for ``x`` [n, D] and a [bk, bq] tile contracted over its dim
    ``over``: the tile stays the MXU's stationary operand and the D rows of
    ``x^T`` stream through it.  The product written the other way round
    streams the tile's rows to fill D = 64 of the array's 128 columns."""
    return jax.lax.dot_general(x, tile, (((0,), (over,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tile_p_ds(st, v, do, lse, delta, *, scale, fold):
    """(p^T, ds^T) of one [bk, bq] tile of the backward, float32: the
    probabilities ``exp(st - lse)`` and ``p (dp - delta)`` with
    ``dp^T = v do^T``; ``ds`` carries the scores' ``scale`` unless ``fold``
    leaves it to the caller (dq: once a grid step; dk: on q)."""
    pt = jnp.exp(st - lse)
    dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dst = pt * (dpt - delta)
    return pt, dst if fold else dst * scale


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #
def _fwd_kernel(*refs, scale, causal, bq, bk, S, has_bias, has_alibi):
    q_ref, k_ref, v_ref = refs[:3]
    b_ref, a_ref, (o_ref, lse_ref) = _optional_refs(refs, 3, has_bias, has_alibi)
    qi = pl.program_id(2)
    # operands stay in their storage dtype (bf16): the MXU runs bf16 x bf16
    # with f32 accumulation (preferred_element_type) at full rate — casting
    # inputs to f32 first would drop matmul throughput ~8x on v5e
    q = q_ref[0, 0]                       # [bq, D]
    D = q.shape[-1]
    fold = _scale_folds(scale)
    if fold:
        q = q * scale                     # exact: once a grid step, not a tile
    slope = a_ref[pl.program_id(1)] if has_alibi else None

    def body(j, carry):
        m, l, acc = carry                 # [1, bq], [1, bq], [D, bq]
        k = k_ref[0, 0, pl.ds(j * bk, bk), :]   # [bk, D]
        v = v_ref[0, 0, pl.ds(j * bk, bk), :]
        bias = b_ref[0, 0, pl.ds(j * bk, bk), :] if has_bias else None
        st = _tile_scores(k, q, j * bk, qi * bq, scale=scale, fold=fold,
                          bias=bias, slope=slope, masked=causal)    # [bk, bq]
        m_new = jnp.maximum(m, jnp.max(st, axis=0, keepdims=True))
        pt = jnp.exp(st - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(pt, axis=0, keepdims=True)
        acc = acc * alpha + _through_tile(v, pt.astype(v.dtype), 0)  # [D, bq]
        return m_new, l, acc

    m0 = jnp.full((1, bq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((1, bq), jnp.float32)
    a0 = jnp.zeros((D, bq), jnp.float32)
    num_kb = pl.cdiv((qi + 1) * bq, bk) if causal else S // bk
    m, l, acc = jax.lax.fori_loop(0, num_kb, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / l).T.astype(o_ref.dtype)
    lse_ref[0, 0, 0] = m + jnp.log(l)     # [1, bq]


def _fwd(q, k, v, bias, slopes, *, causal, scale, bq=None, bk=None):
    """[B, H, S, D] forward: (o, lse [B, H, S] float32).  ``bias`` is dense,
    [Bb, Hb, S, S]."""
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    bq, bk = _block_sizes(S, bq, bk)
    kv_spec = pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h // group, 0, 0))
    qspec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0))
    args = [q, k, v]
    specs = [qspec, kv_spec, kv_spec]
    _optional_operands(args, specs, bias,
                       functools.partial(_bias_spec_qcols, bq=bq, S=S), slopes)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
                          S=S, has_bias=bias is not None,
                          has_alibi=slopes is not None),
        grid=(B, H, S // bq),
        in_specs=specs,
        out_specs=[qspec, _stat_spec(bq)],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S // bq, 1, bq), jnp.float32),
        ],
        compiler_params=_PARALLEL3,
        interpret=_pallas.interpret(),
        name="flash_fwd",
    )(*args)
    return o, lse.reshape(B, H, S)


# --------------------------------------------------------------------------- #
# Backward
# --------------------------------------------------------------------------- #
def _bwd_dq_kernel(*refs, scale, causal, bq, bk, S, has_bias, has_alibi):
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    b_ref, a_ref, (dq_ref,) = _optional_refs(refs, 6, has_bias, has_alibi)
    qi = pl.program_id(2)
    q = q_ref[0, 0]                       # storage dtype: bf16 MXU operands
    do = do_ref[0, 0]
    lse = lse_ref[0, 0, 0]                # [1, bq]
    delta = delta_ref[0, 0, 0]            # [1, bq]
    D = q.shape[-1]
    fold = _scale_folds(scale)
    if fold:
        q = q * scale
    slope = a_ref[pl.program_id(1)] if has_alibi else None

    def body(j, dqt):
        k = k_ref[0, 0, pl.ds(j * bk, bk), :]
        v = v_ref[0, 0, pl.ds(j * bk, bk), :]
        bias = b_ref[0, 0, pl.ds(j * bk, bk), :] if has_bias else None
        st = _tile_scores(k, q, j * bk, qi * bq, scale=scale, fold=fold,
                          bias=bias, slope=slope, masked=causal)
        _, dst = _tile_p_ds(st, v, do, lse, delta, scale=scale, fold=fold)
        return dqt + _through_tile(k, dst.astype(k.dtype), 0)    # [D, bq]

    num_kb = pl.cdiv((qi + 1) * bq, bk) if causal else S // bk
    dqt = jax.lax.fori_loop(0, num_kb, body, jnp.zeros((D, bq), jnp.float32))
    if fold:
        dqt = dqt * scale                 # ds's scale, once a grid step
    dq_ref[0, 0] = dqt.T.astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, bq, bk, S, group, has_bias,
                    bias_per_head, has_alibi):
    """Grid (B, Hkv, S//bk): one KV block per step, accumulating dK/dV over
    the ``group`` query heads that attend to this KV head."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    b_ref, a_ref, (dk_ref, dv_ref) = _optional_refs(refs, 6, has_bias, has_alibi)
    ki = pl.program_id(2)
    # program_id must bind at kernel top level (not inside the fori_loop
    # body, where interpret mode can't re-associate it with the grid)
    hk = pl.program_id(1)
    k = k_ref[0, 0]                       # storage dtype: bf16 MXU operands
    v = v_ref[0, 0]
    D = k.shape[-1]
    fold = _scale_folds(scale)
    num_qb = S // bq
    start_qb = (ki * bk) // bq if causal else 0

    dkt = jnp.zeros((D, bk), jnp.float32)
    dvt = jnp.zeros((D, bk), jnp.float32)
    for g in range(group):      # static unroll over the query-head group
        slope = a_ref[hk * group + g] if has_alibi else None

        def body(i, carry, g=g, slope=slope):
            dkt, dvt = carry
            q = q_ref[0, g, pl.ds(i * bq, bq), :]
            do = do_ref[0, g, pl.ds(i * bq, bq), :]
            lse = lse_ref[0, g, i]                          # [1, bq]
            delta = delta_ref[0, g, i]                      # [1, bq]
            if fold:
                q = q * scale       # st's scale here, ds's through dk's q
            bias = None
            if has_bias:
                bias = b_ref[0, g if bias_per_head else 0, :,
                             pl.ds(pl.multiple_of(i * bq, bq), bq)]
            st = _tile_scores(k, q, ki * bk, i * bq, scale=scale, fold=fold,
                              bias=bias, slope=slope, masked=causal)
            pt, dst = _tile_p_ds(st, v, do, lse, delta, scale=scale, fold=fold)
            dvt = dvt + _through_tile(do, pt.astype(do.dtype), 1)   # [D, bk]
            dkt = dkt + _through_tile(q, dst.astype(q.dtype), 1)
            return dkt, dvt

        dkt, dvt = jax.lax.fori_loop(start_qb, num_qb, body, (dkt, dvt))
    dk_ref[0, 0] = dkt.T.astype(dk_ref.dtype)
    dv_ref[0, 0] = dvt.T.astype(dv_ref.dtype)


def _bwd_dq(q, k, v, do, lse, delta, bias, slopes, *, causal, scale, bq, bk):
    B, H, S, D = q.shape
    group = H // k.shape[1]
    bq, bk = _block_sizes(S, bq, bk)
    qspec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0))
    kv_full = pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h // group, 0, 0))
    args = [q, k, v, do, _stat_rows(lse, bq), _stat_rows(delta, bq)]
    specs = [qspec, kv_full, kv_full, qspec, _stat_spec(bq), _stat_spec(bq)]
    _optional_operands(args, specs, bias,
                       functools.partial(_bias_spec_qcols, bq=bq, S=S), slopes)
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, S=S, has_bias=bias is not None,
                          has_alibi=slopes is not None),
        grid=(B, H, S // bq),
        in_specs=specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        compiler_params=_PARALLEL3,
        interpret=_pallas.interpret(),
        name="flash_bwd_dq",
    )(*args)


def _bwd_dkv(q, k, v, do, lse, delta, bias, slopes, *, causal, scale, bq, bk):
    """dK/dV: grid over KV heads; q/do/lse/delta delivered group-at-a-time."""
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    bq, bk = _block_sizes(S, bq, bk)
    kspec = pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0))
    q_grp = pl.BlockSpec((1, group, S, D), lambda b, h, j: (b, h, 0, 0))
    vec_grp = pl.BlockSpec((1, group, S // bq, 1, bq),
                           lambda b, h, j: (b, h, 0, 0, 0))
    args = [q, k, v, do, _stat_rows(lse, bq), _stat_rows(delta, bq)]
    specs = [q_grp, kspec, kspec, q_grp, vec_grp, vec_grp]
    _optional_operands(args, specs, bias,
                       functools.partial(_bias_spec_krows, group=group, bk=bk, S=S),
                       slopes)
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, S=S, group=group, has_bias=bias is not None,
                          bias_per_head=bias is not None and bias.shape[1] > 1,
                          has_alibi=slopes is not None),
        grid=(B, Hkv, S // bk),
        in_specs=specs,
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, S, D), k.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, S, D), v.dtype)],
        compiler_params=_PARALLEL3,
        interpret=_pallas.interpret(),
        name="flash_bwd_dkv",
    )(*args)


def flash_block_bwd(q, k, v, do, lse, delta, bias=None, slopes=None, *,
                    causal, scale, bq=None, bk=None):
    """Backward kernels against an EXTERNAL softmax normalizer: ``lse`` is
    the (global) log-sum-exp [B, H, S] and ``delta = sum(do * o)``
    [B, H, S], both float32.  Returns (dq, dk, dv).  This is the flash
    backward body — exposed separately so ring attention
    (``parallel/sequence.py``) can use it per KV hop with the final merged
    lse, which makes the distributed backward exact without storing per-hop
    probabilities."""
    kw = dict(causal=causal, scale=scale, bq=bq, bk=bk)
    dq = _bwd_dq(q, k, v, do, lse, delta, bias, slopes, **kw)
    dk, dv = _bwd_dkv(q, k, v, do, lse, delta, bias, slopes, **kw)
    return dq, dk, dv


# [B, H, S, D] forward returning (o, lse [B, H, S]) — the ring-attention hop
# body.
flash_block_fwd = _fwd


def _bwd(causal, scale, bq, bk, res, do):
    q, k, v, bias, slopes, o, lse = res
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
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
    # rebuild (o, lse): a second ``flash_fwd`` a layer (0.31 of the 1.12 ms
    # of the three kernels at (8, 12, 1024, 64), _block_sizes' table) for
    # O(B·S·H·D) memory.  checkpointing.checkpoint_policy() saves these
    # names.
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
    bq, bk = _block_sizes(S, block_q, block_k)
    if not _blocks_lowerable(S, bq, bk, bias is not None) or H % Hkv != 0:
        # e.g. S=1000: largest divisor ≤512 is 500 — neither a sublane
        # multiple nor full-S, so the tile can't lower; take the jnp path
        _fallback_warn_once(q.shape, f"blocks ({bq},{bk}) for "
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
