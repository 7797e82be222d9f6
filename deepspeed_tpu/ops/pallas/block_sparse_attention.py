"""Pallas TPU block-sparse flash attention (forward + backward).

TPU-native replacement for the reference's Triton block-sparse attention
(``deepspeed/ops/sparse_attention/matmul.py`` SDD/DSD kernels and
``softmax.py`` blocked softmax).  Instead of three separate sparse GEMM /
softmax launches stitched together through autograd, the whole sparse
attention is one online-softmax flash kernel whose K-block walk is driven
by a per-(head, q-block) lookup table derived from the sparsity layout —
only blocks present in the layout are ever DMA'd from HBM or multiplied,
so FLOPs *and* HBM traffic scale with layout density.

Design notes:
- The layout (``[H, nb, nb]`` 0/1, from ``ops/sparse_attention/
  sparsity_config.py``) is static host metadata.  From it we build
  row-wise LUTs (for fwd + dq) and column-wise LUTs (for dk/dv), padded to
  the densest row.
- LUT + counts enter via ``pltpu.PrefetchScalarGridSpec`` scalar-prefetch
  so the K/V BlockSpec *index maps* can chase the LUT: grid is
  ``(batch, heads, q-blocks, lut-entries)`` and entry ``j`` DMAs exactly
  the K/V block ``lut[h, qi, j]``.  Padding entries re-fetch the row's
  last valid block and are compute-masked with ``pl.when`` — the DMA is a
  VMEM-resident no-op, never extra HBM traffic.  Per-block memory is
  O(block²), independent of sequence length, so 32k+ sequences fit.
- Online-softmax statistics accumulate in fp32 VMEM scratch across the
  (sequential) innermost grid dimension, exactly like the dense flash
  kernel in ``flash_attention.py``; one layout block maps to one MXU tile,
  which is why layout ``block`` of 64/128 is the fast path.
- Rows whose layout is empty produce zero output and zero gradient (the
  softmax normalizer is clamped; every entry is compute-masked).
- ``causal=True`` additionally applies the elementwise triangular mask on
  diagonal blocks (block-level causality should already be in the layout;
  the flag makes within-block masking exact).
- On the ``cpu`` platform the same kernels run interpreted, for CI parity
  against the masked-dense jnp reference, the analogue of the reference's
  ``tests/unit/ops/sparse_attention/test_sparse_attention.py``.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import pallas as _pallas

NEG_INF = -1e30

_SEMANTICS4 = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


# --------------------------------------------------------------------------- #
# Layout → LUT
# --------------------------------------------------------------------------- #
@functools.lru_cache(maxsize=64)
def _luts_cached(layout_bytes: bytes, H: int, nb: int):
    layout = np.frombuffer(layout_bytes, dtype=np.int32).reshape(H, nb, nb)
    return _build_luts(layout)


def _build_luts(layout: np.ndarray):
    """Row and column LUTs from a [H, nb, nb] 0/1 layout.

    Returns (row_lut [H*nb, max_r], row_cnt [H, nb],
             col_lut [H*nb, max_c], col_cnt [H, nb]) as int32 numpy arrays.
    Padding entries repeat the last valid index (their compute is masked);
    fully-empty rows pad with 0.
    """
    H, nb, _ = layout.shape
    row_cnt = layout.sum(axis=2).astype(np.int32)
    col_cnt = layout.sum(axis=1).astype(np.int32)
    max_r = max(int(row_cnt.max()), 1)
    max_c = max(int(col_cnt.max()), 1)
    row_lut = np.zeros((H * nb, max_r), dtype=np.int32)
    col_lut = np.zeros((H * nb, max_c), dtype=np.int32)
    for h in range(H):
        for i in range(nb):
            cols = np.nonzero(layout[h, i])[0]
            row_lut[h * nb + i, :len(cols)] = cols
            if len(cols):
                row_lut[h * nb + i, len(cols):] = cols[-1]
            rows = np.nonzero(layout[h, :, i])[0]
            col_lut[h * nb + i, :len(rows)] = rows
            if len(rows):
                col_lut[h * nb + i, len(rows):] = rows[-1]
    return row_lut, row_cnt, col_lut, col_cnt


def build_luts(layout: np.ndarray):
    layout = np.ascontiguousarray(np.asarray(layout, dtype=np.int32))
    H, nb, _ = layout.shape
    return _luts_cached(layout.tobytes(), H, nb)


def _lut_block(nb):
    """Index map chasing the LUT: entry j selects K/V (or Q/dO) block
    ``lut[h*nb + i, j]``.  Scalar-prefetch refs arrive as trailing args."""
    def index_map(b, h, i, j, cnt_ref, lut_ref):
        return b, h, lut_ref[h * nb + i, j], 0
    return index_map


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #
def _fwd_kernel(cnt_ref, lut_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_s, l_s, acc_s, *, scale, causal, bs, nb):
    h, qi, j = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    n = cnt_ref[h, qi]

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(j < n)
    def _step():
        col = lut_ref[h * nb + qi, j]
        q = q_ref[0, 0].astype(jnp.float32)          # [bs, D]
        k = k_ref[0, 0].astype(jnp.float32)          # [bs, D] (LUT-selected)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
            cols = col * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # exp(NEG_INF - NEG_INF) = 1 would fabricate mass on rows whose
        # every entry is causally masked — zero them explicitly
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        alpha = jnp.exp(m_prev - m_new)
        m_s[...] = m_new
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        l_safe = jnp.maximum(l_s[...], 1e-30)        # empty rows → zero output
        o_ref[0, 0] = (acc_s[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_s[...] + jnp.log(l_safe)


def _fwd(q, k, v, row_lut, row_cnt, *, scale, causal, bs):
    B, H, S, D = q.shape
    nb = S // bs
    max_nnz = row_lut.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nb, max_nnz),
        in_specs=[
            pl.BlockSpec((1, 1, bs, D), lambda b, h, i, j, *_: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bs, D), _lut_block(nb)),
            pl.BlockSpec((1, 1, bs, D), _lut_block(nb)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bs, D), lambda b, h, i, j, *_: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bs, 1), lambda b, h, i, j, *_: (b, h, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bs, 1), jnp.float32),
            pltpu.VMEM((bs, 1), jnp.float32),
            pltpu.VMEM((bs, D), jnp.float32),
        ],
    )
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, bs=bs, nb=nb),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ],
        compiler_params=_SEMANTICS4,
        interpret=_pallas.interpret(),
    )(row_cnt, row_lut, q, k, v)
    return o, lse


# --------------------------------------------------------------------------- #
# Backward
# --------------------------------------------------------------------------- #
def _dq_kernel(cnt_ref, lut_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dq_s, *, scale, causal, bs, nb):
    h, qi, j = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    n = cnt_ref[h, qi]

    @pl.when(j == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    @pl.when(j < n)
    def _step():
        col = lut_ref[h * nb + qi, j]
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
            cols = col * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_s[...] = dq_s[...] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0, 0] = dq_s[...].astype(dq_ref.dtype)


def _dkv_kernel(cnt_ref, lut_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_s, dv_s, *, scale, causal, bs, nb):
    h, ki, j = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    n = cnt_ref[h, ki]

    @pl.when(j == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    @pl.when(j < n)
    def _step():
        row = lut_ref[h * nb + ki, j]
        q = q_ref[0, 0].astype(jnp.float32)          # [bs, D] (LUT-selected)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = row * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
            cols = ki * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
        dv_s[...] = dv_s[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_s[...] = dk_s[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[...].astype(dv_ref.dtype)


def _bwd_impl(q, k, v, o, lse, do, luts, *, scale, causal, bs):
    B, H, S, D = q.shape
    nb = S // bs
    row_lut, row_cnt, col_lut, col_cnt = luts
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)          # [B, H, S, 1]

    own_blk = pl.BlockSpec((1, 1, bs, D), lambda b, h, i, j, *_: (b, h, i, 0))
    own_vec = pl.BlockSpec((1, 1, bs, 1), lambda b, h, i, j, *_: (b, h, i, 0))
    lut_blk = pl.BlockSpec((1, 1, bs, D), _lut_block(nb))
    lut_vec = pl.BlockSpec((1, 1, bs, 1), _lut_block(nb))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, bs=bs, nb=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, nb, row_lut.shape[1]),
            in_specs=[own_blk, lut_blk, lut_blk, own_blk, own_vec, own_vec],
            out_specs=own_blk,
            scratch_shapes=[pltpu.VMEM((bs, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        compiler_params=_SEMANTICS4,
        interpret=_pallas.interpret(),
    )(row_cnt, row_lut, q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, bs=bs, nb=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, nb, col_lut.shape[1]),
            in_specs=[lut_blk, own_blk, own_blk, lut_blk, lut_vec, lut_vec],
            out_specs=[own_blk, own_blk],
            scratch_shapes=[pltpu.VMEM((bs, D), jnp.float32),
                            pltpu.VMEM((bs, D), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, S, D), k.dtype),
                   jax.ShapeDtypeStruct((B, H, S, D), v.dtype)],
        compiler_params=_SEMANTICS4,
        interpret=_pallas.interpret(),
    )(col_cnt, col_lut, q, k, v, do, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# custom_vjp plumbing (layout enters as static hashable bytes)
# --------------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _sparse(q, k, v, layout_key, scale, causal, bs, H_nb):
    o, _ = _fwd(q, k, v, *_row_luts(layout_key, H_nb),
                scale=scale, causal=causal, bs=bs)
    return o


def _row_luts(layout_key, H_nb):
    row_lut, row_cnt, _, _ = _luts_cached(layout_key, *H_nb)
    return row_lut, row_cnt


def _sparse_fwd(q, k, v, layout_key, scale, causal, bs, H_nb):
    o, lse = _fwd(q, k, v, *_row_luts(layout_key, H_nb),
                  scale=scale, causal=causal, bs=bs)
    return o, (q, k, v, o, lse)


def _sparse_bwd(layout_key, scale, causal, bs, H_nb, res, do):
    q, k, v, o, lse = res
    luts = _luts_cached(layout_key, *H_nb)
    return _bwd_impl(q, k, v, o, lse, do, luts, scale=scale, causal=causal, bs=bs)


_sparse.defvjp(_sparse_fwd, _sparse_bwd)


# --------------------------------------------------------------------------- #
# Public API
# --------------------------------------------------------------------------- #
def block_sparse_attention(q, k, v, layout: np.ndarray, *,
                           causal: bool = False,
                           scale: Optional[float] = None):
    """Block-sparse attention over a static sparsity layout (differentiable).

    Args:
      q, k, v: ``[batch, seq, heads, head_dim]`` (framework-wide convention).
      layout: ``[heads, seq//block, seq//block]`` 0/1 numpy array from a
        :class:`~deepspeed_tpu.ops.sparse_attention.SparsityConfig`; the
        block size is inferred as ``seq // layout.shape[-1]``.
      causal: apply the elementwise triangular mask on top of the layout.
      scale: logit scale; defaults to ``1/sqrt(head_dim)``.
    """
    B, S, H, D = q.shape
    layout = np.ascontiguousarray(np.asarray(layout, dtype=np.int32))
    if layout.ndim != 3 or layout.shape[0] != H:
        raise ValueError(f"layout must be [heads={H}, nb, nb], got {layout.shape}")
    nb = layout.shape[-1]
    if S % nb != 0:
        raise ValueError(f"seq {S} not divisible into {nb} layout blocks")
    bs = S // nb
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    o = _sparse(qt, kt, vt, layout.tobytes(), scale, causal, bs, (H, nb))
    return o.transpose(0, 2, 1, 3)


def sparse_reference_attention(q, k, v, layout: np.ndarray, *,
                               causal: bool = False,
                               scale: Optional[float] = None,
                               rpe=None, key_padding_mask=None, attn_mask=None,
                               key_padding_mask_mode: str = "add",
                               attn_mask_mode: str = "mul"):
    """Masked-dense jnp reference (and fully-general fallback path).

    Semantics of the mask/rpe arguments follow the reference Softmax op
    (``deepspeed/ops/sparse_attention/softmax.py``): ``rpe`` is added to the
    logits; masks either add (``'add'``) or multiply-as-keep (``'mul'``, 0 →
    masked).  Layout blocks that are 0 never contribute probability mass.
    """
    B, S, H, D = q.shape
    nb = layout.shape[-1]
    bs = S // nb
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    mask = jnp.asarray(np.kron(np.asarray(layout, np.float32),
                               np.ones((bs, bs), np.float32)))  # [H, S, S]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if rpe is not None:
        s = s + rpe.astype(jnp.float32)
    if attn_mask is not None:
        am = attn_mask.astype(jnp.float32)
        s = s + am if attn_mask_mode == "add" else jnp.where(am != 0, s, NEG_INF)
    if key_padding_mask is not None:
        kp = key_padding_mask.astype(jnp.float32)[:, None, None, :]  # [B,1,1,S]
        s = s + kp if key_padding_mask_mode == "add" else jnp.where(kp != 0, s, NEG_INF)
    if causal:
        tri = jnp.tril(jnp.ones((S, S), jnp.float32))
        s = jnp.where(tri != 0, s, NEG_INF)
    s = jnp.where(mask[None] != 0, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.maximum(m, NEG_INF / 2))
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(l, 1e-30)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)
