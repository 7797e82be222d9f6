"""Pallas TPU fused cross-entropy over the unembedding (training loss path).

The XLA path (``models/gpt.py:chunked_cross_entropy``) materializes one
``[rows, V]`` fp32 logits block per chunk plus the one-hot contraction —
at GPT-2 vocab that block is the largest single tensor in the step and
its HBM round-trip is pure bandwidth with no MXU work.  This kernel
streams the vocab dimension in VMEM-resident blocks with the online
(flash-style) softmax recurrence, so neither the ``[N, V]`` logits nor
the one-hot tensor ever exists in HBM: forward emits only the per-row
``nll`` and ``lse`` (two ``[N, 1]`` vectors), and the backward recomputes
each score block from ``(x, head, lse)`` — the exact trade
flash attention makes for the attention scores, applied to the loss.

Parity contract (tested in ``tests/unit/ops/test_pallas_ce.py``): with a
single vocab block the forward performs literally the same op sequence as
``logsumexp`` + one-hot contraction — max, exp-shift, sum, log — so fp32
results are bitwise equal to the reference path; multi-block runs differ
only by the online-softmax rescale rounding (≤ a few ulp).  Masked padded
vocab columns use the same ``-1e9`` sentinel as the reference so the two
paths mask identically.

The wrapper in ``models/gpt.py`` takes this kernel where
``ops.pallas.use_kernel("ce")`` says kernels run and :func:`ce_supported`
admits the shape and mesh (vocab a multiple of 128, one device), and the
reference implementation everywhere else.
"""

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import pallas as _pallas

_ROW_BLOCK = 128          # fp32 sublane-multiple; rows are padded up to it
_VMEM_BLOCK_BYTES = 4 << 20   # budget for one [bv, E] head block in VMEM


def _vocab_block(V: int, E: int) -> Optional[int]:
    for bv in (2048, 1024, 512, 256, 128):
        if V % bv == 0 and bv * max(E, 1) * 4 <= _VMEM_BLOCK_BYTES:
            return bv
    return None


def ce_supported(N: int, E: int, V: int) -> bool:
    """Shape + mesh gate for the fused path.  The kernel handles any row
    count (rows pad to the block) but needs the vocab to tile into lane
    blocks, and runs un-sharded — under a >1-device mesh the vocab is
    tensor-parallel and the reference path (which XLA partitions) wins,
    except inside a manual (``shard_map``) region, where the arrays are
    already one device's."""
    from deepspeed_tpu.parallel import mesh as mesh_lib
    return (_vocab_block(V, E) is not None
            and (_pallas.single_device() or mesh_lib.in_manual_mode()))


# --------------------------------------------------------------------------- #
# Forward: grid (row blocks, vocab blocks), vocab innermost.  Scratch
# carries the online-softmax state (m, l) plus the label logit across the
# vocab sweep; outputs land on the last vocab step.
# --------------------------------------------------------------------------- #
def _fwd_kernel(x_ref, h_ref, lab_ref, *rest, bn, bv, vocab_size, has_bias):
    if has_bias:
        b_ref, nll_ref, lse_ref, m_s, l_s, ll_s = rest
    else:
        nll_ref, lse_ref, m_s, l_s, ll_s = rest
        b_ref = None
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full((bn, 1), -jnp.inf, jnp.float32)
        l_s[...] = jnp.zeros((bn, 1), jnp.float32)
        ll_s[...] = jnp.zeros((bn, 1), jnp.float32)

    x = x_ref[...]                                       # [bn, E]
    h = h_ref[...]                                       # [bv, E]
    s = jax.lax.dot_general(x, h, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [bn, bv]
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    if has_bias:
        s = s + b_ref[...].astype(jnp.float32)           # [1, bv] broadcast
    if vocab_size is not None:
        # same -1e9 sentinel as the reference path (bitwise-equal masking)
        s = jnp.where(cols < vocab_size, s, -1e9)
    lab = lab_ref[...]                                   # [bn, 1] int32
    m = m_s[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    l_new = l_s[...] * alpha + jnp.sum(jnp.exp(s - m_new), axis=1,
                                       keepdims=True)
    ll_new = ll_s[...] + jnp.sum(jnp.where(cols == lab, s, 0.0), axis=1,
                                 keepdims=True)
    m_s[...] = m_new
    l_s[...] = l_new
    ll_s[...] = ll_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        lse = m_new + jnp.log(l_new)
        lse_ref[...] = lse
        nll_ref[...] = lse - ll_new


def _fwd_rows(x2, head, head_b, lab2, vocab_size, bn, bv):
    """Per-row (nll, lse) for padded inputs: x2 [Np, E], lab2 [Np, 1]."""
    Np, E = x2.shape
    V = head.shape[0]
    grid = (Np // bn, V // bv)
    in_specs = [
        pl.BlockSpec((bn, E), lambda i, j: (i, 0)),
        pl.BlockSpec((bv, E), lambda i, j: (j, 0)),
        pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
    ]
    args = [x2, head, lab2]
    if head_b is not None:
        in_specs.append(pl.BlockSpec((1, bv), lambda i, j: (0, j)))
        args.append(head_b.reshape(1, V))
    row_spec = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    nll, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, bn=bn, bv=bv, vocab_size=vocab_size,
                          has_bias=head_b is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=[row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((Np, 1), jnp.float32),
                   jax.ShapeDtypeStruct((Np, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bn, 1), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_pallas.interpret(),
        name="ce_fwd",
    )(*args)
    return nll, lse


# --------------------------------------------------------------------------- #
# Backward: two kernels so every output block accumulates over consecutive
# grid steps with the same index (the only legal Pallas accumulation).
# dx grids (rows, vocab) and sums over vocab; dhead grids (vocab, rows)
# and sums over rows.  Both recompute the score block from (x, head, lse).
# --------------------------------------------------------------------------- #
def _score_block(x, h, b_ref, cols, vocab_size):
    s = jax.lax.dot_general(x, h, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if b_ref is not None:
        s = s + b_ref[...].astype(jnp.float32)
    if vocab_size is not None:
        s = jnp.where(cols < vocab_size, s, -1e9)
    return s


def _bwd_dx_kernel(x_ref, h_ref, lab_ref, lse_ref, gr_ref, *rest,
                   bn, bv, vocab_size, has_bias):
    if has_bias:
        b_ref, dx_ref = rest
    else:
        (dx_ref,) = rest
        b_ref = None
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    cols = j * bv + jax.lax.broadcasted_iota(
        jnp.int32, (x_ref.shape[0], bv), 1)
    s = _score_block(x_ref[...], h_ref[...], b_ref, cols, vocab_size)
    p = jnp.exp(s - lse_ref[...])                         # softmax block
    ds = (p - jnp.where(cols == lab_ref[...], 1.0, 0.0)) * gr_ref[...]
    dx_ref[...] += jax.lax.dot_general(
        ds, h_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _bwd_dh_kernel(x_ref, h_ref, lab_ref, lse_ref, gr_ref, *rest,
                   bn, bv, vocab_size, has_bias):
    if has_bias:
        b_ref, dh_ref, db_ref = rest
    else:
        dh_ref, = rest
        b_ref = db_ref = None
    v = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        if has_bias:
            db_ref[...] = jnp.zeros_like(db_ref)

    cols = v * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    s = _score_block(x_ref[...], h_ref[...], b_ref, cols, vocab_size)
    p = jnp.exp(s - lse_ref[...])
    ds = (p - jnp.where(cols == lab_ref[...], 1.0, 0.0)) * gr_ref[...]
    dh_ref[...] += jax.lax.dot_general(
        ds, x_ref[...].astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if has_bias:
        db_ref[...] += jnp.sum(ds, axis=0, keepdims=True)


def _bwd_rows(x2, head, head_b, lab2, lse, gr, vocab_size, bn, bv):
    Np, E = x2.shape
    V = head.shape[0]
    has_bias = head_b is not None
    kw = dict(bn=bn, bv=bv, vocab_size=vocab_size, has_bias=has_bias)
    row = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    common = [
        pl.BlockSpec((bn, E), lambda i, j: (i, 0)),
        pl.BlockSpec((bv, E), lambda i, j: (j, 0)),
        row, row, row,
    ]
    args = [x2, head, lab2, lse, gr]
    bias_args = []
    if has_bias:
        bias_args = [head_b.reshape(1, V)]
    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, **kw),
        grid=(Np // bn, V // bv),
        in_specs=common + ([pl.BlockSpec((1, bv), lambda i, j: (0, j))]
                           if has_bias else []),
        out_specs=pl.BlockSpec((bn, E), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, E), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_pallas.interpret(),
        name="ce_bwd_dx",
    )(*args, *bias_args)

    # transposed grid: vocab outer, rows accumulated
    rowT = pl.BlockSpec((bn, 1), lambda v, i: (i, 0))
    commonT = [
        pl.BlockSpec((bn, E), lambda v, i: (i, 0)),
        pl.BlockSpec((bv, E), lambda v, i: (v, 0)),
        rowT, rowT, rowT,
    ]
    out_specs = pl.BlockSpec((bv, E), lambda v, i: (v, 0))
    out_shape = jax.ShapeDtypeStruct((V, E), jnp.float32)
    if has_bias:
        out_specs = [out_specs, pl.BlockSpec((1, bv), lambda v, i: (0, v))]
        out_shape = [out_shape, jax.ShapeDtypeStruct((1, V), jnp.float32)]
    dh = pl.pallas_call(
        functools.partial(_bwd_dh_kernel, **kw),
        grid=(V // bv, Np // bn),
        in_specs=commonT + ([pl.BlockSpec((1, bv), lambda v, i: (0, v))]
                            if has_bias else []),
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_pallas.interpret(),
        name="ce_bwd_dh",
    )(*args, *bias_args)
    if has_bias:
        dh, db = dh
        return dx, dh, db.reshape(V)
    return dx, dh, None


# --------------------------------------------------------------------------- #
# custom_vjp wrapper (mean NLL over the valid rows)
# --------------------------------------------------------------------------- #
def _pad_rows(x2, lab, N, bn):
    n_pad = (-N) % bn
    if n_pad:
        x2 = jnp.concatenate([x2, jnp.zeros((n_pad, x2.shape[1]), x2.dtype)])
        lab = jnp.concatenate([lab, jnp.zeros((n_pad,), lab.dtype)])
    return x2, lab.reshape(-1, 1).astype(jnp.int32), N + n_pad


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ce(x2, head, head_b, labels, vocab_size, bn, bv):
    nll, _ = _ce_fwd(x2, head, head_b, labels, vocab_size, bn, bv)
    return nll


def _ce_fwd(x2, head, head_b, labels, vocab_size, bn, bv):
    N = x2.shape[0]
    xp, lp, Np = _pad_rows(x2, labels, N, bn)
    nll, lse = _fwd_rows(xp, head, head_b, lp, vocab_size, bn, bv)
    # mean over the REAL rows only; the slice-then-mean matches the
    # reference's jnp.mean(lse - ll) lowering for bitwise fp32 parity
    loss = jnp.mean(nll[:N, 0])
    return loss, (x2, head, head_b, labels, lse)


def _ce_bwd(vocab_size, bn, bv, res, g):
    x2, head, head_b, labels, lse = res
    N, E = x2.shape
    xp, lp, Np = _pad_rows(x2, labels, N, bn)
    # d(mean)/d(nll_i) = g / N on valid rows, 0 on the padding
    rows = jnp.arange(Np)[:, None]
    gr = jnp.where(rows < N, g / N, 0.0).astype(jnp.float32)
    dx, dh, db = _bwd_rows(xp, head, head_b, lp, lse, gr, vocab_size, bn, bv)
    dx = dx[:N].astype(x2.dtype)
    dh = dh.astype(head.dtype)
    db = None if head_b is None else db.astype(head_b.dtype)
    # labels are integral: their cotangent is the zero-sized float0 tangent
    dlab = np.zeros(labels.shape, jax.dtypes.float0)
    return dx, dh, db, dlab


_ce.defvjp(_ce_fwd, _ce_bwd)


def fused_cross_entropy(x2, head, labels, vocab_size: int,
                        head_b=None) -> jax.Array:
    """Mean next-token NLL without materializing logits.

    x2: [N, E] hidden rows; head: [V, E]; labels: [N] int; ``vocab_size``
    masks padded vocab columns (same ``-1e9`` sentinel as the reference).
    Differentiable in x2/head/head_b via the streaming backward kernels.
    """
    V, E = head.shape
    bv = _vocab_block(V, E)
    if bv is None:
        raise ValueError(f"fused CE unsupported for V={V} (call "
                         "ce_supported() first)")
    mask = vocab_size if V != vocab_size else None
    return _ce(x2, head, head_b, labels, mask, _ROW_BLOCK, bv)
