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

Blocks (:func:`ce_blocks`, one rule of the call's shapes): a grid step
works on ``bn`` rows by ``bv`` vocab columns, ``bv`` the largest multiple
of 128 (at most 2,048) that divides the padded vocab and ``bn`` the largest
of 1,024 / 512 / 256 / 128 not above the padded row count, both under a
VMEM budget reckoned from what a step holds.  A grid step costs about a
third of a microsecond whatever it computes, and every step fetches a new
tile, so the tile must be large enough for the MXU's time to dwarf both: at
GPT-2's ``[8192, 768] x [768, 50304]`` the blocks are (1024, 384), a grid
of 8 x 131 steps of 604 MFLOP where (128, 128) ran 64 x 393 of 25 (PERF.md
§ 6, PR 30).

Two kernels, and what each streams how often.  ``ce_fwd`` holds a row block
and sweeps the vocab: the head is read once a row block (``N/bn`` times).
``ce_bwd`` is ONE kernel: it holds a vocab block and sweeps the rows,
computes each score tile once and feeds BOTH gradients from it (four
``N x E x V`` matmuls with the forward's, where a ``dx`` and a ``dh`` kernel
that each recompute the tile make five).  ``dhead``'s block accumulates over
the row sweep; ``dx`` accumulates over the vocab blocks in a float32 scratch
that holds ALL the rows of the sweep in VMEM (:func:`ce_row_sweeps`: 24 MiB
at ``[8192, 768]``), so ``x`` is read once a vocab block (``V/bv`` times)
and the head once a sweep.  Rows past what that scratch may hold make
further sweeps, each with its own partial ``dhead``, summed outside.

Operand dtypes: every matmul takes its operands in the dtype they arrived
in and accumulates in float32; ``lse``, the probabilities, the subtraction
of the one-hot and the scale by ``g / N`` are float32, and the result
``ds`` is rounded ONCE, to the dtype of the operands it meets (the head's
for ``dx``, ``x``'s for ``dhead``), as the flash kernels hand ``p`` and
``ds`` to the MXU and as the XLA path's own backward multiplies.  Float32
inputs stay float32 throughout.  Gradients leave in the dtype of their
primal.

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
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import pallas as _pallas

_ROW_BLOCKS = (1024, 512, 256, 128)
_MAX_VOCAB_BLOCK = 2048
# What one grid step may hold by ce_step_bytes' count, what the backward's dx
# accumulator may hold beside it, and what the compiler is told a kernel may
# use: the default scoped limit (16 MiB) would refuse the accumulator alone.
# The count runs above what Mosaic allocates (a [1024, 384] step at E = 768
# counts 18 MiB; with dx and dhead in kernels of their own, all three compiled
# under a limit of 12), so the limit's margin is not need.
_VMEM_BUDGET_BYTES = 32 * 1024 * 1024
_DX_ACC_BYTES = 32 * 1024 * 1024
_VMEM_LIMIT_BYTES = 80 * 1024 * 1024


def ce_step_bytes(bn: int, bv: int, E: int, itemsize: int) -> int:
    """VMEM one grid step of the backward holds (the forward holds less),
    without the rows' dx accumulator."""
    tiles = 2 * (bn + bv) * E * itemsize        # x and head, double buffered
    scores = 4 * bn * bv * 4      # float32 s, p, ds, and ds as the MXU takes it
    # dx's output block and dhead's (both double buffered), dhead's
    # float32 accumulator
    grads = 2 * (bn + bv) * E * itemsize + bv * E * 4
    # labels, lse, g/N as [bn, 1] columns (a sublane row each, 128 lanes
    # wide in VMEM), double buffered
    columns = 6 * bn * 128 * 4
    return tiles + scores + grads + columns


def ce_blocks(N: int, E: int, V: int, dtype) -> Optional[Tuple[int, int]]:
    """``(bn, bv)`` for an ``[N, E] x [E, V]`` loss in ``dtype``, or None
    where the vocab has no lane-multiple block that fits.  THE rule: the
    widest vocab block first, then the tallest row block the budget takes
    beside it (a narrower vocab block only where even 128 rows do not fit)."""
    itemsize = np.dtype(dtype).itemsize
    rows = -(-N // 128) * 128
    for bv in range(min(V, _MAX_VOCAB_BLOCK) // 128 * 128, 0, -128):
        if V % bv:
            continue
        for bn in _ROW_BLOCKS:
            if bn <= rows and ce_step_bytes(bn, bv, E, itemsize) <= _VMEM_BUDGET_BYTES:
                return bn, bv
    return None


def ce_row_sweeps(N: int, E: int, bn: int) -> Tuple[int, int]:
    """``(sweeps, row blocks a sweep)``: the backward holds the float32 dx
    of a whole sweep's rows in VMEM, as many row blocks as ``_DX_ACC_BYTES``
    take, and the rows are padded to ``sweeps x blocks x bn``.  One sweep
    wherever the rows fit (8 blocks of 1,024 at E = 768)."""
    blocks = -(-N // bn)
    sweeps = -(-blocks // max(1, _DX_ACC_BYTES // (bn * E * 4)))
    return sweeps, -(-blocks // sweeps)


def ce_supported(N: int, E: int, V: int) -> bool:
    """Shape + mesh gate for the fused path.  The kernel handles any row
    count (rows pad to the block) but needs the vocab to tile into lane
    blocks that fit VMEM in the widest dtype it takes, and runs un-sharded —
    under a >1-device mesh the vocab is tensor-parallel and the reference
    path (which XLA partitions) wins, except inside a manual (``shard_map``)
    region, where the arrays are already one device's."""
    from deepspeed_tpu.parallel import mesh as mesh_lib
    return (ce_blocks(N, E, V, jnp.float32) is not None
            and (_pallas.single_device() or mesh_lib.in_manual_mode()))


# --------------------------------------------------------------------------- #
# Forward: grid (row blocks, vocab blocks), vocab innermost.  Scratch
# carries the online-softmax state (m, l) plus the label logit across the
# vocab sweep; outputs land on the last vocab step.
# --------------------------------------------------------------------------- #
def _score_block(x, h, b_ref, cols, vocab_size):
    s = jax.lax.dot_general(x, h, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [bn, bv]
    if b_ref is not None:
        s = s + b_ref[...].astype(jnp.float32)            # [1, bv] broadcast
    if vocab_size is not None:
        # same -1e9 sentinel as the reference path (bitwise-equal masking)
        s = jnp.where(cols < vocab_size, s, -1e9)
    return s


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

    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    s = _score_block(x_ref[...], h_ref[...], b_ref, cols, vocab_size)
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
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_pallas.interpret(),
        name="ce_fwd",
    )(*args)
    return nll, lse


# --------------------------------------------------------------------------- #
# Backward: ONE kernel, grid (sweeps, vocab blocks, row blocks of the sweep),
# rows innermost.  Each step recomputes its score tile from (x, head, lse)
# once and adds to both gradients: dhead's block over consecutive steps (the
# legal Pallas accumulation), dx's rows in a scratch that spans the sweep.
# --------------------------------------------------------------------------- #
def _bwd_kernel(x_ref, h_ref, lab_ref, lse_ref, gr_ref, *rest,
                bn, bv, vocab_size, has_bias):
    if has_bias:
        b_ref, dx_ref, dh_ref, db_ref, acc_dx, acc_dh, acc_db = rest
    else:
        dx_ref, dh_ref, acc_dx, acc_dh = rest
        b_ref = None
    v, i = pl.program_id(1), pl.program_id(2)
    last_v, last_i = pl.num_programs(1) - 1, pl.num_programs(2) - 1

    cols = v * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    x, h = x_ref[...], h_ref[...]
    s = _score_block(x, h, b_ref, cols, vocab_size)
    p = jnp.exp(s - lse_ref[...])                         # softmax block
    ds = (p - jnp.where(cols == lab_ref[...], 1.0, 0.0)) * gr_ref[...]
    to_dx = jnp.dot(ds.astype(h.dtype), h,
                    preferred_element_type=jnp.float32)          # [bn, E]
    to_dh = jax.lax.dot_general(ds.astype(x.dtype), x, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bv, E]
    rows = pl.ds(pl.multiple_of(i * bn, bn), bn)

    # the first visit stores, later ones add: nothing is zeroed beforehand
    @pl.when(v == 0)
    def _():
        acc_dx[rows, :] = to_dx

    @pl.when(v > 0)
    def _():
        acc_dx[rows, :] += to_dx

    @pl.when(i == 0)
    def _():
        acc_dh[...] = to_dh
        if has_bias:
            acc_db[...] = jnp.sum(ds, axis=0, keepdims=True)

    @pl.when(i > 0)
    def _():
        acc_dh[...] += to_dh
        if has_bias:
            acc_db[...] += jnp.sum(ds, axis=0, keepdims=True)

    @pl.when(v == last_v)
    def _():
        dx_ref[...] = acc_dx[rows, :].astype(dx_ref.dtype)

    @pl.when(i == last_i)
    def _():
        dh_ref[...] = acc_dh[...].astype(dh_ref.dtype)
        if has_bias:
            db_ref[...] = acc_db[...]


def _bwd_rows(x2, head, head_b, lab2, lse, gr, vocab_size, bn, bv, sweeps):
    """``(dx [Np, E]`` in ``x2``'s dtype, ``dhead [V, E]`` in ``head``'s,
    ``dbias [V]`` float32 or None) for padded inputs: ``Np`` rows are
    ``sweeps`` sweeps of whole row blocks; lab2, lse, gr are ``[Np, 1]``."""
    Np, E = x2.shape
    V = head.shape[0]
    has_bias = head_b is not None
    nb, nv = Np // (sweeps * bn), V // bv
    col = pl.BlockSpec((bn, 1), lambda s, v, i: (s * nb + i, 0))
    # dx's block stays put until the sweep's last vocab block, when its rows
    # are final: an output block is written back when its index moves on
    dx_spec = pl.BlockSpec(
        (bn, E), lambda s, v, i: (s * nb + jnp.where(v == nv - 1, i, 0), 0))
    # one sweep writes dhead as it is; several write float32 partials
    part = head.dtype if sweeps == 1 else jnp.float32
    out_specs = [dx_spec, pl.BlockSpec((None, bv, E), lambda s, v, i: (s, v, 0))]
    out_shape = [jax.ShapeDtypeStruct((Np, E), x2.dtype),
                 jax.ShapeDtypeStruct((sweeps, V, E), part)]
    scratch = [pltpu.VMEM((nb * bn, E), jnp.float32),
               pltpu.VMEM((bv, E), jnp.float32)]
    if has_bias:
        out_specs.append(pl.BlockSpec((None, 1, bv), lambda s, v, i: (s, 0, v)))
        out_shape.append(jax.ShapeDtypeStruct((sweeps, 1, V), jnp.float32))
        scratch.append(pltpu.VMEM((1, bv), jnp.float32))
    dx, dh, *db = pl.pallas_call(
        functools.partial(_bwd_kernel, bn=bn, bv=bv, vocab_size=vocab_size,
                          has_bias=has_bias),
        grid=(sweeps, nv, nb),
        in_specs=[pl.BlockSpec((bn, E), lambda s, v, i: (s * nb + i, 0)),
                  pl.BlockSpec((bv, E), lambda s, v, i: (v, 0)),
                  col, col, col]
        + ([pl.BlockSpec((1, bv), lambda s, v, i: (0, v))] if has_bias else []),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_pallas.interpret(),
        name="ce_bwd",
    )(x2, head, lab2, lse, gr, *([head_b.reshape(1, V)] if has_bias else []))
    dh = dh[0] if sweeps == 1 else dh.sum(axis=0).astype(head.dtype)
    return dx, dh, db[0].sum(axis=0).reshape(V) if has_bias else None


# --------------------------------------------------------------------------- #
# custom_vjp wrapper (mean NLL over the valid rows)
# --------------------------------------------------------------------------- #
def _pad_rows(x2, lab, bn):
    """Rows padded with zeros to whole sweeps of whole row blocks."""
    N, E = x2.shape
    sweeps, blocks = ce_row_sweeps(N, E, bn)
    n_pad = sweeps * blocks * bn - N
    if n_pad:
        x2 = jnp.concatenate([x2, jnp.zeros((n_pad, E), x2.dtype)])
        lab = jnp.concatenate([lab, jnp.zeros((n_pad,), lab.dtype)])
    return x2, lab.reshape(-1, 1).astype(jnp.int32), sweeps


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ce(x2, head, head_b, labels, vocab_size, bn, bv):
    nll, _ = _ce_fwd(x2, head, head_b, labels, vocab_size, bn, bv)
    return nll


def _ce_fwd(x2, head, head_b, labels, vocab_size, bn, bv):
    N = x2.shape[0]
    xp, lp, _ = _pad_rows(x2, labels, bn)
    nll, lse = _fwd_rows(xp, head, head_b, lp, vocab_size, bn, bv)
    # mean over the REAL rows only; the slice-then-mean matches the
    # reference's jnp.mean(lse - ll) lowering for bitwise fp32 parity
    loss = jnp.mean(nll[:N, 0])
    return loss, (x2, head, head_b, labels, lse)


def _ce_bwd(vocab_size, bn, bv, res, g):
    x2, head, head_b, labels, lse = res
    N = x2.shape[0]
    xp, lp, sweeps = _pad_rows(x2, labels, bn)
    # d(mean)/d(nll_i) = g / N on valid rows, 0 on the padding
    rows = jnp.arange(xp.shape[0])[:, None]
    gr = jnp.where(rows < N, g / N, 0.0).astype(jnp.float32)
    dx, dh, db = _bwd_rows(xp, head, head_b, lp, lse, gr, vocab_size, bn, bv,
                           sweeps)
    db = None if head_b is None else db.astype(head_b.dtype)
    # labels are integral: their cotangent is the zero-sized float0 tangent
    dlab = np.zeros(labels.shape, jax.dtypes.float0)
    return dx[:N], dh, db, dlab


_ce.defvjp(_ce_fwd, _ce_bwd)


def fused_cross_entropy(x2, head, labels, vocab_size: int,
                        head_b=None) -> jax.Array:
    """Mean next-token NLL without materializing logits.

    x2: [N, E] hidden rows; head: [V, E]; labels: [N] int; ``vocab_size``
    masks padded vocab columns (same ``-1e9`` sentinel as the reference).
    Differentiable in x2/head/head_b via the streaming backward kernel.
    """
    V, E = head.shape
    blocks = ce_blocks(x2.shape[0], E, V, max(x2.dtype, head.dtype,
                                               key=lambda d: d.itemsize))
    if blocks is None:
        raise ValueError(f"fused CE unsupported for V={V}, E={E} (call "
                         "ce_supported() first)")
    mask = vocab_size if V != vocab_size else None
    return _ce(x2, head, head_b, labels, mask, *blocks)
