"""Mamba-1's selective scan on the serving path
(``models/hybrid.py:mamba_mixer``): a DIAGONAL recurrence by channel, a decay
of its own for every (channel ``d``, state ``n``) pair,

    h_t[n, d] = exp(dt_t[d] A[n, d]) h_{t-1}[n, d] + dt_t[d] B_t[n] c_t[d]
    y_t[d]    = sum_n C_t[n] h_t[n, d] + D[d] c_t[d]

with ``c_t`` the convolved input, ``dt_t > 0`` the token's step a channel,
``A < 0``, and ``B_t``, ``C_t`` the token's ``S`` input and output weights.
``exp(dt_t[d] A[n, d])`` differs for every (d, n), so there is no chunked
matrix form (what ``hybrid.linear_chunk`` and ``hybrid.delta_chunk`` use
needs ONE decay a head): nothing here reaches the matrix unit, and both
kernels are the vector unit's and ``exp``'s (``S x N`` of them a token).

THE LAYOUT.  The states are kept ``[layers, slots, S, N]`` float32, the
CHANNELS on the lanes (5,120: 40 whole tiles) and the ``S = 16`` states on
the sublanes (two whole tiles); ``[..., N, S]`` would pad 16 lanes to 128 and
hold and move eight times the bytes.  ``A`` is kept the same way, ``[S, N]``.
A token's ``B`` and ``C`` arrive as COLUMNS, ``bc [rows, S, 2]``, and are
laid over the lanes by a lane broadcast; its ``c`` and ``dt`` are rows
``[rows, N]``, laid over the sublanes.

Two kernels share one token's arithmetic (:func:`_token`):

* ``mamba_state_update`` (:func:`mamba_state_update`, the decode rows): the
  stacked states WHOLE and the layer as a scalar (a slice handed to a Pallas
  call is copied out first), eight slots a grid step, updated in place
  (``input_output_aliases``): a state is read once and written once, and
  that is all the bytes there are;
* ``mamba_chunk_scan`` (:func:`mamba_chunk_scan`, a prompt chunk): the
  recurrence over the chunk's tokens IN ORDER, a block of lanes a grid step,
  the state of that block held in registers across the tokens: ``c``, ``dt``
  and ``bc`` are read, ``y`` is written, the slot's state goes in and comes
  out once, and no ``[tokens, N, S]`` array exists anywhere.

A row whose ``dt`` is 0 leaves its state to the bit (``exp(0) h + 0``): a
decode row that carries nothing, and a chunk's rows past its live tokens.
Everywhere the gates refuse (off a TPU, a mesh, widths that are not whole
tiles) the references beside the kernels run: the same lines in
``jax.numpy``, the chunk's a ``lax.scan`` over its tokens.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import pallas as _pallas

_LANES = 128
# slots a grid step of the decode kernel: a sublane tile of the rows' c and dt
_SLOTS_A_STEP = 8
# lanes an inner step of either kernel works on: a state of [16, 512] float32
# is eight registers, and the decay, the write and the read beside it stay
# inside the register file
_LANE_BLOCK = 512
# tokens a loop step of the chunk kernel: a sublane tile of c, dt and y
_TOKENS_A_STEP = 8
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def kernel_shape_ok(rows: int, S: int, N: int, dtype) -> bool:
    """What both kernels take: float32 states, the states whole sublane
    tiles, the channels whole lane blocks, the rows (slots, or a chunk's
    tokens) whole sublane tiles.  A bfloat16 state is taken too (the
    arithmetic is float32 either way) and no configuration keeps one: it is
    the CONTROL that the benchmark's check of the state must refuse, run at
    the kernels' own speed (``benchmarks/kinds/serve_backlog_resident_mamba.py``:
    ``--set planted='"state-bfloat16"'``; ``tools/serve_parity.py --state``)."""
    dtype = np.dtype(dtype)
    return (dtype in (np.dtype(np.float32), np.dtype(jnp.bfloat16))
            and S % (8 * 4 // dtype.itemsize) == 0 and N % _LANE_BLOCK == 0
            and rows % _SLOTS_A_STEP == 0)


def _token(h, a, c, dt, bc, d):
    """One token on a block of lanes: ``h``, ``a`` ``[S, W]``; ``c``, ``dt``,
    ``d`` ``[1, W]``; ``bc [S, 2]`` (``B`` and ``C`` as columns).  -> (h, y
    ``[1, W]``)."""
    h = jnp.exp(dt * a) * h + (dt * c) * bc[:, 0:1]
    return h, jnp.sum(bc[:, 1:2] * h, axis=0, keepdims=True) + d * c


def _update_kernel(lay_ref, s_ref, a_ref, d_ref, c_ref, dt_ref, bc_ref,
                   so_ref, y_ref):
    """Eight slots: ``s_ref`` / ``so_ref [8, S, N]``, ``a_ref [S, N]``,
    ``d_ref [1, N]``, ``c_ref`` / ``dt_ref`` / ``y_ref [8, N]``, ``bc_ref
    [8, S, 2]``."""
    del lay_ref
    N = s_ref.shape[-1]
    for g in range(s_ref.shape[0]):
        bc = bc_ref[g]
        for lo in range(0, N, _LANE_BLOCK):
            lanes = slice(lo, lo + _LANE_BLOCK)
            h, y = _token(s_ref[g, :, lanes].astype(jnp.float32), a_ref[:, lanes],
                          c_ref[g:g + 1, lanes], dt_ref[g:g + 1, lanes], bc,
                          d_ref[:, lanes])
            so_ref[g, :, lanes] = h.astype(so_ref.dtype)
            y_ref[g:g + 1, lanes] = y


def _update_call(state, layer, a, d, c, dt, bc):
    """``state [L, slots, S, N]``, ``layer [1]`` int32, ``a [S, N]``, ``d [1,
    N]``, ``c`` and ``dt`` ``[slots, N]``, ``bc [slots, S, 2]`` -> (state,
    y ``[slots, N]``)."""
    _, n, S, N = state.shape
    G = _SLOTS_A_STEP
    rows = pl.BlockSpec((G, N), lambda i, lay: (i, 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda i, lay: (0,) * len(shape))
    states = pl.BlockSpec((None, G, S, N), lambda i, lay: (lay[0], i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(n // G,),
        in_specs=[states, whole((S, N)), whole((1, N)), rows, rows,
                  pl.BlockSpec((G, S, 2), lambda i, lay: (i, 0, 0))],
        out_specs=[states, rows])
    return pl.pallas_call(
        _update_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((n, N), jnp.float32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_pallas.interpret(),
        name="mamba_state_update",
    )(layer, state, a, d, c, dt, bc)


def _columns(B, C):
    """``B``, ``C`` ``[rows, S]`` -> ``bc [rows, S, 2]``: a token's two
    columns side by side."""
    return jnp.stack([B, C], axis=-1)


def mamba_state_update(state, layer, c, dt, B, C, A, D, live):
    """The decode rows' update of layer ``layer`` of ``state [layers, slots,
    S, N]``; row ``n`` is slot ``n``.  ``c`` (the convolved input) and ``dt``
    ``[slots, N]``, ``B`` and ``C`` ``[slots, S]``, ``A [S, N]`` (negative),
    ``D [N]``, all float32; a row that is not ``live [slots]`` leaves its
    state to the bit.  -> (state, y ``[slots, N]`` float32: ``C . h + D c``
    of each row's state after its token)."""
    n, N = c.shape
    S = B.shape[-1]
    assert state.shape[1:] == (n, S, N) and A.shape == (S, N), (state.shape, A.shape)
    dt = jnp.where(live[:, None], dt, 0.0)
    if (_pallas.use_kernel("mamba_state_update")
            and kernel_shape_ok(n, S, N, state.dtype) and _pallas.single_device()):
        return _update_call(state, jnp.asarray(layer, jnp.int32).reshape(1), A,
                            D[None], c, dt, _columns(B, C))
    s = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    h = (jnp.exp(dt[:, None] * A[None]) * s.astype(jnp.float32)
         + (dt * c)[:, None] * B[:, :, None])
    y = jnp.sum(C[:, :, None] * h, axis=1) + D * c
    state = jax.lax.dynamic_update_index_in_dim(state, h.astype(state.dtype), layer, 0)
    # the barrier is part of the arithmetic on a TPU, as it is for the delta
    # rule's reference (ops/pallas/delta_rule.py: PERF.md § 6, PR 47): XLA,
    # short of memory beside an arena, recomputes what read the state after
    # the update has been written in place
    return jax.lax.optimization_barrier((state, y))


def _scan_kernel(h_ref, a_ref, d_ref, c_ref, dt_ref, bc_ref, ho_ref, y_ref):
    """A block of lanes: ``h_ref`` / ``ho_ref`` / ``a_ref [S, W]``, ``d_ref
    [1, W]``, ``c_ref`` / ``dt_ref`` / ``y_ref [T, W]``, ``bc_ref [T, S, 2]``
    (every block of lanes reads all of it)."""
    T, W = c_ref.shape
    K = _TOKENS_A_STEP
    a, d = a_ref[...], d_ref[...]

    def tokens(j, h):
        at = pl.ds(pl.multiple_of(j * K, K), K)
        c, dt, bc = c_ref[at, :], dt_ref[at, :], bc_ref[at]
        ys = []
        for i in range(K):
            h, y = _token(h, a, c[i:i + 1], dt[i:i + 1], bc[i], d)
            ys.append(y)
        y_ref[at, :] = jnp.concatenate(ys, axis=0)
        return h

    h = jax.lax.fori_loop(0, T // K, tokens, h_ref[...].astype(jnp.float32))
    ho_ref[...] = h.astype(ho_ref.dtype)


def _scan_call(h, a, d, c, dt, bc):
    """``h [S, N]``, ``a [S, N]``, ``d [1, N]``, ``c`` and ``dt`` ``[T, N]``,
    ``bc [T, S, 2]`` -> (h ``[S, N]``, y ``[T, N]``)."""
    S, N = h.shape
    T, W = c.shape[0], _LANE_BLOCK
    lanes = lambda rows: pl.BlockSpec((rows, W), lambda i: (0, i))
    return pl.pallas_call(
        _scan_kernel,
        grid=(N // W,),
        in_specs=[lanes(S), lanes(S), lanes(1), lanes(T), lanes(T),
                  pl.BlockSpec((T, S, 2), lambda i: (0, 0, 0))],
        out_specs=[lanes(S), lanes(T)],
        out_shape=[jax.ShapeDtypeStruct(h.shape, h.dtype),
                   jax.ShapeDtypeStruct((T, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_pallas.interpret(),
        name="mamba_chunk_scan",
    )(h, a, d, c, dt, bc)


def mamba_chunk_scan(h, c, dt, B, C, A, D, live):
    """The recurrence over ``T`` consecutive tokens of one sequence that
    enters with the state ``h [S, N]``.  ``c`` and ``dt`` ``[T, N]``, ``B``
    and ``C`` ``[T, S]``, ``A [S, N]``, ``D [N]``, all float32; ``live [T]``:
    the first ``n`` tokens carry the sequence (the others decay nothing and
    write nothing).  -> (the state after those ``n``, in ``h``'s type, y ``[T,
    N]`` float32)."""
    T, N = c.shape
    S = B.shape[-1]
    assert h.shape == A.shape == (S, N), (h.shape, A.shape)
    dt = jnp.where(live[:, None], dt, 0.0)
    if (_pallas.use_kernel("mamba_chunk_scan")
            and kernel_shape_ok(T, S, N, h.dtype) and _pallas.single_device()):
        return _scan_call(h, A, D[None], c, dt, _columns(B, C))

    def token(h, row):
        c_t, dt_t, b_t, c_out = row
        h = jnp.exp(dt_t[None] * A) * h + (dt_t * c_t)[None] * b_t[:, None]
        return h, jnp.sum(c_out[:, None] * h, axis=0) + D * c_t

    out, y = jax.lax.scan(token, h.astype(jnp.float32), (c, dt, B, C))
    return out.astype(h.dtype), y
