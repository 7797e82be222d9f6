"""The gated delta rule's state update of a serve step's decode rows
(``models/hybrid.py:delta_mixer``): a row's float32 state ``S [dk, dv]`` a
head is decayed, CORRECTED by what it returns for the token's key, written,
and read by the token's query,

    S' = a S;  m = S'^T k;  d = b (v - m);  S_new = S' + k d^T;  o = S_new^T q

with ``a`` the decay and ``b`` the write strength of the token.  The bytes
are the state's, read once and written once; everything else is a few rows.

THE LAYOUT.  The states are kept ``[layers, slots, dk, H * dv]``: the key's
lanes on the sublanes, and the heads' values side by side on the lanes.  A
head's own ``[dk, dv]`` tile pads ``dv = 192`` lanes to 256 in HBM (a third
more bytes held, and moved by every pass: 2.95 MB a slot a layer for the
2.21 MB of Olmo-Hybrid-7B); ``30 x 192 = 5,760`` lanes are 45 whole tiles.

:func:`delta_state_update` is the one way in.  On a TPU, where
:func:`kernel_shape_ok` admits the shape, the kernel ``delta_state_update``
takes the stacked states WHOLE and the layer as a scalar (a slice handed to
a Pallas call is copied out first), a slot a grid step, and updates them in
place (``input_output_aliases``): one read and one write of the state.  What
a head contributes to a lane (its key's and its query's number a sublane,
its decay and its write strength) is laid over the lanes of its values by a
select between lane-broadcast columns, the heads taken in groups whose
values are whole lane tiles (two of 192), so no matrix unit and no
unaligned slice is needed.  It uses ``o = a S^T q + (k . q) d``, which is
``S_new^T q`` with ``S_new`` multiplied out, so that both products read the
state as it came.  Everywhere else the reference beside it runs, the same
arithmetic in ``jax.numpy`` on the state viewed ``[slots, dk, H, dv]`` (on
the chip 1.9 times slower end to end in Olmo-Hybrid's cell: the view is a
copy, 192 lanes being no whole tile), its results held behind an
``optimization_barrier``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import pallas as _pallas

HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128
# two buffers of a slot's state in and two out (8.8 MB at 96 x 5,760), and
# the rows beside them
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def _heads_a_group(dv: int) -> int:
    """Heads whose values side by side are whole lane tiles."""
    return _LANES // np.gcd(dv, _LANES)


def kernel_shape_ok(H: int, dk: int, dv: int, dtype) -> bool:
    """What the kernel takes: float32 states, the key's lanes whole sublane
    tiles, the heads in whole groups of whole lane tiles, and four buffers
    of a slot's state inside the kernel's memory."""
    return (np.dtype(dtype) == np.float32 and dk % 8 == 0
            and H % _heads_a_group(dv) == 0
            and 4 * dk * H * dv * 4 <= _VMEM_LIMIT_BYTES * 3 // 4)


def _kernel(lay_ref, s_ref, q_ref, k_ref, rows_ref, so_ref, o_ref, *, H, dv):
    """One slot: ``s_ref`` / ``so_ref [dk, H dv]``, ``q_ref`` / ``k_ref [dk,
    H]`` (a head a lane), ``rows_ref [4, H dv]``: the values, and the decay,
    the write strength and ``k . q`` of a lane's head; ``o_ref [1, H dv]``."""
    del lay_ref
    dk = s_ref.shape[0]
    g = _heads_a_group(dv)
    W = g * dv
    lane = jax.lax.broadcasted_iota(jnp.int32, (dk, W), 1)

    def over_lanes(ref, first):
        """``[dk, W]``: heads ``first .. first + g``'s columns of ``ref``,
        each over the lanes of its head's values."""
        out = jnp.broadcast_to(ref[:, first:first + 1], (dk, W))
        for i in range(1, g):
            out = jnp.where(lane >= i * dv, ref[:, first + i:first + i + 1], out)
        return out

    for p in range(H // g):
        lanes = slice(p * W, (p + 1) * W)
        s = s_ref[:, lanes]
        kx, qx = over_lanes(k_ref, p * g), over_lanes(q_ref, p * g)
        v, a = rows_ref[0:1, lanes], rows_ref[1:2, lanes]
        b, kq = rows_ref[2:3, lanes], rows_ref[3:4, lanes]
        m = a * jnp.sum(s * kx, axis=0, keepdims=True)
        sq = a * jnp.sum(s * qx, axis=0, keepdims=True)
        d = b * (v - m)
        o_ref[:, lanes] = sq + d * kq
        so_ref[:, lanes] = a * s + kx * d


def _call(state, layer, q, k, rows, dv):
    """``state [L, slots, dk, H dv]``, ``layer [1]`` int32, ``q`` and ``k``
    ``[slots, dk, H]``, ``rows [slots, 4, H dv]`` -> (state, o ``[slots, 1,
    H dv]``)."""
    _, n, dk, HV = state.shape
    H = HV // dv
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(n,),
        in_specs=[
            pl.BlockSpec((None, None, dk, HV), lambda i, lay: (lay[0], i, 0, 0)),
            pl.BlockSpec((None, dk, H), lambda i, lay: (i, 0, 0)),
            pl.BlockSpec((None, dk, H), lambda i, lay: (i, 0, 0)),
            pl.BlockSpec((None, 4, HV), lambda i, lay: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, dk, HV), lambda i, lay: (lay[0], i, 0, 0)),
            pl.BlockSpec((None, 1, HV), lambda i, lay: (i, 0, 0)),
        ])
    return pl.pallas_call(
        functools.partial(_kernel, H=H, dv=dv),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((n, 1, HV), jnp.float32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_pallas.interpret(),
        name="delta_state_update",
    )(layer, state, q, k, rows)


def delta_state_update(state, layer, q, k, v, decay, strength, live):
    """The decode rows' update of layer ``layer`` of ``state [layers, slots,
    dk, H * dv]`` float32; row ``n`` is slot ``n``.  ``q`` (scaled), ``k``
    ``[slots, H, dk]``, ``v [slots, H, dv]``, ``decay`` (``a`` in (0, 1]) and
    ``strength`` (``b``) ``[slots, H]``, all float32; a row that is not
    ``live [slots]`` leaves its state to the bit (``a = 1``, ``b = 0``).
    -> (state, o ``[slots, H, dv]``: what each row's query reads)."""
    n, H, dk = q.shape
    dv = v.shape[-1]
    assert state.shape[1:] == (n, dk, H * dv), (state.shape, q.shape, v.shape)
    a = jnp.where(live[:, None], decay, 1.0)
    b = jnp.where(live[:, None], strength, 0.0)
    kq = jnp.sum(k * q, axis=-1)
    if (_pallas.use_kernel("delta_state_update")
            and kernel_shape_ok(H, dk, dv, state.dtype) and _pallas.single_device()):
        over = lambda t: jnp.repeat(t, dv, axis=-1)         # a head's, a lane
        rows = jnp.stack([v.reshape(n, H * dv), over(a), over(b), over(kq)], axis=1)
        state, o = _call(state, jnp.asarray(layer, jnp.int32).reshape(1),
                         jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), rows, dv)
        return state, o.reshape(n, H, dv)
    s = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    s = s.reshape(n, dk, H, dv)
    m = a[..., None] * jnp.einsum("nkhv,nhk->nhv", s, k, precision=HIGHEST)
    sq = a[..., None] * jnp.einsum("nkhv,nhk->nhv", s, q, precision=HIGHEST)
    d = b[..., None] * (v - m)
    s = a[:, None, :, None] * s + k.transpose(0, 2, 1)[..., None] * d[:, None]
    state = jax.lax.dynamic_update_index_in_dim(
        state, s.reshape(n, dk, H * dv), layer, 0)
    # The barrier is part of the arithmetic on a TPU: without it XLA, short of
    # memory beside a serving arena, recomputes what read the state AFTER the
    # update has been written in place, and serves garbage (the same program
    # beside 1,025 pages right, beside 4,096 wrong, behind the barrier right
    # to the digit: PERF.md § 6, PR 47).
    return jax.lax.optimization_barrier((state, sq + d * kq[..., None]))
