"""Compressed (1-bit) allreduce for the onebit optimizer family.

Capability parity with the reference's hand-rolled compressed allreduce
(``deepspeed/runtime/comm/nccl.py:54`` ``NcclBackend.compressed_allreduce``
and the MPI/HCCL variants): a two-stage compensated sign compression —

  1. worker side: add the local error-feedback buffer, take the elementwise
     sign plus one fp32 scale (``||x||/sqrt(n)``), remember the residual;
  2. exchange: each device all-to-alls its int8 sign chunks so device *d*
     "serves" chunk *d* — 1 byte/element on the wire instead of 4;
  3. server side: average the per-worker ``sign·scale`` reconstructions of
     the served chunk, compensate with a server error buffer, sign+scale
     again, and all-gather the result (1 byte/element again).

Wire volume per element: 2 bytes (all-to-all + all-gather of int8) vs 8
bytes for a ring fp32 allreduce — the same 4x the reference reports.

TPU-native design: the whole algorithm is a pure function over
``jax.lax`` collectives (``all_to_all``/``all_gather``) meant to run inside
``shard_map`` over the data-parallel mesh axis; the error buffers are the
caller's state (the engine stores them sharded one-per-device).  No CUDA
streams, no cupy: XLA schedules the collectives on ICI.

The compressor and its error-feedback state live in
``comm/compression/core`` — shared with the ZeRO++ blockwise collectives —
and are re-exported here so the public surface of this module is unchanged.
"""

from typing import Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.comm.compression.core import (  # noqa: F401 — public API
    CompressionState, ef_compensate, ef_residual, init_compression_state,
    padded_size, sign_scale, zeroed_compression_state)

# kept under its historical private name for callers that reached in
_sign_scale = sign_scale


def compressed_bytes(n: int, world: int) -> int:
    """Bytes this device puts on the wire per call (for the comms logger):
    int8 all-to-all (n/world to each of world-1 peers) + int8 all-gather of
    the served chunk + two fp32 scale gathers."""
    np_ = padded_size(n, world)
    chunk = np_ // world
    return (world - 1) * chunk + (world - 1) * chunk + 2 * 4 * (world - 1)


def compressed_allreduce(x: jax.Array, state: CompressionState,
                         axis_name: str) -> Tuple[jax.Array, CompressionState]:
    """Compensated 1-bit mean over ``axis_name`` (call inside shard_map).

    ``x`` is this device's flat fp32 vector (unpadded length); returns the
    compressed mean (same shape) and the updated error buffers.
    """
    world = jax.lax.axis_size(axis_name)
    n = x.shape[0]
    n_pad = state.worker_error.shape[0]
    chunk = n_pad // world

    flat = jnp.zeros((n_pad,), jnp.float32).at[:n].set(x)

    # -- worker compression -------------------------------------------- #
    compensated = ef_compensate(flat, state.worker_error)
    sign, scale = sign_scale(compensated)
    new_worker_error = ef_residual(compensated, scale * sign.astype(jnp.float32))

    # -- exchange: device d serves chunk d ----------------------------- #
    # [world, chunk] rows = my signs of every chunk → after all_to_all rows
    # = every worker's signs of MY chunk
    theirs = jax.lax.all_to_all(sign.reshape(world, chunk), axis_name,
                                split_axis=0, concat_axis=0)      # [w, c] int8
    scales = jax.lax.all_gather(scale, axis_name)                 # [w]

    recovered = jnp.mean(
        theirs.astype(jnp.float32) * scales[:, None], axis=0)     # [c]

    # -- server compression of the served chunk ------------------------ #
    compensated2 = ef_compensate(recovered, state.server_error)
    sign2, scale2 = sign_scale(compensated2)
    new_server_error = ef_residual(compensated2,
                                   scale2 * sign2.astype(jnp.float32))

    # -- gather every server's compressed chunk ------------------------ #
    all_signs = jax.lax.all_gather(sign2, axis_name)              # [w, c] int8
    all_scales = jax.lax.all_gather(scale2, axis_name)            # [w]
    result = (all_signs.astype(jnp.float32) * all_scales[:, None]).reshape(-1)

    return result[:n], CompressionState(new_worker_error, new_server_error)
