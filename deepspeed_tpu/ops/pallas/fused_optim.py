"""Pallas TPU fused Adam/AdamW update for the NVMe offload walk.

This kernel does the whole update for one leaf block — param, grad, m, v
in, param/m/v out — in a single VMEM pass with the loss-scale unscale and
the clip factor folded in as SMEM scalars, which is what lets the
offload-chunked walk in ``runtime/engine.py`` (``_fused_offload_step``,
its one caller) update chunk N while chunk N+1's NVMe swap-in is still in
flight (the per-leaf launch has no dependency on the rest of the tree).

A compiled step program does not call it: there Adam is the optax chain, a
leaf at a time in the leaf's own shape and layout, which XLA fuses into one
memory pass a leaf.  The kernel wants ``[rows, 128]``, and flattening a
leaf to that is a copy of the array on the chip's tiled layouts: over the
whole tree it cost twice what the kernel took (5.8 ms a step for the chain
against 16.4 at 124M parameters, TPU v5e).

Parity contract (``tests/unit/runtime/test_fused_optim.py``): bitwise
equality with the optax chain in fp32 — the kernel performs the exact
optax 0.2.x op sequence (``(1-b)*g + b*m``, safe int32 count increment,
``m/bc1 / (sqrt(n/bc2) + eps)``, decay-after for AdamW, ``-lr`` scale)
with the same scalar promotion, so there is no tolerance to tune.

Supported chains: ``optax.adamw`` (static lr or schedule) and
``optax.adam`` — i.e. the factory's adam/fusedadam/cpuadam/adamw with
``adam_w_mode`` (the default).  Anything else (``add_decayed_weights``
*before* adam = L2 mode, lamb, onebit, client chains) makes
:func:`match_adam_chain` return ``None`` and the walk is not taken.  The
engine takes the kernel where ``ops.pallas``'s rule says so
(``runtime/engine.py:_fused_opt_active``).
"""

import functools
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import pallas as _pallas

_LANE = 128
_SUBLANE = 8
_INT32_MAX = jnp.iinfo(jnp.int32).max


# --------------------------------------------------------------------------- #
# Spec + state-shape matching
# --------------------------------------------------------------------------- #
def spec_from_config(name: str, params: Dict[str, Any],
                     lr: Union[float, Callable[[int], float]]
                     ) -> Optional[Dict[str, Any]]:
    """Fusion spec for a ds_config optimizer block, or ``None`` when the
    resulting optax chain isn't a decay-after Adam (the only math this
    kernel implements)."""
    name = (name or "adam").lower()
    if name not in ("adam", "adamw", "fusedadam", "cpuadam"):
        return None
    adam_w = bool(params.get("adam_w_mode", True)) or name == "adamw"
    wd = float(params.get("weight_decay", 0.0))
    if not adam_w and wd:
        return None      # L2 mode: decay feeds the moments; different math
    betas = params.get("betas", (0.9, 0.999))
    return {"b1": float(betas[0]), "b2": float(betas[1]),
            "eps": float(params.get("eps", 1e-8)),
            "wd": wd if adam_w else 0.0, "lr": lr}


def match_adam_chain(opt_state) -> Optional[Tuple[int, Optional[int]]]:
    """``(adam_idx, schedule_idx)`` into the chain's state tuple, or
    ``None`` when the structure isn't optax adam/adamw: exactly one
    ScaleByAdamState, at most one ScaleByScheduleState, all other links
    stateless."""
    if not isinstance(opt_state, tuple) or isinstance(opt_state, jnp.ndarray):
        return None
    adam_idx = sched_idx = None
    for i, s in enumerate(opt_state):
        fields = getattr(s, "_fields", None)
        if fields is None:
            return None
        if "mu" in fields and "nu" in fields and "count" in fields:
            if adam_idx is not None:
                return None
            adam_idx = i
        elif "count" in fields:
            if sched_idx is not None:
                return None
            sched_idx = i
        elif len(fields):
            return None
    if adam_idx is None:
        return None
    return adam_idx, sched_idx


def _safe_int32_increment(count):
    # optax.safe_int32_increment — saturates instead of wrapping
    return jnp.where(count < _INT32_MAX, count + 1, _INT32_MAX)


def step_scalars(spec: Dict[str, Any], count, sched_count=None):
    """(neg_lr, bc1, bc2) for this step, matching optax's promotion: the
    bias corrections are ``1 - b**count_inc`` in f32, the step size is
    ``-1 * lr(count)`` (schedule) or the static ``-lr``."""
    count_inc = _safe_int32_increment(count)
    bc1 = (1.0 - spec["b1"] ** count_inc).astype(jnp.float32)
    bc2 = (1.0 - spec["b2"] ** count_inc).astype(jnp.float32)
    lr = spec["lr"]
    if callable(lr):
        sc = count if sched_count is None else sched_count
        neg_lr = jnp.asarray(-1 * lr(sc), jnp.float32)
    else:
        neg_lr = jnp.asarray(-lr, jnp.float32)
    return neg_lr, bc1, bc2


# --------------------------------------------------------------------------- #
# Kernel: one [rows, 128] leaf block per grid step.  scal (SMEM) =
# [inv, clip_factor, neg_lr, bc1, bc2]; inv/clip fold the loss-scale
# unscale and the grad clip so raw accumulated grads can feed the kernel
# with the exact ``(g*inv)*factor`` op order of the unfused path.
# --------------------------------------------------------------------------- #
def _adam_kernel(scal_ref, p_ref, g_ref, mu_ref, nu_ref,
                 op_ref, omu_ref, onu_ref, *, b1, b2, eps, wd):
    g = (g_ref[...].astype(jnp.float32) * scal_ref[0]) * scal_ref[1]
    mu = (1 - b1) * g + b1 * mu_ref[...]
    nu = (1 - b2) * (g * g) + b2 * nu_ref[...]
    u = (mu / scal_ref[3]) / (jnp.sqrt(nu / scal_ref[4]) + eps)
    if wd:
        u = u + wd * p_ref[...]
    u = scal_ref[2] * u
    p = p_ref[...]
    op_ref[...] = (p + u).astype(op_ref.dtype)
    omu_ref[...] = mu
    onu_ref[...] = nu


def _row_block(rows: int) -> int:
    for br in (2048, 1024, 512, 256, 128, 64, 32, 16, 8):
        if rows % br == 0:
            return br
    return rows


def fused_leaf_update(p, g, mu, nu, scal, *, b1, b2, eps, wd):
    """(new_p, new_mu, new_nu) for one leaf.  ``scal`` is the stacked
    [inv, clip_factor, neg_lr, bc1, bc2] f32 vector; shapes are free —
    the leaf is flattened and padded to (rows, 128) lane tiles (the pad
    region computes zeros and is sliced off)."""
    shape, pdt = p.shape, p.dtype
    n = int(p.size)
    tile = _LANE * _SUBLANE
    n_pad = (-n) % tile
    def flat(a, dt=None):
        a = a.reshape(-1) if a.shape != () else a.reshape(1)
        a = a.astype(dt) if dt is not None else a
        if n_pad:
            a = jnp.concatenate([a, jnp.zeros((n_pad,), a.dtype)])
        return a.reshape(-1, _LANE)
    p2, g2 = flat(p), flat(g)
    mu2, nu2 = flat(mu, jnp.float32), flat(nu, jnp.float32)
    rows = p2.shape[0]
    br = _row_block(rows)
    blk = lambda dt: pl.BlockSpec((br, _LANE), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps, wd=wd),
        grid=(rows // br,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),
                  blk(pdt), blk(g2.dtype), blk(jnp.float32),
                  blk(jnp.float32)],
        out_specs=[blk(pdt), blk(jnp.float32), blk(jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANE), pdt),
                   jax.ShapeDtypeStruct((rows, _LANE), jnp.float32),
                   jax.ShapeDtypeStruct((rows, _LANE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=_pallas.interpret(),
        name="fused_adam",
    )(scal.astype(jnp.float32), p2, g2, mu2, nu2)
    def unflat(a, dt):
        return a.reshape(-1)[:n].reshape(shape).astype(dt)
    return (unflat(out[0], pdt), unflat(out[1], mu.dtype),
            unflat(out[2], nu.dtype))
