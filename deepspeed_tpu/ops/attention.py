"""Attention ops — registry + reference implementation.

The reference's attention fast paths are CUDA kernels
(``csrc/transformer/softmax_kernels.cu``, inference ``softmax_context`` in
``csrc/transformer/inference/csrc/pt_binding.cpp:1717-1781``).  Here the
fast path is a Pallas TPU flash-attention kernel
(``deepspeed_tpu/ops/pallas/flash_attention.py``) and the reference path is
pure jnp (XLA still fuses it into a handful of kernels); parity tests compare
the two the way ``tests/unit/ops/accelerators/test_accelerator_forward.py``
compares fused CUDA vs HF modeling.

All implementations share one signature::

    fn(q, k, v, *, causal: bool, bias=None, alibi=None) -> out
    # [batch, seq, heads, head_dim]

``k``/``v`` may carry fewer heads than ``q`` (GQA/MQA, ``H % Hkv == 0``):
the Pallas kernel consumes grouped KV natively (no expansion is ever
materialized on that path); the jnp reference and ring path expand
internally.

``alibi`` takes the per-head ALiBi slope vector [H] — O(H) memory on every
path: the Pallas kernel and the ring body synthesize ``slope * (k_pos -
q_pos)`` from iotas, never materializing an [S, S] bias (the reference
bakes alibi into its softmax kernel the same way,
``csrc/transformer/inference/csrc/softmax.cu``).

``bias`` is a dense additive attention-logit bias broadcastable to
``[batch, heads, q, k]`` (relative-position bias etc.), supported on every
path but inherently O(S^2) — prefer ``alibi`` for ALiBi.  On the kernel
paths both are constants under differentiation.
"""

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

import numpy as np


def expand_kv_heads(q, k, v):
    """Repeat grouped KV heads up to q's head count (jnp paths only; the
    Pallas kernels index grouped KV directly)."""
    H, Hkv = q.shape[2], k.shape[2]
    if Hkv == H:
        return k, v
    assert H % Hkv == 0, f"{H} q heads not a multiple of {Hkv} kv heads"
    return (jnp.repeat(k, H // Hkv, axis=2), jnp.repeat(v, H // Hkv, axis=2))


def canonical_bias(bias):
    """Right-align a logit bias to rank 4 ([B|1, H|1, q, k]); the contract
    admits rank 2/3 ('broadcastable to [B, H, S, S]')."""
    if bias is None:
        return None
    assert bias.ndim <= 4, f"bias rank {bias.ndim} > 4"
    while bias.ndim < 4:
        bias = bias[None]
    return bias


def reference_attention(q, k, v, *, causal: bool = True, bias=None, alibi=None):
    """Pure-jnp multi-head attention, fp32 softmax accumulation (GQA-aware).

    ``bias``/``alibi`` are stop-gradiented: the kernel paths (flash, ring)
    cannot produce an O(S^2) dbias without defeating their memory scaling,
    so the FRAMEWORK-WIDE contract (see ``get_attention_fn``) is that both
    bias forms are constants under differentiation — the reference path
    must agree or a learned bias would silently train only when dispatch
    happened to select it."""
    if bias is not None:
        bias = jax.lax.stop_gradient(bias)
    if alibi is not None:
        alibi = jax.lax.stop_gradient(alibi)
    k, v = expand_kv_heads(q, k, v)
    B, S, H, D = q.shape
    Sk = k.shape[1]
    scale = 1.0 / np.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    bias = canonical_bias(bias)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if alibi is not None:
        slopes = jnp.asarray(alibi, jnp.float32)
        dist = (jnp.arange(Sk)[None, :] - jnp.arange(S)[:, None]).astype(jnp.float32)
        logits = logits + slopes[None, :, None, None] * dist[None, None]
    if causal:
        mask = jnp.tril(jnp.ones((S, Sk), bool), k=Sk - S)
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def flash_attention(q, k, v, *, causal: bool = True, bias=None, alibi=None):
    """Pallas flash attention on TPU (grouped-KV + bias/alibi native); the
    reference path where ``ops.pallas``'s rule says kernels do not run (the
    cpu test mesh: the interpreted kernel is orders of magnitude slower
    than the einsum)."""
    from deepspeed_tpu.ops import pallas
    if not pallas.use_kernel("flash_attention"):
        return reference_attention(q, k, v, causal=causal, bias=bias, alibi=alibi)
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention as fa
    return fa(q, k, v, causal=causal, bias=bias, alibi=alibi)


def ring_attention(q, k, v, *, causal: bool = True, bias=None, alibi=None):
    """Ring attention over the ``seq`` mesh axis (KV blocks rotated by
    ppermute); see ``deepspeed_tpu/parallel/sequence.py``."""
    from deepspeed_tpu.parallel.sequence import ring_attention as ra
    return ra(q, k, v, causal=causal, bias=bias, alibi=alibi)


def ulysses_attention(q, k, v, *, causal: bool = True, bias=None, alibi=None):
    """Ulysses-style all-to-all sequence parallel attention; see
    ``deepspeed_tpu/parallel/sequence.py``."""
    from deepspeed_tpu.parallel.sequence import ulysses_attention as ua
    return ua(q, k, v, causal=causal, bias=bias, alibi=alibi,
              inner=flash_attention)


def alibi_slopes(num_heads: int) -> np.ndarray:
    """Per-head ALiBi slopes (BLOOM; geometric sequence from the paper)."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(np.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if np.log2(num_heads).is_integer():
        return np.asarray(pow2_slopes(num_heads), np.float32)
    closest = 2 ** int(np.floor(np.log2(num_heads)))
    extra = pow2_slopes(2 * closest)[0::2][:num_heads - closest]
    return np.asarray(pow2_slopes(closest) + extra, np.float32)


def alibi_bias(num_heads: int, q_len: int, k_len: int,
               q_offset: int = 0) -> jnp.ndarray:
    """[1, H, q, k] additive ALiBi bias: slope_h * -(q_pos - k_pos)."""
    slopes = jnp.asarray(alibi_slopes(num_heads))
    qpos = q_offset + jnp.arange(q_len)[:, None]
    kpos = jnp.arange(k_len)[None, :]
    dist = (kpos - qpos).astype(jnp.float32)        # <= 0 in the causal past
    return (slopes[:, None, None] * dist)[None]


# Below this sequence length XLA's fused dense attention beats the Pallas
# flash kernel on-chip (r5, v5e, bf16-MXU kernels with (256, 512) blocks:
# flash wins from S=512 up — fwd+bwd 0.386ms vs 0.411ms dense at S=512,
# micro 8 — and the gap widens with S while dense goes O(S^2) in memory).
XLA_FUSED_MAX_SEQ = 256


def auto_attention(q, k, v, *, causal: bool = True, bias=None, alibi=None):
    """Dispatch by sequence length: XLA-fused dense attention for short
    sequences, Pallas flash beyond ``XLA_FUSED_MAX_SEQ``."""
    if q.shape[1] <= XLA_FUSED_MAX_SEQ:
        return reference_attention(q, k, v, causal=causal, bias=bias, alibi=alibi)
    return flash_attention(q, k, v, causal=causal, bias=bias, alibi=alibi)


_REGISTRY = {
    "auto": auto_attention,
    "reference": reference_attention,
    "flash": flash_attention,
    "ring": ring_attention,
    "ulysses": ulysses_attention,
}


def get_attention_fn(impl: str = "auto") -> Callable:
    """Resolve an attention impl by name.

    Contract (ALL impls): ``fn(q, k, v, *, causal, bias=None, alibi=None)``
    with [batch, seq, heads, head_dim]; ``bias`` and ``alibi`` are
    CONSTANTS under differentiation on every path (gradients flow to
    q/k/v only) — a learned T5-style bias is not supported, by design:
    its O(S^2) dbias would defeat the flash/ring memory scaling, and the
    jnp reference path stop-gradients to keep dispatch-invariant
    semantics."""
    assert impl in _REGISTRY, f"unknown attention impl {impl!r}; have {list(_REGISTRY)}"
    return _REGISTRY[impl]


def register_attention(name: str, fn: Callable):
    _REGISTRY[name] = fn
