"""On-device flash-attention parity check (fwd + bwd, interpret=False).

Run standalone on a TPU host: exits 0 and prints PASS when the Pallas kernel
matches the jnp reference within bf16 tolerance ON HARDWARE; any other
platform is an error (exit 1) — CPU CI covers the interpret path instead.
The analogue of the reference's fused-kernel-vs-HF-modeling parity suite
(``tests/unit/ops/accelerators/test_accelerator_forward.py``) run on the
real accelerator.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print(f"FAIL: needs a TPU, found {jax.devices()[0].platform}")
        return 1

    from deepspeed_tpu.ops.attention import reference_attention
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    B, S, H, D = 2, 512, 4, 64
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
               for _ in range(3))

    for causal in (True, False):
        o = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=causal))(q, k, v)
        ref = reference_attention(q, k, v, causal=causal)
        err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - ref.astype(jnp.float32))))
        assert err < 0.05, f"fwd causal={causal} maxerr {err}"

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True).astype(jnp.float32) ** 2)

    gf = jax.jit(jax.grad(loss(flash_attention), argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss(reference_attention), argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        scale = float(jnp.max(jnp.abs(b.astype(jnp.float32)))) + 1e-9
        rel = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) / scale
        assert rel < 0.05, f"grad d{name} rel err {rel}"

    # grouped-KV (GQA) + ALiBi bias on hardware — the round-4 kernel additions
    from deepspeed_tpu.ops.attention import alibi_bias
    Hkv = 2
    kg, vg = (jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.bfloat16)
              for _ in range(2))
    bias = alibi_bias(H, S, S)
    for b in (None, bias):
        o = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                    bias=b))(q, kg, vg)
        ref = reference_attention(q, kg, vg, causal=True, bias=b)
        err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - ref.astype(jnp.float32))))
        assert err < 0.05, f"gqa fwd bias={b is not None} maxerr {err}"

    def loss_b(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=True, bias=bias).astype(jnp.float32) ** 2)

    gf = jax.jit(jax.grad(loss_b(flash_attention), argnums=(0, 1, 2)))(q, kg, vg)
    gr = jax.jit(jax.grad(loss_b(reference_attention), argnums=(0, 1, 2)))(q, kg, vg)
    for name, a, b_ in zip("qkv", gf, gr):
        assert a.shape == b_.shape, (name, a.shape, b_.shape)
        scale = float(jnp.max(jnp.abs(b_.astype(jnp.float32)))) + 1e-9
        rel = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b_.astype(jnp.float32)))) / scale
        assert rel < 0.05, f"gqa+bias grad d{name} rel err {rel}"

    # slopes-only ALiBi (in-kernel bias synthesis, O(H) memory)
    from deepspeed_tpu.ops.attention import alibi_slopes
    slopes = jnp.asarray(alibi_slopes(H))
    o = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                alibi=slopes))(q, kg, vg)
    ref = reference_attention(q, kg, vg, causal=True, bias=bias)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - ref.astype(jnp.float32))))
    assert err < 0.05, f"alibi-slopes fwd maxerr {err}"

    print("PASS: flash attention fwd+bwd parity on TPU (interpret=False), "
          "incl. grouped-KV + ALiBi (dense bias and in-kernel slopes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
