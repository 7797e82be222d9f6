"""On-device block-sparse-attention parity check (fwd + bwd, interpret=False).

Run standalone on a TPU host: exits 0 and prints PASS when the Pallas LUT
kernel matches the masked-dense jnp reference ON HARDWARE; any other
platform is an error (exit 1) — CPU CI covers the interpret path instead.
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

    from deepspeed_tpu.ops.pallas.block_sparse_attention import (
        block_sparse_attention, sparse_reference_attention)
    from deepspeed_tpu.ops.sparse_attention import (
        BigBirdSparsityConfig, FixedSparsityConfig)

    rng = np.random.default_rng(0)
    B, S, H, D = 2, 1024, 4, 64
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
               for _ in range(3))

    cases = [
        (BigBirdSparsityConfig(num_heads=H, block=128, seed=1,
                               attention="bidirectional").make_layout(S), False),
        (FixedSparsityConfig(num_heads=H, block=128, num_local_blocks=2,
                             attention="unidirectional").make_layout(S), True),
    ]
    for layout, causal in cases:
        o = jax.jit(lambda q, k, v: block_sparse_attention(
            q, k, v, layout, causal=causal))(q, k, v)
        ref = sparse_reference_attention(q, k, v, layout, causal=causal)
        err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - ref.astype(jnp.float32))))
        assert err < 0.05, f"fwd causal={causal} maxerr {err}"

    layout, causal = cases[1]

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, layout, causal=causal).astype(jnp.float32) ** 2)

    gf = jax.jit(jax.grad(loss(block_sparse_attention), argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss(sparse_reference_attention), argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        scale = float(jnp.max(jnp.abs(b.astype(jnp.float32)))) + 1e-9
        rel = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) / scale
        assert rel < 0.05, f"grad d{name} rel err {rel}"

    # longer-sequence parity at 8k (bigger LUTs, same kernels)
    S8 = 8192
    q8, k8, v8 = (jnp.asarray(rng.standard_normal((1, S8, 1, D)), jnp.bfloat16)
                  for _ in range(3))
    layout8 = BigBirdSparsityConfig(num_heads=1, block=128, seed=4).make_layout(S8)
    o8 = jax.jit(lambda q, k, v: block_sparse_attention(q, k, v, layout8))(q8, k8, v8)
    r8 = sparse_reference_attention(q8, k8, v8, layout8)
    err8 = float(jnp.max(jnp.abs(o8.astype(jnp.float32) - r8.astype(jnp.float32))))
    assert err8 < 0.05, f"fwd seq=8192 maxerr {err8}"

    # the point of sparsity: HBM traffic and FLOPs scale with density
    # (printed, not asserted: the grid has nnz entries, not nb², so the
    # scaling is structural; the ratio is the signal)
    import time
    S2 = 8192
    q2, k2, v2 = (jnp.asarray(rng.standard_normal((1, S2, H, D)), jnp.bfloat16)
                  for _ in range(3))
    sparse_layout = BigBirdSparsityConfig(
        num_heads=H, block=128, seed=1).make_layout(S2)
    dense_layout = np.ones_like(sparse_layout)

    def timed(layout):
        # vary an input each call so no result can be reused
        f = jax.jit(lambda q, k, v, c: block_sparse_attention(q + c, k, v, layout))
        f(q2, k2, v2, 0.0).block_until_ready()
        t0 = time.perf_counter()
        for i in range(20):
            r = f(q2, k2, v2, float(i + 1))
        r.block_until_ready()
        return (time.perf_counter() - t0) / 20

    t_sparse, t_dense = timed(sparse_layout), timed(dense_layout)
    density = sparse_layout.mean()
    print(f"seq={S2} density={density:.2f} sparse={t_sparse*1e3:.3f}ms "
          f"dense={t_dense*1e3:.3f}ms speedup={t_dense/t_sparse:.2f}x")

    print("PASS: block-sparse attention fwd+bwd parity on TPU (interpret=False)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
