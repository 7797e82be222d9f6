"""On-device decode-attention parity + sustained-decode soak (interpret=False).

Run standalone on a TPU host: exits 0 and prints PASS when both the fused
decode kernel and the paged (block-table) kernel match their jnp references
within bf16 tolerance ON HARDWARE, at GPT-2 head shapes, and a sustained
decode loop completes; any other platform is an error (exit 1) — CPU CI
covers the interpret path instead.  ``chip_smoke.py`` drives the same
kernels through the model; this tool sweeps cache fill levels and the
padded-chunk overhang on the bare kernels.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print(f"FAIL: needs a TPU, found {jax.devices()[0].platform}")
        return 1

    from deepspeed_tpu.ops.pallas.decode_attention import (
        decode_attention, decode_attention_reference, paged_attention,
        paged_attention_reference)

    rng = np.random.default_rng(0)
    B, H, D, T = 4, 12, 64, 2048       # gpt2 heads; caches fold them: H*D

    def maxerr(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    # ---- dense-cache kernel parity across fill levels ------------------- #
    ck, cv = (jnp.asarray(rng.standard_normal((B, T, H * D)), jnp.bfloat16)
              for _ in range(2))
    for Sq in (1, 16):                 # decode and chunked-prefill shapes
        q = jnp.asarray(rng.standard_normal((B, Sq, H, D)), jnp.bfloat16)
        fn = jax.jit(lambda q, ck, cv, p: decode_attention(q, ck, cv, p))
        for pos in (0, 1, 127, 128, T // 2, T - Sq):
            p = jnp.asarray(pos, jnp.int32)
            err = maxerr(fn(q, ck, cv, p),
                         decode_attention_reference(q, ck, cv, p))
            assert err < 0.05, f"decode Sq={Sq} pos={pos} maxerr {err}"

    # ---- paged kernel parity (incl. padded-chunk overhang) -------------- #
    NB, BS, MB = 256, 16, 48           # MB*BS < T: table narrower than cache
    kp, vp = (jnp.asarray(rng.standard_normal((NB, BS, H * D)), jnp.bfloat16)
              for _ in range(2))
    tables = np.zeros((B, MB), np.int32)
    free = list(range(1, NB))
    rng.shuffle(free)
    for b in range(B):
        for j in range(MB):
            tables[b, j] = free.pop()
    tables = jnp.asarray(tables)
    for Sq, length in ((1, 0), (1, 700), (16, MB * BS - 16),
                       # padded chunk: length+Sq spills past the table; the
                       # static MB-bound loop must neither hang nor read a
                       # garbage physical id past the table row
                       (16, MB * BS - 4)):
        q = jnp.asarray(rng.standard_normal((B, Sq, H, D)), jnp.bfloat16)
        lengths = jnp.full((B,), length, jnp.int32)
        out = jax.jit(paged_attention)(q, kp, vp, tables, lengths)
        ref = paged_attention_reference(q, kp, vp, tables, lengths)
        err = maxerr(out, ref)
        assert err < 0.05, f"paged Sq={Sq} len={length} maxerr {err}"

    # ---- sustained decode soak ------------------------------------------ #
    # step pos across the whole cache twice and block on every result, so a
    # kernel that stalls under repeated dispatch stalls here, visibly
    q1 = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.bfloat16)
    fn = jax.jit(lambda q, ck, cv, p: decode_attention(q, ck, cv, p))
    fn(q1, ck, cv, jnp.asarray(0, jnp.int32)).block_until_ready()
    steps = 2 * (T - 1)
    t0 = time.perf_counter()
    for i in range(steps):
        fn(q1, ck, cv, jnp.asarray(i % (T - 1), jnp.int32)).block_until_ready()
    dt = time.perf_counter() - t0
    print(f"soak: {steps} decode steps in {dt:.2f}s "
          f"({steps / dt:.0f} steps/s)")

    print("PASS: decode + paged kernel parity on TPU (interpret=False) and "
          f"{steps}-step sustained-decode soak completed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
