"""On-device timing of the dropless expert bank at OLMoE-1B-7B's widths.

Run standalone on a TPU host (``chiprun --chips 1 -- python
tools/moe_bank_bench.py``); any other platform is an error (exit 1).  For the
serve cell's two shapes (1,024 assignments: 128 decode rows x top 8; 512: a
64-token prompt chunk) it times

* the bank alone: grouped matmul ``[A, 2048] x [64, 2048, 2048]`` -> SwiGLU
  -> ``[A, 1024] x [64, 1024, 2048]`` on rows already sorted, against the
  time its bytes need at the published HBM bandwidth (the bank read once,
  rows in and out), with each of: the program's ``grouped_matmul`` kernel,
  ``jax.lax.ragged_dot``, and JAX's ``pallas.ops.tpu.megablox`` ``gmm`` at
  its default tiling (the comparison of PERF.md § 6, PR 27);
* the whole MoE layer as the model runs it (router, sort, gather, bank,
  combine), and the same arithmetic as "every expert for every token, then
  mask", to hold the result to;

and prints one JSON line.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9          # TPU v5e, published


def timed(fn, *args, repeats=20):
    import jax
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / repeats


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print(f"FAIL: needs a TPU, found {jax.devices()[0].platform}")
        return 1
    from deepspeed_tpu.models.gpt import _ffn, olmoe_config

    cfg = olmoe_config(n_layer=1)
    M, I, N, k = cfg.n_embd, cfg.ffn_dim, cfg.moe_num_experts, cfg.moe_top_k
    bf16 = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    p = {"moe": {"gate": {"wg": jax.random.normal(keys[0], (M, N), bf16) * 0.02},
                 "experts": {"wi": jax.random.normal(keys[1], (N, M, 2 * I), bf16) * 0.02,
                             "wo": jax.random.normal(keys[2], (N, I, M), bf16) * 0.02}}}
    wi, wo = p["moe"]["experts"]["wi"], p["moe"]["experts"]["wo"]

    from jax.experimental.pallas.ops.tpu.megablox import gmm
    from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul
    matmuls = {"grouped_matmul": grouped_matmul, "ragged_dot": jax.lax.ragged_dot,
               "megablox_gmm": lambda a, w, sizes: gmm(a, w, sizes,
                                                       preferred_element_type=bf16)}

    def bank_of(matmul):
        @jax.jit
        def bank(rows, wi, wo, sizes):
            gate, up = jnp.split(matmul(rows, wi, sizes), 2, axis=-1)
            return matmul(jax.nn.silu(gate) * up, wo, sizes)
        return bank

    layer = jax.jit(lambda p, x: _ffn(cfg, p, x, bf16)[0])

    @jax.jit
    def every_expert(p, x):
        """[T, M]: all 64 experts on all tokens, the unchosen masked out."""
        from deepspeed_tpu.moe.dropless import softmax_topk
        logits = x.astype(jnp.float32) @ p["moe"]["gate"]["wg"].astype(jnp.float32)
        probs, _, chosen = softmax_topk(logits, k)
        weight = probs * jax.nn.one_hot(chosen, N).sum(axis=1)         # [T, N]
        bank = p["moe"]["experts"]     # arguments, not constants of the program
        gate, up = jnp.split(jnp.einsum("tm,nmf->ntf", x, bank["wi"]), 2, axis=-1)
        y = jnp.einsum("ntf,nfm->ntm", jax.nn.silu(gate) * up, bank["wo"])
        return jnp.einsum("ntm,tn->tm", y.astype(jnp.float32), weight)

    out = {"device": jax.devices()[0].device_kind, "shapes": {}}
    rng = np.random.default_rng(0)
    for tokens in (128, 64):
        A = tokens * k
        x = jax.random.normal(keys[3], (tokens, M), bf16)
        # near-even routing, as seeded weights give: k distinct experts a token
        chosen = np.stack([rng.permutation(N)[:k] for _ in range(tokens)])
        sizes = jnp.asarray(np.bincount(chosen.reshape(-1), minlength=N), jnp.int32)
        rows = jax.random.normal(keys[3], (A, M), bf16)
        bank_bytes = 2 * (wi.size + wo.size) + 2 * 2 * A * M
        t_bank = {name: timed(bank_of(m), rows, wi, wo, sizes)
                  for name, m in matmuls.items()}
        agree = float(jnp.abs(
            bank_of(grouped_matmul)(rows, wi, wo, sizes).astype(jnp.float32)
            - bank_of(jax.lax.ragged_dot)(rows, wi, wo, sizes)).max())
        t_layer = timed(layer, p, x)
        t_dense = timed(every_expert, p, x)
        gap = float(jnp.abs(layer(p, x).astype(jnp.float32) - every_expert(p, x)).max())
        out["shapes"][str(A)] = {
            "bank_ms": {name: 1e3 * t for name, t in t_bank.items()},
            "bank_bytes": bank_bytes,
            "bytes_ms_at_peak": 1e3 * bank_bytes / HBM_BYTES_PER_S,
            "bank_over_bytes_time": {name: t * HBM_BYTES_PER_S / bank_bytes
                                     for name, t in t_bank.items()},
            "kernel_vs_ragged_dot_max_gap": agree,
            "moe_layer_ms": 1e3 * t_layer, "every_expert_ms": 1e3 * t_dense,
            "layer_vs_every_expert_max_gap": gap,
            "experts_reached": int((np.asarray(sizes) > 0).sum())}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
