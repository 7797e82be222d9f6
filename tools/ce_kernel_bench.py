"""On-device timing of the fused cross-entropy's two kernels alone.

Run standalone on a TPU host (``chiprun --chips 1 -- python
tools/ce_kernel_bench.py``); any other platform is an error (exit 1).  At
the train cell's shape (``[8192, 768] x [768, 50304]``, bf16, GPT-2's
50,257 tokens in a vocab padded to 50,304) it times ``ce_fwd`` and
``ce_bwd`` for each candidate ``(bn, bv)``, ms a call and share of the MXU's
published peak (the forward is one ``N x E x V`` matmul, the backward
three: the score tile again, then a gradient each for ``x`` and the head),
with the pair ``ce_blocks`` takes marked; the whole fused loss with its
gradients; and for reference the XLA path unchunked (``[N, V]`` float32
logits in HBM), forward and backward in one program.  ``--rows``,
``--width``, ``--vocab`` time another shape (a power-of-two vocab, a wider
model); a pair the shape does not admit is left out.  Prints a table, then
one JSON line.
"""

import argparse
import json
import os
import sys
import time
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MXU_FLOPS_PER_S = 197e12          # TPU v5e, bf16, published
CANDIDATES = [(128, 128), (256, 128), (1024, 128), (128, 384), (256, 384),
              (512, 384), (1024, 384), (2048, 384),
              (256, 2048), (512, 1024), (1024, 512)]


def timed(fn, *args, repeats=20):
    import jax
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / repeats


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--width", type=int, default=768)
    ap.add_argument("--vocab", type=int, default=50304, help="padded vocab")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print(f"FAIL: needs a TPU, found {jax.devices()[0].platform}")
        return 1
    from deepspeed_tpu.models.gpt import chunked_cross_entropy
    from deepspeed_tpu.ops import pallas
    from deepspeed_tpu.ops.pallas import cross_entropy as ce

    N, E, V = args.rows, args.width, args.vocab
    real_vocab = 50257 if V == 50304 else V       # GPT-2's tokens; else no mask
    mask = real_vocab if real_vocab != V else None
    bf16 = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (N, E), bf16)
    head = jax.random.normal(keys[1], (V, E), bf16) * 0.02
    labels = jax.random.randint(keys[2], (N,), 0, real_vocab).astype(jnp.int32)
    lab2 = labels.reshape(N, 1)
    gr = jnp.full((N, 1), 1.0 / N, jnp.float32)
    unit = 2.0 * N * E * V                       # one [N, E] x [E, V] matmul
    taken = ce.ce_blocks(N, E, V, bf16)

    def share(matmuls, seconds):
        return 100.0 * matmuls * unit / seconds / MXU_FLOPS_PER_S

    rows = []
    for bn, bv in CANDIDATES:
        if N % bn or V % bv:
            continue
        sweeps = ce.ce_row_sweeps(N, E, bn)[0]
        fwd = jax.jit(lambda x, h, l: ce._fwd_rows(x, h, None, l, mask, bn, bv))
        bwd = jax.jit(lambda *a: ce._bwd_rows(a[0], a[1], None, *a[2:], mask,
                                              bn, bv, sweeps)[:2])
        try:
            _, lse = fwd(x, head, lab2)
            t = {"ce_fwd": timed(fwd, x, head, lab2),
                 "ce_bwd": timed(bwd, x, head, lab2, lse, gr)}
        except Exception as e:   # noqa: BLE001 — a tile the compiler refuses
            rows.append({"blocks": [bn, bv], "refused": str(e).splitlines()[0][:200]})
            continue
        rows.append({
            "blocks": [bn, bv], "taken": (bn, bv) == taken,
            "grid_steps": (N // bn) * (V // bv), "row_sweeps": sweeps,
            "step_mib": ce.ce_step_bytes(bn, bv, E, 2) / 2 ** 20,
            "ms": {k: 1e3 * v for k, v in t.items()},
            "sum_ms": 1e3 * sum(t.values()),
            "mxu_pct": {"ce_fwd": share(1, t["ce_fwd"]),
                        "ce_bwd": share(3, t["ce_bwd"])}})

    fused = jax.jit(jax.value_and_grad(
        lambda x, h: ce.fused_cross_entropy(x, h, labels, real_vocab),
        argnums=(0, 1)))
    with mock.patch.object(pallas, "use_kernel", lambda name: False):
        xla = jax.jit(jax.value_and_grad(
            lambda x, h: chunked_cross_entropy(x[None], h, labels[None], real_vocab,
                                               n_chunks=1), argnums=(0, 1)))
        t_xla = timed(xla, x, head)
    t_fused = timed(fused, x, head)
    (loss_k, (gx_k, gh_k)), (loss_x, (gx_x, gh_x)) = fused(x, head), xla(x, head)
    f32 = jnp.float32
    out = {"device": jax.devices()[0].device_kind, "shape": [N, E, V],
           "blocks_taken": taken, "candidates": rows,
           "fused_fwd_bwd_ms": 1e3 * t_fused,
           "fused_mxu_pct": share(4, t_fused),
           "xla_unchunked_fwd_bwd_ms": 1e3 * t_xla,
           "xla_unchunked_mxu_pct": share(3, t_xla),
           "loss": [float(loss_k), float(loss_x)],
           "max_gap_to_xla": {
               "dx": float(jnp.abs(gx_k.astype(f32) - gx_x.astype(f32)).max()),
               "dh": float(jnp.abs(gh_k.astype(f32) - gh_x.astype(f32)).max()),
               "dx_scale": float(jnp.abs(gx_x.astype(f32)).max()),
               "dh_scale": float(jnp.abs(gh_x.astype(f32)).max())}}

    print(f"{'(bn, bv)':>14} {'steps':>6} {'fwd ms':>8} {'bwd ms':>8} {'sum':>8}   "
          f"fwd% / bwd% of {MXU_FLOPS_PER_S / 1e12:.0f} TFLOP/s")
    for r in rows:
        if "refused" in r:
            print(f"{str(tuple(r['blocks'])):>14} refused: {r['refused']}")
            continue
        ms, pct = r["ms"], r["mxu_pct"]
        print(f"{str(tuple(r['blocks'])):>14} {r['grid_steps']:>6} {ms['ce_fwd']:>8.3f} "
              f"{ms['ce_bwd']:>8.3f} {r['sum_ms']:>8.3f}   "
              f"{pct['ce_fwd']:.1f} / {pct['ce_bwd']:.1f}"
              f"{'   <- ce_blocks' if r['taken'] else ''}")
    print(f"fused loss and gradients {out['fused_fwd_bwd_ms']:.3f} ms "
          f"({out['fused_mxu_pct']:.1f}% over 4 matmuls); XLA unchunked "
          f"{out['xla_unchunked_fwd_bwd_ms']:.3f} ms ({out['xla_unchunked_mxu_pct']:.1f}% over 3)")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
