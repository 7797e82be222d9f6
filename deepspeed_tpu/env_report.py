"""``dst_report`` — environment / op-compatibility report (reference
``deepspeed/env_report.py:113``, surfaced as ``ds_report``).

The reference prints a compat matrix of CUDA op builders; the TPU analogue
reports platform/device inventory, the JAX software stack, and whether each
Pallas fast-path kernel actually lowers on this backend (compile probe), so
"op compatible" keeps its meaning."""

import sys

GREEN_OK = "\033[92m[OKAY]\033[0m"
RED_NO = "\033[91m[NO]\033[0m"
YELLOW = "\033[93m[WARN]\033[0m"


def _versions():
    rows = []
    for mod in ("jax", "jaxlib", "flax", "optax", "orbax.checkpoint", "numpy"):
        try:
            m = __import__(mod)
            for part in mod.split(".")[1:]:
                m = getattr(m, part)
            rows.append((mod, getattr(m, "__version__", "?")))
        except Exception:
            rows.append((mod, None))
    return rows


def _probe_pallas_op(fn):
    try:
        fn()
        return True, ""
    except Exception as e:  # noqa: BLE001 — report, don't raise
        return False, str(e).split("\n")[0][:80]


def op_compatibility():
    """(name, ok, note) per fast-path op — each probe actually compiles and
    runs the kernel on the current backend."""
    import jax
    import jax.numpy as jnp

    def flash():
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
        x = jnp.zeros((1, 128, 2, 64), jnp.bfloat16)
        jax.block_until_ready(flash_attention(x, x, x, causal=True))

    def fused_adam():
        # a compiled step's Adam: the optax chain, which XLA fuses into one
        # memory pass a leaf (the Pallas kernel of ops/pallas/fused_optim.py
        # serves the NVMe offload walk alone)
        from deepspeed_tpu.runtime.optimizers import get_optimizer
        tx = get_optimizer("adamw", {"lr": 1e-3})
        p = {"w": jnp.zeros((128,))}
        s = tx.init(p)
        jax.jit(tx.update)(p, s, p)

    def ring():
        from deepspeed_tpu.parallel.sequence import ring_attention  # noqa: F401

    def sparse_attn():
        import numpy as np

        from deepspeed_tpu.ops.pallas.block_sparse_attention import (
            block_sparse_attention)
        x = jnp.zeros((1, 128, 1, 64), jnp.bfloat16)
        layout = np.ones((1, 2, 2), np.int32)
        jax.block_until_ready(block_sparse_attention(x, x, x, layout))

    def async_io():
        from deepspeed_tpu.ops.aio import AsyncIOBuilder
        b = AsyncIOBuilder()
        assert b.is_compatible(), "g++ or csrc/aio missing"
        b.load()

    def quantizer():
        from deepspeed_tpu.ops.quantizer import quantize_dequantize
        jax.block_until_ready(quantize_dequantize(jnp.ones((128,)), bits=8))

    probes = [("pallas_flash_attention", flash),
              ("pallas_block_sparse_attention", sparse_attn),
              ("fused_optimizer", fused_adam),
              ("ring_attention", ring),
              ("async_io (native)", async_io),
              ("quantizer", quantizer)]
    out = []
    for name, fn in probes:
        ok, note = _probe_pallas_op(fn)
        out.append((name, ok, note))
    return out


def main() -> int:
    import jax

    print("-" * 64)
    print("deepspeed_tpu environment report (dst_report)")
    print("-" * 64)
    print("software stack:")
    for mod, ver in _versions():
        mark = GREEN_OK if ver else RED_NO
        print(f"  {mod:20s} {ver or 'not installed':16s} {mark}")

    print("devices:")
    try:
        devs = jax.devices()
        print(f"  platform={devs[0].platform}  count={len(devs)}  "
              f"process_count={jax.process_count()}")
        for d in devs[:8]:
            stats = {}
            try:
                stats = d.memory_stats() or {}
            except Exception:
                pass
            hbm = stats.get("bytes_limit")
            hbm_s = f"  hbm={hbm / 2**30:.1f}GiB" if hbm else ""
            print(f"    {d}{hbm_s}")
    except Exception as e:  # noqa: BLE001
        print(f"  {RED_NO} no usable backend: {e}")
        return 1

    print("op compatibility (compile probes on this backend):")
    any_fail = False
    for name, ok, note in op_compatibility():
        mark = GREEN_OK if ok else YELLOW
        any_fail |= not ok
        extra = f"  ({note})" if note else ""
        print(f"  {name:28s} {mark}{extra}")
    print("-" * 64)
    return 0


cli_main = main  # console-script entry (pyproject [project.scripts])


if __name__ == "__main__":
    sys.exit(main())
