"""``dst-bench`` — collective micro-benchmark over the device mesh.

The analogue of the reference's ``bin/ds_bench`` (which shells into the
communication benchmark suite to time NCCL allreduce/allgather/…): here the
collectives are XLA's, issued inside ``shard_map`` over a one-axis mesh, and
the numbers are algorithmic bus bandwidths using the standard nccl-tests
accounting so they are comparable with the reference's tables.

Works anywhere JAX has >1 device: real TPU slices (ICI) or the CPU-mesh CI
harness (``--devices N`` forces ``xla_force_host_platform_device_count``
before JAX initializes — same trick as ``tests/conftest.py``).

Timing: a K-deep chain of collectives inside one jitted ``fori_loop``, ended
by a single scalar fetch; two chain lengths are differenced so dispatch and
host round-trip costs cancel.
"""

import argparse
import json
import os
import sys
import time


def _per_op_bus_factor(op: str, n: int) -> float:
    """Bus-bandwidth factor per nccl-tests: bytes moved on the wire per
    byte of payload."""
    if op == "allreduce":
        return 2.0 * (n - 1) / n
    if op in ("allgather", "reducescatter"):
        return (n - 1) / n
    if op == "alltoall":
        return (n - 1) / n
    if op == "ppermute":
        return 1.0
    raise ValueError(op)


def run_bench(ops, sizes_mb, trials, devices=None):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()[: devices or len(jax.devices())]
    n = len(devs)
    if n < 2:
        print(json.dumps({"error": f"need >= 2 devices, have {n}"}))
        return 1
    mesh = Mesh(np.asarray(devs), ("x",))
    rows = []
    for op in ops:
        for mb in sizes_mb:
            nbytes = int(mb * 2 ** 20)
            # payload per device; fp32 words
            words = max(1, nbytes // 4)
            lanes = max(128, min(words, 8192))
            rows_ = max(1, words // lanes)
            x = jnp.ones((n, rows_, lanes), jnp.float32)

            def coll(v):
                if op == "allreduce":
                    return jax.lax.psum(v, "x") / n
                if op == "allgather":
                    g = jax.lax.all_gather(v, "x")        # [n, ...]
                    # consume EVERY gathered shard with device-dependent
                    # weights: indexing only g[axis_index] is legally
                    # simplified back to the input by XLA, eliding the
                    # collective and making the bandwidth number fiction
                    w = (jax.lax.axis_index("x") + 1 + jnp.arange(n)
                         ).astype(v.dtype)
                    return jnp.tensordot(w, g, axes=(0, 0)) / n
                if op == "reducescatter":
                    # scatter over the flattened payload, zero-padded to a
                    # multiple of n, then tile back so the chain's shapes
                    # stay fixed
                    flat = v.reshape(-1)
                    pad = (-flat.shape[0]) % n
                    if pad:
                        flat = jnp.concatenate(
                            [flat, jnp.zeros((pad,), flat.dtype)])
                    s = jax.lax.psum_scatter(flat, "x", scatter_dimension=0,
                                             tiled=True)
                    return jnp.tile(s, n)[: v.size].reshape(v.shape) / n
                if op == "alltoall":
                    r = v.reshape(n, -1, v.shape[-1])
                    r = jax.lax.all_to_all(r, "x", split_axis=0,
                                           concat_axis=0, tiled=False)
                    return r.reshape(v.shape)
                if op == "ppermute":
                    return jax.lax.ppermute(
                        v, "x", [(i, (i + 1) % n) for i in range(n)])
                raise ValueError(op)

            def make_fn(k):
                @jax.jit
                def prog(v):
                    def body(_, vv):
                        return coll(vv)
                    out = jax.lax.fori_loop(0, k, body, v)
                    return jnp.sum(out[..., :1])

                return jax.shard_map(lambda v: prog(v)[None], mesh=mesh,
                                     in_specs=P("x"), out_specs=P("x"),
                                     check_vma=False)

            # ONE jitted program per chain length, compiled before timing
            fns = {k: make_fn(k) for k in (1, 1 + trials)}

            def chain(k):
                t0 = time.perf_counter()
                float(jnp.sum(fns[k](x)))
                return time.perf_counter() - t0

            chain(1)            # warm (compile)
            chain(1 + trials)
            a = min(chain(1) for _ in range(2))
            b = min(chain(1 + trials) for _ in range(2))
            per_op = max((b - a) / trials, 1e-9)
            payload = rows_ * lanes * 4
            busbw = _per_op_bus_factor(op, n) * payload / per_op / 1e9
            rows.append({"op": op, "size_mb": round(payload / 2 ** 20, 3),
                         "devices": n, "time_us": round(per_op * 1e6, 1),
                         "busbw_GBps": round(busbw, 3)})
            print(json.dumps(rows[-1]))
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="dst-bench",
        description="XLA collective micro-benchmark (reference: bin/ds_bench)")
    ap.add_argument("--ops", default="allreduce,allgather,reducescatter,alltoall,ppermute")
    ap.add_argument("--sizes-mb", default="1,8,64")
    ap.add_argument("--trials", type=int, default=16)
    ap.add_argument("--devices", type=int, default=0,
                    help="force N virtual CPU devices (0 = use what's there)")
    args = ap.parse_args(argv)

    if args.devices and os.environ.get("_DST_BENCH_CHILD") != "1":
        # re-exec with the virtual CPU world set before JAX initializes
        env = dict(os.environ)
        env["_DST_BENCH_CHILD"] = "1"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith("--xla_force_host_platform_device_count")]
        flags.append(f"--xla_force_host_platform_device_count={args.devices}")
        env["XLA_FLAGS"] = " ".join(flags)
        env["JAX_PLATFORMS"] = "cpu"
        import subprocess
        child_argv = ["--ops", args.ops, "--sizes-mb", args.sizes_mb,
                      "--trials", str(args.trials),
                      "--devices", str(args.devices)]
        code = ("import jax; jax.config.update('jax_platforms', 'cpu'); "
                "from deepspeed_tpu.comm_bench import main; import sys; "
                f"sys.exit(main({child_argv!r}))")
        return subprocess.call([sys.executable, "-c", code], env=env)

    ops = [o.strip() for o in args.ops.split(",") if o.strip()]
    sizes = [float(s) for s in args.sizes_mb.split(",")]
    return run_bench(ops, sizes, args.trials,
                     devices=args.devices or None)


if __name__ == "__main__":
    sys.exit(main())
