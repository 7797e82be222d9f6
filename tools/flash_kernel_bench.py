"""On-device timing of the three flash-attention kernels alone, by block shape.

Run standalone on a TPU host (``chiprun --chips 1 -- python
tools/flash_kernel_bench.py``); any other platform is an error (exit 1).  At
the two train cells' shapes (``--batch 8 --seq 1024 --head-dim 64``, 12
heads for GPT-2 124M and 25 for XL, causal, bf16) it times ``flash_fwd``,
``flash_bwd_dq`` and ``flash_bwd_dkv`` one call each, for every ``(block_q,
block_k)`` of ``--blocks``: ms a call and the share of the least time the
chip could take (``benchmarks/lib/arith.flash_call``'s operations and bytes
against the published peaks, which is what ``flash_fwd_roofline`` and
``flash_bwd_roofline`` divide by in the train cells), with the pair
``_block_sizes`` takes marked, and beside each pair how many scores its
schedule computes over the causal triangle's.  The ms are the kernel's own
time on the device, read from a profiler trace of the repeats.  ``--no-causal``
times the unmasked schedule.  A pair the compiler refuses is listed as
refused.  Prints a table a head count, then one JSON line.

``--rehearse`` runs the same control flow on the CPU through the Pallas
interpreter, at a toy shape given on the command line; what it prints are the
interpreter's seconds, never a device's.
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

KERNELS = ("fwd", "dq", "dkv")
NAMES = {"fwd": "flash_fwd", "dq": "flash_bwd_dq", "dkv": "flash_bwd_dkv"}
BLOCKS = [(128, 128), (128, 256), (256, 128), (256, 256), (128, 512),
          (256, 512), (512, 128), (512, 256), (512, 512)]


def timed(fn, *args, name, repeats=50):
    """(seconds a call of the kernel ``name`` on the device, seconds a call
    of the whole jitted ``fn`` on the host's clock).  The first is the
    kernel's self time in a profiler trace of the repeats, which is what the
    train cells' ``flash_*_roofline`` divide by; the second also holds the
    layout copies XLA puts round a kernel called bare (0.1-0.2 ms a call
    here; 0.2-0.4 where the statistics were ``[B, H, S, 1]``).  Off the TPU
    the trace has no device plane and the first is None."""
    import jax
    from benchmarks.lib import trace as tr
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            t = time.perf_counter()
            for _ in range(repeats):
                out = fn(*args)
            jax.block_until_ready(out)
            wall = (time.perf_counter() - t) / repeats
        path = tr.newest_xplane(tmp)
        trace = tr.Trace.from_file(path) if path else None
    if trace is None or not trace.devices or not trace.op_counts().get(name):
        return None, wall
    return trace.op_seconds()[name] / trace.op_counts()[name], wall


def scores_over_triangle(kernel, S, bq, bk):
    """Scores the causal schedule computes (whole tiles, the diagonal's
    masked) over the S (S + 1) / 2 the triangle holds."""
    if kernel == "dkv":      # per K block: the q blocks from the diagonal on
        tiles = sum(S // bq - (j * bk) // bq for j in range(S // bk))
    else:                    # per q block: the K blocks up to the diagonal
        tiles = sum(-(-(i + 1) * bq // bk) for i in range(S // bq))
    return tiles * bq * bk / (S * (S + 1) / 2)


def bench(B, H, S, D, blocks, *, causal=True, repeats=50, peak=None):
    """One row a block pair: ms a call of each kernel (None where the pair
    does not divide S or the compiler refuses it)."""
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import arith
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    bf16 = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(key, (B, H, S, D), bf16) for key in keys)
    scale = 1.0 / D ** 0.5
    kw = dict(causal=causal, scale=scale)
    o, lse = jax.jit(lambda q, k, v: fa._fwd(q, k, v, None, None, **kw))(q, k, v)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    taken = fa._block_sizes(S, None, None)

    def call(kern, bq, bk):
        if kern == "fwd":
            return (jax.jit(lambda q, k, v: fa._fwd(q, k, v, None, None, bq=bq,
                                                    bk=bk, **kw)), q, k, v)
        body = fa._bwd_dq if kern == "dq" else fa._bwd_dkv
        return (jax.jit(lambda *a: body(*a, None, None, bq=bq, bk=bk, **kw)),
                q, k, v, do, lse, delta)

    rows = []
    for bq, bk in blocks:
        if S % bq or S % bk:
            continue
        row = {"blocks": [bq, bk], "ms": {}, "jit_ms": {}, "roofline_pct": {},
               "refused": {},
               "taken": taken == (bq, bk)}
        if causal:
            row["scores_over_triangle"] = {
                kern: scores_over_triangle(kern, S, bq, bk) for kern in KERNELS}
        for kern in KERNELS:
            try:
                seconds, wall = timed(*call(kern, bq, bk), name=NAMES[kern],
                                      repeats=repeats)
            except Exception as e:   # noqa: BLE001 — a tile the compiler refuses
                row["refused"][kern] = str(e).strip().splitlines()[0][:200]
                continue
            row["jit_ms"][kern] = 1e3 * wall
            if seconds is not None:
                least, _ = arith.roofline_seconds(
                    *arith.flash_call(NAMES[kern], B, H, S, D, causal=causal), peak)
                row["ms"][kern] = 1e3 * seconds
                row["roofline_pct"][kern] = 100.0 * least / seconds
        rows.append(row)
    return {"shape": [B, H, S, D], "causal": causal,
            "blocks_taken": list(taken),
            "candidates": rows}


def show(result):
    B, H, S, D = result["shape"]
    print(f"(B, H, S, D) = ({B}, {H}, {S}, {D}), "
          f"{'causal' if result['causal'] else 'not causal'}: the kernel's ms "
          f"a call on the device (% of arith.flash_call's bound) [scores over "
          f"the triangle's]")
    print(f"{'(bq, bk)':>12} " + " ".join(f"{NAMES[k]:>30}" for k in KERNELS))
    for r in result["candidates"]:
        cells = []
        for kern in KERNELS:
            if kern in r["refused"]:
                cells.append("refused")
                continue
            if kern in r["ms"]:
                cell = f"{r['ms'][kern]:.3f} ({r['roofline_pct'][kern]:.1f}%)"
            else:           # a rehearsal: the interpreter's time, no device's
                cell = f"jit {r['jit_ms'][kern]:.1f}"
            if "scores_over_triangle" in r:
                cell += f" [{r['scores_over_triangle'][kern]:.3f}]"
            cells.append(cell)
        print(f"{str(tuple(r['blocks'])):>12} " + " ".join(f"{c:>30}" for c in cells)
              + ("   <- _block_sizes" if r["taken"] else ""))
    for r in result["candidates"]:
        for kern, why in r["refused"].items():
            print(f"  {tuple(r['blocks'])} {NAMES[kern]} refused: {why}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--heads", type=int, nargs="+", default=[12, 25])
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--blocks", nargs="+", default=None, metavar="BQxBK",
                    help="block pairs to time, e.g. 256x256 256x512")
    ap.add_argument("--no-causal", action="store_true")
    ap.add_argument("--repeats", type=int, default=50)
    ap.add_argument("--rehearse", action="store_true",
                    help="the control flow on the CPU, through the interpreter")
    args = ap.parse_args(argv)

    import jax
    platform = jax.devices()[0].platform
    if platform != ("cpu" if args.rehearse else "tpu"):
        print(f"FAIL: needs {'the CPU to rehearse' if args.rehearse else 'a TPU'}, "
              f"found {platform}")
        return 1
    blocks = BLOCKS if args.blocks is None else [
        tuple(int(x) for x in pair.split("x")) for pair in args.blocks]
    peak = None
    if not args.rehearse:
        from benchmarks.lib.device import peaks
        peak = peaks(jax.devices()[0].device_kind)
    out = {"device": jax.devices()[0].device_kind, "rehearsal": args.rehearse,
           "shapes": []}
    for H in args.heads:
        result = bench(args.batch, H, args.seq, args.head_dim, blocks,
                       causal=not args.no_causal, repeats=args.repeats, peak=peak)
        show(result)
        out["shapes"].append(result)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
