"""On-device timing of the delta layers' CHUNKED FORM alone
(``models/hybrid.py:delta_chunk``): the tree's, whose writes come from
products (``unit_lower_solve``: diagonal blocks of 16 inverted by unrolled
substitution rows, two made one up to blocks of 64, a block row of the
unknowns a step above), against the parent's, which handed the same systems to
XLA's ``triangular_solve`` (until PR 65; kept HERE, and nowhere a cell runs,
as the form to compare with).

Run standalone on a TPU host (``chiprun --chips 1 -- python
tools/delta_chunk_probe.py``); any other platform is an error (exit 1;
``--rehearse`` runs the control flow on the CPU at a small size).  At the
shapes of ``qwen3-next-80b-a3b.serve-long-delta-moe`` (a chunk of 512, 32
value heads of 128 x 128, write strengths up to 1) and of
``olmo-hybrid-7b.serve-chat-resident`` (176, 30 heads of 96 x 192, strengths
up to 2).  A timed program runs sixteen chunks one after another, so what is
read is the device's time and not the host's 0.2 ms a call; the solve is
also timed alone, on seeded systems of the same shapes, and held to
float64's solution of the first.  Each form's reads and state are compared
with the recurrence a token at a time in float64: the tree's may lie no
further from it than four times the parent's and 1e-4 (exit 1).  One JSON
line at the end.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (what, C, H, dk, dv, the largest write strength)
CALLS = [
    ("qwen3-next chunk", 512, 32, 128, 128, 1.0),
    ("olmo-hybrid chunk", 176, 30, 96, 192, 2.0),
]
REHEARSAL = [("chunk", 40, 3, 8, 16, 2.0)]

# chunks one timed program runs one after another
STACK = 16


def parents_solve(L, rhs):
    """What ``delta_chunk`` called until PR 65."""
    import jax
    return jax.lax.linalg.triangular_solve(L, rhs, left_side=True, lower=True,
                                           unit_diagonal=True)


def timed(fn, stack, repeats):
    """``fn`` over each of the stacked arguments in ONE program: -> (ms a
    call, the first one's output)."""
    import jax
    run = jax.jit(lambda stack: jax.lax.map(lambda a: fn(*a), stack))
    out = jax.block_until_ready(run(stack))
    t = time.perf_counter()
    for _ in range(repeats):
        out = run(stack)
    jax.block_until_ready(out)
    return (1e3 * (time.perf_counter() - t) / repeats / stack[0].shape[0],
            jax.tree.map(lambda a: a[0], out))


def recurrence(q, k, v, g, beta, s_in):
    """The rule a token at a time in float64."""
    import numpy as np
    S, out = np.asarray(s_in, np.float64), []
    for t in range(q.shape[0]):
        S = np.exp(g[t])[:, None, None] * S
        m = np.einsum("hkv,hk->hv", S, k[t])
        S = S + k[t][:, :, None] * (beta[t][:, None] * (v[t] - m))[:, None, :]
        out.append(np.einsum("hkv,hk->hv", S, q[t]))
    return np.stack(out), S


def bench(what, C, H, dk, dv, strength, repeats, rng):
    """One shape's chunk in both forms -> a dict of times and of distances
    from the recurrence."""
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.models import hybrid

    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)
    S = 1 if repeats == 1 else STACK
    args = (unit(rng.normal(size=(S, C, H, dk))) / np.sqrt(dk),
            unit(rng.normal(size=(S, C, H, dk))), rng.normal(size=(S, C, H, dv)),
            -rng.uniform(0.0, 0.05, (S, C, H)), rng.uniform(0.0, strength, (S, C, H)),
            rng.normal(size=(S, H, dk, dv)))
    want_o, want_s = recurrence(*(a[0] for a in args))
    args = tuple(jnp.asarray(a, jnp.float32) for a in args)
    live = jnp.ones(C, bool)
    systems = (jnp.tril(jnp.asarray(0.1 * rng.normal(size=(S, H, C, C)), jnp.float32), -1),
               jnp.asarray(rng.normal(size=(S, H, C, dv)), jnp.float32))
    want_d = np.linalg.solve(np.eye(C) + np.asarray(systems[0][0], np.float64),
                             np.asarray(systems[1][0], np.float64))
    trees_solve = hybrid.unit_lower_solve
    out = {"what": what, "C": C, "H": H, "dk": dk, "dv": dv, "stack": S,
           "ms_a_layer": {}, "solve_ms": {}, "from_recurrence": {}, "solve_from_float64": {}}
    try:
        for name, solve in (("parent", parents_solve), ("tree", trees_solve)):
            # the form looks its solve up when it is traced: a new trace a form
            hybrid.unit_lower_solve = solve
            ms, (o, s) = timed(lambda *a: hybrid.delta_chunk(*a, live), args, repeats)
            out["ms_a_layer"][name] = ms
            out["solve_ms"][name], d = timed(lambda L, b: solve(L, b), systems, repeats)
            out["solve_from_float64"][name] = float(np.abs(np.asarray(d) - want_d).max())
            out["from_recurrence"][name] = max(
                float(np.abs(np.asarray(o) - want_o).max()),
                float(np.abs(np.asarray(s) - want_s).max()))
    finally:
        hybrid.unit_lower_solve = trees_solve
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the control flow on the CPU at a small size")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    platform = jax.devices()[0].platform
    if platform != ("cpu" if args.rehearse else "tpu"):
        print(f"FAIL: needs {'the CPU to rehearse' if args.rehearse else 'a TPU'}, "
              f"found {platform}")
        return 1
    calls, repeats = (REHEARSAL, 1) if args.rehearse else (CALLS, args.repeats)
    rng = np.random.default_rng(args.seed)
    out = {"device": jax.devices()[0].device_kind, "rehearsal": args.rehearse, "calls": []}
    ok = True
    for call in calls:
        r = bench(*call, repeats, rng)
        far = r["from_recurrence"]
        ok = ok and far["tree"] <= max(4 * far["parent"], 1e-4)
        print(f"{r['what']}: C {r['C']}, {r['H']} heads of {r['dk']} x {r['dv']}: "
              + ", ".join(f"{n} {r['ms_a_layer'][n]:.3f} ms a layer (the solve alone "
                          f"{r['solve_ms'][n]:.3f}, {r['solve_from_float64'][n]:.1e} from "
                          f"float64's), {far[n]:.1e} from the recurrence"
                          for n in ("parent", "tree")))
        out["calls"].append(r)
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
