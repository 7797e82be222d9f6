"""Which of a serve step's ``copy`` instructions move a whole stacked leaf.

    python3 tools/stack_copies.py benchmarks/configs/<name>.json [...]
                                  [--min-bytes 1048576] [--top 8] [--json]

Compiles the serve step of each configuration ahead of time for a described
v5e (nothing is attached and nothing runs: needs no chip), as the engine
builds it (the model's serving tree, the cell's slots, chunk, tables and
arena), and reads the compiler's text: every ``copy`` that is not part of a
fusion, the bytes of its result and the compiler's own ``estimated_cycles``.
A copy is PLACED on a leaf of the step's arguments (the weights, the arena,
what the stack caches beside it) when its result has the dimensions of the
whole leaf or of one layer of it, in any order: XLA copies a stack to
give a dot the layout it wants of its operand, and where the operand is a
layer read at a traced index it copies the whole stack, in every region that
reads it (PERF.md § 6, PR 67).  The copies that match no leaf are
activations; the largest are listed, so that a family's ``copy`` time in a
trace (``tools/trace_ops.py``) can be said not to be a stack's.

The cycles are a count to compare two texts by, not a time: a region under a
``conditional`` runs in some steps, a loop's body once a layer.
"""

import argparse
import json
import math
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>\S+) = (?P<dtype>\w+)\[(?P<dims>[\d,]*)\](?P<layout>\{\S*\})? "
    r"(?P<op>[\w-]+)\((?P<operands>[^)]*)\)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(?P<name>\S+) \(.*\{\s*$")
_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')


def instructions(text: str):
    """Every array-valued instruction of a compiled program's text that runs
    as written (a fusion's body is the fusion's) -> dicts of ``name``,
    ``op``, ``dtype``, ``shape``, ``layout``, ``operands``, ``computation``
    and the compiler's ``cycles`` (0 where it estimates none)."""
    fused = set(re.findall(r" fusion\(.*?calls=%(\S+?)[,\s]", text))
    computation, out = None, []
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head["name"]
            continue
        m = _INSTRUCTION.match(line)
        if not m or computation in fused:
            continue
        cycles = _CYCLES.search(line)
        out.append(dict(
            name=m["name"], op=m["op"], dtype=m["dtype"], layout=m["layout"] or "",
            shape=tuple(int(d) for d in m["dims"].split(",") if d),
            operands=m["operands"], computation=computation,
            cycles=int(cycles[1]) if cycles else 0))
    return out


_HLO_TYPES = {"bfloat16": "bf16", "float32": "f32", "float16": "f16", "int32": "s32",
              "int8": "s8", "uint8": "u8", "bool": "pred", "float8_e4m3fn": "f8e4m3fn"}


def _itemsize(hlo_type: str) -> int:
    bits = re.match(r"[a-z]+(\d+)", hlo_type)      # bf16, f8e4m3fn, s32; pred
    return max(int(bits[1]) // 8, 1) if bits else 1


def leaves_of(tree) -> dict:
    """{path: (HLO type, shape)} of the leaves of a tree of shapes."""
    import jax
    return {jax.tree_util.keystr(path): (_HLO_TYPES.get(str(leaf.dtype), str(leaf.dtype)),
                                         tuple(leaf.shape))
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def _same_array(made, shape, cut: bool) -> bool:
    """Whether the dimensions ``made`` are ``shape``'s in any order (ones
    apart) or, with ``cut``, ``shape``'s with ONE of them cut in two (a
    projection's lanes by head)."""
    made, shape = ([d for d in dims if d > 1] for dims in (made, shape))
    for d in list(shape):
        if d in made:
            made.remove(d)
            shape.remove(d)
    if not cut:
        return not made and not shape
    return len(made) == 2 and len(shape) == 1 and made[0] * made[1] == shape[0]


def placed(instruction: dict, leaves: dict):
    """-> (the leaf's path, "stack" | "layer") of the first leaf that
    ``instruction``'s result holds whole, or one layer of, whatever the order
    of its dimensions; a leaf's own dimensions before one with a dimension
    cut in two (:func:`_same_array`); None for none."""
    for cut in (False, True):
        for path, (dtype, shape) in leaves.items():
            if dtype != instruction["dtype"] or len(shape) < 2:
                continue
            if _same_array(instruction["shape"], shape, cut):
                return path, "stack"
            if len(shape) > 2 and _same_array(instruction["shape"], shape[1:], cut):
                return path, "layer"
    return None


def stack_copies(text: str, leaves: dict, min_bytes: int = 1 << 20, ops=("copy",)):
    """The instructions of ``text`` of the kinds ``ops`` whose result is at
    least ``min_bytes``, each with its ``bytes`` and where it was ``placed``
    among ``leaves`` (None: an activation), the costliest first."""
    out = []
    for i in instructions(text):
        size = math.prod(i["shape"]) * _itemsize(i["dtype"])
        if i["op"] in ops and size >= min_bytes:
            out.append(dict(i, bytes=size, placed=placed(i, leaves)))
    return sorted(out, key=lambda i: -i["cycles"])


def step_program(chip, cfg, slots, chunk, BS, blocks, MB, counts=False, donate=False):
    """The whole serve step of ``cfg`` compiled for the described ``chip`` (a
    sharding), as ``init_serving`` builds it: the model's SERVING tree
    (``serving/engine.py``: ``params``), ``slots`` decode rows and a chunk of
    ``chunk`` over an arena of ``blocks`` pages of ``BS`` tokens, bf16.  The
    periodic walk takes a table a page group, ``MB`` blocks wide or a window
    group's ring (a whole number of runs: ``cfg.paged_layout``); a hybrid
    stack takes one table and what it caches beside K and V (``aux``), each
    row's slot and whether it is live.  ``counts``: the step hands back its
    expert counts; ``donate``: arena and ``aux`` are donated.  -> (compiled,
    arena's K, aux, the shapes of the step's weights)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import gpt, hybrid
    from deepspeed_tpu.serving.kv_cache import init_arena
    BF16 = jnp.bfloat16
    model, rows = gpt.GPT(cfg), slots + chunk
    shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=chip)
    ints = lambda *s: shape(s, jnp.int32)
    on_chip = lambda tree: jax.tree.map(lambda a: shape(a.shape, a.dtype), tree)
    params = jax.tree.map(
        lambda p: shape(p.shape, BF16 if jnp.issubdtype(p.dtype, jnp.floating)
                        else p.dtype),
        jax.eval_shape(lambda key: model.serving_params(model.init_params(key))[0],
                       jax.random.PRNGKey(0)))
    kp, vp = on_chip(jax.eval_shape(lambda: init_arena(cfg, blocks, BS, dtype=BF16)))
    kw = dict(chunk=chunk, **({"with_expert_counts": True} if counts else {}))
    if cfg.hybrid:
        aux = on_chip(jax.eval_shape(lambda: hybrid.init_aux(cfg, blocks, BS, slots, BF16)))
        tables, coords = ints(rows, MB), ints(rows, 1)
        more = (aux, ints(rows), shape((rows,), jnp.bool_))
        step = lambda *a: model.paged_step(*a[:8], aux=a[8], slots=a[9], live=a[10], **kw)
    else:
        aux, more = None, ()
        _, widths, _ = cfg.paged_layout(BS, MB, chunk, BF16)
        tables = tuple(ints(rows, w) for w in widths)
        coords = tuple(ints(rows, 1) for _ in widths)
        step = lambda *a: model.paged_step(*a, **kw)
        if cfg.indexed_layers:          # an indexer over a latent: its index keys
            aux = on_chip(jax.eval_shape(lambda: hybrid.init_aux(cfg, blocks, BS, slots, BF16)))
            more = (aux,)
            step = lambda *a: model.paged_step(*a[:8], aux=a[8], **kw)
    compiled = jax.jit(step, donate_argnums=(3, 4, 8) if donate else ()).lower(
        params, ints(rows, 1), ints(rows), kp, vp, tables, coords, ints(rows, 1),
        *more).compile()
    return compiled, kp, aux, params


def described_chip():
    """One described v5e chip's sharding, ``ops.pallas`` steered to it (the
    kernels' gates read the platform) and the persistent compile cache off
    (an entry written without a chip cannot be read back)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from deepspeed_tpu.ops import pallas
    jax.config.update("jax_enable_compilation_cache", False)
    pallas.platform, pallas.interpret = (lambda: "tpu"), (lambda: False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def of_config(chip, path: str, min_bytes: int):
    """The step of the serve configuration at ``path`` at the cell's own
    sizes -> {"config", "copies" (:func:`stack_copies`), "all_copy_cycles"}."""
    import jax.numpy as jnp
    from benchmarks.lib.build import model_from
    from benchmarks.lib.cells import load_json
    from deepspeed_tpu.serving.config import DeepSpeedServingConfig
    config = load_json(path)
    cfg, serve = model_from(config).cfg, config["serve"]
    serving = DeepSpeedServingConfig(**{k: v for k, v in serve["serving"].items()
                                        if k != "dtype"})
    BS = serving.block_size
    MB = serving.max_blocks_per_seq or -(-cfg.n_positions // BS)
    # the arena as the harness sizes it (``benchmarks/lib/serving.py``)
    blocks = int(serve["arena_bytes"]) // (
        2 * cfg.n_layer * BS * cfg.kv_heads * cfg.head_dim * jnp.dtype(config["dtype"]).itemsize)
    compiled, kp, aux, params = step_program(
        chip, cfg, serving.max_batch_size, serving.prefill_chunk, BS, blocks, MB,
        counts=bool(cfg.moe_num_experts), donate=True)
    text = compiled.as_text()
    leaves = leaves_of({"params": params, "arena": kp, "aux": aux})
    return {"config": os.path.basename(path),
            "copies": stack_copies(text, leaves, min_bytes),
            "all_copy_cycles": sum(i["cycles"] for i in instructions(text)
                                   if i["op"] == "copy")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("configs", nargs="+", help="benchmarks/configs/<name>.json")
    ap.add_argument("--min-bytes", type=int, default=1 << 20,
                    help="leave out copies smaller than this")
    ap.add_argument("--top", type=int, default=8,
                    help="how many of the copies that match no leaf to list")
    ap.add_argument("--json", action="store_true", help="one JSON line a configuration")
    args = ap.parse_args(argv)
    chip = described_chip()
    for path in args.configs:
        found = of_config(chip, path, args.min_bytes)
        if args.json:
            print(json.dumps(found))
            continue
        on_leaf = [c for c in found["copies"] if c["placed"]]
        others = [c for c in found["copies"] if not c["placed"]]
        cycles = lambda cs: sum(c["cycles"] for c in cs)
        print(f"{found['config']}: copies' estimated cycles {found['all_copy_cycles']:,}; "
              f"of stacked leaves {cycles(on_leaf):,} in {len(on_leaf)} copies of "
              f"{sum(c['bytes'] for c in on_leaf):,} B; of other arrays over "
              f"{args.min_bytes:,} B {cycles(others):,} in {len(others)}")
        row = lambda c, what: print(
            f"  {c['cycles']:>12,} cycles {c['bytes']:>14,} B  "
            f"{c['dtype']}{list(c['shape'])}{c['layout'].rstrip('}').split(':')[0]}}}  {what}  "
            f"in {c['computation']}")
        for c in on_leaf:
            row(c, f"{c['placed'][1]} of {c['placed'][0]}")
        for c in others[:args.top]:
            row(c, f"no leaf (from {c['operands'][:40]})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
