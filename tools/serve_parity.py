"""On-device agreement of a benchmark configuration AS SERVED with its plain
reference, at the published widths: the chip comparison of the
``model-configs`` guide § 3 point 3, for any configuration file that names a
``serve`` block and a ``reference`` (``benchmarks/configs/olmoe-1b-7b.json``,
``smallthinker-21b-a3b.json``, ``mistral-small-4-119b.json``,
``minicpm-sala-9b.json``, ``zaya1-8b.json``, ``olmo-hybrid-7b.json``,
``keye-vl-2.0-30b-a3b.json``, ``trinity-large-preview.json``: a stack whose
``n_layer`` is whole periods, so its float32 run is ``"n_layer": 4``, the dense
lead and three expert layers; ``jamba2-3b.json``: the whole model as served,
and its float32 run a few layers, ``"n_layer": 4, "attn_layer_period": 4,
"attn_layer_offset": 2`` in ``model.kwargs`` and ``reference.kwargs``;
``deepseek-v3.2-exp.json``: its float32 run is ``"n_layer": 2``, the dense
layer and one expert layer, with ``--long 30000`` for a context that selects;
``qwen3-next-80b-a3b.json``: one period as served; its float32 run holds 64
of the 512 experts, ``"experts_held": [0, 64]`` in ``model.kwargs`` and
``reference.kwargs``, so that the tree fits in float32, under
``JAX_DEFAULT_MATMUL_PRECISION=highest``; ``xing4.0-29b-a4b.json``: one
pipeline stage whole as served; its float32 run is ``"n_layer": 2``, the
dense layer and one expert layer on four residual streams, with ``--long
16384`` for the cell's longest prompt).

Run standalone on a TPU host (``chiprun --chips 1 -- python
tools/serve_parity.py benchmarks/configs/smallthinker-21b-a3b.json``); any
other platform is an error (exit 1).  Seeded bf16 weights; a seeded sample of
prompts (one of them longer than the model's window, where it has one, than
the original positions of its YaRN rope, than the ``dense_len`` under
which its sparse layers attend every key, than the ``topk`` tokens its
indexed layers keep, or than two prompt chunks where its delta layers'
chunked form or its mamba layers' scan must enter with a state) goes
through ``init_serving()`` / ``submit().result()`` with the file's slots and
chunk (prefill in chunks, then decode, on the program's kernels), and each
served sequence through the file's reference in one full float32 forward pass:

* on LOGITS, teacher-forced: for every served token, the reference's best
  logit at that position less the reference's logit of the token served.
  ``TIE_TOL`` (0.0625, the benchmark's) is the most a served token may lose
  by: bf16 cannot promise the same argmax (PERF.md § 2);
* the router, where the reference's module has a ``*_router_choices``: the
  program's block in bf16 (``gpt_block``, layer by layer) against the
  reference's float32 router, as the number of (layer, token) pairs whose
  top-k SET differs (the last places swap on rounding).

Prints one JSON line and exits 0 when every gap is inside ``--margin``
(``TIE_TOL`` by default).  A configuration whose reference names a ``hidden``
(a long context: SmallThinker) goes through the comparison that decides its
cell's ``correct`` instead, all served sequences as one run's sample
(``benchmarks/kinds/serve_backlog_resident.py:check_sample``: the gross limit
on every token's gap and the limit on the median noise scale; under the
limits of the cell's own kind where that has a ``judge``), and exits 0
when that counts nothing wrong.  ``--bank float8_e4m3fn`` serves with the
expert bank rounded through that type: the reading a limit must REFUSE, exit 1;
``--weights float8_e4m3fn`` does the same to every matrix of the blocks (a
dense model's control); ``--index-keys float8_e4m3fn`` caches an indexed
stack's index keys in that type (the weights whole: the selection alone is
rounded); ``--state bfloat16`` keeps a mamba stack's recurrent state in
that type (on LOGITS, which is all this tool compares, it reads a fifth over
what float32 reads and exits 0; what refuses it is the cell's check of the
state itself, ``benchmarks/kinds/serve_backlog_resident_mamba.py``: ``--set
planted='"state-bfloat16"'``, PERF.md § 6, PR 57).
``--patch JSON`` lays a patch over the configuration
file first, and ``--long TOKENS`` adds a prompt that long (a long-context
cell's own lengths): the program in FLOAT32 against the reference, at a few layers of
the published widths, is ``--patch '{"dtype": "float32", "serve": {"serving":
{"dtype": "float32"}}, "model": {"kwargs": {...}}, "reference": {"kwargs":
{...}}}'`` and must read a largest gap of rounding's size.
"""

import argparse
import gc
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TIE_TOL = 0.0625
PROMPTS, NEW = ((100, 11), (64, 12), (17, 13), (129, 14)), 48
PARITY_BLOCKS = 1025            # blocks of ALL layers in the tool's arena


def router_sets_that_differ(cfg, params, seq, choices_fn):
    """(layer, token) pairs whose top-k set under the program's own bf16
    block differs from the reference's float32 router, and their number."""
    import jax
    import numpy as np
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops.attention import get_attention_fn
    recorded, real = [], dropless.softmax_topk

    def recording(logits, k, *rest):
        res = real(logits, k, *rest)
        recorded.append(np.sort(np.asarray(res[2]), axis=-1))
        return res
    dropless.softmax_topk = recording
    try:
        x = params["wte"][seq][None]
        for layer in range(cfg.n_layer):
            p = jax.tree.map(lambda a: a[layer], params["blocks"])
            x, _ = gpt.gpt_block(cfg, p, x, None, False, get_attention_fn("reference"),
                                 kind=cfg.pattern[layer % len(cfg.pattern)])
    finally:
        dropless.softmax_topk = real
    want = np.sort(np.asarray(choices_fn(params, seq)), axis=-1)        # [L, S, k]
    return int((np.stack(recorded) != want).any(axis=-1).sum()), int(want[..., 0].size)


def cell_kind(config_path):
    """The traffic kind's module of the benchmark cell that runs this
    configuration file (the first, where several do); None where none does."""
    from benchmarks.lib import cells
    bench = cells.load_benchmark()
    names = {c["name"] for c in bench["configs"]
             if os.path.basename(c["file"]) == os.path.basename(config_path)}
    cell = next((w["name"] for w in bench["workloads"] if w["config"] in names), None)
    return cells.Cell(cell).kind if cell else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", help="a file of benchmarks/configs/")
    ap.add_argument("--margin", type=float, default=TIE_TOL,
                    help="the most a served token may lose by")
    ap.add_argument("--new", type=int, default=NEW, help="tokens served a prompt")
    ap.add_argument("--bank", default=None, metavar="DTYPE",
                    help="serve with the expert bank rounded through this type "
                         "(float8_e4m3fn: what a bank in the precision below "
                         "bf16 loses; the reference keeps the weights whole)")
    ap.add_argument("--weights", default=None, metavar="DTYPE",
                    help="serve with every matrix of the blocks rounded through "
                         "this type (the reference keeps the weights whole)")
    ap.add_argument("--index-keys", default=None, metavar="DTYPE",
                    help="cache an indexed stack's index keys in this type "
                         "(models/hybrid.py:init_aux's ``ki``)")
    ap.add_argument("--state", default=None, metavar="DTYPE",
                    help="keep a mamba stack's recurrent state in this type "
                         "(models/hybrid.py:init_aux's ``mamba_state``)")
    ap.add_argument("--long", type=int, default=0, metavar="TOKENS",
                    help="one more prompt, this long (the cell's contexts: "
                         "the tool's arena holds 65,600 tokens in all)")
    ap.add_argument("--patch", default=None, metavar="JSON",
                    help="laid over the configuration file before anything is built")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print(f"FAIL: needs a TPU, found {jax.devices()[0].platform}")
        return 1
    import deepspeed_tpu
    from benchmarks.kinds.serve_backlog_resident import check_sample
    from benchmarks.lib.build import model_from
    from benchmarks.lib.cells import load_json, merge, resolve

    config = load_json(args.config)
    if args.patch:
        merge(config, json.loads(args.patch))
    model = model_from(config)
    cfg, ref = model.cfg, config["reference"]
    dtype = jnp.dtype(config["dtype"])
    make_params = jax.jit(lambda key: jax.tree.map(lambda p: p.astype(dtype),
                                                   model.init_params(key)))
    params = served_params = make_params(jax.random.PRNGKey(27))
    # the barrier keeps a rounding: XLA takes a convert down and up again
    # inside one program for excess precision it may leave out
    low = lambda w: jax.lax.optimization_barrier(
        w.astype(jnp.dtype(args.bank or args.weights))).astype(w.dtype)
    if args.weights:
        served_params = jax.jit(lambda p: dict(p, blocks=jax.tree_util.tree_map_with_path(
            lambda path, w: low(w) if path[-1].key.endswith("_w") else w, p["blocks"])),
            donate_argnums=0)(params)
        params = None
    if args.bank:
        # rounded in place (the tree donated): a bank and its rounded copy do
        # not both fit beside an arena; the reference's weights are made
        # again from the seed once the engine is gone.  The bank is the
        # group ``experts`` wherever the family keeps it
        # (``blocks/moe/experts``, a hybrid stack's ``blocks/<mixer>/experts``)
        served_params = jax.jit(lambda p: dict(p, blocks=jax.tree_util.tree_map_with_path(
            lambda path, w: low(w) if any(
                getattr(k, "key", None) == "experts" for k in path) else w, p["blocks"])),
            donate_argnums=0)(served_params)
        params = None
    retyped = {name: jnp.dtype(to) for name, to in (
        ("ki", args.index_keys), ("mamba_state", args.state)) if to}
    if retyped:
        from deepspeed_tpu.models import hybrid
        init_aux = hybrid.init_aux
        hybrid.init_aux = lambda *a: {
            name: leaf.astype(retyped.get(name, leaf.dtype))
            for name, leaf in init_aux(*a).items()}
    eng = deepspeed_tpu.init_serving(model=model, params=served_params, config={
        "serving": dict(config["serve"]["serving"], num_blocks=PARITY_BLOCKS)})
    rng = np.random.default_rng(27)
    lengths = [n for n, _ in PROMPTS]
    window = max((k.window or 0) for k in cfg.pattern)
    if window:
        lengths.append(window + 200)            # prefill AND decode past the window
    if cfg.rope_yarn is not None:               # and past the stretched rope's
        lengths.append(cfg.rope_yarn.original_positions + 100)      # original range
    if "sparse" in cfg.mixers:                  # and past where every key is attended
        lengths.append(cfg.sparse.dense_len + 200)
    if cfg.indexed_layers:                      # and past where every token is kept
        lengths.append(cfg.indexer.topk + 200)
    if {"delta", "mamba"} & set(cfg.mixers):    # and a third chunk entered with both states
        lengths.append(2 * config["serve"]["serving"]["prefill_chunk"] + 77)
    if args.long:
        lengths.append(args.long)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]
    futures = [eng.submit(p, max_new_tokens=args.new) for p in prompts]
    served = [f.result() for f in futures]
    tile_pages = eng.paged_tile_pages
    eng.close()
    del eng, futures, served_params      # the arena, and a rounded bank
    gc.collect()
    if params is None:
        params = make_params(jax.random.PRNGKey(27))

    out = {"config": os.path.basename(args.config), "layers": cfg.n_layer,
           "device": jax.devices()[0].device_kind, "paged_tile_pages": tile_pages,
           "margin": args.margin, "bank": args.bank or config["dtype"],
           "weights": args.weights or config["dtype"],
           "index_keys": args.index_keys or config["dtype"],
           "state": args.state or "float32", "sequences": []}
    kw = ref["kwargs"]
    check = None
    if "hidden" in ref:          # a long context: the comparison of its cell's kind
        check = check_sample(model, params, ref, list(zip(prompts, served)))
        judge = getattr(cell_kind(args.config), "judge", None)
        if judge is not None:    # the resident kind's check under the cell's own limits
            check["wrong"] = judge(check["largest"], check["noise_scale"],
                                   check["noise_scale_median"])
        out.update(wrong=check["wrong"], noise_scale_median=check["noise_scale_median"])
        found = iter(zip(check["largest"], check["mean"], check["noise_scale"]))

        def gaps_of(prompt, new):
            worst, mean, scale = next(found)
            return worst, mean, {"noise_scale": scale}
    else:
        logits_fn = jax.jit(lambda p, i: resolve(ref["logits"])(p, i, **kw))

        def gaps_of(prompt, new):
            seq = jnp.asarray(prompt + new, jnp.int32)
            lg = logits_fn(params, seq)
            at = jnp.arange(len(prompt) - 1, len(seq) - 1)
            gaps = lg[at].max(-1) - lg[at, seq[at + 1]]
            return float(gaps.max()), float(gaps.mean()), {}
    module = importlib.import_module(ref["logits"].partition(":")[0])
    choices = next((getattr(module, n) for n in dir(module)
                    if n.endswith("_router_choices")), None)
    for prompt, new in zip(prompts, served):
        worst, mean, more = gaps_of(prompt, new)
        row = dict({"prompt_tokens": len(prompt), "served_tokens": len(new),
                    "largest_logit_gap": worst, "mean_logit_gap": mean}, **more)
        if choices is not None:
            fn = jax.jit(lambda p, i: choices(p, i, **kw))
            row["topk_sets_that_differ"], row["of_layer_token_pairs"] = (
                router_sets_that_differ(cfg, params, jnp.asarray(prompt + new, jnp.int32), fn))
        out["sequences"].append(row)
    out["largest_logit_gap"] = max(s["largest_logit_gap"] for s in out["sequences"])
    inside = (out["largest_logit_gap"] <= args.margin if check is None
              else check["wrong"] == 0)
    out["ok"] = bool(inside and tile_pages > 0)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
