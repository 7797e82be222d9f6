"""On-device agreement of OLMoE-1B-7B as served with the plain reference, at
the published widths (8 of the 16 layers: what one chip holds beside an arena).

Run standalone on a TPU host (``chiprun --chips 1 -- python
tools/olmoe_parity.py``); any other platform is an error (exit 1).  Seeded
bf16 weights; a seeded sample of prompts goes through ``init_serving()`` /
``submit().result()`` (prefill in chunks of 64, then decode, on the paged
kernel and the grouped-matmul kernel), and each served sequence through
``benchmarks/lib/reference_olmoe.py`` in one full float32 forward pass:

* on LOGITS, teacher-forced: for every served token, the reference's best
  logit at that position less the reference's logit of the token served.
  ``TIE_TOL`` (0.0625, the benchmark's) is the most a served token may lose
  by: bf16 cannot promise the same argmax (PERF.md § 2);
* the router: the program's block in bf16 (``gpt_block``, layer by layer)
  against the reference's float32 router, as the number of (layer, token)
  pairs whose top-8 SET differs (8th and 9th places swap on rounding).

Prints one JSON line and exits 0 when every gap is inside ``TIE_TOL``.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TIE_TOL = 0.0625
LAYERS, PROMPTS, NEW = 8, ((100, 11), (64, 12), (17, 13), (129, 14)), 48


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print(f"FAIL: needs a TPU, found {jax.devices()[0].platform}")
        return 1
    import deepspeed_tpu
    from benchmarks.lib.reference_olmoe import olmoe_logits, olmoe_router_choices
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops.attention import get_attention_fn

    cfg = gpt.olmoe_config(n_layer=LAYERS)
    model = gpt.GPT(cfg)
    bf16 = jnp.bfloat16
    params = jax.jit(lambda key: jax.tree.map(lambda p: p.astype(bf16),
                                              model.init_params(key)))(
        jax.random.PRNGKey(27))
    eng = deepspeed_tpu.init_serving(model=model, params=params, config={"serving": {
        "num_blocks": 1025, "max_batch_size": 8, "dtype": "bfloat16"}})
    rng = np.random.default_rng(27)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n, _ in PROMPTS]
    futures = [eng.submit(p, max_new_tokens=NEW) for p in prompts]
    served = [f.result() for f in futures]
    tile_pages = eng.paged_tile_pages
    eng.close()

    kw = dict(n_head=cfg.n_head, vocab_size=cfg.vocab_size, top_k=cfg.moe_top_k,
              eps=cfg.ln_eps, rope_theta=cfg.rope_theta)
    logits_fn, choices_fn = jax.jit(lambda p, i: olmoe_logits(p, i, **kw)), jax.jit(
        lambda p, i: olmoe_router_choices(p, i, **kw))
    attention = get_attention_fn("reference")
    out = {"device": jax.devices()[0].device_kind, "layers": LAYERS,
           "paged_tile_pages": tile_pages, "tie_tolerance": TIE_TOL, "sequences": []}
    for prompt, new in zip(prompts, served):
        seq = jnp.asarray(prompt + new, jnp.int32)
        lg = logits_fn(params, seq)
        at = jnp.arange(len(prompt) - 1, len(seq) - 1)
        gaps = lg[at].max(-1) - lg[at, seq[at + 1]]
        # the program's routing: its own block, layer by layer, in bf16
        recorded, real = [], dropless.softmax_topk

        def recording(logits, k):
            res = real(logits, k)
            recorded.append(np.sort(np.asarray(res[2]), axis=-1))
            return res
        dropless.softmax_topk = recording
        try:
            x = params["wte"][seq][None]
            for layer in range(LAYERS):
                p = jax.tree.map(lambda a: a[layer], params["blocks"])
                x, _ = gpt.gpt_block(cfg, p, x, None, False, attention)
        finally:
            dropless.softmax_topk = real
        want = np.sort(np.asarray(choices_fn(params, seq)), axis=-1)    # [L, S, k]
        differ = int((np.stack(recorded) != want).any(axis=-1).sum())
        out["sequences"].append({
            "prompt_tokens": len(prompt), "served_tokens": len(new),
            "largest_logit_gap": float(gaps.max()),
            "tokens_not_the_references_argmax": int((gaps > 0).sum()),
            "top8_sets_that_differ": differ, "of_layer_token_pairs": int(want[..., 0].size)})
    out["largest_logit_gap"] = max(s["largest_logit_gap"] for s in out["sequences"])
    out["ok"] = bool(out["largest_logit_gap"] <= TIE_TOL and tile_pages > 0)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
