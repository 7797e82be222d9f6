"""``init_serving`` tells the allocator a tile's size wherever the selected
kernel fetches runs (``paged_gqa_attention`` over a full group and over a
window group's ring, beside the latent kernel of ``test_mistral4.py``), the
step hands the kernel the flags, the stat counts the tiles that are runs in
both kinds of group, and the tokens are those of the same engine under
``run_blocks = 1``."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import gpt
from deepspeed_tpu.ops.pallas import decode_attention as da
from tests.unit.serving_helpers import small_tiles  # noqa: F401  (a fixture)

V, BS = 512, 8
SERVING = dict(block_size=BS, num_blocks=64, max_batch_size=3, prefill_chunk=8,
               dtype="float32")
# heads of 128 lanes (the kernel's gate) on the smallest stacks that hold them
MODELS = {
    # every attention layer one full group, 4 query heads on 2 K/V heads
    "zaya": lambda: gpt.zaya_config(
        vocab_size=V, n_positions=256, n_embd=64, n_layer=2, n_head=4, n_kv_head=2,
        head_dim=128, intermediate_size=32, num_experts=4, router_hidden=16,
        dtype="float32"),
    # multi-head attention of whole lane tiles: a group of one a K/V head
    "olmoe": lambda: gpt.olmoe_config(
        vocab_size=V, n_positions=256, n_embd=256, n_layer=2, n_head=2,
        intermediate_size=32, num_experts=4, top_k=2, dtype=jnp.float32),
    # a full group beside three window groups
    "smallthinker": lambda: gpt.smallthinker_config(
        vocab_size=V, n_positions=256, n_embd=64, n_layer=4, n_head=4, n_kv_head=2,
        head_dim=128, intermediate_size=32, num_experts=4, top_k=2, window=16,
        dtype=jnp.float32),
    # D = 64: the sliced layer and ``paged_attention``, which copies page by page
    "gpt2": lambda: gpt.gpt_config("tiny", n_embd=128, n_head=2, n_layer=2,
                                   vocab_size=V, n_positions=256, dtype=jnp.float32),
    # the two families whose kernels are not this file's (``tests/unit/ops/
    # test_paged_plans.py``).  The latent cache's 128 + 16 lanes in 256, under
    # tables of 32 pages: a tile of 32
    "mistral": lambda: gpt.mistral4_config(
        vocab_size=V, n_positions=256, n_embd=64, intermediate_size=32, n_layer=2,
        n_head=4, head_dim=32, q_lora_rank=48, kv_lora_rank=128, qk_rope_dim=16,
        v_head_dim=24, num_experts=4, top_k=2, dtype="float32"),
    # a page a block of 8 keys of one K/V head of 128 lanes
    "minicpm": lambda: gpt.minicpm_sala_config(
        vocab_size=V, n_positions=256, n_embd=64, intermediate_size=32, n_head=4,
        n_kv_head=2, head_dim=128, mixer_types=["minicpm4", "lightning-attn"],
        sparse=(4, 2, 8, 4, 1, 16, 32), dtype="float32"),
}


@functools.lru_cache(maxsize=None)
def built(name, seed=0):
    model = gpt.GPT(MODELS[name]())
    return model, model.init_params(jax.random.PRNGKey(seed))


def engine(model, params):
    """A NEW engine: every test here (and ``test_paged_plans.py``) reads where
    a new allocator lays its runs, under constants ``small_tiles`` lowered."""
    return deepspeed_tpu.init_serving(model=model, params=params,
                                      config={"serving": SERVING})


def served(model, params, prompts, new):
    eng = engine(model, params)
    try:
        futures = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
        stats = []
        while not all(f.done for f in futures):
            stats.append(eng.step())
        return [f.result() for f in futures], stats, eng.alloc.run_blocks
    finally:
        eng.close()


@pytest.mark.parametrize("name,run_blocks", [
    ("zaya", 4), ("olmoe", 4), ("smallthinker", 4), ("gpt2", 1)])
def test_init_serving_tells_the_allocator_a_tiles_size(
        kernels, small_tiles, name, run_blocks):
    model, params = built(name)
    eng = engine(model, params)
    assert eng.alloc.run_blocks == 1            # the CPU's rule: the references
    eng.close()
    kernels("paged_gqa_attention", "paged_attention")
    eng = engine(model, params)
    assert eng.paged_tile_pages == 2 and eng.alloc.run_blocks == run_blocks
    eng.close()


def _ids(n, seed):
    return list(map(int, np.random.default_rng(seed).integers(0, V, n)))


@pytest.mark.parametrize("name", ["zaya", "olmoe", "smallthinker"])
def test_the_engine_lays_runs_and_serves_the_tokens_of_run_blocks_1(
        kernels, small_tiles, monkeypatch, name):
    """Prompts over several tiles and decode rows that grow past them, three
    slots at once: with the tables in runs of 4 pages and the flags in the
    step the stat counts whole tiles, and every token is the token of the
    same kernel under tables grown block by block and no flags."""
    model, params = built(name, seed=1)
    prompts, new = [_ids(n, seed=n) for n in (45, 13, 70)], (25, 30, 12)
    kernels("paged_gqa_attention")
    tokens, stats, run_blocks = served(model, params, prompts, new)
    assert run_blocks == 4
    assert all(s["programs"] == 1 for s in stats if s["decode_batch"] or s["prefill_tokens"])
    # the prompt of 70 alone is 9 pages, two whole tiles: over half of what the
    # three tables hold are runs while all are resident
    assert max(s["tile_runs_pct"] for s in stats) > 50.0
    assert stats[0]["tile_runs_pct"] > 0.0
    plan = da.softmax_plan
    monkeypatch.setattr(da, "softmax_plan",
                        lambda *a, **kw: plan(*a, **kw)._replace(run_pages=0))
    plain, plain_stats, run_blocks = served(model, params, prompts, new)
    assert run_blocks == 1 and all(s["tile_runs_pct"] == 0.0 for s in plain_stats)
    assert tokens == plain and [len(t) for t in tokens] == list(new)


def test_a_full_and_three_window_groups_lay_runs_and_the_stat_counts_both_kinds(
        kernels, small_tiles):
    """The tiny SmallThinker, a full group beside three rings of 8 pages (two
    runs of 4: the window of 16 keys, a chunk of 8 and the 3 pages that share
    the window's run): every group's table grows in runs, the rings give runs
    back, every call of the kernel takes flags, and ``tile_runs_pct`` is the
    share of whole runs among the tiles of ALL FOUR tables of the live
    sequences."""
    model, params = built("smallthinker", seed=1)
    kernels("paged_gqa_attention")
    eng = engine(model, params)
    try:
        alloc, G = eng.alloc, eng.alloc.run_blocks
        assert G == 4 and alloc.widths == (32, 8, 8, 8)
        assert alloc._in_runs == (True,) * 4
        futures = [eng.submit(_ids(n, seed=n), max_new_tokens=new)
                   for n, new in ((45, 25), (13, 30), (70, 12))]
        ring_tiles = ring_runs = 0
        while not all(f.done for f in futures):
            stats = eng.step()
            alloc.check_consistent()
            tiles = [[run.blocks[i:i + G] for i in range(0, len(run.blocks), G)]
                     for runs in alloc._owned.values() for run in runs]
            held = sum(map(len, tiles))
            whole = sum(alloc._is_run(t) for table in tiles for t in table)
            assert (alloc.tiles_held, alloc.tiles_run) == (held, whole)
            assert stats["tile_runs_pct"] == pytest.approx(
                100.0 * whole / held if held else 0.0)
            rings = [t for runs in alloc._owned.values() for run in runs[1:]
                     for t in (run.blocks[i:i + G] for i in range(0, len(run.blocks), G))]
            ring_tiles += len(rings)
            ring_runs += sum(map(alloc._is_run, rings))
        assert ring_runs > 0.5 * ring_tiles > 0
        assert alloc.given_back_ever > 0 and alloc.given_back_ever % G == 0
        assert [len(f.result()) for f in futures] == [25, 30, 12]
    finally:
        eng.close()
