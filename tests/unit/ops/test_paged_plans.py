"""ONE plan a page group (``ops/pallas/decode_attention.py:PagedAttention``,
from ``GPTConfig.paged_plans``): at each serve cell's published widths its
five fields are what the parent's separately callable rules gave (recorded
with them at PR 44's tree before they went), and on an engine the allocator's
``run_blocks``, the step's flags and the tile a kernel copies are one field
of that plan."""

import jax.numpy as jnp
import pytest

from deepspeed_tpu.models import gpt
from deepspeed_tpu.ops.pallas import decode_attention as da
from deepspeed_tpu.serving.kv_cache import table_widths
from tests.unit.serving.test_gqa_runs_engine import BS, SERVING, _ids, built, engine
from tests.unit.serving_helpers import small_tiles  # noqa: F401  (a fixture)

PLAIN, GQA, MLA, SPARSE = ("paged_attention", "paged_gqa_attention",
                           "paged_mla_attention", "paged_sparse_attention")
# a serve cell: its preset at the published widths; its block_size,
# max_blocks_per_seq and prefill_chunk; and of each page group (kernel,
# tile_pages, run_pages, chunk_queries, rows_a_token) on a described v5e.  On
# the CPU every plan is a reference: no kernel, no tile, no run
CELLS = {
    "gpt2-124m.serve-decode-heavy": (
        lambda: gpt.gpt_config("gpt2"), 16, 64, 64, [(PLAIN, 8, 0, 64, 1)]),
    "gpt2-124m.serve-chat-steady": (
        lambda: gpt.gpt_config("gpt2"), 16, 64, 64, [(PLAIN, 8, 0, 64, 1)]),
    "olmoe-1b-7b.serve-decode-heavy": (
        gpt.olmoe_config, 16, 256, 64, [(GQA, 8, 8, 64, 1)]),
    "smallthinker-21b-a3b.serve-long-context": (
        gpt.smallthinker_config, 16, 1024, 224,
        4 * [(GQA, 8, 32, 32, 1)]),      # the rings in the full group's runs
    "mistral-small-4-119b.serve-reasoning-batch": (
        gpt.mistral4_config, 16, 1024, 384, [(MLA, 32, 32, 16, 1)]),
    "minicpm-sala-9b.serve-long-mixed": (
        lambda: gpt.minicpm_sala_config(mixer_types=["minicpm4", "lightning-attn"]),
        64, 768, 512, [(SPARSE, 8, 0, 1, 2)]),
    "zaya1-8b.serve-reasoning-resident": (
        gpt.zaya_config, 64, 256, 208, [(GQA, 4, 8, 104, 1)]),
}


@pytest.mark.parametrize("device", ["v5e", "cpu"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_a_cells_plans_are_what_the_rules_gave(kernels, cell, device):
    preset, block, MB, chunk, want = CELLS[cell]
    if device == "cpu":
        kernels()
        want = [(None, 0, 0, queries, rows) for _, _, _, queries, rows in want]
    else:
        kernels(PLAIN, GQA, MLA, SPARSE)
    cfg = preset()
    # as ``init_serving``: the tile off the plans at the widths of single
    # blocks, the plans at the widths of that tile
    run_blocks, widths, plans = cfg.paged_layout(block, MB, chunk, jnp.bfloat16)
    assert run_blocks == max(1, *(run for _, _, run, _, _ in want))
    assert widths == table_widths(cfg.page_groups, MB, chunk, block, run_blocks)
    assert [tuple(plan[:5]) for plan in plans] == want


@pytest.mark.parametrize("family,runs", [
    ("gpt2", 0), ("olmoe", 4), ("smallthinker", 4), ("mistral", 32),
    ("minicpm", 0), ("zaya", 4)])
def test_allocator_flags_and_copy_read_one_field_of_one_plan(
        kernels, small_tiles, monkeypatch, family, runs):
    """What the allocator lays together, what ``paged_tile_runs`` flags and
    what a kernel's call fetches with one copy are ``run_pages`` of the plans
    the model gives for the engine's own tables; the step's stats are fields
    of group 0's."""
    kernels(PLAIN, GQA, MLA, SPARSE)
    seen = {"flags": set(), "copies": set(), "plans": set()}
    flags, gqa_call, mla_call = da.paged_tile_runs, da._paged_gqa_call, da._paged_mla_call

    def paged_tile_runs(tables, pages, G):
        seen["flags"].add(G)
        return flags(tables, pages, G)

    def _paged_gqa_call(q, k, v, layer, tables, lengths, plan, tile_runs=None):
        seen["plans"].add(plan)
        seen["copies"].add(plan.run_pages if tile_runs is not None else 0)
        return gqa_call(q, k, v, layer, tables, lengths, plan, tile_runs)

    def _paged_mla_call(q, arena, layer, tables, tile_runs, lengths, scale, R, G):
        seen["copies"].add(G)
        return mla_call(q, arena, layer, tables, tile_runs, lengths, scale, R, G)

    for fn in (paged_tile_runs, _paged_gqa_call, _paged_mla_call):
        monkeypatch.setattr(da, fn.__name__, fn)
    model, params = built(family)
    eng = engine(model, params)
    try:
        plans = model.cfg.paged_plans(BS, eng.alloc.widths, SERVING["prefill_chunk"],
                                      eng._k_pages.dtype)
        # every group of a model the same tile: they share their lanes
        assert [plan.run_pages for plan in plans] == [runs] * len(plans)
        assert all(w % max(1, runs) == 0 for w in eng.alloc.widths)
        assert eng.alloc.run_blocks == max(1, runs)
        assert eng.paged_tile_pages == plans[0].tile_pages > 0
        assert eng.chunk_queries_per_row == plans[0].chunk_queries
        assert eng.attention_rows == (
            SERVING["max_batch_size"] + SERVING["prefill_chunk"]
            // plans[0].chunk_queries) * plans[0].rows_a_token
        eng.submit(_ids(13, seed=3), max_new_tokens=2).result()
    finally:
        eng.close()
    assert seen["flags"] == {plan.run_pages for plan in plans}
    # (``paged_attention``, D = 64's, copies page by page and takes no plan)
    assert seen["copies"] == {plan.run_pages for plan in plans if plan.kernel != PLAIN}
    assert seen["plans"] <= set(plans)
