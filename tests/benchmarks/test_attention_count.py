"""The ONE count of what attention reads (PR 68): ``lib/arith_window.py`` under
``kinds/serve_backlog_resident.py:attention_counters``, and every kind that
calls the resident kind for one stack.  A chunk of ``n`` queries asks each
page once, a row without a request asks nothing, a window of decode rows
reads what ``arith_window.rows`` always read, and the count is the
algorithm's least: never more than the kernel's packed rows read."""

import functools
import importlib
import types

import numpy as np
import pytest

from benchmarks.lib import arith_mla, arith_window, cells
from benchmarks.readers import paged_mla

BENCH = cells.load_benchmark()
# every cell whose kind calls the resident kind, and those of them whose pages
# are K and V pages over ``arith_window`` (window -> layers read from the model)
RESIDENT = [w["name"] for w in BENCH["workloads"]
            if cells.load_json(f"{cells.BENCH_DIR}/traffic/{w['traffic']}.json")["kind"]
            .startswith("serve-backlog-resident")]
PAGED = [c for c in RESIDENT if c.split(".")[0] in (
    "smallthinker-21b-a3b", "mistral-small-4-119b", "zaya1-8b", "olmo-hybrid-7b",
    "trinity-large-preview", "jamba2-3b", "qwen3-next-80b-a3b")]
# what a cell's K/V pages are left under, where ``paged_gqa_bytes`` is more
PAGES_KEY = {"olmo-hybrid-7b": "full_pages_bytes", "jamba2-3b": "full_pages_bytes"}


@functools.lru_cache(maxsize=None)
def served(cell_name):
    """What ``attention_counters`` reads of a ``Serving``, at the cell's own
    sizes, and the kind's module."""
    from benchmarks.lib.build import model_from
    from deepspeed_tpu.serving.config import DeepSpeedServingConfig

    cell = cells.Cell(cell_name)
    model, serving = model_from(cell.config), cell.config["serve"]["serving"]
    srv = types.SimpleNamespace(
        cell=cell, model=model, slots=serving["max_batch_size"],
        chunk=serving["prefill_chunk"],
        block=serving.get("block_size", DeepSpeedServingConfig().block_size),
        lanes=model.cfg.kv_heads * model.cfg.head_dim,
        params={"wte": np.zeros(1, np.float16)})
    from benchmarks.kinds import serve_backlog_resident as resident
    counters = getattr(srv.cell.kind, "attention_counters", resident.attention_counters)
    return srv, importlib.import_module(counters.__module__)


def layers_by_window(mcfg):
    out = {}
    for kind in mcfg.pattern:
        out[kind.window] = out.get(kind.window, 0) + mcfg.n_layer // len(mcfg.pattern)
    return out


def full_layers(srv):
    """window -> layers whose pages the cell's count reads."""
    kw = srv.cell.config["model"]["kwargs"]
    if "layer_types" in kw:
        return {None: kw["layer_types"].count("full_attention")}
    if "attn_layer_period" in kw:
        from benchmarks.lib import arith_jamba
        return {None: arith_jamba.layer_kinds(kw).count("full")}
    return layers_by_window(srv.model.cfg)


def one_chunk(srv, first):
    """Snapshots between which ONE request runs one whole chunk from
    ``first``, and the steps that ran it."""
    plen = first + 2 * srv.chunk
    return ({"before": {7: (plen, first, 0)}, "after": {7: (plen, first + srv.chunk, 0)}},
            [(0.0, 0.1, 0, srv.chunk, 0, 0, 0)])


def decoded(srv, first, n):
    """Snapshots between which ONE request decodes ``n`` tokens whose rows
    sit at ``first .. first + n - 1``, a step a token."""
    return ({"before": {7: (16, first, 5)}, "after": {7: (16, first + n, 5 + n)}},
            [(0.1 * i, 0.1 * i + 0.1, 1, 0, 0, 0, 0) for i in range(n)])


def depth(srv):
    """A position deep in a prompt the cell's positions hold."""
    return min(8 * srv.chunk, srv.model.cfg.n_positions - 3 * srv.chunk)


@pytest.mark.parametrize("cell", RESIDENT)
def test_a_chunk_asks_each_page_once_not_once_a_token(cell):
    """The bytes of one chunk deep in a prompt are far under those of the
    same positions as single-query rows, whatever the stack caches; its
    operations are the same (every query multiplies the keys it sees)."""
    srv, kind = served(cell)
    first = depth(srv)
    chunk = kind.attention_counters(srv, *one_chunk(srv, first))
    rows = kind.attention_counters(srv, *decoded(srv, first, srv.chunk))
    assert (chunk["attention_rows_live"], chunk["attention_chunks"]) == (srv.chunk, 1)
    assert (rows["attention_rows_live"], rows["attention_chunks"]) == (srv.chunk, 0)
    assert chunk["paged_gqa_bytes"] * 4 < rows["paged_gqa_bytes"]
    assert chunk["paged_gqa_flops"] == rows["paged_gqa_flops"]


@pytest.mark.parametrize("cell", PAGED)
def test_a_chunks_pages_are_one_call_of_chunk_rows(cell):
    """Where the cache is K and V pages the chunk's bytes are ONE
    ``arith_window.chunk_rows`` a layer: the span of pages once."""
    srv, kind = served(cell)
    mcfg, first = srv.model.cfg, depth(srv)
    c = kind.attention_counters(srv, *one_chunk(srv, first))
    want = sum(n * arith_window.chunk_rows(first, srv.chunk, srv.block, srv.lanes,
                                            mcfg.n_head, mcfg.head_dim, window)[1]
               for window, n in full_layers(srv).items())
    assert c[PAGES_KEY.get(cell.split(".")[0], "paged_gqa_bytes")] == want
    span = (first + srv.chunk - 1) // srv.block + 1          # a full layer's pages
    assert arith_window.chunk_keys(first, srv.chunk, srv.block)[0] == span * srv.block


@pytest.mark.parametrize("cell", RESIDENT)
def test_a_row_without_a_request_asks_nothing(cell):
    """More programs over the same live rows (more idle rows) cost the same;
    no live row at all costs nothing."""
    srv, kind = served(cell)
    snaps, steps = decoded(srv, depth(srv), 3)
    few = kind.attention_counters(srv, snaps, steps)
    many = kind.attention_counters(srv, snaps, steps * 7)
    assert many["attention_rows_idle"] > few["attention_rows_idle"] > 0
    for key in ("paged_gqa_flops", "paged_gqa_bytes"):
        assert many[key] == few[key] > 0
    still = {"before": snaps["before"], "after": snaps["before"]}
    none = kind.attention_counters(srv, still, steps)
    assert (none["paged_gqa_flops"], none["paged_gqa_bytes"], none["attention_rows_live"]) == (0, 0, 0)
    assert none["attention_rows_idle"] == len(steps) * (srv.slots + srv.chunk)


@pytest.mark.parametrize("cell", PAGED)
def test_a_decode_only_window_reads_what_rows_always_read(cell):
    """No prompt in the window: the pages are ``arith_window.rows`` of the
    live positions a layer, as before PR 68, less only the idle rows' trash
    pages."""
    srv, kind = served(cell)
    mcfg, first = srv.model.cfg, depth(srv)
    snaps = {"before": {1: (16, first, 5), 2: (40, 900, 11)},
             "after": {1: (16, first + 4, 9), 2: (40, 903, 14)}}
    steps = [(0.0, 0.1, 2, 0, 0, 0, 0)] * 4
    c = kind.attention_counters(srv, snaps, steps)
    positions = np.asarray([*range(first, first + 4), 900, 901, 902])
    flops = nbytes = 0
    for window, n in full_layers(srv).items():
        f, b = arith_window.rows(positions, srv.block, srv.lanes, mcfg.n_head,
                                 mcfg.head_dim, window)
        flops, nbytes = flops + n * f, nbytes + n * b
    assert c[PAGES_KEY.get(cell.split(".")[0], "paged_gqa_bytes")] == nbytes
    if cell.split(".")[0] not in PAGES_KEY and "delta_flops" not in c:
        assert c["paged_gqa_flops"] == flops
    assert c["attention_rows_live"] == 7 and c["attention_chunks"] == 0


def packed_rows_keys(first, n, queries, block, window):
    """Keys the kernel reads of one layer when it packs ``queries`` of the
    chunk's consecutive queries a row: each packed row the pages from the
    oldest its first query sees to the one that holds its last query's key."""
    keys = 0
    for start in range(first, first + n, queries):
        last = min(start + queries, first + n) - 1
        oldest = 0 if window is None else max(start - window + 1, 0) // block
        keys += (last // block + 1 - oldest) * block
    return keys


@pytest.mark.parametrize("queries", [1, 16, 32])
@pytest.mark.parametrize("cell", PAGED)
def test_the_least_is_never_over_what_packed_rows_read(cell, queries):
    """For every window the cell's layers have and a chunk at the prompt's
    start, deep in it and ragged at its end: the count's key reads are at
    most ``pages x ceil(n / Sq)``, so no packing reads over 100%."""
    srv, _ = served(cell)
    for window in full_layers(srv):
        for first, n in ((0, srv.chunk), (depth(srv), srv.chunk), (depth(srv) + 5, 37)):
            least = arith_window.chunk_keys(first, n, srv.block, window)[0]
            packed = packed_rows_keys(first, n, queries, srv.block, window)
            assert least <= packed
            assert packed <= -(-n // queries) * ((first + n - 1) // srv.block + 1) * srv.block
    # one packed row over the whole chunk reads exactly the least
    assert arith_window.chunk_keys(depth(srv), srv.chunk, srv.block)[0] == packed_rows_keys(
        depth(srv), srv.chunk, srv.chunk, srv.block, None)


def test_keys_and_attention_are_one_count():
    """``attention`` is ``keys`` at K and V of ``lanes`` each, and a chunk's
    reads are its span where its products are every query's own pages."""
    decode, chunks = np.asarray([5, 40, 9000]), [(8192, 512), (0, 7)]
    layers = {4096: 6, None: 2}
    read, products = arith_window.keys(decode, chunks, layers, 16)
    flops, nbytes = arith_window.attention(decode, chunks, layers, 16, 1024, 48, 128)
    rows = (3 + 519) * 8
    assert flops == 4 * products * 48 * 128
    assert nbytes == (2 * read * 1024 + 2 * rows * 48 * 128) * 2
    assert products > read                       # a chunk multiplies more than it reads
    only_decode = arith_window.keys(decode, [], layers, 16)
    assert only_decode[0] == only_decode[1]      # a decode row reads what it multiplies
    assert arith_window.keys(np.zeros(0, np.int64), [], layers, 16) == (0, 0)


def test_latent_attention_bytes_follow_reads_and_operations_follow_pairs():
    """``arith_mla.latent_attention``: the bytes move with the (chunk, key)
    reads and the live rows alone, the operations with the (query, key)
    pairs alone."""
    flops, nbytes = arith_mla.latent_attention(1000, 50_000, 30, 32, 256, 64)
    assert flops == 2 * 32 * (320 + 256) * 50_000
    assert nbytes == (1000 * 320 + 30 * 32 * (320 + 256)) * 2
    more_pairs = arith_mla.latent_attention(1000, 90_000, 30, 32, 256, 64)
    assert more_pairs[1] == nbytes and more_pairs[0] > flops
    more_reads = arith_mla.latent_attention(2000, 50_000, 30, 32, 256, 64)
    assert more_reads[0] == flops and more_reads[1] == nbytes + 1000 * 320 * 2


@pytest.mark.parametrize("cell", [c for c in RESIDENT if c.split(".")[0] in (
    "mistral-small-4-119b", "xing4.0-29b-a4b")])
def test_the_latent_reader_takes_a_chunks_keys_once(cell):
    """``readers/paged_mla.py:work`` over the kind's own counters: one chunk
    deep in a prompt costs the bytes of its sequence's vectors ONCE (to the
    page or to the key) and the operations of every (query, key) pair."""
    srv, kind = served(cell)
    cfg, first = srv.cell.config, depth(srv)
    c = kind.attention_counters(srv, *one_chunk(srv, first))
    run = {"counters": c, "cell": srv.cell}
    flops, nbytes = paged_mla.work(run)
    L, H = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    vector, latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"], cfg["kv_lora_rank"]
    keys = first + srv.chunk
    assert L * keys <= c["attention_keys_read"] <= L * (keys + srv.block)
    pairs = sum(range(first + 1, first + srv.chunk + 1))
    assert L * pairs <= c["attention_key_products"] <= L * (pairs + srv.chunk * srv.block)
    assert nbytes == (c["attention_keys_read"] * vector
                      + L * srv.chunk * H * (vector + latent)) * 2
    assert flops == 2 * H * (vector + latent) * c["attention_key_products"]
    assert paged_mla.work({"counters": {}, "cell": srv.cell}) is None


@pytest.mark.parametrize("cell", RESIDENT)
def test_every_kind_walks_the_snapshots_with_the_one_function(cell):
    """No kind carries a walk of its own: its module's source names
    ``rows_between`` (or IS the resident kind's count)."""
    from benchmarks.kinds import serve_backlog_resident as resident
    _, kind = served(cell)
    source = open(kind.__file__).read()
    assert "rows_between(srv, snaps)" in source
    assert "snaps[\"after\"].items()" not in source or kind is resident
