"""Ahead-of-time compiles for the chip: what the v5e compiler accepts.

The TPU compiler is installed here and compiles for a chip that is described
and not attached (``/opt/skills/guides/on-chip-measurement`` §2 step 3).
Interpret mode cannot see what it refuses — a DMA slice not aligned to the
tiling, too much VMEM — so every Pallas kernel the GPT-2 train and serve
paths select is compiled here at gpt2 (12 heads) and gpt2-xl (25 heads)
shapes, D=64, bf16.  Nothing runs: a compile that passes is not a chip run
(``chip_smoke.py`` is).  The test steers ``ops.pallas`` to the chip itself;
the program has no switch for it.
"""

import dataclasses
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.ops import pallas
from deepspeed_tpu.ops.pallas import cross_entropy as ce
from deepspeed_tpu.ops.pallas import decode_attention as da
from deepspeed_tpu.ops.pallas import flash_attention as fa
from deepspeed_tpu.ops.pallas import fused_optim as fo
from tests.unit.serving_helpers import compiled_text

BF16 = jnp.bfloat16
# (heads, n_embd); D = 64 and vocab 50304 (50257 padded) for both
WIDTHS = {"gpt2": (12, 768), "gpt2-xl": (25, 1600)}
V, D, S, B = 50304, 64, 1024, 8
CHUNK, MAX_BLOCKS = 64, 64          # serving prefill_chunk, table width


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip; the persistent compile cache stays off
    around these compiles (an entry written without a chip cannot be read
    back, and the next compile would warn)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # nothing is attached, only described: several test workers may load
    # the compiler at once (libtpu otherwise keeps one process per host)
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def on_the_chip(monkeypatch):
    monkeypatch.setattr(pallas, "platform", lambda: "tpu")
    monkeypatch.setattr(pallas, "interpret", lambda: False)


def _compile(fn, *args, donate=()):
    """The ahead-of-time compile of this file's kernels and functions:
    ``args`` are shapes on the described chip (a whole serve step compiles in
    ``_step_program``)."""
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


def _compiled_text(chip, fn, *shapes):
    return _compile(fn, *(jax.ShapeDtypeStruct(s, dt, sharding=chip)
                          for s, dt in shapes)).as_text()


def _step_program(chip, cfg, slots, chunk, BS, blocks, MB, counts=False, donate=False):
    """The whole serve step of ``cfg`` compiled for the chip, as
    ``init_serving`` builds it (``tools/stack_copies.py:step_program``, which
    says what the arguments are) -> (compiled, arena's K, aux)."""
    from tools.stack_copies import step_program
    return step_program(chip, cfg, slots, chunk, BS, blocks, MB, counts, donate)[:3]


def _flash_fwd(H, E):
    qkv = ((B, S, H, D), BF16)
    return (lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
            qkv, qkv, qkv)


def _flash_bwd(H, E):
    qkv = ((B, S, H, D), BF16)
    loss = lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv


def _fused_ce(H, E, vocab=V):
    """The forward and the backward kernel at the train cell's rows (micro
    8 x 1024), on the tile ``ce_blocks`` takes for the width."""
    N = B * S
    real = 50257 if vocab == V else vocab
    loss = lambda x, head, lab: ce.fused_cross_entropy(x, head, lab, real)
    return (jax.value_and_grad(loss, argnums=(0, 1)),
            ((N, E), BF16), ((vocab, E), BF16), ((N,), jnp.int32))


def _fused_adam(H, E):
    """The leaf shapes of the model: embedding, an MLP matrix, a bias."""
    def step(*leaves):
        scal = jnp.ones((5,), jnp.float32)
        return [fo.fused_leaf_update(p, p, p, p, scal, b1=0.9, b2=0.999,
                                     eps=1e-8, wd=0.01) for p in leaves]
    f32 = jnp.float32
    return step, ((50257, E), f32), ((E, 4 * E), f32), ((E,), f32)


def _decode(Sq):
    def case(H, E):
        cache = ((B, S, H * D), BF16)
        return (da.decode_attention, ((B, Sq, H, D), BF16), cache, cache,
                ((), jnp.int32))
    return case


def _paged(Sq, BS):
    def case(H, E):
        pages = ((512, BS, H * D), BF16)
        return (da.paged_attention, ((B, Sq, H, D), BF16), pages, pages,
                ((B, MAX_BLOCKS), jnp.int32), ((B,), jnp.int32))
    return case


TRAIN = {"flash_fwd": _flash_fwd, "flash_bwd": _flash_bwd,
         "fused_ce": _fused_ce, "fused_adam": _fused_adam}
# case, and the block the kernel would DMA (bk = 128 cache rows / one page)
SERVE = {"decode_sq1": (_decode(1), 128),
         "decode_chunk": (_decode(CHUNK), 128),
         "paged_sq1_bs16": (_paged(1, 16), 16),
         "paged_chunk_bs16": (_paged(CHUNK, 16), 16),
         "paged_sq1_bs32": (_paged(1, 32), 32),
         "paged_chunk_bs32": (_paged(CHUNK, 32), 32)}


@pytest.mark.parametrize("width", list(WIDTHS))
@pytest.mark.parametrize("kernel", list(TRAIN))
def test_train_kernel_compiles(chip, kernel, width):
    fn, *shapes = TRAIN[kernel](*WIDTHS[width])
    assert "tpu_custom_call" in _compiled_text(chip, fn, *shapes)
    assert not fa._FALLBACK_WARNED


# what reaches the three flash kernels besides the two train cells' call:
# (S, H, Hkv, D, causal, bias, alibi)
FLASH_VARIANTS = {
    "bert_not_causal": (512, 12, 12, 64, False, False, False),
    "gqa_d128": (1024, 8, 2, 128, True, False, False),
    "alibi": (1024, 12, 12, 64, True, False, True),
    "dense_bias_d128": (2048, 4, 4, 128, True, True, False),
    "s_no_multiple_of_128": (320, 4, 4, 64, True, False, False),
    "s_under_a_lane_tile": (64, 4, 4, 64, True, False, False),
}


@pytest.mark.parametrize("variant", list(FLASH_VARIANTS))
def test_flash_variant_compiles(chip, variant):
    """Forward and backward through ``flash_attention`` with what the train
    cells do not pass: the scores lie keys-major in all three kernels (a
    query block is a tile's LANE dim, statistics are rows, products
    transposed), and interpret mode cannot see a layout Mosaic refuses."""
    S_, H, Hkv, D_, causal, bias, alibi = FLASH_VARIANTS[variant]
    slopes = jnp.arange(1, H + 1, dtype=jnp.float32) / H if alibi else None

    def loss(q, k, v, *b):
        return fa.flash_attention(q, k, v, causal=causal, alibi=slopes,
                                  bias=b[0] if bias else None
                                  ).astype(jnp.float32).sum()

    shapes = [((2, S_, H, D_), BF16)] + [((2, S_, Hkv, D_), BF16)] * 2
    if bias:
        shapes.append(((1, H, S_, S_), jnp.float32))
    text = _compiled_text(chip, jax.grad(loss, argnums=(0, 1, 2)), *shapes)
    assert text.count("tpu_custom_call") >= 3
    assert not fa._FALLBACK_WARNED


@pytest.mark.parametrize("E,vocab,blocks", [
    (2048, V, (512, 384)),        # OLMoE's width: fewer rows, two row sweeps
    (768, 65536, (256, 2048)),    # a power-of-two vocab: 2,048 columns
])
def test_fused_ce_compiles_on_its_chosen_tile(chip, E, vocab, blocks):
    """The tile is chosen from the shape (``ce_blocks``) and so are the rows
    whose dx the backward holds (``ce_row_sweeps``), their VMEM reckoned by
    hand: the chip's compiler has to agree beyond the two widths of
    ``test_train_kernel_compiles`` ((1024, 384) at both; one sweep of 24 MiB
    at 768, two at 1,600), wherever a GPT-family loss could bring the
    kernels on one device."""
    assert ce.ce_blocks(B * S, E, vocab, BF16) == blocks
    fn, *shapes = _fused_ce(None, E, vocab)
    text = _compiled_text(chip, fn, *shapes)
    assert "ce_fwd" in text and "ce_bwd" in text


# gpt2-large's 20 heads are 1280 lanes, the widest GPT-2 the gate admits
SERVE_WIDTHS = {**WIDTHS, "gpt2-large": (20, 1280)}


@pytest.mark.parametrize("width", list(SERVE_WIDTHS))
@pytest.mark.parametrize("kernel", list(SERVE))
def test_serve_kernel_compiles_or_gate_says_einsum(chip, kernel, width):
    """Through the public dispatch, as the model calls it: where
    ``kernel_shape_ok`` admits the shape the program holds the kernel and
    the chip's compiler accepts it; where it does not (gpt2-xl: 25 heads of
    64 are 1600 lanes, not a multiple of 128) the program is the einsum.
    The paged cases are the tiled kernel (``paged_tile_pages`` pages a
    tile, two tile buffers an operand)."""
    H, E = SERVE_WIDTHS[width]
    case, block = SERVE[kernel]
    fn, *shapes = case(H, E)
    has_kernel = "tpu_custom_call" in _compiled_text(chip, fn, *shapes)
    assert has_kernel == da.kernel_shape_ok(H, H, D, block, BF16)
    assert has_kernel == (width != "gpt2-xl")
    if kernel.startswith("paged"):
        assert da.softmax_plan(
            H, H, D, block, MAX_BLOCKS, 0, BF16, name="paged_attention"
        ).tile_pages == (128 // block if has_kernel else 0)


@pytest.mark.parametrize("rows,Sq", [(128, 1), (1, CHUNK)])
def test_paged_kernel_compiles_at_olmoe_heads(chip, rows, Sq):
    """OLMoE-1B-7B's attention: 16 heads of D=128 (one head a lane slice,
    where GPT-2's D=64 puts two in one), 2048 lanes, a table of 256 blocks
    for 4096 positions, 128 decode rows or one prompt chunk.  On the
    admitted side of the gate: the program holds the tiled kernel (8 pages
    a tile: 4 x 8 x 16 x 4096 B are 2 of its 4 MiB) and the chip's compiler
    accepts it."""
    H, D128, BS, MB = 16, 128, 16, 256
    assert da.kernel_shape_ok(H, H, D128, BS, BF16)
    pages = ((4097, BS, H * D128), BF16)
    text = _compiled_text(chip, da.paged_attention, ((rows, Sq, H, D128), BF16),
                          pages, pages, ((rows, MB), jnp.int32), ((rows,), jnp.int32))
    assert "tpu_custom_call" in text
    assert da.softmax_plan(H, H, D128, BS, MB, 0, BF16,
                           name="paged_attention").tile_pages == 8


def _kernel_rows(text, kernel):
    """Rows (the grid) of every call of ``kernel`` in a compiled program."""
    return sorted(int(rows) for rows in re.findall(
        rf"%{kernel}[.\d]* = \w+\[(\d+),[^\n]*tpu_custom_call", text))


@pytest.mark.parametrize("slots,H,head_dim,MB,kernel", [
    (256, 12, 64, 64, "paged_attention"), (128, 16, 128, 256, "paged_gqa_attention")],
                         ids=["gpt2-124m", "olmoe-1b-7b"])
def test_paged_kernel_compiles_at_a_serve_steps_rows(chip, slots, H, head_dim, MB, kernel):
    """A layer's attention in the one program of a serve step, at the
    benchmark cells' sizes: ``slots`` decode rows a query each and the prompt
    chunk's 64 tokens as ONE row of 64 queries; no call of ``slots + CHUNK``
    single-query rows.  GPT-2's heads of 64 (two a lane tile) keep the layer
    sliced out of the arena and ``paged_attention``, the flattened block
    tables (256 x 64 int32: 64 KiB) its scalar prefetch; OLMoE's heads of 128
    are whole lane tiles, a group of ONE on a K/V head each: the arena whole
    through ``paged_gqa_attention`` (a row's table of 256 blocks an SMEM
    block), and no layer of K and V sliced out."""
    rows, BS = slots + CHUNK, 16
    arena = ((2, 1025, BS, H * head_dim), BF16)
    fn = lambda *a: da.paged_layer_attention(*a, chunk=CHUNK)
    text = _compiled_text(chip, fn, ((rows, 1, H, head_dim), BF16), arena, arena,
                          ((), jnp.int32), ((rows, MB), jnp.int32), ((rows,), jnp.int32))
    plan = da.softmax_plan(H, H, head_dim, BS, MB, CHUNK, BF16)
    assert plan.chunk_queries == CHUNK
    assert _kernel_rows(text, kernel) == [1, slots]
    assert da.softmax_plan(H, H, head_dim, BS, MB, 0, BF16,
                           name="paged_attention").tile_pages == 8
    assert plan.kernel == kernel and plan.tile_pages == 8
    # D = 64 did not move: the layer's slice is still there for its kernel
    assert ("dynamic-slice" in text) == (kernel == "paged_attention")


@pytest.mark.parametrize("window,MB", [(None, 1024), (4096, 271)],
                         ids=["full", "window"])
def test_paged_gqa_kernel_compiles_at_smallthinker_heads(chip, window, MB):
    """SmallThinker-21B-A3B's attention as its serve cell runs it: 28 query
    heads on 4 K/V heads of D=128 (7 rows of one product a K/V lane slice),
    pages of 16, the arena of two-layer pages WHOLE with the layer a scalar;
    32 slots at ``Sq = 1`` and the chunk of 224 as 7 rows of 32 queries (224
    rows of one product).  A full layer's table is 1,024 blocks wide (256 of
    them are the whole 1 MiB of SMEM, which is why a step is handed its
    row's table as a block); a window layer's is the ring of ``(4096 + 224 -
    1) / 16 + 1`` blocks."""
    H, Hkv, D128, BS, slots, chunk = 28, 4, 128, 16, 32, 224
    rows = slots + chunk
    assert da.gqa_kernel_shape_ok(H, Hkv, D128, BS, BF16)
    assert not da.kernel_shape_ok(H, Hkv, D128, BS, BF16)     # the old gate: MHA only
    arena = ((2, 57344, BS, Hkv * D128), BF16)
    fn = lambda q, k, v, layer, tables, lengths: da.paged_layer_attention(
        q, k, v, layer, tables, lengths, window=window, chunk=chunk)
    text = _compiled_text(chip, fn, ((rows, 1, H, D128), BF16), arena, arena,
                          ((), jnp.int32), ((rows, MB), jnp.int32), ((rows,), jnp.int32))
    plan = da.softmax_plan(H, Hkv, D128, BS, MB, chunk, BF16, window=window)
    assert plan.chunk_queries == 32
    assert _kernel_rows(text, "paged_gqa_attention") == [chunk // 32, slots]
    assert "dynamic-slice" not in text        # no layer of K and V sliced out
    assert plan.tile_pages == 8


def test_paged_sparse_kernel_compiles_at_minicpm_sala_heads(chip):
    """MiniCPM-SALA's sparse layers as served: a row a (token, K/V head) of 16
    query heads of 128 lanes, under a table of the 128 pages it chose (every
    block up to ``dense_len``; 64 beyond), pages of 64 keys of ONE K/V head;
    16 slots and a chunk of 512 tokens are 1,056 rows."""
    g, BS, columns, rows, D128 = 16, 64, 128, (16 + 512) * 2, 128
    arena = ((4, 11264 * 2, BS, D128), BF16)
    text = _compiled_text(chip, da.paged_sparse_attention, ((rows, 1, g, D128), BF16),
                          arena, arena, ((), jnp.int32), ((rows, columns), jnp.int32),
                          ((rows,), jnp.int32))
    assert _kernel_rows(text, "paged_sparse_attention") == [rows]
    assert "dynamic-slice" not in text        # no layer of K and V sliced out
    # eight pages of 16 KiB a tile, where 128 rows would be two
    assert da.chosen_plan(2, g, D128, BS, columns, BF16).tile_pages == 8
    assert da.paged_tile_pages(16, 1024, 1, 4 * D128, BF16) == 8      # SmallThinker's, as it was


@pytest.mark.parametrize("n,tables", [(512, 1), (16, 16)], ids=["chunk", "decode_rows"])
def test_sparse_block_scores_compiles_at_minicpm_sala_heads(chip, n, tables):
    """MiniCPM-SALA's selection as served: the chunk's 512 queries under one
    table and the 16 decode rows under their own, 16 query heads of 128 lanes
    on each of 2 K/V heads over the 3,072 compressed keys of 768 pages; the
    kernel, then a bisection and a list by rank: no sort, and nothing as
    large as the heads' scores."""
    from deepspeed_tpu.models import gpt, hybrid
    from deepspeed_tpu.ops.pallas import sparse_select as ss
    cfg = gpt.minicpm_sala_config(mixer_types=["minicpm4"], first_layer=9, dtype=BF16)
    g, MB, D128 = 16, 768, 128
    assert hybrid.selects_on_chip(cfg, n, MB, tables == 1)
    text = _compiled_text(
        chip, lambda q, kc, at: hybrid._select_on_chip(cfg, q, kc, at, 64),
        ((n, 2, g, D128), BF16), ((tables, MB, 4 * 2 * D128), BF16), ((n,), jnp.int32))
    # ``readers/sala.py`` reads the attend kernel's time by ITS name
    assert ss.KERNEL in text and "paged_sparse_attention" not in ss.KERNEL
    assert " sort(" not in text
    assert not re.search(rf"f32\[{n},2,{g},{4 * MB}\]", text)


def test_the_hybrid_step_walks_its_runs_of_layers(chip):
    """The whole step of a MiniCPM-SALA stack of S L L S S at the published
    widths: a scan a run of one kind; in a run of sparse layers the kernel
    once for the 16 decode slots and once, under the branch a step without a
    prompt takes the other side of, for the chunk's 512 tokens (a row a
    K/V head each); and no gather of chosen keys into a dense array."""
    from deepspeed_tpu.models import gpt
    S, L = "minicpm4", "lightning-attn"
    cfg = gpt.minicpm_sala_config(mixer_types=[S, L, L, S, S], first_layer=9, dtype=BF16)
    slots, chunk = 16, 512
    rows = slots + chunk
    text = _step_program(chip, cfg, slots, chunk, 64, 1025, 768)[0].as_text()
    assert _kernel_rows(text, "paged_sparse_attention") == [
        2 * slots, 2 * slots, 2 * chunk, 2 * chunk]              # S, and S S
    assert text.count("conditional(") >= 2
    # nothing as large as a row's chosen keys (64 pages x 64 keys x 128 lanes)
    # times the rows is ever made: the selection went into the table
    assert not re.search(rf"bf16\[{2 * rows},(64|128),64,128\]", text)
    # the selection: the kernel for the decode rows and for the chunk, a layer
    # (S, and S S under one scan); no sort under its scope, and the 16 heads'
    # scores of the chunk's queries over 3,072 compressed keys never in HBM
    assert text.count("sparse_block_scores") >= 4
    assert not [l for l in text.splitlines() if " sort(" in l and "sparse_select" in l]
    assert not re.search(rf"f32\[{chunk},2,16,3072\]", text)


def test_paged_gqa_kernel_compiles_at_zaya_heads(chip):
    """ZAYA1-8B's attention as its serve cell runs it: 8 query heads on 2 K/V
    heads of D=128 (4 rows of one product a K/V lane slice), pages of 64
    tokens of 256 lanes (32 KiB), the arena of 4,000 blocks of 20 layers
    WHOLE with the layer a scalar, tables 256 blocks wide; 48 slots at ``Sq =
    1`` and the chunk of 208 as 2 rows of 104 queries (416 rows of one
    product); a tile of 4 pages, 256 keys."""
    H, Hkv, D128, BS, slots, chunk, MB = 8, 2, 128, 64, 48, 208, 256
    rows = slots + chunk
    assert da.gqa_kernel_shape_ok(H, Hkv, D128, BS, BF16)
    arena = ((20, 4000, BS, Hkv * D128), BF16)
    fn = lambda q, k, v, layer, tables, lengths: da.paged_layer_attention(
        q, k, v, layer, tables, lengths, chunk=chunk)
    text = _compiled_text(chip, fn, ((rows, 1, H, D128), BF16), arena, arena,
                          ((), jnp.int32), ((rows, MB), jnp.int32), ((rows,), jnp.int32))
    plan = da.softmax_plan(H, Hkv, D128, BS, MB, chunk, BF16)
    assert plan.chunk_queries == 104
    assert _kernel_rows(text, "paged_gqa_attention") == [chunk // 104, slots]
    assert "dynamic-slice" not in text        # no layer of K and V sliced out
    assert plan.tile_pages == 4


def _gqa_calls(text):
    return [line for line in text.splitlines()
            if re.search(r"%paged_gqa_attention[.\d]* = ", line) and "tpu_custom_call" in line]


def _operands(call):
    return call[call.index("operand_layout_constraints="):call.index("frontend_attributes=")]


@pytest.mark.parametrize("H,Hkv,BS,slots,chunk,MB,arena,attend,copy,window", [
    (8, 2, 64, 48, 208, 256, (20, 4000), 4, 8, None),    # 256 keys an update, 512 a copy
    (16, 16, 16, 128, 64, 256, (16, 4097), 8, 8, None),  # 128 keys: 1 MiB already
    (28, 4, 16, 32, 224, 1024, (2, 57344), 8, 32, None),     # 128 and 512
    (28, 4, 16, 32, 224, 320, (2, 57344), 8, 32, 4096),      # a ring of 10 runs
    (48, 8, 16, 32, 512, 2400, (2, 49152), 8, 16, None),     # 128 and 256: 1 MiB
    (48, 8, 16, 32, 512, 304, (2, 49152), 8, 16, 4096),      # a ring of 19 runs
], ids=["zaya", "olmoe", "smallthinker-full", "smallthinker-window",
        "trinity-full", "trinity-window"])
def test_paged_gqa_kernel_compiles_with_run_flags(chip, H, Hkv, BS, slots, chunk, MB,
                                                  arena, attend, copy, window):
    """Each group of each cell ``paged_gqa_attention`` serves, as its step
    calls it: beside its row's table and the next row's, their flags (a word
    a tile of ``copy`` pages) as SMEM blocks, and the arenas viewed ``[layers,
    pages * BS, lanes]`` (a bitcast: no copy of them is made for the kernel),
    so that a tile of pages that lie together is one DMA an operand; the
    attend keeps the tile of a call without flags.  A window group's ring is
    as wide as the allocator makes it, a whole number of runs, and its kernel
    reads the flag of the ring tile a logical tile lies in (a dynamic index
    into the SMEM block, where a full group's is the loop's counter)."""
    from deepspeed_tpu.serving.kv_cache import window_table_blocks
    D128, rows, (L, NB) = 128, slots + chunk, arena
    plan = da.softmax_plan(H, Hkv, D128, BS, MB, chunk, BF16, window=window)
    assert plan.tile_pages == attend
    assert plan.run_pages == copy
    assert window is None or MB == window_table_blocks(window, chunk, BS, copy)
    tiles = MB // copy
    fn = lambda q, k, v, layer, tables, lengths: da.paged_layer_attention(
        q, k, v, layer, tables, lengths, chunk=chunk, window=window,
        tile_runs=plan.tile_runs(tables, NB))
    pages = ((L, NB, BS, Hkv * D128), BF16)
    text = _compiled_text(chip, fn, ((rows, 1, H, D128), BF16), pages, pages,
                          ((), jnp.int32), ((rows, MB), jnp.int32), ((rows,), jnp.int32))
    Sq = plan.chunk_queries
    calls = _gqa_calls(text)
    assert _kernel_rows(text, "paged_gqa_attention") == sorted([chunk // Sq, slots])
    view = f"bf16[{L},{NB * BS},{Hkv * D128}]"
    for call in calls:
        operands = _operands(call)
        n = slots if f"s32[{slots},1,{MB}]" in operands else chunk // Sq
        assert operands.count(f"s32[{n},1,{MB}]") == 2
        assert operands.count(f"s32[{n},1,{tiles}]") == 2
        assert operands.count(view) == 2
    assert not re.search(rf"= {re.escape(view)}\S* copy\(", text)
    assert "dynamic-slice" not in text.replace("dynamic-slice(s32", "")


def test_the_zaya_step_reads_its_bank_and_its_pages_where_they_lie(chip):
    """The whole step of two ZAYA1-8B layers at the published widths, 48
    slots and a chunk of 208: the cca mixer's attention is the paged GQA
    kernel at two shapes; the top-1 bank's two matmuls take the STACKED
    leaves (no layer's bank sliced or copied out) at 256 assignments (two
    whole row tiles) and, under the branch a step without a prompt chunk
    takes, at the 48 decode rows alone (one tile)."""
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.ops.pallas import grouped_matmul as gm
    cfg = gpt.zaya_config(n_layer=2, dtype=BF16)
    slots, chunk, BS, NB, MB = 48, 208, 64, 257, 256
    rows = slots + chunk
    assert gm.kernel_shape_ok(rows, 2048, 4096, BF16) and gm.kernel_shape_ok(rows, 2048, 2048, BF16)
    assert gm.rows_to_whole_tiles(rows, 2048, BF16) == 0
    assert gm.rows_to_whole_tiles(slots, 2048, BF16) == 80        # 48 -> 128
    compiled, _, aux = _step_program(chip, cfg, slots, chunk, BS, NB, MB, counts=True)
    assert aux["cca_state"].shape == (2, slots, 2 * 1280 + 128)
    text = compiled.as_text()
    assert _kernel_rows(text, "paged_gqa_attention") == [chunk // 104, slots]
    # each takes its rows' run flags (32 tiles of 8 pages) and the arena viewed
    # ``[layers, pages * 64, 256]``
    for call in _gqa_calls(text):
        assert _operands(call).count(",1,32]") == 2
        assert _operands(call).count(f"bf16[2,{NB * BS},256]") == 2
    calls = _bank_calls(text)
    assert len(calls) == 4 and all("bf16[2,16,2048," in call for call in calls)
    assert sorted(int(r) for r in re.findall(
        r"%grouped_matmul[.\d]* = bf16\[(\d+),", text)) == [128, 128, rows, rows]
    assert not _bank_copies(text, 16, 2048, 4096) and not _bank_copies(text, 16, 2048, 2048)
    assert text.count("conditional(") >= 1


def _bank_matmul_compiles(chip, rows, G, K, N, stacked):
    """``grouped_matmul`` compiled for the chip on a bank ``[G, K, N]``, or
    ``stacked`` on the 8 layers' ``[8, G, K, N]`` with a traced layer, which
    the program must hand the kernel WHOLE: no slice and no copy of it."""
    from deepspeed_tpu.ops.pallas import grouped_matmul as gm
    assert gm.kernel_shape_ok(rows, K, N, BF16)
    if not stacked:
        text = _compiled_text(chip, gm.grouped_matmul, ((rows, K), BF16),
                              ((G, K, N), BF16), ((G,), jnp.int32))
    else:
        text = _compiled_text(chip, gm.grouped_matmul, ((rows, K), BF16),
                              ((8, G, K, N), BF16), ((G,), jnp.int32),
                              ((), jnp.int32))
        call, = _bank_calls(text)
        assert f"bf16[8,{G},{K},{N}]" in call
        assert not _bank_copies(text, G, K, N)
    assert "tpu_custom_call" in text and "grouped_matmul" in text


def _bank_calls(text):
    """Every call of the kernel ``grouped_matmul`` in a compiled program,
    the line with its operands' shapes."""
    return re.findall(r"%grouped_matmul[.\d]* = [^\n]*tpu_custom_call[^\n]*", text)


def _bank_copies(text, G, K, N):
    """The lines of a compiled program that make an array of one layer's
    bank (a slice or a copy out of the stack, fused or not)."""
    return [line.strip()[:160] for line in text.splitlines()
            if re.search(rf"= bf16\[(1,)?{G},{K},{N}\]", line)]


@pytest.mark.parametrize("stacked", [False, True], ids=["a_layer", "stacked"])
@pytest.mark.parametrize("rows", [1024, 512])
@pytest.mark.parametrize("K,N", [(2048, 2048), (1024, 2048)])
def test_grouped_matmul_compiles_at_olmoe_bank(chip, rows, K, N, stacked):
    """The expert bank's two matmuls (gate|up ``[64, 2048, 2048]``, down
    ``[64, 1024, 2048]``) at the serve cell's 1,024 (128 rows x top 8) and
    512 (a prompt chunk) assignments: the program holds the kernel and the
    chip's compiler accepts its blocks and its VMEM."""
    _bank_matmul_compiles(chip, rows, 64, K, N, stacked)


@pytest.mark.parametrize("stacked", [False, True], ids=["a_layer", "stacked"])
@pytest.mark.parametrize("K,N", [(2560, 1536), (768, 2560)])
def test_grouped_matmul_compiles_at_smallthinker_bank(chip, K, N, stacked):
    """SmallThinker's bank (gate|up ``[64, 2560, 1536]``, down ``[64, 768,
    2560]``) at the serve cell's 256 rows x top 6 = 1,536 assignments,
    twelve whole row tiles."""
    from deepspeed_tpu.ops.pallas import grouped_matmul as gm
    assert gm.rows_to_whole_tiles(1536, K, BF16) == 0
    assert gm.rows_to_whole_tiles(250 * 6, K, BF16) == 36     # 1,500 -> 1,536
    _bank_matmul_compiles(chip, 1536, 64, K, N, stacked)


def test_generate_keeps_its_cache_zero_filled(chip):
    """``generate()`` builds its KV cache inside the program.  The TPU
    compiler turned those zeros into an uninitialised ``AllocateBuffer``
    (it takes the layer loop's partial dynamic-update-slice for a full
    overwrite), and attention multiplied the never-written rows' garbage by
    its zero probabilities: on the chip, token 0 everywhere once the garbage
    held a NaN.  ``gpt_generate`` keeps the zeros behind an optimization
    barrier; no buffer of the cache's shape may come from AllocateBuffer."""
    from deepspeed_tpu.models.gpt import GPT, gpt_config
    cfg = gpt_config("gpt2", n_layer=2)
    model = GPT(cfg)
    prompt, new = 150, 16                # T=166: rows 150.. stay unwritten
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=chip),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    ids = jax.ShapeDtypeStruct((1, prompt), jnp.int32, sharding=chip)
    text = _compile(lambda p, i: model.generate(p, i, new), params, ids).as_text()
    cache = f"bf16[{cfg.n_layer},1,{prompt + new},{cfg.n_embd}]"
    assert cache in text
    assert not [line for line in text.splitlines()
                if "AllocateBuffer" in line and f"= {cache}" in line]


def test_paged_mla_kernel_compiles_at_mistral4_heads(chip):
    """Mistral-Small-4's latent attention as its serve cell runs it: 32
    heads the rows of ONE product against the cached vector (256 latent + 64
    rope lanes, 384 in the arena), pages of 16, the one-array arena of 50,000
    blocks WHOLE with the layer a scalar, tables 1,024 blocks wide as SMEM
    blocks; 128 slots at ``Sq = 1`` and the chunk of 384 as 24 rows of 16
    queries (512 rows of the product); a tile of 32 pages, attended 256 keys
    a step."""
    H, W, R, BS, slots, chunk, MB = 32, 384, 256, 16, 128, 384, 1024
    rows = slots + chunk
    assert da.mla_kernel_shape_ok(W, R, BS, BF16)
    assert not da.mla_kernel_shape_ok(320, R, BS, BF16)       # the cache unpadded
    fn = lambda q, arena, layer, tables, lengths: da.paged_mla_attention(
        q, arena, layer, tables, lengths, scale=128 ** -0.5, value_lanes=R,
        chunk=chunk)
    text = _compiled_text(chip, fn, ((rows, 1, H, W), BF16),
                          ((5, 50000, BS, W), BF16), ((), jnp.int32),
                          ((rows, MB), jnp.int32), ((rows,), jnp.int32))
    plan = da.latent_plan(W, R, H, BS, MB, chunk, BF16, 128 ** -0.5)
    assert plan.chunk_queries == 16
    assert _kernel_rows(text, "paged_mla_attention") == [chunk // 16, slots]
    assert "dynamic-slice" not in text        # no layer of the arena sliced out
    assert plan.tile_pages == plan.run_pages == 32


# a serve cell's model at its published widths (one period of its layers: the
# program scans them; Mistral's bank one chip's 32 of 128 experts, as served),
# its slots, chunk and positions, its kernel and the queries a row of the
# chunk holds
SERVE_CELLS = {
    "gpt2-124m": (lambda m: m.gpt_config("gpt2", n_layer=1, dtype=BF16),
                  256, 64, 1024, "paged_attention", 64),
    "olmoe-1b-7b": (lambda m: m.olmoe_config(n_layer=1, dtype=BF16),
                    128, 64, 4096, "paged_gqa_attention", 64),
    "smallthinker-21b-a3b": (lambda m: m.smallthinker_config(n_layer=4, dtype=BF16),
                             32, 224, 16384, "paged_gqa_attention", 32),
    "mistral-small-4-119b": (lambda m: m.mistral4_config(
        n_layer=1, experts_held=(0, 32), dtype=BF16),
                             128, 384, 16384, "paged_mla_attention", 16),
}


def _step_text(chip, cell, periods=2, blocks=1025):
    """(config, compiled text) of the whole step of a serve configuration
    at ``periods`` periods of its layers, over an arena of ``blocks``:
    compiled once a (cell, periods, blocks) whoever reads it
    (``serving_helpers.py:compiled_text``)."""
    from deepspeed_tpu.models import gpt
    make, slots, chunk, positions, kernel, Sq = SERVE_CELLS[cell]
    cfg = make(gpt)
    cfg = dataclasses.replace(cfg, n_layer=cfg.n_layer * periods)
    return cfg, compiled_text(chip, (cell, periods, blocks), lambda: _step_program(
        chip, cfg, slots, chunk, 16, blocks, positions // 16)[0].as_text())


@pytest.mark.parametrize("cell", list(SERVE_CELLS))
def test_the_step_program_attends_the_chunk_packed(chip, cell):
    """The whole step of each serve configuration, compiled ahead of time:
    every layer kind holds its paged kernel at TWO shapes, the decode slots a
    query a row and the prompt chunk ``Sq > 1`` queries a row, and no
    attention call runs ``slots + chunk`` rows.  (Read off the text at two
    periods of layers, which ``..._reads_the_bank_in_place`` and the run
    flags' tests compile anyway: the layer scan is a loop, so the rows a layer
    kind are the one period's.)"""
    _, slots, chunk, _, kernel, Sq = SERVE_CELLS[cell]
    cfg, text = _step_text(chip, cell)
    assert chunk % Sq == 0 and Sq > 1
    assert _kernel_rows(text, kernel) == sorted(
        [slots, chunk // Sq] * len(cfg.pattern))


def test_the_olmoe_step_reads_its_pages_where_they_lie(chip):
    """The whole step of two OLMoE layers at the published widths, 128 slots
    and a chunk of 64, over the serve cell's arena of 4,097 blocks: the
    attention is ``paged_gqa_attention`` on the arena whole, and the program
    makes NO array of one layer's K or V (0.27 GB each, copied out in every
    layer of every step until PR 43: PERF.md § 6)."""
    _, slots, chunk, _, kernel, Sq = SERVE_CELLS["olmoe-1b-7b"]
    cfg, text = _step_text(chip, "olmoe-1b-7b", blocks=4097)
    assert cfg.n_layer == 2 and cfg.n_head == cfg.kv_heads == 16 and cfg.head_dim == 128
    assert _kernel_rows(text, kernel) == [chunk // Sq, slots]
    assert not _kernel_rows(text, "paged_attention")
    assert "bf16[2,4097,16,2048]" in text           # the arena itself
    assert not re.search(r"bf16\[(1,)?4097,16,2048\]", text)


@pytest.mark.parametrize("periods", [1, 2])
def test_the_mistral_step_hands_the_latent_kernel_its_run_flags(chip, periods):
    """The whole Mistral step with the flag block: each call of
    ``paged_mla_attention`` takes, beside its row's table and the next row's
    (1,024 blocks), their flags (32 tiles of 32 pages) as SMEM blocks, and the
    arena viewed ``[layers, pages * 16, 384]`` (a bitcast: no copy of it is
    made for the kernel).  The flags are the same for every layer: the
    ``[rows, 32, 32]`` comparison that makes them stands outside the loop
    over layers."""
    _, slots, chunk, _, kernel, Sq = SERVE_CELLS["mistral-small-4-119b"]
    _, text = _step_text(chip, "mistral-small-4-119b", periods=periods)
    calls = [line for line in text.splitlines()
             if re.search(rf"%{kernel}[.\d]* = ", line) and "tpu_custom_call" in line]
    assert len(calls) == 2
    arena = f"bf16[{periods},{1025 * 16},384]"
    for line, rows in zip(sorted(calls, key=lambda l: "[128,1,1024]" in l),
                          (chunk // Sq, slots)):
        operands = line[line.index("operand_layout_constraints="):
                        line.index("frontend_attributes=")]
        assert operands.count(f"s32[{rows},1,1024]") == 2
        assert operands.count(f"s32[{rows},1,32]") == 2
        assert arena in operands
    assert not re.search(rf"= {re.escape(arena)}\S* copy\(", text)
    rows = slots + chunk
    made = [line for line in text.splitlines() if f"s32[{rows},32,32]" in line
            and " = " in line and "parameter(" not in line]
    assert made
    body = text[:text.index("ENTRY ")]
    assert not [line for line in made if line in body and " fusion(" in line]


@pytest.mark.parametrize("cell,tiles,windows,ring", [
    ("olmoe-1b-7b", 32, 0, 0), ("smallthinker-21b-a3b", 32, 3, 320)])
def test_the_step_hands_every_group_its_run_flags(chip, cell, tiles, windows, ring):
    """The whole step of the two ``gpt_paged_step`` models that
    ``paged_gqa_attention`` serves: a group's two calls (decode rows, packed
    chunk) take the flags of their rows' tables beside them and the arena
    viewed ``[layers, pages * 16, lanes]``, a window group's over its ring
    (SmallThinker: 10 runs of 32 pages) as the full group's over its table;
    no call is left that copies page by page.  The flags are made once,
    outside the loop over layers."""
    _, slots, chunk, positions, kernel, Sq = SERVE_CELLS[cell]
    cfg, text = _step_text(chip, cell)
    MB, lanes = positions // 16, cfg.kv_heads * cfg.head_dim
    groups, pages = len(cfg.pattern), 1025 * len(cfg.pattern)
    flagged = [c for c in _gqa_calls(text) if f"bf16[2,{pages * 16},{lanes}]" in _operands(c)]
    plain = [c for c in _gqa_calls(text) if f"bf16[2,{pages},16,{lanes}]" in _operands(c)]
    assert len(flagged) == 2 * groups == 2 * (1 + windows) and not plain
    full = [c for c in flagged if f",1,{MB}]" in _operands(c)]
    assert len(full) == 2
    rings = [c for c in flagged if c not in full]
    for width, flags, calls in ((MB, tiles, full), (ring, ring // 32, rings)):
        for call in calls:
            operands = _operands(call)
            n = slots if f"s32[{slots},1,{width}]" in operands else chunk // Sq
            assert operands.count(f"s32[{n},1,{width}]") == 2
            assert operands.count(f"s32[{n},1,{flags}]") == 2
    rows = slots + chunk
    made = [line for line in text.splitlines()
            if f"s32[{rows},{tiles},{MB // tiles}]" in line and " = " in line
            and "parameter(" not in line]
    assert made
    body = text[:text.index("ENTRY ")]
    assert not [line for line in made if line in body and " fusion(" in line]


@pytest.mark.parametrize("cell", list(SERVE_CELLS))
def test_the_step_program_reads_the_bank_in_place(chip, cell):
    """The whole step at TWO periods of layers (the layer scan stays a loop,
    and the bank's stack has a layer to be sliced out of): the program hands
    ``grouped_matmul`` the stacked bank ``[L, experts, K, N]`` itself, twice
    a layer kind, and makes NO array of one layer's bank (a bank sliced by
    the scan like any leaf is copied out for the Pallas call, more device
    time than its matmuls: PERF.md § 6, PR 38).  The bank stands in BOTH
    branches of the block's tail (``_rows_that_carry``: all rows, or the
    decode rows alone in a step without a chunk), attention in neither.
    GPT-2 has no bank: no ``grouped_matmul``, and the same attention rows as
    at one period."""
    _, slots, chunk, _, kernel, Sq = SERVE_CELLS[cell]
    cfg, text = _step_text(chip, cell)
    assert _kernel_rows(text, kernel) == sorted(
        [slots, chunk // Sq] * len(cfg.pattern))
    calls = _bank_calls(text)
    if not cfg.moe_num_experts:
        assert not calls and "grouped_matmul" not in text
        return
    from deepspeed_tpu.models.gpt import GPT
    leaves = jax.eval_shape(GPT(cfg).init_params, jax.random.PRNGKey(0))[
        "blocks"]["moe"]["experts"]
    assert len(calls) == 2 * 2 * len(cfg.pattern)
    for leaf in leaves.values():
        L, G, K, N = leaf.shape
        assert L == cfg.n_layer and not _bank_copies(text, G, K, N)
        assert sum(f"bf16[{L},{G},{K},{N}]" in call
                   for call in calls) == 2 * len(cfg.pattern)


# the four serve cells' attention: (chunk, rows a query, products, key lanes,
# value lanes, keys a tile) -> queries a row
CHUNK_SHAPES = {
    "gpt2-124m": ((64, 1, 12, 128, 128, 128), 64),
    "olmoe-1b-7b": ((64, 1, 16, 128, 128, 128), 64),
    "smallthinker-21b-a3b": ((224, 7, 4, 128, 128, 128), 32),
    "mistral-small-4-119b": ((384, 32, 1, 384, 256, 256), 16),
}


@pytest.mark.parametrize("cell", list(CHUNK_SHAPES))
def test_chunk_queries_divide_the_chunk_and_fit_the_budget(cell, monkeypatch):
    """``paged_chunk_queries``: the LARGEST divisor of the chunk whose row
    fits the budget (so twice the budget never gives fewer queries, and a
    budget of nothing gives one), at the four serve cells' shapes."""
    shape, want = CHUNK_SHAPES[cell]
    chunk = shape[0]
    assert da.paged_chunk_queries(*shape, BF16) == want and chunk % want == 0
    # float32 rows are twice as wide
    assert da.paged_chunk_queries(*shape, jnp.float32) <= want
    budget = da._CHUNK_VMEM_BYTES
    seen = []
    for scale in (0, 0.25, 0.5, 1, 2, 4, 64):
        monkeypatch.setattr(da, "_CHUNK_VMEM_BYTES", int(budget * scale))
        seen.append(da.paged_chunk_queries(*shape, BF16))
        assert chunk % seen[-1] == 0
    assert seen == sorted(seen) and seen[0] == 1 and seen[-1] == chunk


@pytest.mark.parametrize("stacked", [False, True], ids=["a_layer", "stacked"])
@pytest.mark.parametrize("K,N", [(4096, 4096), (2048, 4096)])
def test_grouped_matmul_compiles_at_mistral4_held_bank(chip, K, N, stacked):
    """The 32 held experts' bank (gate|up ``[32, 4096, 4096]``, down ``[32,
    2048, 4096]``) at the serve cell's 512 rows x top 4 = 2,048 assignments,
    sixteen whole row tiles, most of them in no group."""
    from deepspeed_tpu.ops.pallas import grouped_matmul as gm
    assert gm.rows_to_whole_tiles(2048, K, BF16) == 0
    _bank_matmul_compiles(chip, 2048, 32, K, N, stacked)


def test_paged_gqa_kernel_compiles_at_olmo_hybrid_heads(chip):
    """Olmo-Hybrid-7B's full layers as its serve cell runs them: 30 query
    heads on 30 K/V heads of D=128 (a group of ONE: neither a power of two
    nor OLMoE's 16), pages of 16 tokens of 3,840 lanes, the arena of four
    layers WHOLE with the layer a scalar, tables 128 blocks wide; 80 slots
    at ``Sq = 1`` and the chunk of 176 packed.  ``softmax_plan`` picks
    ``paged_gqa_attention`` as for OLMoE, and its tables grow in runs."""
    H, D128, BS, slots, chunk, MB = 30, 128, 16, 80, 176, 128
    rows = slots + chunk
    assert da.gqa_kernel_shape_ok(H, H, D128, BS, BF16)
    plan = da.softmax_plan(H, H, D128, BS, MB, chunk, BF16)
    assert plan.kernel == "paged_gqa_attention" and plan.run_pages > 0
    assert chunk % plan.chunk_queries == 0
    arena = ((4, 4096, BS, H * D128), BF16)
    fn = lambda q, k, v, layer, tables, lengths: da.paged_layer_attention(
        q, k, v, layer, tables, lengths, chunk=chunk)
    text = _compiled_text(chip, fn, ((rows, 1, H, D128), BF16), arena, arena,
                          ((), jnp.int32), ((rows, MB), jnp.int32), ((rows,), jnp.int32))
    assert _kernel_rows(text, "paged_gqa_attention") == [chunk // plan.chunk_queries, slots]
    assert "dynamic-slice" not in text        # no layer of K and V sliced out


def _state_calls(text):
    """(layers, slots) of the stacked states each call of the kernel
    ``delta_state_update`` takes and gives back."""
    return [(int(a), int(b)) for a, b in re.findall(
        r"%delta_state_update[.\d]* = \(f32\[(\d+),(\d+),96,5760\][^\n]*tpu_custom_call", text)]


def test_the_delta_state_kernel_compiles_at_olmo_hybrid_states(chip):
    """The decode rows' state update at the published widths: 80 slots of 30
    heads of ``96 x 192`` float32, kept ``[12, 80, 96, 5760]`` (no lane of it
    padding: a head's own ``[96, 192]`` tile would pad 192 lanes to 256),
    the stack WHOLE with the layer a scalar and updated in place."""
    from deepspeed_tpu.ops.pallas import delta_rule
    L, n, H, dk, dv = 12, 80, 30, 96, 192
    assert delta_rule.kernel_shape_ok(H, dk, dv, jnp.float32)
    f32, sd = jnp.float32, lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=chip)
    compiled = _compile(
        delta_rule.delta_state_update,
        sd((L, n, dk, H * dv), f32), sd((), jnp.int32), sd((n, H, dk), f32),
        sd((n, H, dk), f32), sd((n, H, dv), f32), sd((n, H), f32), sd((n, H), f32),
        sd((n,), jnp.bool_), donate=0)
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert _state_calls(text) == [(L, n)]                        # the stack, in place
    state = L * n * dk * H * dv * 4
    assert memory.alias_size_in_bytes >= state and memory.temp_size_in_bytes < 64 << 20
    # the states as they are held: not a byte of padding
    assert memory.argument_size_in_bytes - state < 16 << 20


def test_the_olmo_hybrid_step_updates_its_states_in_place(chip):
    """The whole step of one period (L L L F) of Olmo-Hybrid-7B at the
    published widths, 80 slots and a chunk of 176: the delta layers' states
    go through the kernel on the stacked array (no layer's 177 MB sliced or
    copied out), the full layer's attention is the paged GQA kernel at two
    shapes, and the chunked form with its solve sits under the branch a step
    without a prompt chunk takes the other side of."""
    from deepspeed_tpu.models import gpt
    cfg = gpt.olmo_hybrid_config(
        layer_types=3 * ["linear_attention"] + ["full_attention"], dtype=BF16)
    slots, chunk, BS, NB, MB = 80, 176, 16, 1025, 128
    compiled, _, aux = _step_program(chip, cfg, slots, chunk, BS, NB, MB, donate=True)
    assert aux["delta_state"].shape == (3, slots, 96, 5760) and aux["delta_state"].dtype == jnp.float32
    assert aux["delta_conv"].shape == (3, slots, 3, 11520)
    text = compiled.as_text()
    plan = da.softmax_plan(30, 30, 128, BS, MB, chunk, BF16)
    assert _kernel_rows(text, "paged_gqa_attention") == [chunk // plan.chunk_queries, slots]
    assert _state_calls(text) == [(3, slots)]
    assert text.count("conditional(") >= 2
    # nothing as large as a layer's states is made beside them
    assert not re.search(rf"f32\[{slots},96,5760\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_masked_chunk_attention_compiles_at_keye_vl2_heads(chip):
    """Keye-VL-2.0's prompt chunk as served: 512 queries of 32 heads of 128 on
    4 K/V heads, over a sequence's K and V of 46,080 keys (a table of 720
    pages of 64) under a mask a query: the kernel, and nothing as large as
    the dense scores."""
    from deepspeed_tpu.ops.pallas import indexed_attention as ia
    C, H, Hkv, D128, T = 512, 32, 4, 128, 720 * 64
    assert ia.kernel_shape_ok(C, D128, T, BF16) and ia.key_tile(T) == 512
    text = _compiled_text(chip, ia.masked_chunk_attention, ((C, H, D128), BF16),
                          ((T, Hkv * D128), BF16), ((T, Hkv * D128), BF16),
                          ((C, T), jnp.bool_), ((), jnp.int32))
    assert text.count(f'"{ia.KERNEL}"') >= 1 or ia.KERNEL in text
    assert not re.search(rf"f32\[\d+,\d+,{T}\]", text)


@pytest.mark.parametrize("T", [5760, 46080])
def test_masked_latent_attention_compiles_at_deepseek_v32_heads(chip, T):
    """DeepSeek-V3.2-Exp's prompt chunk as served: 512 queries of 128 heads of
    128 + 64 lanes a key and 128 a value, over the cached vectors of the
    first extent of a table of 720 pages of 64 and of the whole table, under
    a mask a query and the chunk's last position: the kernel (23 MiB of VMEM
    a grid step, which the compiler grants because the call stands alone in
    the loop over head groups: its output is updated in place), the heads'
    keys and values a group of 16 heads at a time, and nothing as large as
    the dense scores."""
    from deepspeed_tpu.ops.pallas import indexed_attention as ia
    C, H, dn, dr, dv, R, W = 512, 128, 128, 64, 128, 512, 640
    assert ia.latent_kernel_shape_ok(C, H, dn, dr, dv, T, BF16) and ia.latent_key_tile(T) == 1152
    text = _compiled_text(
        chip, lambda *a: ia.masked_latent_attention(*a, scale=0.1147),
        ((C, H, dn + dr), BF16), ((T, W), BF16), ((C, T), jnp.bool_), ((), jnp.int32),
        ((R, H, dn), BF16), ((R, H, dv), BF16))
    assert f"%{ia.LATENT_KERNEL}" in text and "tpu_custom_call" in text
    assert not re.search(rf"f32\[\d+,\d+,{T}\]", text)
    assert re.search(rf"bf16\[{T},{16 * dn}\]", text)           # a group's keys
    assert not re.search(rf"bf16\[(\d+,)?{T},{H * dn}\]", text)  # never all heads'


def _selects_in_vmem_and_sorts_nothing(text, calls):
    """A step's indexed layers, from its compiled text: at least ``calls``
    calls of the kernel ``index_select``, every one under ``index_topk``, and
    no sort under the mixer's scope."""
    from deepspeed_tpu.ops.pallas import index_select as ix
    lines = text.splitlines()
    selects = [l for l in lines if f"%{ix.KERNEL}" in l and "custom-call(" in l]
    assert len(selects) >= calls and all("index_topk" in l for l in selects)
    assert not [l for l in lines if " sort(" in l and "attn_indexed" in l]


# a decode step's rows at Keye-VL-2.0's and DeepSeek-V3.2-Exp's slots, a
# chunk's tile of queries at their 16 and 64 index heads over DeepSeek's
# extents of a table of 720 pages of 64
INDEX_SELECT_SHAPES = [(8, 46080, 2048), (12, 46080, 2048), (128, 46080, 2048),
                       (128, 5760, 2048), (32, 5760, 2048), (32, 23040, 2048),
                       (32, 46080, 2048)]


@pytest.mark.parametrize("n,T,k", INDEX_SELECT_SHAPES)
def test_index_select_compiles_at_the_served_shapes(chip, n, T, k):
    """The selection's kernel at every shape a serve cell hands it: the
    gate admits it, the mask comes out of the kernel, and nothing sorts."""
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.ops.pallas import index_select as ix
    assert hybrid.selects_in_vmem(n, T, k)
    text = _compiled_text(chip, lambda s: hybrid.chosen_tokens(s, k, 64), ((n, T), jnp.float32))
    assert f"%{ix.KERNEL}" in text and "tpu_custom_call" in text and " sort(" not in text


@pytest.mark.parametrize("n", [32, 1024], ids=["decode_rows", "chunk"])
def test_minicpm_salas_blocks_are_chosen_by_the_plain_bisection(chip, n):
    """MiniCPM-SALA's (query, K/V head) rows choose 64 of 768 blocks: rows
    too short to pay for the kernel's passes (PERF.md section 6, PR 63: 0.13
    ms against the fusions' 0.065 at 1,024 rows), so its gate sends them to
    the bisection in plain ``jax.numpy``; still no sort."""
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.ops.pallas import index_select as ix
    assert not hybrid.selects_in_vmem(n, 768, 64)
    text = _compiled_text(chip, lambda s: hybrid.chosen_tokens(s, 64, 128), ((n, 768), jnp.float32))
    assert f"%{ix.KERNEL}" not in text and " sort(" not in text


@pytest.mark.parametrize("n", [8, 12])
def test_a_decode_rows_positions_compile_without_a_sort(chip, n):
    """A decode step's rows at the two indexed cells' slots: the positions
    by rank behind the kernel, no sort, scatter or gather, and nothing as
    large as a flat list by rank (``[n, 2048, 46080]``)."""
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.ops.pallas import index_select as ix
    T, k = 46080, 2048
    text = _compiled_text(chip, lambda s: hybrid.chosen_positions(s, k), ((n, T), jnp.float32))
    assert f"%{ix.KERNEL}" in text
    assert not re.search(r" (sort|scatter|gather)\(", text)
    assert not re.search(rf"\[{n},{k},{T}\]", text)


def test_the_keye_vl2_step_selects_tokens_and_reads_its_bank_in_place(chip):
    """The whole step of two indexed layers at the published widths, 8 slots
    and a chunk of 512 under tables of 720 pages: the masked chunk attend
    under the branch a step without a prompt skips, the bank's grouped
    matmuls over the stacked leaves, NO sort in the mixer (the 8 decode rows
    and the chunk's tiles of 128 queries select through the kernel
    ``index_select``, under the scope ``index_topk``), and no layer of K, V
    or index keys sliced out of its arena."""
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.ops.pallas import indexed_attention as ia
    cfg = gpt.keye_vl2_config(n_layer=2, dtype=BF16)
    text = _step_program(chip, cfg, 8, 512, 64, 1025, 720)[0].as_text()
    assert ia.KERNEL in text and "grouped_matmul" in text
    assert text.count("conditional(") >= 4          # a chunk or none, and its extent, a body
    _selects_in_vmem_and_sorts_nothing(text, calls=2)   # decode rows, the chunk's tiles
    # a layer's pages are never copied out: [1025, 64, 512] K or V, [1025, 64, 64] index keys
    assert not re.search(r"bf16\[1025,64,(512|64)\]\S* (dynamic-slice|copy)\(", text)


def test_the_deepseek_v32_step_selects_rows_of_its_latent_cache(chip):
    """The whole step of DeepSeek-V3.2-Exp's cell at the dense layer and ONE
    expert layer, published widths with 16 of 256 experts held, 12 slots and
    a chunk of 512 under tables of 720 pages: every layer selects under the
    scope ``attn_indexed`` (the decode rows and the chunk's tiles of 32
    queries through the kernel ``index_select``: nothing sorts; the chunk in
    the branch a step without a prompt skips, in one branch of a
    ``switch`` an extent of its table, which attends through the kernel
    ``masked_latent_attention`` and writes no float32 scores of an extent's
    width), no layer runs ``paged_mla_attention``
    (its contexts pass ``index_topk``), the bank's grouped matmuls read the
    stacked leaves, and no layer of the latent cache or of the index keys is
    sliced out of its arena."""
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.ops.pallas import indexed_attention as ia
    cfg = gpt.deepseek_v32_config(n_layer=2, dense_layers=1, vocab_size=16160,
                                  experts_held=(0, 16), dtype=BF16)
    text = _step_program(chip, cfg, 12, 512, 64, 1025, 720, counts=True)[0].as_text()
    assert "grouped_matmul" in text and "paged_mla_attention" not in text
    # the chunk's attend: the kernel, in the branch of every extent
    calls = [l for l in text.splitlines() if f"%{ia.LATENT_KERNEL}" in l and "custom-call(" in l]
    assert len(calls) >= gpt.CHUNK_EXTENTS and all("index_attend" in l for l in calls)
    # (the indexer's 64 heads' scores of a tile of 32 queries are its own)
    assert not any(re.search(r"f32\[\d+,\d+,(5760|46080)\]", l)
                   for l in text.splitlines() if "index_attend" in l)
    assert text.count("conditional(") >= 4          # a chunk or none, and its extent, a body
    _selects_in_vmem_and_sorts_nothing(text, calls=1 + gpt.CHUNK_EXTENTS)
    assert "route_groups" in text and "latent_project" in text
    # a layer's pages are never copied out: [1025, 64, 640] latents, [1025, 64, 128] index keys
    assert not re.search(r"bf16\[1025,64,(640|128)\]\S* (dynamic-slice|copy)\(", text)


def test_the_trinity_step_reads_its_bank_behind_a_dense_lead(chip):
    """The whole step of Trinity-Large-Preview's cell at ONE period, the
    dense lead and three expert layers at the published widths with 16 of
    256 experts held, 32 slots and a chunk of 512, the full group's table
    2,400 columns wide: ``paged_gqa_attention`` at a group of SIX query heads
    runs the decode rows and the packed chunk in every layer, the bank's
    stack ``[3, 16, K, N]`` (the EXPERT layers alone) goes to
    ``grouped_matmul`` whole, twice an expert layer in each branch of the
    block's tail (all rows; the decode rows alone), and the program makes no
    array of one layer's bank."""
    from deepspeed_tpu.models import gpt
    slots, chunk, BS, blocks = 32, 512, 16, 1025
    cfg = gpt.trinity_config(n_layer=4, dense_layers=1, vocab_size=25024,
                             vocab_multiple=64, experts_held=(0, 16), dtype=BF16)
    # a ring of 19 runs of 16 pages: the 289 the window and a chunk want and
    # the 15 that share the run of the window's first page
    assert cfg.paged_layout(BS, 2400, chunk, BF16)[1] == (304, 304, 304, 2400)
    text = _step_program(chip, cfg, slots, chunk, BS, blocks, 2400,
                         counts=True)[0].as_text()
    Sq = da.paged_chunk_queries(chunk, 6, 8, 128, 128, 128, BF16)
    assert Sq > 1 and _kernel_rows(text, "paged_gqa_attention") == sorted(
        [slots, chunk // Sq] * 4)
    calls = _bank_calls(text)
    assert len(calls) == 2 * 2 * 3
    for K, N in ((3072, 6144), (3072, 3072)):
        assert sum(f"bf16[3,16,{K},{N}]" in call for call in calls) == 2 * 3
        assert not _bank_copies(text, 16, K, N)


def _mamba_state_calls(text):
    """(layers, slots) of the stacked states each call of the kernel
    ``mamba_state_update`` takes and gives back."""
    return [(int(a), int(b)) for a, b in re.findall(
        r"%mamba_state_update[.\d]* = \(f32\[(\d+),(\d+),16,5120\][^\n]*tpu_custom_call", text)]


def test_the_selective_scan_kernels_compile_at_jamba2_states(chip):
    """Both kernels of ``ops/pallas/selective_scan.py`` at the published
    widths: the decode rows' update of 384 slots of ``16 x 5120`` float32,
    the stack WHOLE with the layer a scalar and updated in place; the chunk's
    scan of 512 tokens, which makes nothing of ``[tokens, 5120, 16]``."""
    from deepspeed_tpu.ops.pallas import selective_scan as ss
    L, n, S, N, T = 26, 384, 16, 5120, 512
    assert ss.kernel_shape_ok(n, S, N, jnp.float32) and ss.kernel_shape_ok(T, S, N, jnp.float32)
    f32, sd = jnp.float32, lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=chip)
    compiled = _compile(
        ss.mamba_state_update,
        sd((L, n, S, N), f32), sd((), jnp.int32), sd((n, N), f32), sd((n, N), f32),
        sd((n, S), f32), sd((n, S), f32), sd((S, N), f32), sd((N,), f32),
        sd((n,), jnp.bool_), donate=0)
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert _mamba_state_calls(text) == [(L, n)]                  # the stack, in place
    state = L * n * S * N * 4
    assert memory.alias_size_in_bytes >= state and memory.temp_size_in_bytes < 64 << 20
    assert memory.argument_size_in_bytes - state < 32 << 20      # not a byte of padding
    compiled = _compile(
        ss.mamba_chunk_scan,
        sd((S, N), f32), sd((T, N), f32), sd((T, N), f32), sd((T, S), f32),
        sd((T, S), f32), sd((S, N), f32), sd((N,), f32), sd((T,), jnp.bool_))
    text = compiled.as_text()
    assert "mamba_chunk_scan" in text and "tpu_custom_call" in text
    assert not re.search(rf"f32\[{T},({N},{S}|{S},{N})\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_the_jamba2_step_scans_its_states_in_place(chip):
    """The whole step of a slice of AI21-Jamba2-3B at the published widths
    (mamba x 2, full, mamba), 384 slots and a chunk of 512: the mamba layers'
    states go through the decode kernel on the stacked array (no layer's 126
    MB sliced or copied out), the chunk's scan sits under the branch a step
    without a prompt chunk takes the other side of, the full layer's
    attention is the paged GQA kernel at a group of TWENTY query heads on one
    K/V head, and nothing of ``[tokens, 5120, 16]`` is made."""
    from deepspeed_tpu.models import gpt
    cfg = gpt.jamba_config(n_layer=4, attn_layer_period=4, attn_layer_offset=2, dtype=BF16)
    assert cfg.mixers == ("mamba", "mamba", "full", "mamba")
    slots, chunk, BS, NB, MB = 384, 512, 16, 4097, 256
    rows = slots + chunk
    compiled, kp, aux = _step_program(chip, cfg, slots, chunk, BS, NB, MB, donate=True)
    assert kp.shape == (1, NB, BS, 128)                          # ONE K/V head of 128
    assert aux["mamba_state"].shape == (3, slots, 16, 5120) and aux["mamba_state"].dtype == jnp.float32
    assert aux["mamba_conv"].shape == (3, slots, 3, 5120)
    text = compiled.as_text()
    plan = da.softmax_plan(20, 1, 128, BS, MB, chunk, BF16)
    assert plan.kernel == "paged_gqa_attention" and plan.chunk_queries > 1
    assert _kernel_rows(text, "paged_gqa_attention") == [chunk // plan.chunk_queries, slots]
    assert _mamba_state_calls(text) == 2 * [(3, slots)]          # a call a run of the walk
    assert "mamba_chunk_scan" in text and text.count("conditional(") >= 2
    # nothing as large as a layer's states is made beside them, and nothing
    # of a token's states a channel
    assert not re.search(rf"f32\[({slots}|{chunk}|{rows}),(16,5120|5120,16)\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# ---- Qwen3-Next-80B-A3B: the most and the narrowest experts, heads of 256 ------------ #
@pytest.mark.parametrize("stacked", [False, True], ids=["a_layer", "stacked"])
@pytest.mark.parametrize("rows", [5504, 384], ids=["chunk_step", "decode_rows"])
@pytest.mark.parametrize("K,N", [(2048, 1024), (512, 2048)])
def test_grouped_matmul_compiles_at_qwen3_next_held_bank(chip, rows, K, N, stacked):
    """The 256 held experts' bank (gate|up ``[256, 2048, 1024]``, down ``[256,
    512, 2048]``: the most and the narrowest experts any cell holds) at the
    serve cell's 544 rows x top 10 = 5,440 assignments (5,504 in whole row
    tiles) and at the 32 decode rows' 320 (384), half of them in no group, 1
    to 11 rows an expert: a walk of up to ``43 + 255`` visits."""
    from deepspeed_tpu.ops.pallas import grouped_matmul as gm
    assert gm.rows_to_whole_tiles(5440, K, BF16) == 64
    assert gm.rows_to_whole_tiles(320, K, BF16) == 64
    _bank_matmul_compiles(chip, rows, 256, K, N, stacked)


def _qwen3_next_state_calls(text):
    return [(int(a), int(b)) for a, b in re.findall(
        r"%delta_state_update[.\d]* = \(f32\[(\d+),(\d+),128,4096\][^\n]*tpu_custom_call", text)]


def test_the_qwen3_next_step_compiles_at_the_published_widths(chip):
    """The whole step of one period (L L L F) of Qwen3-Next-80B-A3B at the
    published widths over one chip's 256 of 512 experts, 32 slots and a chunk
    of 512 over pages of 64 tokens under tables of 720: the full layer's
    attention is the paged GQA kernel at D = 256, 16 query heads on 2 K/V
    heads (a page row of 512 lanes), at two shapes; the delta layers' states
    ``[128, 32 x 128]`` go through the kernel on the stacked array in place
    (32 VALUE heads on 16 key heads); the bank's matmuls are the grouped
    kernel over the stacked leaves of the 256 held, a call a layer's matrix
    (gate|up and down) a run of the walk; the chunked form with its solve
    sits under the branch a step without a prompt chunk takes the other side
    of."""
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.ops.pallas import delta_rule
    cfg = gpt.qwen3_next_config(
        layer_types=3 * ["linear_attention"] + ["full_attention"],
        experts_held=(0, 256), vocab_size=75968, vocab_multiple=64, dtype=BF16)
    H, Hkv, D256, BS, slots, chunk, MB, NB = 16, 2, 256, 64, 32, 512, 720, 2049
    assert da.gqa_kernel_shape_ok(H, Hkv, D256, BS, BF16)
    assert delta_rule.kernel_shape_ok(32, 128, 128, jnp.float32)
    plan = da.softmax_plan(H, Hkv, D256, BS, MB, chunk, BF16)
    assert plan.kernel == "paged_gqa_attention" and chunk % plan.chunk_queries == 0
    compiled, kp, aux = _step_program(chip, cfg, slots, chunk, BS, NB, MB,
                                      counts=True, donate=True)
    assert kp.shape == (1, NB, BS, Hkv * D256)                   # ONE layer of four pages
    assert aux["delta_state"].shape == (3, slots, 128, 4096) and aux["delta_state"].dtype == jnp.float32
    assert aux["delta_conv"].shape == (3, slots, 3, 8192)
    text = compiled.as_text()
    assert _kernel_rows(text, "paged_gqa_attention") == [chunk // plan.chunk_queries, slots]
    assert _qwen3_next_state_calls(text) == [(3, slots)]
    calls = _bank_calls(text)
    assert calls and all("bf16[3,256," in c or "bf16[1,256," in c for c in calls)
    # no layer's bank sliced or copied out of its stack (the full layer's
    # stack of ONE is a parameter as it stands)
    assert not re.search(r"= bf16\[256,(2048,1024|512,2048)\]", text)
    assert text.count("conditional(") >= 2
    assert not re.search(rf"f32\[{slots},128,4096\]", text)      # no layer's states copied
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 30


def test_paged_mla_kernel_compiles_at_xing4_heads(chip):
    """Xing4.0's latent attention as its serve cell runs it in nineteen steps
    of twenty: a prompt chunk of 512 queries of 32 heads over up to 16,896
    keys of 512 latent + 64 rope lanes (640 in the arena, pages of 64, tables
    264 blocks wide) beside 16 decode rows, the one-array arena of 4,225
    blocks WHOLE with the layer a scalar (Mistral's cell gives the kernel 384
    queries over 12,288 keys of 384 lanes in a step of fourteen)."""
    H, W, R, BS, slots, chunk, MB = 32, 640, 512, 64, 16, 512, 264
    rows = slots + chunk
    assert da.mla_kernel_shape_ok(W, R, BS, BF16)
    fn = lambda q, arena, layer, tables, lengths: da.paged_mla_attention(
        q, arena, layer, tables, lengths, scale=192 ** -0.5, value_lanes=R, chunk=chunk)
    text = _compiled_text(chip, fn, ((rows, 1, H, W), BF16),
                          ((7, 4225, BS, W), BF16), ((), jnp.int32),
                          ((rows, MB), jnp.int32), ((rows,), jnp.int32))
    plan = da.latent_plan(W, R, H, BS, MB, chunk, BF16, 192 ** -0.5)
    assert plan.chunk_queries > 1 and chunk % plan.chunk_queries == 0
    assert _kernel_rows(text, "paged_mla_attention") == sorted(
        [chunk // plan.chunk_queries, slots])
    assert "dynamic-slice" not in text        # no layer of the arena sliced out


@pytest.mark.parametrize("stacked", [False, True], ids=["a_layer", "stacked"])
@pytest.mark.parametrize("K,N", [(3584, 2048), (1024, 3584)])
def test_grouped_matmul_compiles_at_xing4_bank(chip, K, N, stacked):
    """Xing4.0's WHOLE bank (gate|up ``[64, 3584, 2048]``, down ``[64, 1024,
    3584]``) at the serve cell's 528 rows x top 4 = 2,112 assignments, 33 an
    expert, which the caller pads to seventeen whole row tiles."""
    from deepspeed_tpu.ops.pallas import grouped_matmul as gm
    assert gm.rows_to_whole_tiles(2112, K, BF16) == 64
    _bank_matmul_compiles(chip, 2176, 64, K, N, stacked)


def test_the_xing4_step_mixes_four_streams_round_every_sublayer(chip):
    """The whole step of Xing4.0's cell at the published widths, the dense
    layer and TWO expert layers with all 64 experts and the 131,072-row head,
    16 slots and a chunk of 512 under tables of 264 pages: the carry is a
    token's four streams (``bf16[528,1,4,3584]``), every sublayer reads and
    writes them under the scopes ``hc_coeff`` / ``hc_pre`` / ``hc_post`` in
    both bodies of the walk (all rows; the decode rows alone), the maps'
    product with ``phi`` is a float32 dot over the 14,336 lanes, every layer
    attends through ``paged_mla_attention`` at the decode rows and the packed
    chunk, the bank's grouped matmuls read the stacked leaves, and no layer
    of the latent cache is sliced out of its arena."""
    from deepspeed_tpu.models import gpt
    slots, chunk, BS, blocks, MB = 16, 512, 64, 1025, 264
    cfg = gpt.xing4_config(n_layer=3, dense_layers=1, dtype=BF16)
    compiled, kp, _ = _step_program(chip, cfg, slots, chunk, BS, blocks, MB,
                                    counts=True, donate=True)
    assert kp.shape == (3, blocks, BS, 640)
    text = compiled.as_text()
    assert f"bf16[{slots + chunk},1,4,3584]" in text and f"bf16[{slots},1,4,3584]" in text
    for scope in ("hc_coeff", "hc_pre", "hc_post"):
        assert scope in text, scope
    assert re.search(r"f32\[24,(528|16)\][^\n]* (convolution|dot|fusion)\(", text)
    plan = da.latent_plan(640, 512, 32, BS, MB, chunk, BF16, 192 ** -0.5)
    assert _kernel_rows(text, "paged_mla_attention") == sorted(
        [slots, chunk // plan.chunk_queries] * 2)
    calls = _bank_calls(text)
    assert len(calls) == 2 * 2 and all("bf16[2,64," in c for c in calls)
    assert not _bank_copies(text, 64, 3584, 2048) and not _bank_copies(text, 64, 1024, 3584)
    assert not re.search(r"bf16\[1025,64,640\]\S* (dynamic-slice|copy)\(", text)
    assert text.count("conditional(") >= 4
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# a latent stack's cell: its configuration at the dense lead (where it has
# one) and TWO layers of the scan, its slots, chunk, block and table
LATENT_STEPS = {
    "deepseek-v3.2-exp": (lambda m: m.deepseek_v32_config(
        n_layer=3, dense_layers=1, vocab_size=16160, experts_held=(0, 16), dtype=BF16),
                          12, 512, 64, 720, 5),
    "mistral-small-4-119b": (lambda m: m.mistral4_config(
        n_layer=2, experts_held=(0, 32), dtype=BF16), 128, 384, 16, 1024, 3),
    "xing4.0-29b-a4b": (lambda m: m.xing4_config(n_layer=3, dense_layers=1, dtype=BF16),
                        16, 512, 64, 264, 3),
}


@pytest.mark.parametrize("cell", list(LATENT_STEPS))
def test_a_latent_step_relays_no_stack_of_its_up_projections(chip, cell):
    """The whole step of each latent stack AS THE ENGINE BUILDS IT (the
    model's serving tree), compiled for the chip: no ``copy``, ``transpose``,
    slice or fusion of the program makes an array that holds a whole stacked
    projection of the latent layers (``q_b_w [L, Rq, H hd]``, ``kv_b_w [L, R,
    H (dn + dv)]``, ``index_q_w [L, Rq, heads lanes]``, ``kv_a_w``,
    ``index_kw_w``, canonical or transposed, in whatever layout), and none
    makes one LAYER of any but ``kv_b_w``: every product reads its layer of
    the stack where it lies.  (W_UK and W_UV are cut out of ``kv_b``'s layer
    for einsums over heads and for the chunk's kernel, and the layer is
    copied out for them, 34 MB of DeepSeek's: PERF.md § 7.)  On the
    canonical tree the same program held nine relays of a whole stack and a
    copy of a layer out of each of three (PERF.md § 6, PR 67)."""
    from deepspeed_tpu.models import gpt
    from tools import stack_copies as sc
    make, slots, chunk, BS, MB, relaid = LATENT_STEPS[cell]
    cfg = make(gpt)
    model = gpt.GPT(cfg)
    canonical = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    tree, bytes_relaid = jax.eval_shape(lambda p: model.serving_params(p), canonical)
    assert len(bytes_relaid) == relaid
    leaves = {name: ("bf16", stack[name].shape) for stack, names in (
        (canonical["blocks"], bytes_relaid),
        (tree["blocks"], [gpt.SERVING_LEAVES[name] for name in bytes_relaid]))
        for name in names}
    compiled, *_ = sc.step_program(chip, cfg, slots, chunk, BS, 1025, MB,
                                   counts=True, donate=True)
    moved = {(i["placed"], i["name"]) for i in sc.stack_copies(
        compiled.as_text(), leaves, ops=("copy", "transpose", "fusion", "slice",
                                         "dynamic-slice")) if i["placed"]}
    assert {leaf for (leaf, _), _ in moved} <= {"kv_b_w", "kv_b_t"}, moved
    assert {part for (_, part), _ in moved} <= {"layer"}, moved


@pytest.mark.parametrize("make", ["olmoe_config", "trinity_config"])
def test_the_serving_tree_of_a_stack_without_a_latent_is_the_callers(make):
    """No latent projection, nothing relaid: the tree the engine keeps is the
    caller's own, leaf for leaf the same arrays, so its step is the program
    it was."""
    from deepspeed_tpu.models import gpt
    kw = dict(vocab_size=256, n_embd=64, n_layer=2, n_head=4, num_experts=4, top_k=2)
    if make == "trinity_config":
        kw.update(n_layer=4, dense_layers=1, n_kv_head=2)
    model = gpt.GPT(getattr(gpt, make)(**kw))
    params = model.init_params(jax.random.PRNGKey(0))
    tree, relaid = model.serving_params(params)
    assert tree is params and relaid == {}
