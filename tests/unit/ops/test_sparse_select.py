"""A sparse layer's selection without the heads' scores in HBM and without a
sort (``models/hybrid.py:_select_on_chip``: the kernel of
``ops/pallas/sparse_select.py`` through the Pallas interpreter, then
``chosen_tokens``' bisection and the list by rank) against the plain
``jax.numpy`` form (``_select``: an einsum, a softmax, ``top_k`` and a sort):
the same ``blocks`` and ``at`` to the entry, under one table and under a table
a row, on both sides of ``dense_len``, with fewer blocks behind a query than it
may choose, with two blocks that tie at the last place, and with rows of a
chunk that carry nothing; and the shape gate."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.gpt import GPT, minicpm_sala_config
from deepspeed_tpu.ops.pallas import sparse_select as ss
from tests.unit.serving_helpers import Driver

# compressed keys of 32 every 16 and blocks (pages) of 64 as published; the
# top 8 of 128 blocks (the first and the two or three of the last 128 keys
# among them), every key up to 512
SPARSE = dict(kernel=32, stride=16, block=64, topk=8, init_blocks=1, window=128,
              dense_len=512)
BS, MB, Hkv, G, D, R = 64, 128, 2, 16, 128, 4


def config(mixer_types=("minicpm4",), **sparse):
    """The published heads (16 of 128 lanes on each of 2 K/V heads) on a tiny
    model, ``sparse`` laid over :data:`SPARSE`."""
    return minicpm_sala_config(
        vocab_size=128, n_positions=MB * BS, n_embd=64, n_head=Hkv * G, n_kv_head=Hkv,
        head_dim=D, intermediate_size=64, mixer_types=list(mixer_types),
        sparse=tuple(dict(SPARSE, **sparse).values()), dtype="float32")


CFG = config()


def _inputs(seed, positions, shared, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    n = len(positions)
    q = jnp.asarray(rng.normal(size=(n, Hkv, G, D)), dtype)
    kc = jnp.asarray(rng.normal(size=(1 if shared else n, MB, R * Hkv * D)) * 0.5, dtype)
    return q, kc, jnp.asarray(positions, jnp.int32)


def _a_straddling_key(q, kc, positions):
    """Every query the same vector and the compressed keys 4 b - 1 of a few
    blocks b along it, each less than the one before: the keys that straddle
    the borders b - 1 | b are the highest of BOTH blocks, pair by pair."""
    u = np.zeros(D, np.float32)
    u[:8] = 1.0
    q = jnp.broadcast_to(jnp.asarray(u, q.dtype), q.shape)
    pages = np.asarray(kc, np.float32).reshape(MB, R, Hkv, D) * 0.05
    for i, b in enumerate((20, 41, 63, 90, 111)):
        pages[b, 0] = (3.0 - 0.25 * i) * u
    return q, jnp.asarray(pages.reshape(kc.shape), kc.dtype), positions


# name: (positions, one table?, what is other than SPARSE)
CASES = {
    # (a) a chunk of two query tiles under one table, and rows under their own
    "a_chunk_under_one_table": (4000 + np.arange(32), True, None),
    "rows_under_their_own_tables": ([8191, 700, 5000, 2049], False, None),
    # (b) the last row at or under dense_len and the first past it
    "a_chunk_across_dense_len": (496 + np.arange(32), True, None),
    "rows_on_both_sides_of_dense_len": ([511, 512, 30, 513], False, None),
    # (c) fewer blocks behind the query than topk, every key attended
    # (dense_len 64 here: the blocks of no key are the list's last)
    "fewer_blocks_behind_than_topk": (192 + np.arange(32), True,
                                      dict(dense_len=64, topk=8)),
    "rows_with_fewer_blocks_behind_than_topk": ([65, 130, 300, 8000], False,
                                                dict(dense_len=64, topk=8)),
    # (d) two blocks score exactly alike: the first and the window's three are
    # forced, so of 9 places five are left, and the third pair that ties
    # straddles the last
    "a_straddling_key_ties_two_blocks": (8128 + np.arange(32), True, dict(topk=9)),
    # (e) the chunk's last rows are not live: position 0, and nothing read
    "a_chunk_whose_last_rows_are_not_live": (
        np.concatenate([6000 + np.arange(21), np.zeros(11, np.int64)]), True, None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_and_the_bisection_choose_what_the_reference_chooses(kernels, case):
    kernels(ss.KERNEL)
    positions, shared, planted = CASES[case]
    cfg = config(**(planted or {}))
    q, kc, at = _inputs(len(case), positions, shared)
    if "straddling" in case:
        q, kc, at = _a_straddling_key(q, kc, at)
    assert hybrid.selects_on_chip(cfg, len(positions), MB, shared)
    rows = kc.reshape(kc.shape[0], MB * R, Hkv, D)
    want_blocks, want_at = jax.jit(lambda q, kc, at: hybrid._select(cfg, q, kc, at, BS))(q, rows, at)
    got_blocks, got_at = jax.jit(
        lambda q, kc, at: hybrid._select_on_chip(cfg, q, kc, at, BS))(q, kc, at)
    np.testing.assert_array_equal(np.asarray(got_at), np.asarray(want_at))
    np.testing.assert_array_equal(np.asarray(got_blocks), np.asarray(want_blocks))
    # and the case is what its name says
    t, sp = np.asarray(positions), cfg.sparse
    blocks = np.asarray(want_blocks)
    selecting = t + 1 > sp.dense_len
    if "dense_len" in case:
        assert selecting.any() and not selecting.all()
        assert (blocks[~selecting] == np.arange(blocks.shape[-1])).all()
    if "fewer" in case:
        short = selecting & (t // BS + 1 < sp.topk)
        assert short.any()
        for i in np.flatnonzero(short):
            held = t[i] // BS + 1
            assert (blocks[i, :, :held] == np.arange(held)).all()
            assert (blocks[i, :, held:sp.topk] == MB - 1).all()
    if "straddling" in case:
        score = np.asarray(jax.jit(
            lambda q, kc, at: hybrid._block_scores(cfg, q, kc, at, BS))(q, rows, at))
        split = 0
        for i in range(len(t)):
            kth = np.sort(score[i, 0])[-sp.topk]
            tied = np.flatnonzero(score[i, 0] == kth)
            taken = np.isin(tied, blocks[i, 0, :sp.topk])
            if len(tied) == 2 and taken.sum() == 1:
                assert tied[1] == tied[0] + 1 and taken[0], "of two that tie, the lower"
                split += 1
        assert split, "no row's last place fell between two blocks that tie"
    if "not_live" in case:
        assert (blocks[t == 0] == np.arange(blocks.shape[-1])).all()


def test_the_block_scores_are_the_references(kernels):
    """The kernel's scores alone: the reference's to float32's rounding where
    they are finite, the same blocks forced and the same blocks past the
    query."""
    kernels(ss.KERNEL)
    sp = CFG.sparse
    q, kc, at = _inputs(3, 3000 + np.arange(16), True)
    want = np.asarray(hybrid._block_scores(CFG, q, kc.reshape(1, MB * R, Hkv, D), at, BS))
    got = np.asarray(jax.jit(lambda q, kc, at: ss.sparse_block_scores(
        q, kc, at, stride=sp.stride, block=BS, init_blocks=sp.init_blocks,
        window=sp.window))(q, kc, at))
    finite = np.isfinite(want)
    assert (got[~finite] == want[~finite]).all() and finite.any() and (want == np.inf).any()
    assert np.abs(got[finite] - want[finite]).max() < 1e-6 * np.abs(want[finite]).max() + 1e-9


def test_a_sparse_layer_serves_the_same_logits_either_way(kernels):
    """One sparse layer before a linear one, served a prompt in chunks of a
    query tile and then a token a step, past ``dense_len`` from the fifth
    chunk on: with the kernel, the bisection and the table read by a compare
    and a sum the step's logits are what the plain selection and a gather
    give (the same pages walked: what is left is nothing)."""
    cfg = config(("minicpm4", "lightning-attn"), dense_len=64)
    model = GPT(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    seq = np.random.default_rng(0).integers(0, 128, 700).astype(np.int32)

    def served():
        return Driver(model, params, slots=2, chunk=16, block_size=BS,
                      blocks_a_slot=MB).sequence(seq, (16,) * 43, slot=1)

    kernels()
    assert not hybrid.selects_on_chip(cfg, 16, MB, True)
    want = served()
    kernels(ss.KERNEL)
    assert hybrid.selects_on_chip(cfg, 16, MB, True) and hybrid.selects_on_chip(cfg, 2, MB, False)
    got = served()
    assert np.abs(want).max() > 0.1 and np.abs(got - want).max() < 1e-5


def test_the_tables_entries_by_a_compare_and_a_sum():
    rng = np.random.default_rng(1)
    tables = jnp.asarray(rng.integers(0, 5000, (6, MB)), jnp.int32)
    blocks = jnp.asarray(rng.integers(0, MB, (6, Hkv, 16)), jnp.int32)
    for of in (tables, tables[:1]):
        want = np.take_along_axis(np.broadcast_to(np.asarray(of), tables.shape)[:, None],
                                  np.asarray(blocks), axis=2)
        np.testing.assert_array_equal(np.asarray(hybrid._entries_at(of, blocks)), want)


def test_the_shape_gate(kernels):
    assert ss.kernel_shape_ok(512, 16, 128, 768, True)            # the served chunk
    assert ss.kernel_shape_ok(16, 16, 128, 768, False)            # and its decode rows
    assert ss.query_tile(512, True) == 16 and ss.query_tile(16, False) == 1
    assert not ss.kernel_shape_ok(8, 2, 16, 16, True)             # the tiny models' heads
    assert not ss.kernel_shape_ok(512, 16, 128, 200, True)        # blocks in no lane tile
    assert not ss.kernel_shape_ok(520, 16, 128, 768, True)        # a chunk in no query tile
    assert not ss.kernel_shape_ok(16, 4, 128, 768, False)         # a row's heads under a sublane tile
    # on the CPU the reference runs, whatever the shapes
    kernels()
    assert not hybrid.selects_on_chip(CFG, 32, MB, True)
