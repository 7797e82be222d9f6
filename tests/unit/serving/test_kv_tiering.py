"""KVTieringManager unit tests — spill/restage round trips over a real
CPU arena, budget refusal, epoch coherence (the PR 10 stale-chunk race on
the serving path), and prefetch-ring readiness."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.runtime.offload import TIER_HOST, TIER_NVME  # noqa: E402
from deepspeed_tpu.serving.kv_tiering import KVTieringManager  # noqa: E402

L, NB, BS, HD = 2, 12, 4, 6      # pages fold heads into lanes: H*D


def make_arena(seed=0):
    rng = np.random.default_rng(seed)
    kp = jnp.asarray(rng.normal(size=(L, NB, BS, HD)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(L, NB, BS, HD)).astype(np.float32))
    return kp, vp


@pytest.fixture
def mgr(tmp_path):
    m = KVTieringManager(offload_dir=str(tmp_path / "tier"),
                         spill_chunk_blocks=2, ring_depth=2)
    yield m
    m.close()


def test_spill_restage_bitwise_round_trip(mgr):
    kp, vp = make_arena()
    blocks = [3, 7, 2, 9, 5]                   # > chunk size: exercises ring
    want_k = np.asarray(kp)[:, blocks].copy()
    want_v = np.asarray(vp)[:, blocks].copy()
    tier = mgr.spill(7, blocks, kp, vp, tokens=18)
    assert tier in (TIER_HOST, TIER_NVME)
    assert mgr.is_spilled(7) and mgr.spilled_tokens(7) == 18

    # scribble over the source blocks (they get reallocated meanwhile)
    kp = kp.at[:, blocks].set(0.0)
    vp = vp.at[:, blocks].set(0.0)
    dest = [1, 4, 6, 8, 10]                    # different physical blocks
    kp, vp, info = mgr.restage(7, kp, vp, dest)
    np.testing.assert_array_equal(np.asarray(kp)[:, dest], want_k)
    np.testing.assert_array_equal(np.asarray(vp)[:, dest], want_v)
    assert info["blocks"] == 5 and info["tokens"] == 18
    assert not mgr.is_spilled(7)               # record consumed
    with pytest.raises(KeyError):
        mgr.restage(7, kp, vp, dest)


def test_prefetch_ready_then_restage_is_a_ring_hit(mgr):
    kp, vp = make_arena(1)
    mgr.spill(1, [2, 3], kp, vp, tokens=8)
    # force the bytes off the host cache so the prefetch does real work
    mgr.store.drain()
    mgr.store._host.clear()
    mgr.store._host_bytes = 0
    assert not mgr.restage_ready(1) or mgr.store.ready("kvseq/1/1")
    mgr.begin_restage(1)
    mgr.staging.drain()
    assert mgr.restage_ready(1)
    kp, vp, info = mgr.restage(1, kp, vp, [5, 6])
    assert info["ready"] is True


def test_spill_budget_refusal(tmp_path):
    m = KVTieringManager(offload_dir=str(tmp_path / "b"),
                         spill_budget_bytes=1)   # nothing fits
    try:
        kp, vp = make_arena()
        assert m.spill(1, [2], kp, vp, tokens=4) is None
        assert not m.is_spilled(1)
        assert m.stats()["kv_spills"] == 0
    finally:
        m.close()


def test_empty_spill_refused(mgr):
    kp, vp = make_arena()
    assert mgr.spill(1, [], kp, vp, tokens=0) is None


def test_epoch_coherence_no_stale_resurrection(mgr):
    """The serving mirror of the PR 10 stale-chunk race: respilling a rid
    supersedes (and removes) the older epoch's chunk; discard removes the
    live one — after which nothing about the rid is readable, even though
    its old block ids are long since reused."""
    kp, vp = make_arena(2)
    mgr.spill(5, [2, 3], kp, vp, tokens=8)
    mgr.staging.drain()                     # write-through is async
    first_key = "kvseq/5/1"
    assert mgr.staging.chunk_info(first_key) is not None

    # restage into reused blocks, then spill the SAME rid again
    kp, vp, _ = mgr.restage(5, kp, vp, [2, 3])
    mgr.staging.drain()
    assert mgr.staging.chunk_info(first_key) is None   # consumed + removed
    mgr.spill(5, [4, 6], kp, vp, tokens=8)
    mgr.staging.drain()
    second_key = "kvseq/5/2"
    assert mgr.staging.chunk_info(second_key) is not None
    assert mgr.staging.chunk_info(first_key) is None   # old epoch dead

    # finished sequence: discard drops the record and every staged copy
    assert mgr.discard(5)
    mgr.staging.drain()
    assert mgr.staging.chunk_info(second_key) is None
    assert not mgr.restage_ready(5)
    assert not mgr.discard(5)                          # idempotent
    with pytest.raises(KeyError):
        mgr.restage(5, kp, vp, [4, 6])
    assert mgr.stats()["kv_spilled_seqs"] == 0
    assert mgr.stats()["kv_spilled_bytes"] == 0


def test_respill_supersedes_budget_accounting(mgr):
    kp, vp = make_arena(3)
    one = mgr.chunk_bytes(kp, 1)
    mgr.spill(9, [2], kp, vp, tokens=4)
    assert mgr.stats()["kv_spilled_bytes"] == one
    mgr.spill(9, [2, 3, 4], kp, vp, tokens=12)  # supersedes, not adds
    assert mgr.stats()["kv_spilled_bytes"] == 3 * one
    assert mgr.spilled_tokens(9) == 12


def test_device_buffer_path_when_larger_than_host_cache(tmp_path):
    """A spill bigger than the whole host budget ships device buffers
    straight to staging and never washes the LRU."""
    m = KVTieringManager(offload_dir=str(tmp_path / "d"),
                         host_cache_bytes=8)    # smaller than any spill
    try:
        kp, vp = make_arena(4)
        blocks = [1, 2, 3]
        want_k = np.asarray(kp)[:, blocks].copy()
        tier = m.spill(3, blocks, kp, vp, tokens=12)
        assert tier == TIER_NVME
        assert m.store.host_bytes() == 0        # LRU untouched
        kp, vp, info = m.restage(3, kp, vp, [7, 8, 9])
        assert info["source"] == TIER_NVME
        np.testing.assert_array_equal(np.asarray(kp)[:, [7, 8, 9]], want_k)
    finally:
        m.close()


def test_owned_tempdir_cleanup_and_idempotent_close():
    m = KVTieringManager()
    d = m.offload_dir
    import os
    assert os.path.isdir(d)
    m.close()
    m.close()
    assert not os.path.exists(d)
