"""Tiered offload engine units (``deepspeed_tpu/runtime/offload``):
staging-pool durability (CRC'd chunk files, async queues), tiered-store
residency/eviction/ring accounting, the residency planner's refusal
logic, and the per-block chunking of the pytree swappers built on top."""

import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.runtime.offload import (HBMBudgetError, ResidencyPlan,
                                           StagingError, StagingPool,
                                           TieredStore, check_budget,
                                           plan_residency, tree_bytes)


class TestStagingPool:
    def test_write_read_roundtrip(self, tmp_path):
        pool = StagingPool(str(tmp_path), buffer_size=64)
        x = np.arange(1000, dtype=np.float32).reshape(10, 100)
        pool.write("k", x).result()
        got = pool.read("k").result()
        np.testing.assert_array_equal(got, x)
        assert got.dtype == x.dtype and got.shape == x.shape
        snap = pool.snapshot()
        assert snap["bytes_written"] == x.nbytes
        assert snap["bytes_read"] == x.nbytes
        pool.close()

    def test_crc_detects_corruption(self, tmp_path):
        pool = StagingPool(str(tmp_path))
        pool.write("k", np.arange(64, dtype=np.int32)).result()
        pool.drain()
        chunk = next(p for p in os.listdir(tmp_path) if p.endswith(".chunk"))
        with open(tmp_path / chunk, "r+b") as f:
            f.seek(8)
            b = f.read(1)
            f.seek(8)
            f.write(bytes([b[0] ^ 0xFF]))
        with pytest.raises(StagingError):
            pool.read("k").result()
        pool.close()

    def test_truncation_detected(self, tmp_path):
        pool = StagingPool(str(tmp_path))
        pool.write("k", np.arange(64, dtype=np.int32)).result()
        pool.drain()
        chunk = next(p for p in os.listdir(tmp_path) if p.endswith(".chunk"))
        with open(tmp_path / chunk, "r+b") as f:
            f.truncate(32)
        with pytest.raises(StagingError):
            pool.read("k").result()
        pool.close()

    def test_drain_joins_all_writes(self, tmp_path):
        pool = StagingPool(str(tmp_path), thread_count=2)
        futs = [pool.write(f"k{i}", np.full((256,), i, np.float32))
                for i in range(16)]
        pool.drain()
        assert all(f.done for f in futs)
        assert pool.snapshot()["write_count"] == 16
        pool.close()

    def test_manifest_sync(self, tmp_path):
        pool = StagingPool(str(tmp_path))
        pool.write("k", np.zeros((8,), np.float64)).result()
        pool.sync_manifest()
        assert (tmp_path / "STAGING_MANIFEST.json").exists()
        pool.close()

    def test_depth_backpressure_is_accounted(self, tmp_path, monkeypatch):
        """A submitter blocked on the queue-depth cap is a staged-I/O
        stall: it must show up in wait_s / submit_wait_s."""
        orig = StagingPool._do_write

        def slow(self, key, array):
            time.sleep(0.2)
            orig(self, key, array)

        monkeypatch.setattr(StagingPool, "_do_write", slow)
        pool = StagingPool(str(tmp_path), queue_depth=1, thread_count=1)
        pool.write("a", np.zeros((8,), np.float32))
        pool.write("b", np.zeros((8,), np.float32))  # blocks on the cap
        pool.drain()
        snap = pool.snapshot()
        assert snap["submit_wait_s"] > 0
        assert snap["wait_s"] >= snap["submit_wait_s"]
        pool.close()


class TestTieredStore:
    def test_host_hit_counts_as_ring_hit(self, tmp_path):
        store = TieredStore(StagingPool(str(tmp_path)), max_in_cpu=None)
        x = np.arange(32, dtype=np.float32)
        store.put("k", x)
        np.testing.assert_array_equal(store.get("k"), x)
        st = store.stats()
        assert st["ring_hits"] == 1 and st["ring_misses"] == 0

    def test_max_in_cpu_zero_evicts_and_rereads(self, tmp_path):
        store = TieredStore(StagingPool(str(tmp_path)), max_in_cpu=0)
        x = np.arange(32, dtype=np.float32)
        store.put("k", x)
        store.drain()          # write durable -> host copy dropped
        assert store.stats()["host_keys"] == 0
        np.testing.assert_array_equal(store.get("k"), x)
        assert store.stats()["ring_misses"] == 1   # blocking read = miss

    def test_prefetch_turns_miss_into_hit(self, tmp_path):
        store = TieredStore(StagingPool(str(tmp_path)), max_in_cpu=0)
        x = np.arange(64, dtype=np.float32)
        store.put("k", x)
        store.drain()
        store.prefetch(["k"])
        store.drain()
        np.testing.assert_array_equal(store.get("k"), x)
        assert store.stats()["ring_hits"] == 1

    def test_invalidate_drops_everything(self, tmp_path):
        store = TieredStore(StagingPool(str(tmp_path)))
        store.put("k", np.zeros((8,), np.float32))
        store.drain()
        store.invalidate()
        assert store.stats()["host_keys"] == 0
        assert not [p for p in os.listdir(tmp_path) if p.endswith(".chunk")]

    def test_same_key_writes_land_in_order(self, tmp_path, monkeypatch):
        """Two overlapping writes of one key on a multi-worker pool: the
        older (artificially slow) write must not clobber the newer one on
        disk — the per-key chaining race."""
        orig = StagingPool._do_write

        def slow_zeros(self, key, array):
            if np.asarray(array).flat[0] == 0:   # only the first value
                time.sleep(0.25)
            orig(self, key, array)

        monkeypatch.setattr(StagingPool, "_do_write", slow_zeros)
        pool = StagingPool(str(tmp_path), thread_count=2)
        store = TieredStore(pool, max_in_cpu=0)
        store.put("k", np.zeros((8,), np.float32))
        store.put("k", np.ones((8,), np.float32))
        store.drain()
        np.testing.assert_array_equal(pool.read_sync("k"),
                                      np.ones((8,), np.float32))
        pool.close()

    def test_put_drops_stale_prefetch(self, tmp_path):
        """A prefetch read issued before a put would serve pre-put bytes
        if joined afterwards; put must drop it."""
        store = TieredStore(StagingPool(str(tmp_path)), max_in_cpu=0)
        store.put("k", np.zeros((8,), np.float32))
        store.drain()
        store.prefetch(["k"])
        store.put("k", np.ones((8,), np.float32))
        store.drain()                      # write durable -> host evicted
        np.testing.assert_array_equal(store.get("k"),
                                      np.ones((8,), np.float32))

    def test_get_not_blocked_by_write_backpressure(self, tmp_path,
                                                   monkeypatch):
        """put() blocked on the staging depth cap must not hold the store
        lock: a concurrent get() of a host-resident key returns WHILE the
        saturating thread is still held at the cap (an order of events, not
        a duration: the slow writes stay parked until the get is back)."""
        orig = StagingPool._do_write
        writing, got = threading.Event(), threading.Event()

        def slow(self, key, array):
            if key.startswith("slow"):
                writing.set()
                assert got.wait(30.0), "get() never came back"
            orig(self, key, array)

        monkeypatch.setattr(StagingPool, "_do_write", slow)
        pool = StagingPool(str(tmp_path), queue_depth=1, thread_count=1)
        store = TieredStore(pool)
        x = np.arange(4, dtype=np.float32)
        store.put("x", x)
        blocked = threading.Event()

        def saturate():
            store.put("slow0", np.zeros((4,), np.float32))
            blocked.set()
            store.put("slow1", np.zeros((4,), np.float32))  # blocks on cap

        t = threading.Thread(target=saturate)
        t.start()
        # the first slow write is parked in the pool's one thread and the
        # second put is on its way into the cap behind it
        assert writing.wait(30.0) and blocked.wait(30.0)
        time.sleep(0.1)                    # let the thread hit the cap
        np.testing.assert_array_equal(store.get("x"), x)
        # the saturating thread was held for the whole of the get
        assert t.is_alive() and not got.is_set()
        got.set()
        t.join()
        pool.close()

    def test_remove_drops_every_copy(self, tmp_path):
        store = TieredStore(StagingPool(str(tmp_path)))
        store.put("k", np.arange(8, dtype=np.float32))
        store.remove("k")
        assert store.residency("k") == ()
        with pytest.raises(StagingError):
            store.staging.read_sync("k")


class TestResidencyPlanner:
    def _params(self, n_layer=4, d=64):
        return {"blocks": {"w": jax.ShapeDtypeStruct((n_layer, d, d),
                                                     jnp.float32)},
                "emb": jax.ShapeDtypeStruct((128, d), jnp.float32)}

    def test_window_smaller_than_plain(self):
        plan = plan_residency(self._params(), None, budget_bytes=1 << 30,
                              world=8, compute_itemsize=4, prefetch_depth=1,
                              params_tier="cpu")
        assert plan.window_peak_bytes < plan.plain_peak_bytes
        assert plan.n_layer == 4
        assert plan.fits_plain and plan.fits_window

    def test_window_scales_with_depth_not_layers(self):
        lo = plan_residency(self._params(n_layer=16), None, 1 << 30, 8, 4,
                            prefetch_depth=1, params_tier="cpu")
        hi = plan_residency(self._params(n_layer=16), None, 1 << 30, 8, 4,
                            prefetch_depth=4, params_tier="cpu")
        per_slice = tree_bytes(self._params()["blocks"], itemsize=4) // 4
        assert hi.window_peak_bytes - lo.window_peak_bytes == 3 * per_slice

    def test_refusal_without_offload(self):
        plan = plan_residency(self._params(), None, budget_bytes=1 << 10,
                              world=8, compute_itemsize=4)
        with pytest.raises(HBMBudgetError, match="offload_param"):
            check_budget(plan, offload_enabled=False)

    def test_window_rescues_with_offload(self):
        plain_over = plan_residency(self._params(), None, budget_bytes=1,
                                    world=8, compute_itemsize=4,
                                    params_tier="cpu")
        budget = plain_over.window_peak_bytes + 1
        plan = plan_residency(self._params(), None, budget_bytes=budget,
                              world=8, compute_itemsize=4, params_tier="cpu")
        assert not plan.fits_plain or plan.fits_window
        assert check_budget(plan, offload_enabled=True) is plan

    def test_unstacked_model_has_no_window(self):
        plan = plan_residency({"w": jax.ShapeDtypeStruct((64, 64),
                                                         jnp.float32)},
                              None, budget_bytes=1 << 10, world=8,
                              compute_itemsize=4, params_tier="cpu")
        assert not plan.fits_window
        with pytest.raises(HBMBudgetError):
            check_budget(plan, offload_enabled=True)

    def test_describe_and_record(self):
        plan = plan_residency(self._params(), None, 1 << 20, 8, 4,
                              params_tier="nvme", optimizer_tier="nvme")
        assert "params@nvme" in plan.describe()
        rec = plan.as_record()
        assert rec["window_peak_bytes"] == plan.window_peak_bytes
        assert isinstance(plan, ResidencyPlan)


class TestPerBlockChunking:
    def test_stacked_blocks_leaf_chunks_per_layer(self, tmp_path):
        from deepspeed_tpu.runtime.swap_tensor import (
            AsyncPartitionedParameterSwapper)
        sw = AsyncPartitionedParameterSwapper(
            str(tmp_path), None, chunk_paths=lambda k: "blocks" in k.split("__"))
        tree = {"blocks": {"w": np.arange(4 * 8, dtype=np.float32).reshape(4, 8)},
                "emb": np.ones((8,), np.float32)}
        sw.swap_out_tree(tree, prefix="param", sync=True)
        chunks = [p for p in os.listdir(tmp_path) if p.endswith(".chunk")]
        assert sum("__blk" in c for c in chunks) == 4    # one per layer
        assert len(chunks) == 5                          # + unchunked emb
        back = sw.swap_in_tree(
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         tree), prefix="param")
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            np.testing.assert_array_equal(a, np.asarray(b))

    def test_remove_evicts_host_cache_too(self, tmp_path):
        """remove() must drop the store's host-LRU copies (and pending
        entries), not just the NVMe chunks — otherwise a later get()
        serves a removed leaf from the cache."""
        from deepspeed_tpu.runtime.swap_tensor import (
            AsyncPartitionedParameterSwapper)
        sw = AsyncPartitionedParameterSwapper(
            str(tmp_path), None, chunk_paths=lambda k: "blocks" in k.split("__"))
        tree = {"blocks": {"w": np.ones((3, 4), np.float32)},
                "emb": np.ones((4,), np.float32)}
        sw.swap_out_tree(tree, prefix="param", sync=True)
        assert sw.store.stats()["host_keys"] > 0
        sw.remove(prefix="param")
        assert sw.store.stats()["host_keys"] == 0
        assert sw.pool.keys() == []
        assert not [p for p in os.listdir(tmp_path) if p.endswith(".chunk")]
