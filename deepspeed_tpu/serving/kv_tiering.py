"""Tiered KV cache — spill evicted sequences to host/NVMe, restore on
re-admission.

The serving-side mirror of the PR 10 offload engine: where the trainer
virtualizes optimizer state across hbm/host/nvme (ZeRO-Infinity, arxiv
2104.07857), this module virtualizes the *paged KV arena*.  A sequence
the scheduler would otherwise destructively evict instead has its block
contents gathered device→host through a bounded copy ring and handed to a
:class:`~deepspeed_tpu.runtime.offload.TieredStore` (host LRU bounded by
``kv_host_cache_bytes``, write-through to CRC-framed NVMe chunks).  On
re-admission the bytes restage through the store's async prefetch ring —
kicked while the sequence still waits, polled via ``restage_ready`` so
admission happens only once the window is resident (the T3 move, arxiv
2401.16677: overlap the restore against decode of everything else) — and
are scattered back into freshly allocated blocks.  Restore is bitwise
(the store CRC-verifies every chunk), so greedy token-identity holds by
construction rather than by recompute.

Coherence is epoch-keyed: every spill of a sequence gets a fresh
``kvseq/<rid>/<epoch>`` key and removes its predecessor, and a restage or
discard removes the key outright — so a finished sequence's stale bytes
can never resurface in a reused block id (the PR 10 stale-chunk race,
closed on the serving path).

Copy plumbing is two tiny jits (a ``take`` gather and an ``at[].set``
scatter over fixed ``spill_chunk_blocks``-sized chunks) — deliberately
separate from the engine's step function, whose compiled-program count
stays at two.  Pad lanes of both route to physical block 0, the trash
block, which is garbage-by-design.
"""

import shutil
import tempfile
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from deepspeed_tpu.runtime.offload import (StagingPool, TieredStore,
                                           TIER_HOST, TIER_NVME)


@dataclass
class SpillRecord:
    key: str
    nbytes: int
    tokens: int
    n_blocks: int
    epoch: int


class KVTieringManager:
    """Owns the spill/restage data path for one serving engine.

    The engine thread drives spill/restage; the staging pool's worker
    threads complete the I/O — so the bookkeeping below is shared state.
    Discipline (enforced by dslint's lock-discipline pass, which covers
    ``deepspeed_tpu/serving/``): ``_lock`` wraps only dict/counter
    mutation, never a store or staging call — those block on disk and
    backpressure, and must not stall a concurrent ``stats()``.
    """

    def __init__(self, offload_dir: Optional[str] = None,
                 host_cache_bytes: int = 1 << 30,
                 spill_budget_bytes: int = 0,
                 spill_chunk_blocks: int = 8,
                 ring_depth: int = 2):
        if offload_dir is None:
            offload_dir = tempfile.mkdtemp(prefix="dst-kv-tier-")
            self._owns_dir = True
        else:
            self._owns_dir = False
        self.offload_dir = offload_dir
        self.host_cache_bytes = int(host_cache_bytes)
        self.spill_budget_bytes = int(spill_budget_bytes)  # 0 = unbounded
        self.spill_chunk_blocks = max(1, int(spill_chunk_blocks))
        self.ring_depth = max(1, int(ring_depth))
        self.staging = StagingPool(offload_dir)
        self.store = TieredStore(self.staging, max_in_cpu=self.host_cache_bytes)
        self._lock = threading.Lock()
        self._seqs: Dict[int, SpillRecord] = {}   # guarded-by: _lock
        self._epoch = 0                           # guarded-by: _lock
        self._spilled_bytes = 0                   # guarded-by: _lock
        self.spill_count = 0                      # guarded-by: _lock
        self.restage_count = 0                    # guarded-by: _lock
        self.restage_wait_s = 0.0                 # guarded-by: _lock
        self._gather = None      # lazy jits, engine-thread only
        self._scatter = None
        self._closed = False

    # ---- copy plumbing -------------------------------------------------- #
    def _copy_fns(self, kp):
        """Build (once) the chunk gather/scatter jits for this arena's
        shape/dtype.  Donation on the scatter updates the arena in place
        on accelerators; CPU cannot donate (jax warns and copies)."""
        if self._gather is None:
            import jax
            import jax.numpy as jnp

            def gather(kp, vp, idx):
                return jnp.take(kp, idx, axis=1), jnp.take(vp, idx, axis=1)

            def scatter(kp, vp, idx, kb, vb):
                return kp.at[:, idx].set(kb), vp.at[:, idx].set(vb)

            donate = (0, 1) if jax.default_backend() != "cpu" else ()
            self._gather = jax.jit(gather)
            self._scatter = jax.jit(scatter, donate_argnums=donate)
        return self._gather, self._scatter

    def _gather_to_host(self, kp, vp, blocks: List[int]) -> np.ndarray:
        """Bounded copy ring, device→host: dispatch up to ``ring_depth``
        chunk gathers before draining the oldest (``np.asarray`` is the
        D2H sync point), so the transfer overlaps the next gather's
        dispatch.  → one ``[2, L, n_blocks, BS, H*D]`` host array."""
        import jax.numpy as jnp
        gather, _ = self._copy_fns(kp)
        CH = self.spill_chunk_blocks
        ring: deque = deque()
        k_parts, v_parts = [], []

        def drain_one():
            dk, dv, n = ring.popleft()
            k_parts.append(np.asarray(dk)[:, :n])
            v_parts.append(np.asarray(dv)[:, :n])

        for off in range(0, len(blocks), CH):
            chunk = blocks[off:off + CH]
            idx = np.zeros((CH,), np.int32)   # pad lanes gather trash
            idx[:len(chunk)] = chunk
            ring.append((*gather(kp, vp, jnp.asarray(idx)), len(chunk)))
            if len(ring) >= self.ring_depth:
                drain_one()
        while ring:
            drain_one()
        return np.stack([np.concatenate(k_parts, axis=1),
                         np.concatenate(v_parts, axis=1)])

    def _scatter_from_host(self, kp, vp, data: np.ndarray,
                           dest_blocks: List[int]):
        """Host→device, same chunking; returns the updated arena pair."""
        import jax.numpy as jnp
        _, scatter = self._copy_fns(kp)
        CH = self.spill_chunk_blocks
        L, _, BS, HD = kp.shape
        hk, hv = data[0], data[1]
        for off in range(0, len(dest_blocks), CH):
            chunk = dest_blocks[off:off + CH]
            n = len(chunk)
            idx = np.zeros((CH,), np.int32)   # pad lanes scatter to trash
            idx[:n] = chunk
            kb = np.zeros((L, CH, BS, HD), hk.dtype)
            vb = np.zeros((L, CH, BS, HD), hk.dtype)
            kb[:, :n] = hk[:, off:off + n]
            vb[:, :n] = hv[:, off:off + n]
            kp, vp = scatter(kp, vp, jnp.asarray(idx),
                             jnp.asarray(kb), jnp.asarray(vb))
        return kp, vp

    # ---- capacity ------------------------------------------------------- #
    def chunk_bytes(self, kp, n_blocks: int) -> int:
        """Spill footprint of ``n_blocks`` arena blocks (K and V)."""
        L, _, BS, HD = kp.shape
        return 2 * L * int(n_blocks) * BS * HD * np.dtype(kp.dtype).itemsize

    def can_spill(self, nbytes: int) -> bool:
        """Whether the spill budget admits ``nbytes`` more.  Budget 0 is
        unbounded — the host+NVMe tier is then 'full' only when the disk
        itself fails, which surfaces as a StagingError."""
        if not self.spill_budget_bytes:
            return True
        with self._lock:
            return self._spilled_bytes + nbytes <= self.spill_budget_bytes

    # ---- spill path ------------------------------------------------------ #
    def spill(self, rid: int, blocks: List[int], kp, vp,
              tokens: int) -> Optional[str]:  # may-block: staging backpressure
        """Capture ``rid``'s block contents into the tiered store before
        its arena blocks are reclaimed.  Returns the landing tier
        (``"host"``/``"nvme"``) or None when the spill budget refuses —
        the caller then falls back to destructive evict+recompute."""
        nbytes = self.chunk_bytes(kp, len(blocks))
        if not blocks or not self.can_spill(nbytes):
            return None
        with self._lock:
            self._epoch += 1
            epoch = self._epoch
            old = self._seqs.pop(rid, None)
            if old is not None:
                self._spilled_bytes -= old.nbytes
        if old is not None:
            # superseded spill: its epoch key must not outlive this one
            self.store.remove(old.key)
        key = f"kvseq/{rid}/{epoch}"
        if nbytes > self.host_cache_bytes:
            # larger than the whole host cache: ship the device buffers
            # straight to staging (worker-side DMA), don't wash the LRU
            import jax.numpy as jnp
            idx = jnp.asarray(np.asarray(blocks, np.int32))
            self.store.put_device(
                key, jnp.stack([jnp.take(kp, idx, axis=1),
                                jnp.take(vp, idx, axis=1)]))
        else:
            self.store.put(key, self._gather_to_host(kp, vp, blocks))
        with self._lock:
            self._seqs[rid] = SpillRecord(key=key, nbytes=nbytes,
                                          tokens=int(tokens),
                                          n_blocks=len(blocks), epoch=epoch)
            self._spilled_bytes += nbytes
            self.spill_count += 1
        return TIER_HOST if TIER_HOST in self.store.residency(key) else TIER_NVME

    def spilled_tokens(self, rid: int) -> int:
        with self._lock:
            rec = self._seqs.get(rid)
            return rec.tokens if rec is not None else 0

    def is_spilled(self, rid: int) -> bool:
        with self._lock:
            return rid in self._seqs

    # ---- restage path ---------------------------------------------------- #
    def begin_restage(self, rid: int) -> None:
        """Kick the async prefetch for ``rid``'s spilled bytes (idempotent;
        a no-op when host-resident or already in flight)."""
        with self._lock:
            rec = self._seqs.get(rid)
        if rec is not None:
            self.store.prefetch([rec.key])

    def restage_ready(self, rid: int) -> bool:
        """True when ``restage`` would not block on the NVMe read."""
        with self._lock:
            rec = self._seqs.get(rid)
        return rec is not None and self.store.ready(rec.key)

    def restage(self, rid: int, kp, vp,  # may-block: joins the chunk read
                dest_blocks: List[int]) -> Tuple[Any, Any, Dict[str, Any]]:
        """Restore ``rid``'s spilled KV into ``dest_blocks`` and drop the
        spill record + chunk.  Returns ``(kp, vp, info)`` — the arena pair
        is rebuilt by the scatter jit.  Raises KeyError when ``rid`` has
        no spill record and StagingError when the bytes are unreadable
        (the caller falls back to recompute)."""
        with self._lock:
            rec = self._seqs.get(rid)
        if rec is None:
            raise KeyError(f"no spill record for rid {rid}")
        assert len(dest_blocks) == rec.n_blocks, (
            f"restage of {rec.n_blocks} blocks into {len(dest_blocks)}")
        ready = self.store.ready(rec.key)
        source = (TIER_HOST if TIER_HOST in self.store.residency(rec.key)
                  else TIER_NVME)
        t0 = time.perf_counter()
        data = self.store.get(rec.key)
        wait = time.perf_counter() - t0
        kp, vp = self._scatter_from_host(kp, vp, data, dest_blocks)
        with self._lock:
            self._seqs.pop(rid, None)
            self._spilled_bytes -= rec.nbytes
            self.restage_count += 1
            self.restage_wait_s += wait
        self.store.remove(rec.key)   # restored: the staged copy is dead
        return kp, vp, {"source": source, "ready": ready, "wait_s": wait,
                        "bytes": rec.nbytes, "blocks": rec.n_blocks,
                        "tokens": rec.tokens}

    def discard(self, rid: int) -> bool:
        """Drop ``rid``'s spill record and every staged copy (sequence
        finished or fell back to recompute).  The remove joins any
        in-flight write first, so a reused key epoch can never read these
        bytes back."""
        with self._lock:
            rec = self._seqs.pop(rid, None)
            if rec is not None:
                self._spilled_bytes -= rec.nbytes
        if rec is None:
            return False
        self.store.remove(rec.key)
        return True

    # ---- introspection / lifecycle --------------------------------------- #
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out = {"kv_spilled_seqs": len(self._seqs),
                   "kv_spilled_bytes": self._spilled_bytes,
                   "kv_spills": self.spill_count,
                   "kv_restages": self.restage_count,
                   "kv_restage_wait_ms": self.restage_wait_s * 1000.0}
        store = self.store.stats()
        out["kv_host_bytes"] = store.get("host_bytes", 0)
        out["kv_nvme_bytes"] = self.staging.total_bytes()
        out["kv_ring_hits"] = store.get("ring_hits", 0)
        out["kv_ring_misses"] = store.get("ring_misses", 0)
        return out

    def describe(self) -> str:
        """Tier occupancy summary for ArenaExhausted messages."""
        s = self.stats()
        budget = (f"{self.spill_budget_bytes}B budget"
                  if self.spill_budget_bytes else "unbounded")
        return (f"host {s['kv_host_bytes']}B/{self.host_cache_bytes}B, "
                f"nvme {s['kv_nvme_bytes']}B ({budget}), "
                f"{s['kv_spilled_seqs']} spilled seqs")

    def drain(self) -> None:
        """Join every in-flight copy-ring task (spill writes, restage
        prefetch reads) without closing the backend.  The serving engine
        calls this before close() and during wedge recovery — after a
        drain no staged task can still reference the old arena arrays."""
        if not self._closed:
            self.staging.drain()

    def close(self) -> None:
        """Idempotent shutdown: drain staging, drop an owned tempdir."""
        if self._closed:
            return
        self._closed = True
        self.staging.close()
        if self._owns_dir:
            shutil.rmtree(self.offload_dir, ignore_errors=True)
