"""Host-RAM KV spill tier — the second storage tier under the device
block pool (ROADMAP item 3 prong b).

Device HBM holds the live block pool; when the prefix cache evicts a
resident block under LRU pressure (``BlockAllocator._pop_block``), its
payload would be gone and a later warm-prefix admission would pay a full
recompute-prefill. With a :class:`HostKVSpillTier` attached to the paged
adapter (``PagedEngineAdapter(kv_spill_tier=...)``):

  * **spill** — the manager's eviction hook
    (:meth:`~...modules.block_kv_cache.BlockKVCacheManager.set_spill_hook`)
    copies the evicted block's K/V payload device→host into this bounded
    pool, keyed by the block's CONTENT CHAIN HASH (the same blake2b chain
    the Python allocator and the handoff records use). Content-hash keying
    makes staleness impossible: a chain hash names a deterministic KV
    payload (same weights, same tokens → same values), so a stored payload
    can never be wrong, only absent.
  * **restore** — at admission, after the device prefix-cache hit is
    cut, the adapter walks the prompt's remaining full-block chain hashes
    through :meth:`HostKVSpillTier.get`; consecutive hits are re-admitted
    by ONE batched async H2D write instead of recompute-prefill, turning
    a recompute-preemption into a swap. Restored streams are bit-identical
    to recomputed ones (pinned by ``tests/test_fleet.py``).

The pool is bounded (``max_blocks``) with oldest-touched-first eviction;
every spill/restore/evict flows through ``nxdi_kv_spill_*`` /
``nxdi_kv_restore_*`` metrics, the always-on :attr:`stats` counters, and
``kv.spill`` / ``kv.restore`` flight-recorder events. The disaggregated
prefill handoff (``fleet/handoff.py``) rides the same pool:
:meth:`seed` loads a received record's block payloads so the decode-side
admission restores them through the identical path.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Dict, Optional

import numpy as np

from ...resilience.errors import ConfigurationError
from ...resilience.faults import FAULTS as _FAULTS
from ...telemetry import get_registry
from ...telemetry import metrics as tmetrics
from ...telemetry.trace import get_recorder as _get_recorder

__all__ = ["HostKVSpillTier"]


class HostKVSpillTier:
    """Bounded host-RAM pool of spilled KV block payloads, keyed by
    content chain hash. One tier may back several adapters/replicas —
    content-hash keying makes sharing safe (and is exactly how the fleet
    bench shares warmth across replicas of the same weights)."""

    def __init__(self, max_blocks: int = 256, telemetry=None):
        if max_blocks < 1:
            raise ConfigurationError("max_blocks must be >= 1")
        self.max_blocks = max_blocks
        self._telemetry = telemetry
        # hash -> {"k": np (L, Bs, H, D), "v": np (L, Bs, H, D)}
        self._pool: "OrderedDict[bytes, Dict[str, np.ndarray]]" = \
            OrderedDict()
        # always-on host counters (asserted by tests/test_fleet.py)
        self.stats: Dict[str, int] = {
            "spilled": 0, "restored": 0, "evicted": 0, "hits": 0,
            "misses": 0, "seeded": 0, "spill_errors": 0}

    # -- introspection -----------------------------------------------------
    def __len__(self) -> int:
        return len(self._pool)

    @property
    def nbytes(self) -> int:
        """Host bytes currently held by the pooled payloads."""
        return sum(p["k"].nbytes + p["v"].nbytes
                   for p in self._pool.values())

    def contains(self, content_hash: bytes) -> bool:
        """Read-only membership probe (no LRU touch) — the tier-aware
        ``prefix_warmth`` extension uses it per queued request."""
        return content_hash in self._pool

    # -- write side --------------------------------------------------------
    def spill(self, content_hash: bytes, k: np.ndarray,
              v: np.ndarray) -> None:
        """Park one evicted block's payload. Deduplicates by hash (a
        re-spill only refreshes recency); evicts the oldest-touched
        payload past ``max_blocks``. The ``kv_spill`` fault point fires
        here — the adapter's eviction hook treats a spill failure as
        best-effort (counted, never failing the allocation that evicted
        the block)."""
        if _FAULTS.active:
            _FAULTS.fire("kv_spill")
        if content_hash in self._pool:
            self._pool.move_to_end(content_hash)
            return
        self._pool[content_hash] = {"k": np.asarray(k), "v": np.asarray(v)}
        self.stats["spilled"] += 1
        evicted = 0
        while len(self._pool) > self.max_blocks:
            self._pool.popitem(last=False)
            evicted += 1
        self.stats["evicted"] += evicted
        rec = _get_recorder()
        if rec.enabled:
            rec.instant("kv.spill", cat="fleet",
                        hash=content_hash.hex()[:16],
                        pool_blocks=len(self._pool))
        reg = self._registry()
        if reg is not None:
            tmetrics.kv_spill_blocks_counter(reg).inc()
            if evicted:
                tmetrics.kv_spill_evictions_counter(reg).inc(evicted)
            tmetrics.kv_spill_bytes_gauge(reg).set(self.nbytes)

    def seed(self, payloads: Dict[bytes, Dict[str, np.ndarray]]) -> None:
        """Load received handoff payloads (decode-side admission path);
        counted separately from pressure spills, same bound/eviction."""
        for h, p in payloads.items():
            fresh = h not in self._pool
            self._pool[h] = {"k": np.asarray(p["k"]),
                             "v": np.asarray(p["v"])}
            self._pool.move_to_end(h)
            if fresh:
                self.stats["seeded"] += 1
        evicted = 0
        while len(self._pool) > self.max_blocks:
            self._pool.popitem(last=False)
            evicted += 1
        self.stats["evicted"] += evicted
        reg = self._registry()
        if reg is not None:
            if evicted:
                tmetrics.kv_spill_evictions_counter(reg).inc(evicted)
            tmetrics.kv_spill_bytes_gauge(reg).set(self.nbytes)

    # -- read side ---------------------------------------------------------
    def get(self, content_hash: bytes
            ) -> Optional[Dict[str, np.ndarray]]:
        """The payload for ``content_hash`` (touching its recency), or
        None. Payloads stay resident after a hit — a shared prefix may be
        restored by many admissions."""
        p = self._pool.get(content_hash)
        if p is None:
            self.stats["misses"] += 1
            return None
        self._pool.move_to_end(content_hash)
        self.stats["hits"] += 1
        return p

    def note_restored(self, n_blocks: int, n_tokens: int) -> None:
        """Restore accounting, called by the adapter after its batched
        H2D write was issued (stats + metrics live here so every consumer
        of one shared tier aggregates in one place)."""
        self.stats["restored"] += n_blocks
        reg = self._registry()
        if reg is not None:
            tmetrics.kv_restore_blocks_counter(reg).inc(n_blocks)
            tmetrics.kv_restore_tokens_counter(reg).inc(n_tokens)

    def _registry(self):
        if self._telemetry is not None:
            return self._telemetry if self._telemetry.enabled else None
        reg = get_registry()
        return reg if reg.enabled else None
