"""Bounded per-replica LoRA adapter pool — S-LoRA-style paged adapter
serving over the stacked in-graph factors (ROADMAP item 4; reference:
modules/lora_serving/, PAPER.md §L4).

The traced paged graphs gather each row's (A, B) factors from the stacked
``lora_A_<mod>`` / ``lora_B_<mod>`` device arrays by per-row
``adapter_ids`` (modules/lora.py), so ONE ragged dispatch mixes rows from
different adapters at one-dispatch-per-step cost. What was missing is the
RESIDENCY layer: a replica serves K tenants whose adapters do not all fit
the ``max_loras`` device slots at once. :class:`LoraAdapterPool` owns
that layer for one application:

  * **device residency** — slots ``1..max_loras-1`` of the stacked
    arrays (slot 0 is the pinned ZERO adapter: base-model rows gather it
    and stay bit-identical). ``acquire(name)`` returns the adapter's
    resident slot, loading it on miss; residency is LRU with per-slot
    pin counts, so a slot serving live rows is never evicted from under
    them (``release`` unpins — eviction only claims refcount-0 slots).
  * **host-RAM spill/restore** — the same two-tier shape as the KV
    spill tier (serving/fleet/kv_tier.py): an evicted slot's factors are
    copied device→host into a bounded ``OrderedDict`` cache
    (oldest-touched eviction), and a later re-acquire restores from host
    RAM instead of re-reading the checkpoint. Spills are BEST-EFFORT —
    the ``adapter_spill`` fault point fires inside the spill and a trip
    is swallowed and counted (``stats["spill_errors"]``), never failing
    the acquisition that evicted the slot.
  * **transactional swap** — the device write of a swap snapshots every
    stacked leaf it will touch and restores them on ANY failure, so a
    failed swap (the ``adapter_swap`` fault point fires between the
    snapshot and the write) never corrupts a resident slot; the failure
    surfaces as a retry-safe typed
    :class:`~..resilience.errors.StepFailure` (``phase="adapter_swap"``).

Adapters are registered by name, either as a PEFT checkpoint dir
(loaded + GQA-transformed lazily via the application's
``lora_adapter_arrays``) or as pre-transformed host arrays
(``register_arrays`` — tests/bench/chaos need no torch checkpoint).
Loading is keyed off the registration, so the pool never interprets
paths itself.

Observability: ``nxdi_lora_residency_hits_total`` /
``nxdi_lora_swaps_total{adapter}`` / ``nxdi_lora_swap_bytes`` (README
"Observability"), the always-on :attr:`stats` counters (served under
``debug_state()["lora"]``), and ``lora.swap`` / ``lora.spill`` flight-
recorder events.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, List, Optional

import numpy as np

from ..resilience.errors import (CapacityError, ConfigurationError,
                                 StepFailure)
from ..resilience.faults import FAULTS as _FAULTS
from ..telemetry import get_registry
from ..telemetry import metrics as tmetrics
from ..telemetry.trace import get_recorder as _get_recorder

__all__ = ["LoraAdapterPool"]


class LoraAdapterPool:
    """Bounded device-slot residency for named LoRA adapters over ONE
    paged application's stacked adapter arrays."""

    def __init__(self, app, adapters: Optional[Dict[str, str]] = None,
                 host_cache_adapters: int = 8, telemetry=None):
        if getattr(app.spec, "lora", None) is None:
            raise ConfigurationError(
                "LoraAdapterPool needs an application built with "
                "lora_config (TpuConfig.lora_config) — the stacked "
                "adapter arrays are the pool's backing store")
        if app.spec.lora.max_loras < 2:
            raise ConfigurationError(
                "max_loras must be >= 2 to pool adapters: slot 0 is the "
                "pinned zero adapter (base model)")
        if host_cache_adapters < 1:
            raise ConfigurationError("host_cache_adapters must be >= 1")
        self.app = app
        self._telemetry = telemetry
        self.max_host = host_cache_adapters
        # device slots 1..max_loras-1 (slot 0 = zero adapter, never written)
        self._free: List[int] = list(range(1, app.spec.lora.max_loras))
        self._slots: Dict[str, int] = {}       # resident name -> slot
        self._lru: "OrderedDict[str, None]" = OrderedDict()
        self._pins: Dict[str, int] = {}        # resident name -> refcount
        # registration: name -> ("path", dir) | ("arrays", {mod: (A, B)})
        self._sources: Dict[str, Any] = {}
        # host-RAM spill cache: name -> {mod: (A, B)} (bounded, LRU)
        self._host: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
        self.stats: Dict[str, int] = {
            "hits": 0, "misses": 0, "swaps": 0, "swap_bytes": 0,
            "swap_errors": 0, "cold_loads": 0, "restores": 0,
            "spills": 0, "spill_errors": 0, "host_evictions": 0,
            "evictions": 0}
        self.stats["swap_s"] = 0.0
        for name, path in (adapters or {}).items():
            self.register(name, path)

    # -- registration ------------------------------------------------------
    def register(self, name: str, path: str) -> None:
        """Declare ``name`` as a PEFT checkpoint dir, loaded lazily (and
        GQA-transformed) on first acquisition."""
        self._sources[name] = ("path", path)

    def register_arrays(self, name: str, arrays: Dict[str, Any]) -> None:
        """Declare ``name`` from pre-transformed host arrays
        (``{module: (A (L,in,r), B (L,r,out))}`` — the
        ``lora_adapter_arrays`` layout)."""
        self._sources[name] = ("arrays", arrays)

    @property
    def names(self):
        return tuple(self._sources)

    @property
    def n_slots(self) -> int:
        """Usable device slots (slot 0 excluded)."""
        return self.app.spec.lora.max_loras - 1

    def resident(self, name: str) -> bool:
        """Read-only residency probe (no LRU touch) — the router's
        adapter-affinity scoring uses it per queued request."""
        return name in self._slots

    def slot_of(self, name: str) -> Optional[int]:
        return self._slots.get(name)

    # -- the acquire/release lifecycle -------------------------------------
    def acquire(self, name: str) -> int:
        """Pin ``name`` into a device slot and return the slot id. A hit
        touches recency; a miss claims a free slot (evicting the
        least-recently-used UNPINNED resident when none is free, its
        factors spilled host-side best-effort) and swaps the adapter in
        transactionally. Raises :class:`CapacityError` when every slot is
        pinned by live rows, :class:`ConfigurationError` for a name never
        registered."""
        if name in self._slots:
            self._lru.move_to_end(name)
            self._pins[name] += 1
            self.stats["hits"] += 1
            reg = self._registry()
            if reg is not None:
                tmetrics.lora_residency_hits_counter(reg).inc()
            return self._slots[name]
        if name not in self._sources:
            raise ConfigurationError(
                f"unknown adapter {name!r}; registered: "
                f"{sorted(self._sources)}")
        self.stats["misses"] += 1
        slot = self._claim_slot()
        arrays = self._load(name)
        self._swap_in(name, slot, arrays)
        self._slots[name] = slot
        self._lru[name] = None
        self._pins[name] = 1
        return slot

    def release(self, name: str) -> None:
        """Unpin one acquisition. The adapter stays resident (warm for
        the next acquire) until LRU pressure evicts it; releasing a
        non-resident name is a no-op (rollback paths release blindly)."""
        if name in self._pins and self._pins[name] > 0:
            self._pins[name] -= 1

    def pins(self, name: str) -> int:
        return self._pins.get(name, 0)

    # -- internals ---------------------------------------------------------
    def _claim_slot(self) -> int:
        if self._free:
            return self._free.pop()
        for victim in self._lru:               # oldest-touched first
            if self._pins.get(victim, 0) == 0:
                return self._evict(victim)
        raise CapacityError(
            f"all {self.n_slots} adapter slots are pinned by live rows; "
            "release sequences (or raise max_loras) before acquiring "
            "another adapter")

    def _evict(self, name: str) -> int:
        slot = self._slots.pop(name)
        del self._lru[name]
        self._pins.pop(name, None)
        self.stats["evictions"] += 1
        self._spill(name, slot)
        return slot

    def _spill(self, name: str, slot: int) -> None:
        """Best-effort device→host copy of the evicted slot's factors
        into the bounded host cache, so a re-acquire restores from RAM
        instead of the checkpoint. A failure (the ``adapter_spill``
        fault point models one) is swallowed and counted — the eviction
        that triggered the spill must always proceed."""
        try:
            if _FAULTS.active:
                _FAULTS.fire("adapter_spill")
            lw = self.app.params["layers"]
            arrays = {}
            for mod in self.app.spec.lora.target_modules:
                arrays[mod] = (np.asarray(lw[f"lora_A_{mod}"][:, slot]),
                               np.asarray(lw[f"lora_B_{mod}"][:, slot]))
            self._host[name] = arrays
            self._host.move_to_end(name)
            while len(self._host) > self.max_host:
                self._host.popitem(last=False)
                self.stats["host_evictions"] += 1
            self.stats["spills"] += 1
            rec = _get_recorder()
            if rec.enabled:
                rec.instant("lora.spill", cat="lora", adapter=name,
                            slot=slot, host_cached=len(self._host))
        except Exception:
            self.stats["spill_errors"] += 1

    def _load(self, name: str) -> Dict[str, Any]:
        cached = self._host.get(name)
        if cached is not None:
            self._host.move_to_end(name)
            self.stats["restores"] += 1
            return cached
        kind, src = self._sources[name]
        self.stats["cold_loads"] += 1
        if kind == "arrays":
            return src
        return self.app.lora_adapter_arrays(src)

    def _swap_in(self, name: str, slot: int,
                 arrays: Dict[str, Any]) -> None:
        """Transactional device write: snapshot every stacked leaf the
        swap touches, write, and restore the snapshot on ANY failure —
        a failed swap never corrupts a resident slot (the freed slot
        itself holds stale factors, but nothing maps to it)."""
        import time
        lw = self.app.params["layers"]
        snapshot = {}
        for mod in arrays:
            snapshot[f"lora_A_{mod}"] = lw[f"lora_A_{mod}"]
            snapshot[f"lora_B_{mod}"] = lw[f"lora_B_{mod}"]
        t0 = time.perf_counter()
        try:
            if _FAULTS.active:
                _FAULTS.fire("adapter_swap")
            self.app.write_lora_slot(slot, arrays)
        except Exception as e:
            for key, leaf in snapshot.items():
                lw[key] = leaf
            self._free.append(slot)
            self.stats["swap_errors"] += 1
            from .adapter import _trace_error
            raise _trace_error(StepFailure(
                f"adapter swap of {name!r} into slot {slot} failed; the "
                "stacked factors were restored from the pre-swap "
                "snapshot (no resident slot corrupted)",
                phase="adapter_swap", seq_ids=(), retry_safe=True)) from e
        dt = time.perf_counter() - t0
        nbytes = sum(np.asarray(a).nbytes + np.asarray(b).nbytes
                     for a, b in arrays.values())
        self.stats["swaps"] += 1
        self.stats["swap_bytes"] += nbytes
        self.stats["swap_s"] += dt
        rec = _get_recorder()
        if rec.enabled:
            rec.instant("lora.swap", cat="lora", adapter=name, slot=slot,
                        bytes=nbytes, s=round(dt, 6))
        reg = self._registry()
        if reg is not None:
            tmetrics.lora_swaps_counter(reg).inc(adapter=name)
            tmetrics.lora_swap_bytes_counter(reg).inc(nbytes)

    def _registry(self):
        if self._telemetry is not None:
            return self._telemetry if self._telemetry.enabled else None
        reg = get_registry()
        return reg if reg.enabled else None

    # -- introspection -----------------------------------------------------
    def debug_state(self) -> Dict[str, Any]:
        return {
            "resident": {n: {"slot": s, "pins": self._pins.get(n, 0)}
                         for n, s in self._slots.items()},
            "free_slots": list(self._free),
            "host_cached": list(self._host),
            "stats": dict(self.stats),
        }
