"""Native C++ block allocator tests: build via ctypes, exact behavioral
equivalence with the Python allocator (same block-id sequences), prefix
caching, LRU eviction, error paths (SURVEY §2.10 native-equiv components)."""

import numpy as np
import pytest

from neuronx_distributed_inference_tpu import native
from neuronx_distributed_inference_tpu.modules.block_kv_cache import (
    BlockAllocator, NativeBlockAllocator, make_block_allocator)


@pytest.fixture(scope="module")
def lib():
    import shutil
    if not native.native_enabled() or shutil.which("g++") is None:
        pytest.skip("native path disabled or no g++ on this box")
    return native.load_library()


def test_native_builds_and_loads(lib):
    assert lib is not None


def test_factory_prefers_native(lib):
    a = make_block_allocator(16, 4)
    assert isinstance(a, NativeBlockAllocator)


def _random_workload(alloc, rng, rounds=120):
    """Drive allocate/extend/free with shared prefixes; log every result."""
    log = []
    live = {}
    prompts = [rng.integers(0, 50, size=rng.integers(1, 40)).tolist()
               for _ in range(8)]
    for step in range(rounds):
        op = rng.integers(0, 3)
        if op == 0 or not live:
            base = prompts[rng.integers(0, len(prompts))]
            cut = rng.integers(1, len(base) + 1)
            toks = base[:cut] + rng.integers(0, 50, size=rng.integers(0, 6)).tolist()
            try:
                blocks, cached = alloc.allocate(toks)
            except RuntimeError:
                log.append(("oom",))
                continue
            sid = step
            live[sid] = (list(blocks), len(toks))
            log.append(("alloc", tuple(blocks), cached))
        elif op == 1:
            sid = list(live)[int(rng.integers(0, len(live)))]
            blocks, n = live[sid]
            try:
                blocks = alloc.extend(blocks, n + 3)
            except RuntimeError:
                log.append(("oom-extend",))
                continue
            live[sid] = (blocks, n + 3)
            log.append(("extend", tuple(blocks)))
        else:
            sid = list(live)[int(rng.integers(0, len(live)))]
            blocks, _ = live.pop(sid)
            alloc.free(blocks)
            log.append(("free", alloc.num_free))
    for blocks, _ in live.values():
        alloc.free(blocks)
    log.append(("end", alloc.num_free))
    return log


def test_native_matches_python_exactly(lib):
    """Same RNG-driven workload must produce identical block ids, cached
    counts, and free counts in both implementations."""
    py = BlockAllocator(64, 4, enable_prefix_caching=True)
    nat = NativeBlockAllocator(64, 4, enable_prefix_caching=True)
    log_py = _random_workload(py, np.random.default_rng(7))
    log_nat = _random_workload(nat, np.random.default_rng(7))
    assert log_py == log_nat


def test_native_matches_python_no_prefix(lib):
    py = BlockAllocator(32, 2, enable_prefix_caching=False)
    nat = NativeBlockAllocator(32, 2, enable_prefix_caching=False)
    log_py = _random_workload(py, np.random.default_rng(11), rounds=60)
    log_nat = _random_workload(nat, np.random.default_rng(11), rounds=60)
    assert log_py == log_nat


def test_native_prefix_hit(lib):
    a = NativeBlockAllocator(32, 4)
    toks = list(range(12))
    b1, c1 = a.allocate(toks)
    assert c1 == 0 and len(b1) == 3
    b2, c2 = a.allocate(toks)
    assert c2 == 12 and b2 == b1            # full prefix reuse
    b3, c3 = a.allocate(toks[:8] + [99, 98, 97, 96])
    assert c3 == 8 and b3[:2] == b1[:2] and b3[2] != b1[2]
    a.free(b1)
    a.free(b2)
    a.free(b3)
    # cached blocks stay resident: allocating again still hits
    b4, c4 = a.allocate(toks)
    assert c4 == 12


def test_native_lru_eviction_and_oom(lib):
    a = NativeBlockAllocator(5, 2)          # blocks 1..4 usable
    b1, _ = a.allocate([1, 2, 3, 4])        # 2 blocks
    b2, _ = a.allocate([5, 6, 7, 8])        # 2 blocks
    with pytest.raises(RuntimeError):
        a.allocate([9, 10, 11, 12])         # OOM: all referenced
    a.free(b1)                               # b1 cached (LRU)
    b3, c3 = a.allocate([9, 10, 11, 12])    # evicts b1's blocks
    assert c3 == 0 and len(b3) == 2
    # b1's content was evicted: no prefix hit anymore
    a.free(b3)
    b4, c4 = a.allocate([1, 2, 3, 4])
    assert c4 == 0


def test_native_double_free_raises(lib):
    a = NativeBlockAllocator(8, 2)
    b, _ = a.allocate([1, 2])
    a.free(b)
    with pytest.raises(RuntimeError):
        a.free(b)
