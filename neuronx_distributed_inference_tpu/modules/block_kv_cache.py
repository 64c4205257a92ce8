"""Paged (block) KV cache — TPU-native analog of the reference's
``BlockKVCacheManager`` (reference: modules/kvcache/block_kv_cache_manager.py,
431 LoC) plus the host-side block allocator with vLLM-style prefix caching
(the reference exposes the same surface to vLLM via ``slot_mapping`` /
``active_block_table`` inputs).

Device layout:
  k, v : (num_layers, num_blocks, block_size, num_kv_heads, head_dim)
  sharded P(None, None, None, ("ep","tp"), None) — heads sharded, blocks
  replicated across dp (each dp shard could own a block range; that variant
  arrives with attention-DP decode).
  A LATENT pool (MLA: deepseek_v2/v3, longcat_flash) keeps ONE row a token
  a layer in "k", (num_layers, num_blocks, block_size, 1, latent_lanes), and
  a "v" of no lanes: allocator, tables, slot mapping, preemption and release
  are the same (a block is still block_size tokens of every layer).

In-graph ops (pure, used inside the jitted step):
  * ``write_slots``       — scatter new K/V at flat slot ids
    (reference: write via slot_mapping, block_kv_cache_manager.py:268-375)
  * ``gather_layer_kv``   — assemble a per-request (B, S, H, D) view of one
    layer from an ``active_block_table`` (reference: :183-267 gather via
    block table), straight from the stacked pool

Host side:
  * ``BlockAllocator`` — free-list allocator + content-hash prefix cache
    (reference analog: vLLM's block manager; prefix-caching bucket logic
    model_wrapper.py:923-1045 selects buckets from cached-prefix length).

Block 0 is reserved as the NULL block: slot_mapping entries < 0 drop writes,
block_table entries 0 read zeros (masked out by the position mask anyway).
"""

from __future__ import annotations

import hashlib
import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import AXIS_MP
from ..resilience.errors import (CapacityError, ConfigurationError,
                                 KVCacheStateError)
from ..resilience.faults import FAULTS as _FAULTS
from ..telemetry import get_registry, metrics as tmetrics


@dataclass(frozen=True)
class BlockKVSpec:
    num_layers: int
    num_blocks: int            # includes the reserved null block 0
    block_size: int
    num_kv_heads: int          # padded/replicated per GQASharding
    head_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    # lanes of a V slot where they differ from K's. A LATENT pool (MLA:
    # :func:`latent_page`) keeps one row a token in "k" and NOTHING in "v"
    # (0 lanes): the values are lanes of the same row
    v_head_dim: Optional[int] = None

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.num_layers, self.num_blocks, self.block_size,
                self.num_kv_heads, self.head_dim)

    @property
    def v_shape(self) -> Tuple[int, ...]:
        return self.shape[:4] + (self.head_dim if self.v_head_dim is None
                                 else self.v_head_dim,)

    @property
    def is_latent(self) -> bool:
        """A latent pool: one row a token in "k", no V, no head axis."""
        return self.v_head_dim == 0

    @property
    def bytes_per_token(self) -> int:
        """Bytes a token takes in the pool, all layers, K and V."""
        return (self.num_layers * self.num_kv_heads
                * (self.shape[4] + self.v_shape[4])
                * jnp.dtype(self.dtype).itemsize)

    def blocks_for(self, seq_len: int) -> int:
        return -(-seq_len // self.block_size)


#: the second-minor extent of a bfloat16 tile on the device, (16, 128)
POOL_HEAD_TILE = 16


def pool_kv_heads(num_kv_heads: int, tp: int = 1) -> int:
    """Heads a page of the pool has room for, for a model of
    ``num_kv_heads`` kv heads. A page is ``(block_size, heads, head_dim)``
    and the device keeps arrays in tiles over their two minor dimensions:
    with MORE than a tile's worth of heads that are not whole tiles (30:
    Olmo-Hybrid-7B) it stores the pool with the block's tokens minor
    instead, so the slot write and the decode kernel, which read pages as
    they are declared, each paid whole-pool copies a step (three a side, 7
    GB of temps: the step did not fit the chip). The pool then rounds its
    heads up to whole tiles (30 -> 32; the padded heads hold zeros and the
    attention block pads its q, k, v to match): every reshape of the pool
    is a bitcast again, as for 16 heads. One chip only: a shard's heads are
    not padded. How many of these heads share one SLOT of a page is
    :func:`pool_page`'s to say, which is what the application allocates
    by."""
    if tp != 1 or num_kv_heads <= POOL_HEAD_TILE:
        return num_kv_heads
    return -(-num_kv_heads // POOL_HEAD_TILE) * POOL_HEAD_TILE


def pool_page(num_kv_heads: int, head_dim: int, tp: int = 1
              ) -> Tuple[int, int]:
    """``(head slots, lanes of a slot)`` of a page of the pool: what the
    application allocates, ``(block_size, slots, lanes)`` a page, and the
    ONE place that decides a page's shape. The pool is stored as the decode
    kernel reads a shard's page (``decode_attention.paged_pool_fold`` of a
    shard's :func:`pool_kv_heads` and their width), so that no consumer is
    handed a relayout of it:

    * heads narrower than a vreg go ``128 // head_dim`` neighbours to a
      128-lane slot (8 heads of 64, granite-4.0-h / Llama-3.2-1B: ``(4,
      128)``). As ``(8, 64)`` the device padded nothing and tiled the page
      its own way, and every decode step, one-row chunk and pack moved the
      whole pool between that layout and its consumer's FOUR times: 4.1 of
      a 25.0 ms step and 5.2 of a 23.9 ms chunk on granite-h-chat-closed
      (ledger, PR 40). Stored so, the compiled step, chunk and pack hold
      no pool-sized copy, at ``tp=1`` and, a shard's heads folding evenly,
      at ``tp=4`` (``tests/test_chip_aot.py``);
    * a FEW heads (2 to 7 a shard) of whole vregs share one slot of
      ``heads x head_dim`` lanes. With 2 heads of 256 to a page
      (Qwen3-Next) the device tiles the page ``(2, 128)``, and the decode
      kernel, which reads a page as a ``(tokens x heads, lanes)`` matrix in
      whole ``(8, 128)`` tiles, was handed a relayout of the whole pool on
      every call: six ``reshape`` copies of 403 MB, 10.4 of a decode step's
      28.9 ms, and the chunk programs' gathers six more (my chip run and
      AOT, PR 36);
    * 9 to 15 heads of whole vregs (ten 128-lane PAIRS of a differential
      stack's 20 heads of 64, Phi-4-mini-flash) share one slot as the few
      do: as ``(32, 10, 128)`` the device kept the page tokens-minor and
      every step copied both pools (4.9 GB of temps by AOT, PR 54);
    * everything else (heads that do not fold evenly: 96 lanes, an odd
      count of narrow heads; whole tiles of 8 or 16 heads of whole vregs,
      and more, which :func:`pool_kv_heads` rounds up to whole tiles) keeps
      a slot a head.

    A slot never straddles two shards: the fold is of a shard's heads. A
    token's heads in one row are the same bytes, so allocator, tables, slot
    mapping, preemption, prefix hashing, spill and handoff see no
    difference; the slot write is unchanged (a token's heads are contiguous
    either way); a chunk splits the lanes of the rows it GATHERED
    (``model_base._attn_block``)."""
    from ..ops.decode_attention import paged_pool_fold
    heads = pool_kv_heads(num_kv_heads, tp)
    fold = paged_pool_fold(heads // tp, head_dim) if heads % tp == 0 else 1
    return heads // fold, fold * head_dim


#: lanes of a vreg: a latent row is stored in whole ones
LATENT_LANE_TILE = 128


def latent_lanes(latent_dim: int) -> int:
    """Lanes of a token's row in a LATENT pool (Multi-head Latent
    Attention: ``[normed c | rotated k_rope]``, ``latent_dim`` values a
    token a layer - 576 for DeepSeek-V2/V3 and LongCat-Flash against 64
    heads x (192 + 128) expanded): ``latent_dim`` rounded up to whole
    vregs, 640. The device keeps an array's minor dimension in tiles of 128
    lanes whatever is declared, so the 64 lanes of padding cost no bytes
    that a row of 576 would not have cost too; declared, every consumer (the
    slot write, the gather, the decode kernel's page copies and its two
    matmuls) sees whole tiles. The padding holds zeros."""
    return -(-latent_dim // LATENT_LANE_TILE) * LATENT_LANE_TILE


def latent_page(latent_dim: int) -> Tuple[int, int, int]:
    """``(slots, K lanes, V lanes)`` of a page of a latent pool: ONE slot a
    token, :func:`latent_lanes` wide, and no V (its lanes are ``c``, the
    first ``kv_lora_rank`` of the same row)."""
    return 1, latent_lanes(latent_dim), 0


def pool_spec(spec, num_blocks: int, block_size: int) -> BlockKVSpec:
    """The pool a paged application allocates for the decoder ``spec``
    (``models.model_base.DecoderSpec``) at ``num_blocks`` usable blocks:
    latent rows for an MLA spec (:func:`latent_page`), else the kv heads'
    page (:func:`pool_page`); one more block, the null block 0."""
    if spec.mla is not None:
        slots, lanes, v_lanes = latent_page(spec.mla.latent_dim)
    else:
        slots, lanes = pool_page(spec.gqa.num_kv_heads, spec.head_dim,
                                 spec.gqa.tp)
        v_lanes = None
    return BlockKVSpec(
        # SSM-only layers carry no KV pages (recurrent/hybrid stacks); the
        # window layers of a stack with a window pool keep theirs in the
        # ring (:func:`window_pool_spec`)
        num_layers=spec.num_attn_layers - spec.num_window_layers,
        num_blocks=num_blocks + 1,
        block_size=block_size, num_kv_heads=slots, head_dim=lanes,
        dtype=spec.kv_dtype, v_head_dim=v_lanes)


def window_ring_pages(window: int, widest: int, block_size: int) -> int:
    """Pages of a row's RING in the window layers' pool: room for ``window +
    widest + block_size`` tokens, ``widest`` the widest prefill step. A
    token at position ``p`` lies in ring page ``(p // block_size) % R``; a
    chunk of ``T <= widest`` tokens overwrites positions ``R x block_size``
    behind its own, which lie in front of the window of its first query as
    long as ``R x block_size >= window + T``, and the ``R`` logical pages
    that end at the chunk's last page reach back to that window's first key
    wherever in its page the chunk ends (one more page)."""
    return -(-(window + widest + block_size) // block_size)


def window_pool_spec(spec, rows: int, block_size: int, widest: int
                     ) -> BlockKVSpec:
    """The SECOND pool of a stack whose window layers keep a window's worth
    of a row (``DecoderSpec.window_pool``: a ``layer_pattern`` with a
    ``sliding_window`` on the paged path): the window layers' keys and
    values, :func:`window_ring_pages` pages for each of ``rows`` batch
    slots, page ``slot x R + j`` the slot's ``j``-th. A page has the shape
    :func:`pool_page` gives the global layers'. No allocator and no null
    block: a slot's ring is the row's for as long as the row holds the slot
    (the adapter's state slots), is overwritten in place as the row
    advances, and is never freed; a pad row and a released row write
    nothing there. Its size comes from the spec, the rows and the warmed
    widths, not from ``pa_num_blocks``."""
    slots, lanes = pool_page(spec.gqa.num_kv_heads, spec.head_dim,
                             spec.gqa.tp)
    ring = window_ring_pages(spec.sliding_window, widest, block_size)
    return BlockKVSpec(
        num_layers=spec.num_window_layers, num_blocks=rows * ring,
        block_size=block_size, num_kv_heads=slots, head_dim=lanes,
        dtype=spec.kv_dtype)


def index_page(index_dim: int, block_size: int) -> Tuple[int, int]:
    """``(rows, lanes)`` of a page of the INDEX-KEY pool (a learned sparse
    selection, ``models.model_base.SparseSpec``: one key of ``index_dim``
    values a token a layer, on the K / V pools' block table), decided here
    as :func:`pool_page` decides the K / V page. The device keeps an array's
    minor dimension in whole 128-lane tiles, so a key of 64 values a row
    would take the bytes of 128: ``128 // index_dim`` tokens share a row
    instead, token ``o`` of a block in row ``o % rows``, lanes ``(o // rows)
    x index_dim`` on (a page of 32 tokens of 64 values is ONE bfloat16 tile,
    ``(16, 128)``). A scorer reads a row as it lies with its queries placed
    in a segment's lanes (zeros in the neighbours'), as the decode kernel's
    narrow heads are. A width that does not fold evenly keeps a row a
    token."""
    fold = LATENT_LANE_TILE // index_dim if (
        index_dim < LATENT_LANE_TILE and LATENT_LANE_TILE % index_dim == 0
        and block_size % (LATENT_LANE_TILE // index_dim) == 0) else 1
    return block_size // fold, fold * index_dim


def index_pool_shape(spec, num_blocks: int, block_size: int
                     ) -> Tuple[int, int, int, int]:
    """The index-key pool of ``spec`` (its ``sparse`` set) beside a K / V
    pool of ``num_blocks`` usable blocks: ``(layers, blocks + the null
    block, rows, lanes)`` of :func:`index_page`."""
    rows, lanes = index_page(spec.sparse.index_dim, block_size)
    return (spec.num_attn_layers, num_blocks + 1, rows, lanes)


def init_index_pool(shape, dtype, mesh: Optional[Mesh] = None):
    """The zeroed index-key pool: replicated (ONE key head serves every
    query head of every shard)."""
    sharding = NamedSharding(mesh, P()) if mesh is not None else None
    return jnp.zeros(shape, dtype, device=sharding)


def write_index_keys(pool: jnp.ndarray, new: jnp.ndarray, layer,
                     slot_mapping: jnp.ndarray, positions: jnp.ndarray,
                     block_size: int) -> jnp.ndarray:
    """The step's index keys ``new`` (B, T, index_dim) into the pool (L, N,
    rows, lanes) at ``layer``, by the K / V pools' flat ``slot_mapping``
    (B, T; negative = drop). Tokens that share a row (:func:`index_page`)
    are written as WHOLE rows: a token's row takes the neighbour segment
    from the same step where the neighbour is in it (``positions`` (B, T)
    tell: a row's tokens are consecutive) and from the pool where it is
    not, so two tokens of one row write the same bytes and a scatter's
    order does not matter."""
    n_layers, n, rows, lanes = pool.shape
    b, t, dim = new.shape
    fold = lanes // dim
    flat = pool.reshape(n_layers, n * rows, lanes)
    li = jnp.asarray(layer, jnp.int32)
    live = slot_mapping >= 0
    slot = jnp.maximum(slot_mapping, 0)
    off = slot % block_size
    row = (slot // block_size) * rows + off % rows               # (B, T)
    new = new.astype(pool.dtype)
    if fold > 1:
        seg = off // rows
        old = flat[li, row].reshape(b, t, fold, dim)
        at = jnp.arange(t, dtype=jnp.int32)[None, :]
        parts = []
        for g in range(fold):
            shift = (g - seg) * rows                             # (B, T)
            there = jnp.clip(at + shift, 0, t - 1)
            found = (jnp.take_along_axis(live, there, axis=1)
                     & (at + shift == there)
                     & (jnp.take_along_axis(positions, there, axis=1)
                        == positions + shift))
            theirs = jnp.take_along_axis(new, there[..., None], axis=1)
            parts.append(jnp.where((seg == g)[..., None], new, jnp.where(
                found[..., None], theirs, old[:, :, g])))
        new = jnp.concatenate(parts, axis=-1)
    # negative indices WRAP in a jax scatter: past the end, so they drop
    row = jnp.where(live, row, n * rows).reshape(-1)
    flat = flat.at[li, row].set(new.reshape(-1, lanes), mode="drop",
                                unique_indices=False)
    return flat.reshape(pool.shape)


def gather_index_rows(pool: jnp.ndarray, layer, block_table: jnp.ndarray
                      ) -> jnp.ndarray:
    """The pages ``block_table`` (B, max_blocks) names of layer ``layer`` of
    the index-key pool, as they lie: (B, max_blocks, rows, lanes), one gather
    from the flat (L x N, ...) pool as :func:`gather_layer_kv`'s."""
    n_layers, n, rows, lanes = pool.shape
    flat = pool.reshape(n_layers * n, rows, lanes)
    return flat[jnp.asarray(layer, jnp.int32) * n + block_table]


def block_cache_pspec() -> P:
    return P(None, None, None, AXIS_MP, None)


def init_block_cache(spec: BlockKVSpec, mesh: Optional[Mesh] = None):
    # born on its sharding: at tp=4 each chip allocates its quarter of the
    # pool, nothing is staged whole on the default device first
    # a latent pool has no head axis to shard: every shard's heads are
    # projections of the same row
    pspec = P() if spec.is_latent else block_cache_pspec()
    sharding = NamedSharding(mesh, pspec) if mesh is not None else None
    return {"k": jnp.zeros(spec.shape, spec.dtype, device=sharding),
            "v": jnp.zeros(spec.v_shape, spec.dtype, device=sharding)}


# ---------------------------------------------------------------------------
# In-graph ops (operate on ONE layer's cache, called inside the layer scan)
# ---------------------------------------------------------------------------

def write_slots(cache_layer: jnp.ndarray, new: jnp.ndarray,
                slot_mapping: jnp.ndarray) -> jnp.ndarray:
    """Scatter tokens into flat slots.

    cache_layer (N, Bs, H, D); new (B, T, H, D); slot_mapping (B, T) flat slot
    ids (block*block_size + offset), negative = drop (padding).
    """
    return write_slots_at_layer(cache_layer[None], new, 0, slot_mapping)[0]


def write_slots_at_layer(cache: jnp.ndarray, new: jnp.ndarray, layer,
                         slot_mapping: jnp.ndarray) -> jnp.ndarray:
    """In-place slot write into the FULL stacked cache (L, N, Bs, H, D) at
    ``layer`` (traced scalar inside the layer scan) — see
    kv_cache.write_tokens_at_layer for the carry-aliasing rationale."""
    L, n, bs, h, d = cache.shape
    flat = cache.reshape(L, n * bs, h, d)
    slots = slot_mapping.reshape(-1)
    # negative indices WRAP in jax scatter (slot -1 = last flat slot, which is
    # a real allocated block) — remap them past the end so mode="drop"
    # actually drops them
    slots = jnp.where(slots < 0, n * bs, slots)
    vals = new.astype(cache.dtype).reshape(-1, h, d)
    li = jnp.asarray(layer, jnp.int32)
    flat = flat.at[li, slots].set(vals, mode="drop", unique_indices=False)
    return flat.reshape(L, n, bs, h, d)


def gather_block_kv(cache_layer: jnp.ndarray, block_table: jnp.ndarray
                    ) -> jnp.ndarray:
    """Assemble per-request contiguous KV from the block table.

    cache_layer (N, Bs, H, D); block_table (B, max_blocks) int32 →
    (B, max_blocks*Bs, H, D). Table entries 0 = null block (zeros).
    """
    return gather_layer_kv(cache_layer[None], 0, block_table)


def gather_layer_kv(cache: jnp.ndarray, layer, block_table: jnp.ndarray
                    ) -> jnp.ndarray:
    """:func:`gather_block_kv` of layer ``layer`` (int or traced scalar
    inside the layer scan) of the FULL stacked cache (L, N, Bs, H, D), as
    ONE gather from the flat (L*N, ...) pool by ``layer*N + block_table``:
    it reads the blocks the tables name and nothing else. Cutting the
    layer out first (a dynamic slice in front of the gather) is a copy of
    the layer's whole pool on every layer of every dispatch. The paged
    layout keeps heads minor: the gather is row-indexed, not head-sliced.
    A pool stored with several heads to a slot (:func:`pool_page`) comes
    back that way; the caller splits the lanes of the GATHERED rows.

    A page of fewer slots than a tile has rows (4 slots of 128 lanes:
    8 heads of 64) is gathered as the ``(tokens x slots, lanes)`` matrix it
    is in memory, the decode kernel's view of it. Gathered as ``(tokens,
    slots, lanes)`` the compiler wanted the page's tokens in the tile's
    rows, and a one-row chunk paid a relayout of the whole pool for K and
    for V in EVERY layer (AOT, PR 41: two 268 MB ``copy`` in the loop's
    body). One chip only: across shards the slots are the sharded axis and
    do not merge with the tokens. Pages of whole tiles keep the gather they
    had."""
    from ..ops.decode_attention import PAGED_ROW_TILE
    L, n, bs, h, d = cache.shape
    mesh = jax.sharding.get_abstract_mesh()
    # shards of the slot axis under the ambient mesh (block_cache_pspec)
    shards = math.prod(mesh.shape[a] for a in AXIS_MP if a in mesh.axis_names)
    page = (bs * h,) if shards == 1 and h < PAGED_ROW_TILE else (bs, h)
    flat = cache.reshape((L * n,) + page + (d,))
    g = flat[jnp.asarray(layer, jnp.int32) * n + block_table]
    b, mb = block_table.shape
    return g.reshape(b, mb * bs, h, d)


# ---------------------------------------------------------------------------
# Host-side slot-mapping construction
# ---------------------------------------------------------------------------

def slots_from_table(block_table: np.ndarray, positions: np.ndarray,
                     block_size: int) -> np.ndarray:
    """positions (B, T) in-sequence token positions -> flat slot ids (B, T)
    using each row's block table. Negative positions stay negative (drop)."""
    blk_idx = positions // block_size
    offs = positions % block_size
    blocks = np.take_along_axis(
        np.asarray(block_table), np.maximum(blk_idx, 0), axis=1)
    slots = blocks * block_size + offs
    return np.where(positions < 0, -1, slots).astype(np.int32)


def cut_cached_at_unwritten(blocks: Sequence[int], cached_tokens: int,
                            block_size: int, unwritten) -> int:
    """Clamp a prefix-cache hit against blocks whose contents are not
    fully written yet: a hit on a block freshly allocated by a sibling in
    the same batch — or by a still-in-flight chunked prefill — may read
    slots the writer's chunk has not landed. Cut the cached prefix at the
    first such block and recompute from there (recomputing a shared block
    writes identical values, so the cut is always safe). ``unwritten`` is
    any container of block ids supporting ``in``."""
    for bi in range(cached_tokens // block_size):
        if blocks[bi] in unwritten:
            return bi * block_size
    return cached_tokens


def slots_from_table_into(out: np.ndarray, block_table: np.ndarray,
                          positions: np.ndarray, block_size: int) -> None:
    """In-place :func:`slots_from_table` for the serving adapters' per-step
    scratch buffers: same slot values, no fresh (B, T) allocations on the
    decode hot path (positions here are always real — the negative-drop
    branch of the allocating variant is not needed)."""
    np.floor_divide(positions, block_size, out=out)
    out[:] = np.take_along_axis(block_table, out, axis=1)
    out *= block_size
    out += positions % block_size


# ---------------------------------------------------------------------------
# Block allocator + prefix cache (host)
# ---------------------------------------------------------------------------

def _hash_block(parent: bytes, tokens: Sequence[int]) -> bytes:
    h = hashlib.blake2b(digest_size=16)
    h.update(parent)
    h.update(np.asarray(tokens, np.int64).tobytes())
    return h.digest()


@dataclass
class _BlockMeta:
    ref_count: int = 0
    content_hash: Optional[bytes] = None   # set only for FULL immutable blocks


class BlockAllocator:
    """Free-list block allocator with content-hash prefix caching.

    * ``allocate(seq)`` returns (block_ids, num_cached_tokens): full prompt
      blocks whose content hash is already resident are reused (ref_count++)
      and need no recompute; the remainder are fresh blocks.
    * ``free(block_ids)`` decrements refs; cached blocks stay resident until
      evicted LRU when the free list runs dry.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 enable_prefix_caching: bool = True):
        self.block_size = block_size
        self.enable_prefix_caching = enable_prefix_caching
        self.num_blocks = num_blocks
        self.meta: Dict[int, _BlockMeta] = {i: _BlockMeta() for i in range(1, num_blocks)}
        self.free_list: List[int] = list(range(1, num_blocks))  # 0 = null block
        self.hash_to_block: Dict[bytes, int] = {}
        self._lru: List[int] = []          # cached, ref_count==0, oldest first
        # eviction hook (host-RAM KV spill tier, serving/fleet/): called
        # with (block_id, chain_hash) just BEFORE an LRU-resident prefix
        # block's hash registration is dropped — the last moment its
        # device payload is still identifiable by content
        self.on_evict = None

    @property
    def num_free(self) -> int:
        return len(self.free_list) + len(self._lru)

    def _pop_block(self) -> int:
        if self.free_list:
            return self.free_list.pop()
        if self._lru:                      # evict the oldest unreferenced cached block
            blk = self._lru.pop(0)
            h = self.meta[blk].content_hash
            if h is not None:
                if self.on_evict is not None:
                    self.on_evict(blk, h)
                self.hash_to_block.pop(h, None)
            self.meta[blk] = _BlockMeta()
            return blk
        raise CapacityError("out of KV cache blocks")

    def allocate(self, token_ids: Sequence[int]) -> Tuple[List[int], int]:
        """Allocate blocks for a prompt. Returns (block_ids, cached_tokens).
        On OOM this call's partial allocations are rolled back."""
        n_blocks = max(1, -(-len(token_ids) // self.block_size))
        blocks: List[int] = []
        cached_tokens = 0
        parent = b""
        matching = self.enable_prefix_caching
        for bi in range(n_blocks):
            chunk = token_ids[bi * self.block_size:(bi + 1) * self.block_size]
            full = len(chunk) == self.block_size
            h = _hash_block(parent, chunk) if (matching and full) else None
            if h is not None and h in self.hash_to_block:
                blk = self.hash_to_block[h]
                m = self.meta[blk]
                if m.ref_count == 0 and blk in self._lru:
                    self._lru.remove(blk)
                m.ref_count += 1
                blocks.append(blk)
                cached_tokens += self.block_size
                parent = h
                continue
            matching = False                # prefix broken; rest are fresh
            try:
                blk = self._pop_block()
            except CapacityError:
                # roll back this call: prefix-HIT blocks keep their valid
                # hashes; fresh blocks were hashed before their content was
                # written, so the hashes must go or later allocations would
                # prefix-"hit" garbage KV
                n_hit = cached_tokens // self.block_size
                self.free(blocks[:n_hit])
                self.invalidate(blocks[n_hit:])
                raise
            m = self.meta[blk]
            m.ref_count += 1
            if self.enable_prefix_caching and full:
                hh = _hash_block(parent, chunk)
                m.content_hash = hh
                self.hash_to_block[hh] = blk
                parent = hh
            blocks.append(blk)
        return blocks, cached_tokens

    def probe(self, token_ids: Sequence[int]) -> Tuple[int, List[int]]:
        """READ-ONLY prefix-warmth probe: (cached_tokens, hit block ids)
        that :meth:`allocate` WOULD serve from the prefix cache right now.
        Unlike allocate it takes no references, touches no LRU order and
        registers no hashes — schedulers call it per queued request to
        order admissions warm-first, so it must not perturb cache state."""
        if not self.enable_prefix_caching:
            return 0, []
        parent = b""
        blocks: List[int] = []
        cached = 0
        for bi in range(len(token_ids) // self.block_size):
            chunk = token_ids[bi * self.block_size:
                              (bi + 1) * self.block_size]
            parent = _hash_block(parent, chunk)
            blk = self.hash_to_block.get(parent)
            if blk is None:
                break
            blocks.append(blk)
            cached += self.block_size
        return cached, blocks

    def extend(self, blocks: List[int], new_len: int) -> List[int]:
        """Grow a running sequence's block list to cover ``new_len`` tokens.
        On OOM the blocks added by this call are rolled back."""
        need = max(1, -(-new_len // self.block_size))
        added: List[int] = []
        while len(blocks) + len(added) < need:
            try:
                blk = self._pop_block()
            except CapacityError:
                self.free(added)
                raise
            self.meta[blk].ref_count += 1
            added.append(blk)
        blocks.extend(added)
        return blocks

    def free(self, blocks: Sequence[int]):
        for blk in blocks:
            m = self.meta[blk]
            m.ref_count -= 1
            if m.ref_count < 0:
                raise KVCacheStateError(f"double free of block {blk}")
            if m.ref_count == 0:
                if m.content_hash is not None:
                    self._lru.append(blk)  # keep resident for prefix reuse
                else:
                    self.free_list.append(blk)

    def invalidate(self, blocks: Sequence[int]):
        """Free blocks whose pending content was never written (aborted
        admission): drop their hash registration once unreferenced so the
        prefix cache can never serve them. Blocks still referenced by
        another sequence keep their hash — that content predates the
        aborted call and is valid."""
        for blk in blocks:
            m = self.meta[blk]
            m.ref_count -= 1
            if m.ref_count < 0:
                raise KVCacheStateError(f"double free of block {blk}")
            if m.ref_count == 0:
                if m.content_hash is not None:
                    if self.hash_to_block.get(m.content_hash) == blk:
                        del self.hash_to_block[m.content_hash]
                    m.content_hash = None
                self.free_list.append(blk)


class NativeBlockAllocator:
    """ctypes wrapper over the C++ allocator (native/block_allocator.cpp) —
    same interface and identical block-id sequences as :class:`BlockAllocator`
    (asserted by tests). The default allocator (``NXDI_TPU_NATIVE=0``
    selects the Python one)."""

    MAX_BLOCKS = 65536

    def __init__(self, num_blocks: int, block_size: int,
                 enable_prefix_caching: bool = True):
        from .. import native
        import ctypes
        self._ct = ctypes
        self._lib = native.load_library()
        if self._lib is None:
            raise ImportError("native library disabled (NXDI_TPU_NATIVE=0)")
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.enable_prefix_caching = enable_prefix_caching
        self._h = self._lib.nxdi_alloc_create(num_blocks, block_size,
                                              int(enable_prefix_caching))

    def __del__(self):
        lib, h = getattr(self, "_lib", None), getattr(self, "_h", None)
        if lib is not None and h:
            lib.nxdi_alloc_destroy(h)
            self._h = None

    @property
    def num_free(self) -> int:
        return self._lib.nxdi_alloc_num_free(self._h)

    def allocate(self, token_ids: Sequence[int]) -> Tuple[List[int], int]:
        ct = self._ct
        toks = np.ascontiguousarray(np.asarray(token_ids, np.int64))
        max_out = max(1, -(-len(toks) // self.block_size))
        out = (ct.c_int * max_out)()
        cached = ct.c_int(0)
        n = self._lib.nxdi_alloc_allocate(
            self._h, toks.ctypes.data_as(ct.POINTER(ct.c_int64)), len(toks),
            out, max_out, ct.byref(cached))
        if n < 0:
            raise CapacityError("out of KV cache blocks")
        return list(out[:n]), int(cached.value)

    def probe(self, token_ids: Sequence[int]) -> Tuple[int, List[int]]:
        """Read-only prefix-warmth probe (see :meth:`BlockAllocator.probe`)."""
        if not self.enable_prefix_caching:
            return 0, []
        ct = self._ct
        toks = np.ascontiguousarray(np.asarray(token_ids, np.int64))
        max_out = max(1, len(toks) // self.block_size)
        out = (ct.c_int * max_out)()
        cached = self._lib.nxdi_alloc_probe(
            self._h, toks.ctypes.data_as(ct.POINTER(ct.c_int64)),
            len(toks), out, max_out)
        return int(cached), list(out[:cached // self.block_size])

    def extend(self, blocks: List[int], new_len: int) -> List[int]:
        ct = self._ct
        need = max(1, -(-new_len // self.block_size))
        buf = (ct.c_int * max(need, len(blocks)))(*blocks)
        n = self._lib.nxdi_alloc_extend(self._h, buf, len(blocks), new_len,
                                        max(need, len(blocks)))
        if n < 0:
            raise CapacityError("out of KV cache blocks")
        return list(buf[:n])

    def free(self, blocks: Sequence[int]):
        ct = self._ct
        arr = (ct.c_int * len(blocks))(*blocks)
        if self._lib.nxdi_alloc_free(self._h, arr, len(blocks)) < 0:
            raise KVCacheStateError("double free of a KV block")

    def invalidate(self, blocks: Sequence[int]):
        ct = self._ct
        arr = (ct.c_int * len(blocks))(*blocks)
        if self._lib.nxdi_alloc_invalidate(self._h, arr, len(blocks)) < 0:
            raise KVCacheStateError("double free of a KV block")


def make_block_allocator(num_blocks: int, block_size: int,
                         enable_prefix_caching: bool = True):
    """The native C++ allocator, or the Python one under NXDI_TPU_NATIVE=0.
    A native build that fails raises (native.NativeBuildError): which
    allocator runs is a choice, not an accident of the toolchain."""
    from .. import native
    if native.native_enabled():
        return NativeBlockAllocator(num_blocks, block_size,
                                    enable_prefix_caching)
    return BlockAllocator(num_blocks, block_size, enable_prefix_caching)


class BlockKVCacheManager:
    """Host-side owner: spec + cache pytree + allocator + per-seq block tables
    (reference: BlockKVCacheManager + the vLLM-facing surface).

    Telemetry (host-side, no-op while disabled): blocks in-use/total gauges,
    allocation-failure counter, prefix-cache hit-token counter."""

    def __init__(self, spec: BlockKVSpec, mesh: Optional[Mesh] = None,
                 enable_prefix_caching: bool = True):
        self.spec = spec
        self.mesh = mesh
        self.cache = init_block_cache(spec, mesh)
        self.allocator = make_block_allocator(spec.num_blocks, spec.block_size,
                                              enable_prefix_caching)
        self.tables: Dict[int, List[int]] = {}     # seq_id -> block list
        self.lens: Dict[int, int] = {}
        self._hit_blocks: Dict[int, int] = {}      # leading prefix-HIT blocks
        self._tel_occupancy()

    def _tel_registry(self):
        reg = get_registry()
        return reg if reg.enabled else None

    def _tel_occupancy(self, reg=None):
        reg = reg if reg is not None else self._tel_registry()
        if reg is None:
            return
        usable = self.spec.num_blocks - 1          # null block excluded
        tmetrics.kv_blocks_total_gauge(reg).set(usable)
        # num_free counts free-list + unreferenced prefix-cached residents;
        # in-use = blocks some live sequence still references
        tmetrics.kv_blocks_in_use_gauge(reg).set(
            usable - self.allocator.num_free)

    def begin_sequence(self, seq_id: int, token_ids: Sequence[int]
                       ) -> Tuple[List[int], int]:
        if seq_id in self.tables:      # stale table from an unreleased run
            self.end_sequence(seq_id)  # (would otherwise leak its blocks)
        reg = self._tel_registry()
        try:
            if _FAULTS.active:
                _FAULTS.fire("paged_alloc")
            blocks, cached = self.allocator.allocate(token_ids)
        except CapacityError:
            if reg is not None:
                tmetrics.kv_alloc_failures_counter(reg).inc()
            raise
        self.tables[seq_id] = blocks
        self.lens[seq_id] = len(token_ids)
        self._hit_blocks[seq_id] = cached // self.spec.block_size
        if reg is not None:
            if cached:
                tmetrics.prefix_hit_tokens_counter(reg).inc(cached)
            self._tel_occupancy(reg)
        return blocks, cached

    def grow(self, seq_id: int, n_new: int = 1) -> List[int]:
        self.lens[seq_id] += n_new
        try:
            if _FAULTS.active:
                _FAULTS.fire("paged_alloc")
            self.tables[seq_id] = self.allocator.extend(
                self.tables[seq_id], self.lens[seq_id])
        except CapacityError:
            self.lens[seq_id] -= n_new
            reg = self._tel_registry()
            if reg is not None:
                tmetrics.kv_alloc_failures_counter(reg).inc()
            raise
        self._tel_occupancy()
        return self.tables[seq_id]

    def shrink(self, seq_id: int, n_tokens: int = 1) -> List[int]:
        """Inverse of :meth:`grow`: forget the last ``n_tokens`` and free
        blocks no longer covered. Used to roll a sequence back to its
        pre-step state when a decode step fails after growth."""
        if seq_id not in self.tables:
            raise KVCacheStateError(f"shrink of unknown seq_id {seq_id}")
        new_len = self.lens[seq_id] - n_tokens
        if new_len < 0:
            raise KVCacheStateError(
                f"shrink below zero for seq_id {seq_id} "
                f"({self.lens[seq_id]} - {n_tokens})")
        need = max(1, self.spec.blocks_for(new_len))
        blocks = self.tables[seq_id]
        if len(blocks) > need:
            extra = blocks[need:]
            del blocks[need:]
            self.allocator.free(extra)
        self.lens[seq_id] = new_len
        self._tel_occupancy()
        return blocks

    def end_sequence(self, seq_id: int):
        self.allocator.free(self.tables.pop(seq_id))
        self.lens.pop(seq_id)
        self._hit_blocks.pop(seq_id, None)
        self._tel_occupancy()

    def abort_sequence(self, seq_id: int, unwritten=None):
        """End a sequence admitted by a transaction that failed before (or
        while) its prefill wrote KV: prefix-HIT blocks — whose content
        predates the aborted call — are freed normally, but fresh blocks
        are :meth:`~BlockAllocator.invalidate`\\ d so their never-written
        contents can never be served as prefix hits.

        ``unwritten`` (chunked-prefill teardown) overrides the allocator's
        hit/fresh split with an explicit container of block ids whose
        content never fully landed: a prefix HIT on a block another
        still-pending sequence allocated (and hashed) but has not written
        yet is itself unwritten, and must be invalidated — not freed as
        valid — or its garbage KV becomes servable once the last holder
        lets go."""
        blocks = self.tables.pop(seq_id)
        n_hit = self._hit_blocks.pop(seq_id, 0)
        self.lens.pop(seq_id)
        if unwritten is None:
            self.allocator.free(blocks[:n_hit])
            self.allocator.invalidate(blocks[n_hit:])
        else:
            self.allocator.free([b for b in blocks if b not in unwritten])
            self.allocator.invalidate(
                [b for b in blocks if b in unwritten])
        self._tel_occupancy()

    def set_spill_hook(self, hook) -> None:
        """Install ``hook(block_id, chain_hash)`` to run just before a
        prefix-cached resident block is LRU-evicted (the moment its
        content would otherwise become unreachable) — the attach point of
        the host-RAM KV spill tier (serving/fleet/kv_tier.py).

        The hook is keyed by the PYTHON allocator's blake2b chain hashes
        (the same :func:`_hash_block` chain the spill tier and the
        handoff records use), so it requires the Python
        :class:`BlockAllocator`. A native (C++) allocator is swapped for
        an equivalent fresh Python one when NOTHING live depends on it —
        no sequence tables and every block free. The swap may still
        discard unreferenced prefix-cache residency (warm prompts
        recompute once); it can never discard live sequence state —
        swapping with live tables (or referenced blocks) raises typed
        instead. The hook must not raise — the adapter's spill hook
        swallows and counts its own failures (``kv_spill`` fault-point
        contract)."""
        alloc = self.allocator
        if not isinstance(alloc, BlockAllocator):
            if self.tables or alloc.num_free != self.spec.num_blocks - 1:
                raise ConfigurationError(
                    "set_spill_hook needs the Python BlockAllocator's "
                    "eviction callback, and this manager's native "
                    "allocator holds live state — attach the spill "
                    "tier before the first admission (or build with "
                    "NXDI_TPU_NATIVE=0)")
            alloc = BlockAllocator(self.spec.num_blocks,
                                   self.spec.block_size,
                                   alloc.enable_prefix_caching)
            self.allocator = alloc
        alloc.on_evict = hook

    def probe_cached_tokens(self, token_ids: Sequence[int]
                            ) -> Tuple[int, List[int]]:
        """READ-ONLY prefix-warmth probe: (cached_tokens, hit block ids)
        a :meth:`begin_sequence` of ``token_ids`` would currently serve
        from the prefix cache. No references are taken and no LRU/hash
        state moves — safe to call per queued request. The serving engine
        uses it to admit warm-prefix requests first; callers holding
        pending (unwritten) admissions must additionally cut the count at
        the first unwritten block (:func:`cut_cached_at_unwritten`)."""
        return self.allocator.probe(list(token_ids))

    def block_table_array(self, seq_ids: Sequence[int], max_blocks: int
                          ) -> np.ndarray:
        out = np.zeros((len(seq_ids), max_blocks), np.int32)
        for i, sid in enumerate(seq_ids):
            blks = self.tables.get(sid, [])[:max_blocks]
            out[i, :len(blks)] = blks
        return out

    def fill_block_table(self, out: np.ndarray, seq_ids: Sequence[int],
                         counts: List[int]) -> None:
        """Incrementally refresh a cached block-table array IN PLACE:
        rewrite only rows whose block list length differs from the
        ``counts`` snapshot (updated in place too). Valid while tables
        only grow append-only between calls — every serving path that
        shrinks or rebuilds a table (step rollback, preemption,
        end/begin_sequence) drops its scratch and rebuilds from
        :meth:`block_table_array`. Entries past a row's block count are
        left as-is: readers mask them out by position, so their values
        never reach a live attention weight or cache write."""
        for i, sid in enumerate(seq_ids):
            blks = self.tables.get(sid, ())
            n = min(len(blks), out.shape[1])
            if n != counts[i]:
                out[i, :n] = blks[:n]
                counts[i] = n

    @property
    def max_blocks_per_seq(self) -> int:
        return max((len(b) for b in self.tables.values()), default=1)
