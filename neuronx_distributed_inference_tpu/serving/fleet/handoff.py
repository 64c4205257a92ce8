"""Disaggregated prefill → decode handoff (ROADMAP item 3 prong c).

A **prefill-role** engine runs admission + chunked prefill only (any
:class:`~..adapter.PagedEngineAdapter`); once a sequence's first token
has materialized, :func:`capture_handoff` snapshots it into a JSON-safe
**handoff record** — a superset of the serialized
:class:`~...resilience.preemption.Preempted` requeue payload plus the
sequence's fully-written KV block payloads (content-chain-hash keyed,
read device→host) — and releases it from the prefill engine.

A **decode-role** engine admits the record with :func:`admit_handoff`:
the block payloads seed its :class:`~.kv_tier.HostKVSpillTier`, and the
record's recompute prompt goes through the ordinary transactional
``add_requests`` path, whose spill-restore step re-admits the KV by
async H2D copy instead of recompute-prefill. Because the record's tokens
ride the exact ``Preempted`` replay contract (prompt + every sampled
token; the last sampled token's KV intentionally unwritten), the decode
engine's greedy continuation is **bit-identical to a single-engine run**
(pinned by ``tests/test_fleet.py``).

The record is pure JSON (payloads base64-encoded with dtype/shape), so
it crosses process boundaries: ``json.dumps(handoff_to_json(rec))`` on
the prefill host, ``handoff_from_json(json.loads(...))`` on the decode
host. Failures are typed :class:`~...resilience.errors.HandoffError`
with the failing side's engine state unchanged (capture reads before it
releases; admission is transactional), and the ``handoff`` fault point
makes both sides' failure paths deterministic in tests.

**Live decode→decode migration** (ISSUE 17) generalizes the same wire
form: :func:`migrate` captures a MID-DECODE sequence off one fleet
replica (fully-written blocks, delivered tokens, remaining deadline
budget, the fleet trace id — all riding the ``nxdi-handoff-v1`` record
with backward-compatible field additions ``kind`` / ``delivered_tokens``
/ ``trace``) and re-admits it on another replica so the client stream
CONTINUES bit-identically: the destination seeds its spill tier, the
transactional admission restores the KV in one batched H2D write, and
only the uncovered suffix recomputes. The source sequence is released
ONLY after the destination accepted the record, so a failure at either
fault point (``migrate_capture`` / ``migrate_admit``) leaves BOTH
engines unchanged — free pools exact, the un-migrated stream still
serving on the source.
"""

from __future__ import annotations

import base64
from typing import Any, Dict, Optional

import numpy as np

from ...resilience.errors import HandoffError, ServingError
from ...resilience.faults import FAULTS as _FAULTS
from ...resilience.preemption import Preempted
from ...telemetry import get_registry
from ...telemetry import metrics as tmetrics
from ...telemetry.request_trace import trace_of
from ...telemetry.trace import get_recorder as _get_recorder

__all__ = ["HANDOFF_SCHEMA", "capture_handoff", "admit_handoff",
           "migrate", "handoff_to_json", "handoff_from_json"]

HANDOFF_SCHEMA = "nxdi-handoff-v1"


def _capture(adapter, seq_id: int, *, point: str, reason: str,
             now: Optional[float] = None):
    """Read-only capture core shared by :func:`capture_handoff` and
    :func:`migrate`: snapshot one RUNNING sequence into a handoff
    record WITHOUT releasing it (the caller decides when — handoff
    releases immediately, migration only after the destination accepted
    the record). ``point`` is the fault point traversed; ``reason``
    lands in the ``Preempted`` payload. Returns ``(record, pre)``;
    raises :class:`HandoffError` with the adapter unchanged."""
    st = adapter.seqs.get(seq_id)
    if st is None:
        state = ("still mid-prefill" if seq_id in getattr(
            adapter, "_chunks", {}) else "not running")
        raise HandoffError(
            f"cannot capture seq_id {seq_id}: {state} — hand off after "
            "its first token materializes", seq_ids=(seq_id,))
    if adapter.app.spec.sparse is not None:
        from ...models.model_base import sparse_refusal
        raise HandoffError(sparse_refusal(["host KV spill / handoff"]),
                           seq_ids=(seq_id,))
    mgr = adapter.app.kv_mgr
    bs = mgr.spec.block_size
    table = mgr.tables[seq_id]
    try:
        if _FAULTS.active:
            # literal point names: the fault-points lint pass checks
            # fire() sites statically, so no parameterized fire here
            if point == "migrate_capture":
                _FAULTS.fire("migrate_capture")
            else:
                _FAULTS.fire("handoff")
        # full blocks whose every slot was written: (bi+1)*bs <= position
        # (position indexes the last SAMPLED token, whose KV is unwritten)
        cache = adapter.app.cache
        kv_blocks = []
        parent = b""
        for bi in range(st.position // bs):
            parent = _chain_hash(parent, st.tokens[bi * bs:(bi + 1) * bs])
            blk = table[bi]
            kv_blocks.append({
                "hash": parent,
                "k": np.asarray(cache["k"][:, blk]),
                "v": np.asarray(cache["v"][:, blk]),
            })
    except ServingError:
        raise
    except Exception as e:
        raise HandoffError(
            f"{reason} capture of seq_id {seq_id} failed; the sequence "
            "is still running on the source engine",
            seq_ids=(seq_id,)) from e
    pre = Preempted(
        seq_id=seq_id, tokens=tuple(st.tokens), prompt_len=st.prompt_len,
        n_generated=len(st.tokens) - st.prompt_len, reason=reason,
        deadline=st.deadline, meta=st.meta)
    record = {
        "schema": HANDOFF_SCHEMA,
        "preempted": pre.to_json(now=now),
        "block_size": bs,
        "kv_blocks": kv_blocks,
        # v1-compatible field additions (ISSUE 17): admitters that
        # predate them ignore unknown keys, so old records stay valid
        "kind": reason,
        "delivered_tokens": pre.n_generated,
        "trace": trace_of(pre.meta),
    }
    return record, pre


def capture_handoff(adapter, seq_id: int,
                    now: Optional[float] = None) -> Dict[str, Any]:
    """Snapshot one RUNNING sequence of a prefill-role adapter into a
    handoff record and release it. The record holds the serialized
    ``Preempted`` payload (tokens = prompt + everything sampled,
    remaining deadline budget, meta passthrough) plus the K/V payloads of
    every fully-written block (positions ``[0, position)`` — the last
    sampled token's KV is intentionally absent, exactly like a
    preemption-requeue). Raises :class:`HandoffError` for a pending
    (mid-prefill) or unknown seq_id, leaving the adapter unchanged."""
    record, pre = _capture(adapter, seq_id, point="handoff",
                           reason="handoff", now=now)
    adapter.release([seq_id])
    kv_blocks = record["kv_blocks"]
    rec = _get_recorder()
    if rec.enabled:
        # meta rides the record verbatim, so the trace id recorded here
        # is the SAME one the decode side stitches onto at admit
        rec.instant("handoff.send", cat="fleet", seq_id=int(seq_id),
                    tokens=len(pre.tokens), blocks=len(kv_blocks),
                    engine=adapter.engine_name, trace=trace_of(pre.meta))
    reg = get_registry()
    if reg.enabled:
        tmetrics.handoffs_counter(reg).inc(role="send")
    return record


def admit_handoff(adapter, record: Dict[str, Any], seq_id: int,
                  now: Optional[float] = None) -> Dict[int, int]:
    """Admit a handoff record on a decode-role adapter: seed its spill
    tier with the record's block payloads, then run the ordinary
    transactional ``add_requests`` — the spill-restore step re-admits the
    KV via H2D copy and only the uncovered suffix recomputes. Returns the
    adapter's first-token dict (``{}`` under a deferred prefill budget).
    Raises :class:`HandoffError` for a malformed record or a decode
    adapter without a spill tier; admission failures propagate typed with
    the decode engine rolled back (transactional)."""
    tier = getattr(adapter, "_kv_tier", None)
    if tier is None:
        raise HandoffError(
            "decode-role adapter has no kv_spill_tier — build it with "
            "PagedEngineAdapter(app, kv_spill_tier=HostKVSpillTier(...)) "
            "so the handoff KV can be restored instead of recomputed")
    try:
        if _FAULTS.active:
            _FAULTS.fire("handoff")
        if record.get("schema") != HANDOFF_SCHEMA:
            raise KeyError(f"not an {HANDOFF_SCHEMA} record: "
                           f"schema={record.get('schema')!r}")
        if int(record["block_size"]) != adapter.app.kv_mgr.spec.block_size:
            raise KeyError(
                f"handoff block_size {record['block_size']} != decode "
                f"engine's {adapter.app.kv_mgr.spec.block_size}")
        pre = Preempted.from_json(record["preempted"], now=now)
        payloads = {b["hash"]: {"k": b["k"], "v": b["v"]}
                    for b in record["kv_blocks"]}
    except ServingError:
        raise
    except Exception as e:
        raise HandoffError(
            f"handoff admission failed before any decode-engine state "
            f"changed: {e}") from e
    tier.seed(payloads)
    first = adapter.add_requests(**pre.admission_kwargs(seq_id=seq_id,
                                                        now=now))
    rec = _get_recorder()
    if rec.enabled:
        rec.instant("handoff.recv", cat="fleet", seq_id=int(seq_id),
                    tokens=len(pre.tokens), blocks=len(payloads),
                    engine=adapter.engine_name, trace=trace_of(pre.meta))
    reg = get_registry()
    if reg.enabled:
        tmetrics.handoffs_counter(reg).inc(role="recv")
    return first


def migrate(router, request_id: str, src: Optional[str] = None,
            dst: Optional[str] = None,
            now: Optional[float] = None) -> str:
    """Live decode→decode migration of one in-flight fleet request:
    capture its mid-decode sequence off the source replica (fully
    written blocks via the spill-tier wire form, delivered tokens,
    remaining deadline budget, the fleet trace id) and re-admit it on
    the destination so the client stream CONTINUES bit-identically —
    the KV moves, only the uncovered suffix recomputes.

    ``src`` defaults to the replica currently serving the request (and
    must match it when given); ``dst`` defaults to the warmest other
    healthy replica with a spill tier (``EngineRouter._pick_migration_
    dst``). Returns the destination replica name.

    Failure semantics (the ``migrate_capture`` / ``migrate_admit``
    fault points): the source sequence is released ONLY after the
    destination accepted the record, so a typed :class:`HandoffError`
    from either side leaves BOTH engines unchanged — free pools exact,
    the un-migrated stream keeps serving on the source."""
    req = router._requests.get(request_id)
    if req is None or req.stream.finished:
        raise HandoffError(
            f"cannot migrate request {request_id!r}: not in flight on "
            "this router")
    if src is None:
        src = req.replica
    elif src != req.replica:
        raise HandoffError(
            f"request {request_id!r} is served by replica "
            f"{req.replica!r}, not {src!r}")
    src_rep = router._replica(src)
    if src_rep.state == "dead":
        raise HandoffError(
            f"source replica {src!r} is dead — its requests fail over "
            "through the requeue-recompute path, not migration")
    if dst is None:
        dst = router._pick_migration_dst(req, exclude=src)
    dst_rep = router._replica(dst)
    if dst == src:
        raise HandoffError(f"migration source and destination are both "
                           f"{src!r}")
    tier = getattr(dst_rep.engine.adapter, "_kv_tier", None)
    if tier is None:
        raise HandoffError(
            f"destination replica {dst!r} has no kv_spill_tier — the "
            "migrated KV could not be restored, only recomputed; build "
            "the decode adapters with kv_spill_tier=HostKVSpillTier(...)")
    # flush already-sampled tokens into the fleet stream first so the
    # delivered count and the capture agree exactly
    router._pump(req)
    if req.stream.finished or request_id not in router._requests:
        raise HandoffError(
            f"request {request_id!r} finished while migration started — "
            "nothing to move")
    sid = src_rep.engine.seq_id_of(request_id)
    if sid is None:
        raise HandoffError(
            f"request {request_id!r} is not running on {src!r} yet "
            "(queued or mid-prefill) — migrate after its first token "
            "materializes")
    record, pre = _capture(src_rep.engine.adapter, sid,
                           point="migrate_capture", reason="migrate",
                           now=now)
    delivered = req.stream.n_tokens
    if tuple(pre.tokens) != tuple(req.prompt) + tuple(req.stream.tokens):
        raise HandoffError(
            f"request {request_id!r} capture disagrees with the fleet "
            f"stream ({len(pre.tokens)} captured tokens vs "
            f"{len(req.prompt)} prompt + {delivered} delivered) — "
            "source unchanged, not migrating")
    # the adapter's prompt_len/n_generated describe its LOCAL admission
    # (after a prior requeue or migration the recompute prompt already
    # contains earlier generations), so re-anchor the record to the
    # FLEET-level split — exactly what EngineRouter._requeue submits
    pre = Preempted(
        seq_id=pre.seq_id, tokens=pre.tokens,
        prompt_len=len(req.prompt), n_generated=delivered,
        reason="migrate", deadline=pre.deadline, meta=pre.meta)
    record["preempted"] = pre.to_json(now=now)
    record["delivered_tokens"] = delivered
    remaining = req.max_new_tokens - delivered
    if remaining <= 0:
        raise HandoffError(
            f"request {request_id!r} has no remaining token budget — "
            "let it finish on the source")
    payloads = {b["hash"]: {"k": b["k"], "v": b["v"]}
                for b in record["kv_blocks"]}
    with router._scoped_registry(dst):
        try:
            if _FAULTS.active:
                _FAULTS.fire("migrate_admit")
        except ServingError:
            raise
        except Exception as e:
            raise HandoffError(
                f"migration admit of request {request_id!r} on {dst!r} "
                "failed before any destination state changed; the "
                "stream keeps serving on the source") from e
        tier.seed(payloads)
        inner = dst_rep.engine.submit_record(
            pre, remaining, stop_tokens=req.stop_tokens,
            request_id=request_id)
    # the destination owns the request now: tear the source copy down
    # (cancel finishes the OLD inner stream and releases the sequence's
    # device state; the fleet stream never sees it — rebind below)
    with router._scoped_registry(src):
        src_rep.engine.cancel(request_id)
    req.inner = inner
    req.replica = dst
    req.pumped = 0
    router.stats["migrations"] += 1
    router.stats["migrated_kv_tokens"] += (
        len(record["kv_blocks"]) * int(record["block_size"]))
    rec = _get_recorder()
    if rec.enabled:
        tid = trace_of(pre.meta)
        rec.instant("handoff.send", cat="fleet", seq_id=int(sid),
                    tokens=len(pre.tokens), blocks=len(payloads),
                    engine=src_rep.engine.adapter.engine_name, trace=tid)
        rec.instant("handoff.recv", cat="fleet", seq_id=int(sid),
                    tokens=len(pre.tokens), blocks=len(payloads),
                    engine=dst_rep.engine.adapter.engine_name, trace=tid)
        rec.instant("trace.requeue", cat="request", trace=tid,
                    request_id=request_id, reason="migrate",
                    from_replica=src, to_replica=dst,
                    n_delivered=delivered)
    reg = get_registry()
    if reg.enabled:
        tmetrics.handoffs_counter(reg).inc(role="migrate_send")
        tmetrics.handoffs_counter(reg).inc(role="migrate_recv")
    return dst


# ---------------------------------------------------------------------------
# JSON wire format (cross-process)
# ---------------------------------------------------------------------------

def handoff_to_json(record: Dict[str, Any]) -> Dict[str, Any]:
    """Pure-JSON form of a handoff record: block payloads become base64
    raw bytes + dtype/shape (bfloat16 and friends round-trip via
    ml_dtypes names), hashes become hex strings."""
    out = dict(record)
    blocks = []
    for b in record["kv_blocks"]:
        k, v = np.asarray(b["k"]), np.asarray(b["v"])
        blocks.append({
            "hash": b["hash"].hex(),
            "dtype": k.dtype.name,
            "shape": list(k.shape),
            "k": base64.b64encode(k.tobytes()).decode("ascii"),
            "v": base64.b64encode(v.tobytes()).decode("ascii"),
        })
    out["kv_blocks"] = blocks
    return out


def handoff_from_json(data: Dict[str, Any]) -> Dict[str, Any]:
    """Inverse of :func:`handoff_to_json`. Raises
    :class:`HandoffError` on malformed input."""
    try:
        out = dict(data)
        blocks = []
        for b in data["kv_blocks"]:
            dtype = _np_dtype(b["dtype"])
            shape = tuple(int(s) for s in b["shape"])
            blocks.append({
                "hash": bytes.fromhex(b["hash"]),
                "k": np.frombuffer(base64.b64decode(b["k"]),
                                   dtype=dtype).reshape(shape),
                "v": np.frombuffer(base64.b64decode(b["v"]),
                                   dtype=dtype).reshape(shape),
            })
        out["kv_blocks"] = blocks
        return out
    except HandoffError:
        raise
    except Exception as e:
        raise HandoffError(f"malformed handoff JSON: {e}") from e


def _np_dtype(name: str) -> np.dtype:
    """numpy dtype from its name, reaching into ml_dtypes for the
    accelerator dtypes numpy itself does not know (bfloat16, fp8)."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def _chain_hash(parent: bytes, tokens) -> bytes:
    from ...modules.block_kv_cache import _hash_block
    return _hash_block(parent, list(tokens))
