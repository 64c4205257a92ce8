"""Compiled-graph observatory — static truth about the serving graphs.

AOT-lowers and compiles every (kind, bucket, batch) graph an application's
bucket ladders imply (``jax.jit(...).lower(...).compile()`` — no execution,
no device state touched) and harvests XLA's own static analysis:

  * ``cost_analysis()``   — flops and bytes accessed per invocation;
  * ``memory_analysis()`` — argument/output/temp byte footprints (peak ≈
    arguments + outputs + temps);
  * compile wall time per graph (the cold-start cost item 5 of the
    ROADMAP tracks: ``compile_plus_first_gen_s`` grew 5.7s→14.3s).

All of it works on the CPU backend — this is the evidence base for
re-earning the frozen kernel-admission constants (``model_base.py``
heuristics) and for AOT warm-start work, WITHOUT waiting for TPU hardware
(cf. full-program XLA compilation analysis, PAPERS.md arxiv 1810.09868).

Per-graph results land in the metrics registry when one is live
(``nxdi_compile_seconds`` / ``nxdi_graph_flops`` / ``nxdi_graph_bytes`` /
``nxdi_graph_peak_bytes``, labels ``kind``+``bucket``) and in the returned
report dict (schema ``nxdi-graph-report-v1``), which also carries a static
roofline estimate per bucket: arithmetic intensity, the
compute-vs-memory-bound verdict, and the estimated step time projected
onto the published peaks of a named ``device_kind`` (utils/device.py
``DEVICE_PEAKS``; default v5e — 197 TFLOP/s bf16, 819 GB/s HBM).

tests/test_graph_observatory.py drives this on the tiny synthetic model:
graph counts, flops and bytes are exact on any backend; the projected
step time is an estimate, never a measurement (those come from
``benchmark/run.py`` on the chip).

Compiling through fresh ``jax.jit`` wrappers keeps the application's own
jit cache keys untouched — running the observatory can never change what
the serving path executes (the XLA persistent compile cache still
deduplicates the work).

Sharding observatory (multichip census)
---------------------------------------
When the application's mesh spans more than one device the same AOT
compile yields the **post-SPMD partitioned** HLO, and
:func:`census_collectives` reads every collective out of it: op kind
(all-reduce / all-gather / reduce-scatter / collective-permute /
all-to-all), payload bytes, and the replica-group shape mapped back to
the mesh axes the groups ride (``comm="tp"`` / ``"dp"`` / ``"ep+tp"`` /
…) plus the wire payload dtype (``f32`` / ``s8`` / ``f8e4m3fn`` — the
dimension that makes the quantized-collective win census-visible). The
census lands per graph in the report, in the
``nxdi_graph_collectives_total`` / ``nxdi_graph_collective_bytes``
gauges (labels ``kind``+``comm``+``dtype``), and in a third roofline
leg: the estimated collective wire time at the table's ICI bandwidth
(v5e: 200 GB/s) and its assumed per-chip DCN share (25 GB/s; axes named
by the ``parallel.mesh.Topology`` spec — by default ``dp``, the
outermost axis — are priced at DCN, everything else at ICI), upgrading the per-graph verdict to compute- vs memory- vs
**comm**-bound — the regime EQuARX (PAPERS.md arxiv 2506.17615) shows
dominates DCN-scale decode. The leg also reports ``comm_bytes_saved``:
wire bytes the sub-fp32 payloads avoid relative to an fp32 exchange of
the same shapes.

Collectives censused inside a ``while``/``scan`` body are counted once
(static census, not dynamic executions). On a single-device mesh the
census doubles as a guard: the unsharded graphs must contain ZERO
collectives (an accidental ``shard_map``/``psum`` leaking into the
1-device path raises here instead of silently running).

``scripts/check_spmd_sharding.py`` builds on this census as a tier-1
lint: it compiles a pinned multichip graph set, fails on the SPMD
partitioner's involuntary-full-rematerialization pattern, and diffs the
census against the committed golden (``artifacts/spmd_golden.json``).
"""

from __future__ import annotations

import contextlib
import os
import re
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from . import metrics as tmetrics
from .registry import get_registry
from ..parallel.mesh import Topology, topology_from_env
from ..utils.device import V5E, device_peaks

__all__ = ["analyze_app", "census_collectives", "aggregate_census",
           "comm_roofline_seconds", "mesh_comm_labels",
           "capture_compiler_stderr", "REMAT_WARNING_RE", "SPMD_CHANNEL_RE",
           "GRAPH_REPORT_SCHEMA", "SHARDING_REPORT_SCHEMA",
           "COLLECTIVE_KINDS"]

GRAPH_REPORT_SCHEMA = "nxdi-graph-report-v1"
SHARDING_REPORT_SCHEMA = "nxdi-sharding-report-v1"

# ---------------------------------------------------------------------------
# SPMD partitioner warning channel (shared by __graft_entry__'s multichip
# runner and scripts/check_spmd_sharding.py — one copy of the spellings)
# ---------------------------------------------------------------------------

# the partitioner's replicate-then-partition last resort, spelled
# differently across XLA builds (older W-lines: "[SPMD] Involuntary full
# rematerialization. ... SPMD will replicate the tensor"; newer E-lines:
# "[spmd] Involuntary full rematerialization. The compiler was not able
# to go from sharding ...") — match the stable core phrase
REMAT_WARNING_RE = re.compile(r"involuntary full rematerialization", re.I)
SPMD_CHANNEL_RE = re.compile(r"\[spmd\]", re.I)


@contextlib.contextmanager
def capture_compiler_stderr(counts: Optional[Dict[str, int]] = None,
                            tee: bool = True):
    """Capture everything written to fd 2 (Python AND C++ — the SPMD
    partitioner logs through glog) around a compile. Yields a one-element
    list holding the captured text after exit. With ``tee``, bytes are
    written THROUGH to the real stderr as they arrive (a pump thread off
    a pipe) — a hard kill mid-compile loses the counts but not the live
    warning tail the multichip runner's log used to stream. With
    ``counts``, accumulates ``spmd_warnings`` (all [SPMD] channel lines)
    and ``involuntary_remat`` (the replicate-then-partition subset).
    Degrades to a no-op when fd 2 is not a real descriptor."""
    out: List[str] = [""]
    # glog/XLA logs to LITERAL fd 2, not sys.stderr — which under test
    # runners (pytest capture) is a temp-file wrapper on another fd
    fd = 2
    try:
        saved = os.dup(fd)
    except OSError:
        yield out
        return
    read_fd, write_fd = os.pipe()
    chunks: List[bytes] = []

    def _pump():
        while True:
            try:
                data = os.read(read_fd, 65536)
            except OSError:
                break
            if not data:
                break
            chunks.append(data)
            if tee:
                try:
                    os.write(saved, data)
                except OSError:
                    pass
        os.close(read_fd)

    pump = threading.Thread(target=_pump, daemon=True)
    pump.start()
    try:
        sys.stderr.flush()
        os.dup2(write_fd, fd)
        yield out
    finally:
        sys.stderr.flush()
        os.dup2(saved, fd)
        os.close(write_fd)      # EOF to the pump (fd now points at saved)
        pump.join(timeout=10.0)
        if not pump.is_alive():
            os.close(saved)
        # else: pump stalled on a blocked downstream write — leak
        # `saved` rather than free an fd number the thread still tees to
        out[0] = b"".join(list(chunks)).decode("utf-8", "replace")
        if counts is not None:
            counts["involuntary_remat"] += len(
                REMAT_WARNING_RE.findall(out[0]))
            counts["spmd_warnings"] += sum(
                1 for l in out[0].splitlines() if SPMD_CHANNEL_RE.search(l))


# ---------------------------------------------------------------------------
# HLO collective census
# ---------------------------------------------------------------------------

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")

# one HLO instruction line: "%name = <type> <op>(...), attr=..., ..."
# (async pairs: count the -start, skip the -done — one wire transfer)
_COLLECTIVE_LINE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.-]+\s*=\s*(?P<type>\([^)]*\)|\S+)\s+"
    r"(?P<op>" + "|".join(COLLECTIVE_KINDS) + r")(?P<suffix>-start|-done)?\(")

# dtype tokens are arbitrary letter/digit runs (f32, bf16, f8e4m3b11fnuz)
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "s4": 1, "u4": 1,
}

_EXPLICIT_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^=]*?\})\}")
_IOTA_GROUPS_RE = re.compile(
    r"replica_groups=\[(?P<dims>[0-9,]+)\]<=\[(?P<reshape>[0-9,]+)\]"
    r"(?:T\((?P<perm>[0-9,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{(\{[^=]*?\})\}")


def _shape_payload(type_str: str, async_start: bool = False
                   ) -> Tuple[int, str, int]:
    """(bytes, dtype, element count) of an HLO result type. A sync tuple
    result (a variadic combined collective) transfers EVERY element; an
    async ``-start`` tuple carries (operand..., result) where the earlier
    elements alias inputs already counted — only the LAST element is the
    transferred output. ``dtype`` is the first transferred shape's element
    type token (variadic collectives are homogeneous in practice)."""
    shapes = _SHAPE_RE.findall(type_str)
    if not shapes:
        return 0, "f32", 0
    if async_start:
        # legacy 4-element permute-start tuples trail u32[] context
        # scalars after the result — strip them before taking the last
        while len(shapes) > 1 and shapes[-1][1] == "" and \
                shapes[-1][0] in ("u32", "s32"):
            shapes.pop()
        shapes = shapes[-1:]
    total = 0
    elems = 0
    for dt, dims in shapes:
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
        elems += n
    return total, shapes[0][0], elems


def _shape_bytes(type_str: str, async_start: bool = False) -> int:
    return _shape_payload(type_str, async_start)[0]


def _parse_int_groups(body: str) -> List[Tuple[int, ...]]:
    return [tuple(int(x) for x in grp.split(",") if x.strip())
            for grp in re.findall(r"\{([0-9,\s]*)\}", body)]


def _iota_groups(dims: Sequence[int], reshape: Sequence[int],
                 perm: Optional[Sequence[int]]) -> List[Tuple[int, ...]]:
    """Expand the V2 iota replica-group syntax
    ``[g,s]<=[r...]T(p...)``: arange(prod) reshaped to ``r``, transposed
    by ``p``, re-reshaped to ``g`` groups of ``s``."""
    ids = np.arange(int(np.prod(reshape))).reshape(tuple(reshape))
    if perm is not None:
        ids = ids.transpose(tuple(perm))
    ids = ids.reshape(tuple(dims))
    return [tuple(int(x) for x in row) for row in ids]


def _line_groups(line: str) -> Optional[List[Tuple[int, ...]]]:
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        dims = [int(x) for x in m.group("dims").split(",")]
        reshape = [int(x) for x in m.group("reshape").split(",")]
        perm = ([int(x) for x in m.group("perm").split(",")]
                if m.group("perm") else None)
        return _iota_groups(dims, reshape, perm)
    m = _EXPLICIT_GROUPS_RE.search(line)
    if m:
        return _parse_int_groups(m.group(1))
    return None


def mesh_comm_labels(mesh) -> Dict[frozenset, str]:
    """Map replica-group *signatures* (frozenset of frozenset of LOGICAL
    device indices — position in ``mesh.devices.flat``, which is the
    device-assignment order the partitioned HLO numbers its partitions
    in) to the mesh-axis subsets they ride, e.g. ``{{0,1},{2,3}} ->
    "tp"`` on a dp2xtp2 mesh. Only axes with extent > 1 participate."""
    shape = tuple(mesh.devices.shape)
    names = tuple(mesh.axis_names)
    logical = np.arange(int(np.prod(shape))).reshape(shape)
    live = [i for i, s in enumerate(shape) if s > 1]
    out: Dict[frozenset, str] = {}
    for bits in range(1, 1 << len(live)):
        subset = [live[i] for i in range(len(live)) if bits & (1 << i)]
        rest = [i for i in range(len(shape)) if i not in subset]
        grouped = logical.transpose(rest + subset).reshape(
            -1, int(np.prod([shape[i] for i in subset])))
        sig = frozenset(frozenset(int(x) for x in row) for row in grouped)
        out.setdefault(sig, "+".join(names[i] for i in subset))
    return out


def _groups_label(groups: List[Tuple[int, ...]],
                  labels: Optional[Dict[frozenset, str]]) -> str:
    if labels is None:
        return "unmapped"
    sig = frozenset(frozenset(g) for g in groups)
    return labels.get(sig, "other")


def _pairs_label(pairs: List[Tuple[int, ...]],
                 labels: Optional[Dict[frozenset, str]]) -> str:
    """collective-permute has source→target pairs, not groups: the comm
    axis is the smallest axis subset within whose groups every pair
    stays (a tp-ring shift maps to "tp")."""
    if labels is None:
        return "unmapped"
    if not pairs:
        # unparseable/empty pairs would vacuously match EVERY subset —
        # surface them as unmatched instead of mislabeling (and
        # mispricing) the permute
        return "other"
    best = None
    for sig, label in labels.items():
        if all(any(s in grp and t in grp for grp in sig)
               for s, t in pairs):
            if best is None or len(label) < len(best):
                best = label
    return best or "other"


def census_collectives(hlo_text: str, mesh=None) -> List[Dict[str, Any]]:
    """Census every collective op in post-SPMD optimized HLO text.

    Returns one entry per op occurrence: ``{"kind", "comm", "dtype",
    "bytes", "elems", "elem_bytes", "group_size"}`` where ``kind`` is the
    op with underscores (``all_reduce``…), ``comm`` names the mesh-axis
    subset the replica groups ride (via :func:`mesh_comm_labels`;
    ``"unmapped"`` without a mesh, ``"other"`` when groups match no axis
    subset), ``dtype`` is the wire payload element type (``f32``, ``s8``,
    ``f8e4m3fn``…), ``bytes`` the op's result-tensor payload and
    ``elems``/``elem_bytes`` its element count and per-element wire width.
    Async ``-start``/``-done`` pairs are counted once (at the start)."""
    labels = mesh_comm_labels(mesh) if mesh is not None else None
    entries: List[Dict[str, Any]] = []
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_LINE_RE.match(line)
        if not m or m.group("suffix") == "-done":
            continue
        kind = m.group("op")
        if kind == "collective-permute":
            pm = _PAIRS_RE.search(line)
            pairs = _parse_int_groups(pm.group(1)) if pm else []
            comm = _pairs_label(pairs, labels)
            group_size = 2
        else:
            groups = _line_groups(line) or []
            comm = _groups_label(groups, labels) if groups else "other"
            group_size = max((len(g) for g in groups), default=1)
        nbytes, dtype, elems = _shape_payload(m.group("type"),
                                              m.group("suffix") == "-start")
        entries.append({
            "kind": kind.replace("-", "_"),
            "comm": comm,
            "dtype": dtype,
            "bytes": nbytes,
            "elems": elems,
            "elem_bytes": _DTYPE_BYTES.get(dtype, 4),
            "group_size": group_size,
        })
    return entries


def aggregate_census(entries: Sequence[Dict[str, Any]]
                     ) -> Dict[str, Dict[str, Any]]:
    """Aggregate per-op census entries to ``{"kind@comm@dtype": {"count",
    "bytes"}}`` — the shape the golden diff and the gauges key on. The
    dtype leg makes quantized (s8/f8) wire payloads first-class: an int8
    ring exchange and an fp32 all-reduce never fold into one bucket."""
    out: Dict[str, Dict[str, Any]] = {}
    for e in entries:
        key = f"{e['kind']}@{e['comm']}@{e.get('dtype', 'f32')}"
        slot = out.setdefault(key, {"count": 0, "bytes": 0})
        slot["count"] += 1
        slot["bytes"] += e["bytes"]
    return out


# ring-model wire-byte factors per collective kind: how many times the
# result tensor's bytes cross the wire per participating device
# (g = replica-group size)
def _wire_factor(kind: str, group_size: int) -> float:
    g = max(group_size, 2)
    if kind == "all_reduce":         # reduce-scatter + all-gather ring
        return 2.0 * (g - 1) / g
    if kind == "reduce_scatter":     # result is the 1/g shard
        return float(g - 1)
    if kind == "collective_permute":
        return 1.0
    # all_gather / all_to_all: result is the full tensor
    return (g - 1) / g


def _wire_bytes(entry: Dict[str, Any]) -> float:
    # element byte-width comes from the CENSUS ENTRY — the op's actual
    # wire payload dtype, not the graph dtype — so a quantized s8
    # all-reduce prices at a quarter of the f32 one. Entries from older
    # callers without the dtype leg fall back to their total bytes.
    if "elems" in entry and "elem_bytes" in entry:
        b = float(entry["elems"] * entry["elem_bytes"])
    else:
        b = float(entry["bytes"])
    return _wire_factor(entry["kind"], entry["group_size"]) * b


def _wire_bytes_saved(entry: Dict[str, Any]) -> float:
    """Wire bytes this op avoids relative to an fp32 exchange of the same
    shape — nonzero only for sub-fp32 *numeric* payloads (s8/u8/f8…), the
    quantized-collective win. Bool masks (pred) are not savings."""
    eb = entry.get("elem_bytes", 4)
    if eb >= 4 or entry.get("dtype") == "pred" or "elems" not in entry:
        return 0.0
    return (_wire_factor(entry["kind"], entry["group_size"])
            * entry["elems"] * (4 - eb))


def comm_roofline_seconds(entries: Sequence[Dict[str, Any]],
                          ici_gbps: float, dcn_gbps: float,
                          topology: Optional[Topology] = None) -> float:
    """Estimated wire time of one invocation's collectives under the
    assumed link bandwidths (GB/s). Traffic over axes the ``topology``
    marks as DCN-crossing (default: :func:`~..parallel.mesh
    .topology_from_env` — ``dp``, the outermost, DCN-friendly mesh axis)
    is priced at DCN bandwidth; every other axis (and unmapped/other
    groups) rides ICI."""
    if topology is None:
        topology = topology_from_env()
    total = 0.0
    for e in entries:
        axes = set(e["comm"].split("+"))
        bw = dcn_gbps if topology.is_dcn(axes) else ici_gbps
        if bw > 0:
            total += _wire_bytes(e) / (bw * 1e9)
    return total


def _cost(compiled) -> Tuple[float, float]:
    """(flops, bytes accessed) from XLA cost analysis; zeros when the
    backend reports nothing. Handles both the dict and the legacy
    list-of-dicts return shape."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return 0.0, 0.0
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = ca or {}
    return (float(ca.get("flops", 0.0) or 0.0),
            float(ca.get("bytes accessed", 0.0) or 0.0))


def _memory(compiled) -> Optional[Dict[str, int]]:
    """Byte footprints from XLA memory analysis; None when the backend
    does not expose it. ``peak_bytes`` approximates live memory as
    arguments + outputs + temps (donated aliases excluded by XLA)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None

    def g(attr: str) -> int:
        return int(getattr(ma, attr, 0) or 0)

    out = {
        "argument_bytes": g("argument_size_in_bytes"),
        "output_bytes": g("output_size_in_bytes"),
        "temp_bytes": g("temp_size_in_bytes"),
        "alias_bytes": g("alias_size_in_bytes"),
        "generated_code_bytes": g("generated_code_size_in_bytes"),
    }
    out["peak_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                         + out["temp_bytes"])
    return out


def _graph_entries(app) -> List[Tuple[str, str, Callable[[], Tuple]]]:
    """Enumerate the (kind, bucket_label, build) entries of ``app``'s
    bucket ladders — the same graphs ``warmup()`` would run, but built
    through FRESH jit wrappers so lowering never touches the app's
    compiled-callable cache. ``build()`` returns (jitted_fn, args, kwargs)
    ready for ``.lower()``."""
    cfg = app.tpu_config
    rng = jax.random.PRNGKey(0)
    entries: List[Tuple[str, str, Callable[[], Tuple]]] = []
    chunk = max(cfg.decode_chunk_tokens, 1)

    if getattr(cfg, "is_block_kv_layout", False):
        b = cfg.batch_size
        width_bt = app.max_blocks

        def paged_args(w: int, b=b):
            return (app.params, app.cache,
                    np.zeros((b, w), np.int32), np.zeros((b, w), np.int32),
                    np.full((b, w), -1, np.int32),
                    np.zeros((b, width_bt), np.int32),
                    np.zeros((b,), np.int32),
                    app._default_sampling_params(b), rng)

        for w in app.ctx_buckets:
            entries.append((
                "paged", f"w{w}xb{b}",
                lambda w=w: (app._jit_paged(), paged_args(w), {})))
        entries.append((
            "paged", f"w1xb{b}",
            lambda: (app._jit_paged(), paged_args(1), {})))
        if chunk > 1:
            entries.append((
                "paged_loop", f"k{chunk}xb{b}",
                lambda: (app._jit_paged_loop(chunk),
                         (app.params, app.cache, np.zeros((b,), np.int32),
                          np.zeros((b,), np.int32),
                          np.zeros((b, width_bt), np.int32),
                          app._default_sampling_params(b), rng), {})))
        # the speculative verify graph (serving/speculation/): the ragged
        # k+1-wide dispatch at the default self-draft ladder top (k=3)
        sw = 4
        entries.append((
            "spec_verify", f"W{sw}xb{b}",
            lambda: (app._jit_spec_verify(False),
                     (app.params, app.cache, np.zeros((b, sw), np.int32),
                      np.zeros((b, sw), np.int32),
                      np.full((b, sw), -1, np.int32),
                      np.zeros((b, width_bt), np.int32),
                      np.ones((b,), np.int32)), {})))
        # the ragged UNIFIED dispatch (serving/ragged/): one mixed
        # prefill+decode+verify graph at the same representative width
        def ragged_args():
            return (app.params, app.cache, np.zeros((b, sw), np.int32),
                    np.zeros((b, sw), np.int32),
                    np.full((b, sw), -1, np.int32),
                    np.zeros((b, width_bt), np.int32),
                    np.ones((b,), np.int32),
                    np.zeros((b,), np.int32),
                    app._default_sampling_params(b),
                    rng)

        entries.append((
            "ragged", f"W{sw}xb{b}",
            lambda: (app._jit_ragged(False), ragged_args(), {})))
        if app.spec.lora is not None:
            # the multi-LoRA variant: same ragged graph plus the per-row
            # adapter gather (serving/lora_pool.py) — reported separately
            # so the bytes/flops delta of the gathered (A,B) einsum is
            # visible in the graph report
            entries.append((
                "ragged_lora", f"W{sw}xb{b}",
                lambda: (app._jit_ragged(False), ragged_args(),
                         {"adapter_ids": np.zeros((b,), np.int32)})))
        return entries

    cb = cfg.ctx_batch_size

    def prefill_args(s: int, b: int):
        return (app.params, app.cache, np.zeros((b, s), np.int32),
                np.zeros((b, s), np.int32), np.arange(b, dtype=np.int32),
                np.ones((b,), np.int32), app._default_sampling_params(b),
                rng, None, app.replacements, None, None, None, None)

    for s in app.ctx_buckets:
        entries.append((
            "prefill", f"ctx{s}xb{cb}",
            lambda s=s: (app._jit_prefill(), prefill_args(s, cb), {})))
    for bb in app.batch_buckets:
        entries.append((
            "decode", f"b{bb}",
            lambda bb=bb: (app._jit_decode(None),
                           (app.params, app.cache,
                            np.zeros((bb, 1), np.int32),
                            np.zeros((bb, 1), np.int32),
                            np.arange(bb, dtype=np.int32),
                            app._default_sampling_params(bb), rng,
                            None, app.replacements, None), {})))
        if chunk > 1:
            entries.append((
                "decode_loop", f"b{bb}xk{chunk}",
                lambda bb=bb: (app._jit_decode_loop(chunk),
                               (app.params, app.cache,
                                np.zeros((bb,), np.int32),
                                np.zeros((bb,), np.int32),
                                np.arange(bb, dtype=np.int32),
                                app._default_sampling_params(bb), rng),
                               {"num_steps": chunk})))
    return entries


def _hlo_text(compiled) -> Optional[str]:
    try:
        return compiled.as_text()
    except Exception:
        return None


def analyze_app(app, registry=None,
                device_kind: str = V5E) -> Dict[str, Any]:
    """AOT-compile every bucket-ladder graph of ``app`` and return the
    graph report (see module docstring). Gauges are recorded on
    ``registry`` (default: the process-global one) when it is enabled.
    The roofline legs are a PROJECTION onto ``device_kind``'s published
    peaks (utils/device.py; an unknown kind raises) — the report names
    the kind under ``assumptions``, whatever backend compiled the graphs.

    On a multi-device mesh the partitioned HLO of each graph is censused
    for collectives (per-graph ``collectives`` + the third roofline leg);
    on a single-device mesh the census is a guard — any collective in an
    unsharded graph raises RuntimeError."""
    reg = registry if registry is not None else get_registry()
    peaks = device_peaks(device_kind)
    hbm_gbps, peak_tflops = peaks.hbm_gbps, peaks.bf16_tflops
    ici_gbps, dcn_gbps = peaks.ici_gbps, peaks.dcn_gbps
    if app.params is None:
        raise ValueError("load_weights() or init_random_weights() first")
    if app.cache is None:
        raise ValueError("init_cache() first")
    mesh = app.mesh
    n_mesh_devices = int(np.prod(mesh.devices.shape))
    graphs: List[Dict[str, Any]] = []
    app_census: List[Dict[str, Any]] = []
    for kind, bucket, build in _graph_entries(app):
        fn, args, kwargs = build()
        t0 = time.perf_counter()
        with app._mesh_ctx():
            compiled = fn.lower(*args, **kwargs).compile()
        compile_s = time.perf_counter() - t0
        flops, bytes_acc = _cost(compiled)
        mem = _memory(compiled)
        peak = mem["peak_bytes"] if mem else 0
        hlo = _hlo_text(compiled)
        census = (census_collectives(hlo, mesh)
                  if hlo is not None else None)
        if census is not None and n_mesh_devices == 1 and census:
            # single-device collective pin: an accidental shard_map/psum
            # leaking into the unsharded path would silently tax every
            # step — make it loud instead
            raise RuntimeError(
                f"single-device graph ({kind}, {bucket}) contains "
                f"collectives: {aggregate_census(census)} — a "
                "shard_map/psum leaked into the unsharded path")
        coll_bytes = sum(e["bytes"] for e in census) if census else 0
        t_compute = flops / (peak_tflops * 1e12)
        t_memory = bytes_acc / (hbm_gbps * 1e9)
        t_comm = (comm_roofline_seconds(census, ici_gbps, dcn_gbps)
                  if census else 0.0)
        saved = (sum(_wire_bytes_saved(e) for e in census)
                 if census else 0.0)
        legs = {"compute": t_compute, "memory": t_memory, "comm": t_comm}
        roofline = {
            "est_step_ms": round(max(legs.values()) * 1e3, 6),
            "bound": max(legs, key=legs.get),
            "t_compute_ms": round(t_compute * 1e3, 6),
            "t_memory_ms": round(t_memory * 1e3, 6),
            "t_comm_ms": round(t_comm * 1e3, 6),
            # wire bytes the quantized (sub-fp32) payloads avoid vs
            # an fp32 exchange of the same shapes — 0 on fp32 graphs
            "comm_bytes_saved": int(round(saved)),
        }
        graph: Dict[str, Any] = {
            "kind": kind,
            "bucket": bucket,
            "compile_seconds": round(compile_s, 4),
            "flops": flops,
            "bytes_accessed": bytes_acc,
            "memory": mem,
            "arithmetic_intensity": (round(flops / bytes_acc, 3)
                                     if bytes_acc else None),
            "collectives": (aggregate_census(census)
                            if census is not None else None),
            "collective_count": len(census) if census is not None else None,
            "collective_bytes": coll_bytes if census is not None else None,
            "roofline": roofline,
        }
        graphs.append(graph)
        if census:
            app_census.extend(census)
        if reg.enabled:
            tmetrics.compile_seconds_gauge(reg).set(compile_s, kind=kind,
                                                    bucket=bucket)
            tmetrics.graph_flops_gauge(reg).set(flops, kind=kind,
                                                bucket=bucket)
            tmetrics.graph_bytes_gauge(reg).set(bytes_acc, kind=kind,
                                                bucket=bucket)
            tmetrics.graph_peak_bytes_gauge(reg).set(peak, kind=kind,
                                                     bucket=bucket)
    if reg.enabled:
        # collective census gauges aggregate over the app's whole graph
        # set — kind here is the COLLECTIVE kind, comm the mesh-axis
        # group, dtype the wire payload element type
        coll_g = tmetrics.graph_collectives_gauge(reg)
        bytes_g = tmetrics.graph_collective_bytes_gauge(reg)
        for key, slot in aggregate_census(app_census).items():
            ckind, comm, dtype = key.split("@", 2)
            coll_g.set(slot["count"], kind=ckind, comm=comm, dtype=dtype)
            bytes_g.set(slot["bytes"], kind=ckind, comm=comm, dtype=dtype)
    return {
        "schema": GRAPH_REPORT_SCHEMA,
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "mesh": {"devices": n_mesh_devices,
                 "axes": {a: int(s) for a, s in
                          zip(mesh.axis_names, mesh.devices.shape)
                          if int(s) > 1}},
        "assumptions": {"device_kind": device_kind,
                        "hbm_gbps": hbm_gbps, "peak_tflops": peak_tflops,
                        "ici_gbps": ici_gbps, "dcn_gbps": dcn_gbps},
        "graphs": graphs,
        "totals": {
            "graphs": len(graphs),
            "compile_seconds": round(sum(g["compile_seconds"]
                                         for g in graphs), 4),
            "flops": sum(g["flops"] for g in graphs),
            "bytes_accessed": sum(g["bytes_accessed"] for g in graphs),
            "collectives": len(app_census),
            "collective_bytes": sum(e["bytes"] for e in app_census),
            "comm_bytes_saved": int(round(sum(
                _wire_bytes_saved(e) for e in app_census))),
        },
    }
