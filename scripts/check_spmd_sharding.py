#!/usr/bin/env python
"""Tier-1 SPMD regression guard: compile the multichip graphs on a CPU
mesh and assert on the partitioned HLO (ROADMAP item 2's lint).

Four failure channels, all ahead of hardware:

  1. **Involuntary full rematerialization** — the SPMD partitioner's
     "replicate the tensor and then partition it" last resort (the exact
     regression PR 5 fixed in moe.py's ``tkg_experts_local`` reshard,
     previously only visible as a ``MULTICHIP_r05.json`` tail grep).
     Detected on the compiler's warning channel (stderr captured at the
     fd level around each compile — glog W/E lines from
     ``spmd_partitioner.cc``) AND structurally in the optimized HLO (a
     full-mesh ``all-gather`` feeding a ``dynamic-slice`` is
     replicate-then-partition by construction).
  2. **Requested shardings** — what THIS code asks of the partitioner:
     every argument's sharding, every ``with_sharding_constraint`` and
     ``shard_map`` of the jaxpr, as counts per spec, diffed exactly
     against the committed golden ``artifacts/spmd_golden.json``.
  3. **Collective census drift** — every collective of every pinned
     graph (kind x mesh-axis comm group, counts + payload bytes, via
     ``telemetry/observatory.census_collectives``) is what XLA EMITTED,
     so it is diffed only where the golden entry was earned on the
     running jax / jaxlib (each entry records them); otherwise the lint
     says "golden earned on another XLA, census not compared". Where
     compared, a new collective, a changed count, or payload bytes
     drifting past ±25% is red, improvements too (symmetric): rerun
     with ``--update-golden`` to re-earn the golden.
  4. **SPMD warning channel** — any other ``[SPMD]`` partitioner
     complaint during the pinned compiles fails the run.

Pinned graph set (tiny configs reusing ``__graft_entry__``'s mesh
plumbing; all CPU-mesh compiles, no execution):

  * ``dense_tkg_dp2tp2``  — dense decode step, dp2 x tp2
  * ``moe_tkg_dp2ep2tp2`` — hybrid-MoE decode (``tkg_experts_local``
    reshard — the PR-5 remat surface), dp2 x ep2 x tp2 (8 devices)
  * ``paged_decode_dp2tp2`` / ``paged_loop_dp2tp2`` — the serving/paged
    step + fused decode loop on a mesh (first compiled
    coverage of the paged path on multi-device)
  * ``cb_decode_dp2tp2``  — continuous-batching decode step
  * ``paged_spec_verify_dp2tp2`` — the speculative ragged k+1-wide
    verify dispatch (serving/speculation/) at the default self-draft
    ladder top (W=4)
  * ``paged_ragged_dp2tp2`` — the ragged UNIFIED mixed
    prefill+decode+verify dispatch (serving/ragged/,
    ``model_base.paged_ragged_step``) at the same W=4
  * ``paged_ragged_lora_dp2tp2`` — the same unified dispatch on a
    LoRA-built app with per-row ``adapter_ids`` (multi-LoRA serving,
    serving/lora_pool.py): the stacked (A, B) gather + delta einsum
    must partition cleanly (lora_A replicated, lora_B sharded with its
    base projection) and add NO collective over the plain ragged graph
  * ``cb_decode_int8_dp2tp2`` / ``paged_decode_fp8_dp2tp2`` — the same
    decode steps with ``CollectiveConfig`` quantized collectives (int8 /
    fp8 wire payloads): the row-parallel output all-reduces lower to
    s8/f8 ppermute rings, and the golden pins the wire-byte reduction
    (the census keys carry the payload dtype, so an accidental fall-back
    to fp32 collectives is a red diff, not a silent 4x wire regression)

Usage::

    python scripts/check_spmd_sharding.py                 # full lint
    python scripts/check_spmd_sharding.py --graphs cb_decode_dp2tp2
    python scripts/check_spmd_sharding.py --update-golden # re-earn golden
    python scripts/check_spmd_sharding.py --hlo-file F    # doctored HLO:
        run the remat detector + census parse on a saved HLO text only
    python scripts/check_spmd_sharding.py --census-json F # diff a census
        snapshot against the golden without compiling
    python scripts/check_spmd_sharding.py --list          # pinned names

Wired into the suite as a tier-1 test
(``tests/test_sharding_observatory.py``), including a doctored-HLO
negative test proving the remat detector fires.

Relationship to ``scripts/nxdi_lint.py``: this script stays the COMPILE
lint (a CPU-mesh XLA compile set is minutes of work, not an AST pass),
while its static golden/pin consistency slice — golden schema, PINNED
<-> golden graph-set sync, census well-formedness — runs in-process with
every other pass as ``nxdi_lint``'s ``spmd-golden`` pass.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))    # package + __graft_entry__ imports

GOLDEN_PATH = REPO_ROOT / "artifacts" / "spmd_golden.json"
GOLDEN_SCHEMA = "nxdi-spmd-golden-v2"
BYTES_TOL = 1.25          # golden payload-bytes drift tolerance (either way)


# ---------------------------------------------------------------------------
# structural remat detector (doctorable; mirrors the warning channel)
# ---------------------------------------------------------------------------

_HLO_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(?P<name>%?[\w.-]+)\s*=\s*(?:\([^)]*\)|\S+)\s+"
    r"(?P<op>[\w-]+)\((?P<operands>[^)]*)\)")
_AG_GROUPS_RE = re.compile(
    r"replica_groups=(?:\{(\{[^=]*?\})\}|\[([0-9,]+)\]<=)")


def _all_gather_spans(line: str, num_partitions: Optional[int]) -> bool:
    """True when the all-gather's replica group spans every partition —
    the replicate step of replicate-then-partition. Subset-axis gathers
    (a legit ep all-gather + local slice) do not match."""
    if num_partitions is None:
        return True          # doctored mode without a mesh: any gather
    m = _AG_GROUPS_RE.search(line)
    if not m:
        return False
    if m.group(1) is not None:
        groups = [g for g in re.findall(r"\{([0-9,\s]*)\}", m.group(1))]
        sizes = [len([x for x in g.split(",") if x.strip()])
                 for g in groups]
        return bool(sizes) and max(sizes) >= num_partitions
    dims = [int(x) for x in m.group(2).split(",")]
    return len(dims) >= 2 and dims[-1] >= num_partitions or \
        (len(dims) == 1 and dims[0] >= num_partitions)


def find_replicate_then_partition(
        hlo_text: str, num_partitions: Optional[int] = None
) -> List[str]:
    """Structural replicate-then-partition findings: a full-mesh
    ``all-gather`` whose value feeds a ``dynamic-slice`` — the HLO shape
    of the partitioner's remat fallback (gather everything, re-slice per
    partition). Returns human-readable finding strings. Instruction
    names are matched with the ``%`` sigil stripped — some dump flavors
    omit it (the census regex tolerates both; so must this detector)."""
    gathers: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _HLO_OP_RE.match(line)
        if not m:
            continue
        if m.group("op") in ("all-gather", "all-gather-start") and \
                _all_gather_spans(line, num_partitions):
            gathers[m.group("name").lstrip("%")] = line.strip()
    if not gathers:
        return []
    # async pairs: the consumer slices the -done instruction's value,
    # never the -start's — alias each -done to its flagged -start. The
    # -done operand is TUPLE-typed, which defeats _HLO_OP_RE's
    # first-paren operand capture, so scan the call body directly.
    for line in hlo_text.splitlines():
        if "all-gather-done(" not in line:
            continue
        m = _HLO_OP_RE.match(line)
        if not m:
            continue
        body = line.split("all-gather-done(", 1)[1]
        srcs = {t.lstrip("%") for t in re.findall(r"%?[\w.-]+", body)
                if any(c.isalpha() for c in t)}
        if srcs & set(gathers):
            gathers.setdefault(m.group("name").lstrip("%"), line.strip())
    findings = []
    for line in hlo_text.splitlines():
        m = _HLO_OP_RE.match(line)
        if not m or m.group("op") != "dynamic-slice":
            continue
        operands = {t.strip().split(" ")[-1].lstrip("%")
                    for t in m.group("operands").split(",")}
        for name in gathers:
            if name in operands:
                findings.append(
                    f"full-mesh all-gather {name} feeds dynamic-slice "
                    f"{m.group('name')} (replicate-then-partition)")
    return findings


# ---------------------------------------------------------------------------
# pinned multichip graphs (tiny configs; CPU mesh)
# ---------------------------------------------------------------------------

def _tiny_hf():
    return dict(model_type="llama", hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, vocab_size=512,
                rms_norm_eps=1e-5, rope_theta=10000.0, hidden_act="silu",
                tie_word_embeddings=False, torch_dtype="float32")


def _entry_graph(moe: bool):
    """Dense / hybrid-MoE decode step over __graft_entry__'s mesh
    plumbing and tiny configs (the multichip-runner graphs)."""
    import jax
    import jax.numpy as jnp
    from functools import partial
    import __graft_entry__ as ge
    from neuronx_distributed_inference_tpu.models import model_base
    from neuronx_distributed_inference_tpu.parallel.mesh import (MeshConfig,
                                                                 build_mesh)
    ep = 2 if moe else 1
    n = 4 * ep
    mesh = build_mesh(MeshConfig(tp=2, cp=1, dp=2, ep=ep),
                      devices=jax.devices()[:n])
    batch = 4
    with jax.sharding.set_mesh(mesh):
        tcfg, spec, params, cache = ge._make(
            tp=2 * ep, mesh=mesh, batch=batch, seq=32, moe=moe,
            hybrid_moe=moe)
        fn = jax.jit(partial(model_base.token_generation_step, spec, tcfg),
                     donate_argnums=(1,))
        args = (params, cache, jnp.zeros((batch, 1), jnp.int32),
                jnp.full((batch, 1), 16, jnp.int32),
                jnp.arange(batch, dtype=jnp.int32), None,
                jax.random.PRNGKey(1))
    return mesh, fn, args, {}


_APP_CACHE: Dict[Tuple[bool, Optional[str]], Any] = {}


def _serving_app(paged: bool, collective_dtype: Optional[str] = None,
                 lora: bool = False):
    key = (paged, collective_dtype, lora)
    if key in _APP_CACHE:         # each app serves several pinned graphs
        return _APP_CACHE[key]    # — one weights+cache init per config
    from neuronx_distributed_inference_tpu.config import (CollectiveConfig,
                                                          LoraServingConfig,
                                                          TpuConfig)
    from neuronx_distributed_inference_tpu.models.application import (
        CausalLMApplication, PagedCausalLMApplication)
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from neuronx_distributed_inference_tpu.parallel.mesh import \
        mesh_from_config
    extra = ({"is_block_kv_layout": True, "pa_block_size": 16,
              "is_prefix_caching": True}
             if paged else {"is_continuous_batching": True})
    if collective_dtype is not None:
        extra["collective_config"] = CollectiveConfig(dtype=collective_dtype)
    if lora:
        # a SEPARATE app (not the plain paged one): the stacked adapter
        # arrays ride the params pytree, so grafting them onto the
        # shared app would shift every existing pinned graph's signature
        extra["lora_config"] = LoraServingConfig(
            max_loras=3, max_lora_rank=4,
            target_modules=["q_proj", "v_proj"])
    tcfg = TpuConfig(batch_size=2, seq_len=128, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     decode_chunk_tokens=4, tp_degree=4,
                     attention_dp_degree=2, **extra)
    mesh = mesh_from_config(tcfg)
    cls = PagedCausalLMApplication if paged else CausalLMApplication
    app = cls(None, LlamaInferenceConfig(tcfg, **_tiny_hf()), LlamaFamily,
              mesh=mesh)
    app.init_random_weights(seed=0).init_cache()
    return _APP_CACHE.setdefault(key, app)


def _app_graph(paged: bool, kind: str,
               collective_dtype: Optional[str] = None,
               lora: bool = False):
    from neuronx_distributed_inference_tpu.telemetry import observatory
    app = _serving_app(paged, collective_dtype, lora)
    for k, bucket, build in observatory._graph_entries(app):
        if k == kind:
            fn, args, kwargs = build()
            return app.mesh, fn, args, kwargs
    raise LookupError(f"graph kind {kind!r} not in the app's ladder")


PINNED: Dict[str, Any] = {
    # name -> zero-arg builder returning (mesh, jitted_fn, args, kwargs)
    "dense_tkg_dp2tp2": lambda: _entry_graph(moe=False),
    "moe_tkg_dp2ep2tp2": lambda: _entry_graph(moe=True),
    "paged_decode_dp2tp2": lambda: _app_graph(True, "paged"),
    "paged_loop_dp2tp2": lambda: _app_graph(True, "paged_loop"),
    "cb_decode_dp2tp2": lambda: _app_graph(False, "decode"),
    "paged_spec_verify_dp2tp2": lambda: _app_graph(True, "spec_verify"),
    "paged_ragged_dp2tp2": lambda: _app_graph(True, "ragged"),
    # the multi-LoRA ragged dispatch: per-row gathered (A, B) factors
    # (lora_A replicated, lora_B row-sharded over tp) riding the SAME
    # unified graph — pins that the adapter gather adds no collective
    # beyond the existing row-parallel reduces
    "paged_ragged_lora_dp2tp2": lambda: _app_graph(True, "ragged_lora",
                                                   lora=True),
    # quantized-collective decode graphs (EQuARX-style s8/f8 ppermute
    # rings replacing the row-parallel fp32 all-reduces) — the dtype leg
    # of the census keys pins the wire-byte reduction
    "cb_decode_int8_dp2tp2": lambda: _app_graph(False, "decode", "int8"),
    "paged_decode_fp8_dp2tp2": lambda: _app_graph(True, "paged", "fp8"),
}


def running_xla() -> Dict[str, str]:
    import jax
    import jaxlib
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__}


def requested_shardings(jaxpr, args) -> Dict[str, int]:
    """Counts per spec of the argument shardings, sharding constraints
    and shard_maps of one traced graph (sub-jaxprs included)."""
    import jax
    from collections import Counter
    keys = []
    for leaf in jax.tree_util.tree_leaves(args):
        sharding = getattr(leaf, "sharding", None)
        keys.append(
            f"input:{getattr(sharding, 'spec', type(sharding).__name__)}")

    def walk(jp) -> None:
        for eqn in jp.eqns:
            if eqn.primitive.name == "sharding_constraint":
                keys.append(f"constraint:{eqn.params['sharding'].spec}")
            elif eqn.primitive.name == "shard_map":
                keys.append(f"shard_map:{eqn.params['in_specs']}->"
                            f"{eqn.params['out_specs']}")
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (list, tuple))
                            else (value,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jaxpr)
    return dict(Counter(keys))


def compile_pinned(name: str) -> Tuple[Any, Dict[str, int], str, str]:
    """Trace and compile one pinned graph on its CPU mesh. Returns (mesh,
    requested shardings, optimized HLO text, captured compiler stderr)."""
    import jax
    from neuronx_distributed_inference_tpu.telemetry.observatory import \
        capture_compiler_stderr
    mesh, fn, args, kwargs = PINNED[name]()
    with capture_compiler_stderr() as captured:
        with jax.sharding.set_mesh(mesh):
            traced = fn.trace(*args, **kwargs)
            compiled = traced.lower().compile()
    requested = requested_shardings(traced.jaxpr.jaxpr, (args, kwargs))
    return mesh, requested, compiled.as_text(), captured[0]


# ---------------------------------------------------------------------------
# golden census diff
# ---------------------------------------------------------------------------

def diff_census(graph: str, golden: Dict[str, Dict[str, Any]],
                current: Dict[str, Dict[str, Any]],
                bytes_tol: float = BYTES_TOL) -> List[str]:
    """Symmetric census diff for one graph: any new/missing collective
    key, any count change, payload bytes drifting past ``bytes_tol``
    (ratio, either direction) is a finding."""
    msgs = []
    for key in sorted(set(golden) | set(current)):
        g, c = golden.get(key), current.get(key)
        if g is None:
            msgs.append(f"{graph}: NEW collective {key}: {c} (not in "
                        "golden — a collective was added to this graph)")
        elif c is None:
            msgs.append(f"{graph}: collective {key} DISAPPEARED (golden "
                        f"had {g}; improvement? --update-golden)")
        else:
            if g["count"] != c["count"]:
                msgs.append(f"{graph}: {key} count {g['count']} -> "
                            f"{c['count']}")
            gb, cb = max(g["bytes"], 1), max(c["bytes"], 1)
            ratio = cb / gb
            if ratio > bytes_tol or ratio < 1.0 / bytes_tol:
                msgs.append(f"{graph}: {key} payload bytes {g['bytes']} "
                            f"-> {c['bytes']} ({ratio:.2f}x)")
    return msgs


def diff_graph(name: str, gentry: Dict[str, Any], current: Dict[str, Any]
               ) -> Tuple[List[str], bool]:
    """One pinned graph against its golden entry: the requested shardings
    always (exact), the census only when the entry was earned on the XLA
    that produced ``current``. Returns (findings, census compared?)."""
    msgs = []
    want, got = gentry["requested"], current["requested"]
    for key in sorted(set(want) | set(got)):
        if want.get(key) != got.get(key):
            msgs.append(f"{name}: requested sharding {key} x"
                        f"{want.get(key, 0)} -> x{got.get(key, 0)}")
    compared = gentry["xla"] == current["xla"]
    if compared:
        msgs += diff_census(name, gentry["collectives"],
                            current["collectives"])
    return msgs, compared


def load_golden(path: Path) -> Dict[str, Any]:
    data = json.loads(path.read_text())
    if data.get("schema") != GOLDEN_SCHEMA:
        raise ValueError(f"{path}: schema {data.get('schema')!r} != "
                         f"{GOLDEN_SCHEMA!r}")
    return data


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def _setup_jax():
    from neuronx_distributed_inference_tpu.compat import force_cpu_devices
    force_cpu_devices(8)
    import jax
    if len(jax.devices()) < 8:
        print(f"check_spmd_sharding: SKIP — need 8 virtual CPU devices, "
              f"got {len(jax.devices())} (backend initialized before "
              "force_cpu_devices could run?)", file=sys.stderr)
        return False
    return True


def _lint_hlo(name: str, hlo: str, stderr_text: str,
              num_partitions: Optional[int]) -> List[str]:
    # one copy of the warning spellings, shared with the multichip runner
    from neuronx_distributed_inference_tpu.telemetry.observatory import (
        REMAT_WARNING_RE as REMAT_RE, SPMD_CHANNEL_RE as SPMD_WARNING_RE)
    findings = [f"{name}: {m}" for m in
                find_replicate_then_partition(hlo, num_partitions)]
    remat = REMAT_RE.findall(stderr_text)
    if remat:
        findings.append(
            f"{name}: compiler reported involuntary full "
            f"rematerialization x{len(remat)} (SPMD replicate-then-"
            "partition fallback — see the re-emitted warnings above)")
    spmd_lines = [l for l in stderr_text.splitlines()
                  if SPMD_WARNING_RE.search(l) and not REMAT_RE.search(l)]
    if spmd_lines:
        findings.append(f"{name}: {len(spmd_lines)} other [SPMD] "
                        f"compiler warning(s): {spmd_lines[0][:160]}")
    return findings


def main(argv: Sequence[str] = ()) -> int:
    argv = list(argv)

    def opt(flag: str) -> Optional[str]:
        if flag not in argv:
            return None
        i = argv.index(flag)
        if i + 1 >= len(argv):
            print(f"check_spmd_sharding: {flag} needs a value",
                  file=sys.stderr)
            raise SystemExit(2)
        return argv[i + 1]

    golden_path = Path(opt("--golden") or GOLDEN_PATH)

    if "--list" in argv:
        print("\n".join(PINNED))
        return 0

    hlo_file = opt("--hlo-file")
    if hlo_file is not None:
        # doctored-HLO mode: detectors only, no compile, no golden
        text = Path(hlo_file).read_text()
        np_s = opt("--num-partitions")
        findings = _lint_hlo(Path(hlo_file).name, text, "",
                             int(np_s) if np_s else None)
        for f in findings:
            print(f"check_spmd_sharding: {f}", file=sys.stderr)
        if findings:
            return 1
        print("check_spmd_sharding: OK (no remat pattern in "
              f"{hlo_file})")
        return 0

    census_file = opt("--census-json")
    if census_file is not None:
        # diff-only mode: {"graphs": {name: {"collectives": {...}}}}
        if not golden_path.exists():
            print(f"check_spmd_sharding: golden {golden_path} missing — "
                  "run with --update-golden first", file=sys.stderr)
            return 2
        try:
            golden = load_golden(golden_path)
        except ValueError as e:
            print(f"check_spmd_sharding: {e}", file=sys.stderr)
            return 2
        snap = json.loads(Path(census_file).read_text())
        snap_graphs = snap.get("graphs")
        if not isinstance(snap_graphs, dict):
            print(f"check_spmd_sharding: {census_file} has no 'graphs' "
                  "table — expected a census snapshot shaped like the "
                  "golden, not e.g. the sharding-report artifact",
                  file=sys.stderr)
            return 2
        msgs: List[str] = []
        # symmetric over graphs too: a graph the golden pins but the
        # snapshot dropped (partial census) is as red as a new one
        for gname in sorted(set(golden["graphs"]) | set(snap_graphs)):
            gentry = golden["graphs"].get(gname)
            gdata = snap_graphs.get(gname)
            if gentry is None:
                msgs.append(f"{gname}: not in the golden — run "
                            "--update-golden to pin it")
            elif gdata is None:
                msgs.append(f"{gname}: MISSING from the snapshot (the "
                            "golden pins it — partial census?)")
            elif not isinstance(gdata.get("collectives"), dict):
                print(f"check_spmd_sharding: {census_file}: graph "
                      f"{gname} has no 'collectives' table",
                      file=sys.stderr)
                return 2
            else:
                msgs += diff_census(gname, gentry["collectives"],
                                    gdata["collectives"])
        for m in msgs:
            print(f"check_spmd_sharding: {m}", file=sys.stderr)
        if msgs:
            return 1
        print(f"check_spmd_sharding: OK ({len(snap_graphs)} census "
              "snapshots match the golden)")
        return 0

    if not _setup_jax():
        return 0
    from neuronx_distributed_inference_tpu.telemetry import observatory

    graphs_arg = opt("--graphs")
    names = (graphs_arg or ",".join(PINNED)).split(",")
    unknown = [n for n in names if n not in PINNED]
    if unknown:
        print(f"check_spmd_sharding: unknown graph(s) {unknown}; "
              f"pinned set: {list(PINNED)}", file=sys.stderr)
        return 2

    update = "--update-golden" in argv
    golden = None
    if not update:
        if not golden_path.exists():
            print(f"check_spmd_sharding: golden {golden_path} missing — "
                  "run with --update-golden first", file=sys.stderr)
            return 2
        golden = load_golden(golden_path)

    findings: List[str] = []
    results: Dict[str, Any] = {}
    not_compared: List[str] = []
    xla = running_xla()
    for name in names:
        import numpy as np
        mesh, requested, hlo, stderr_text = compile_pinned(name)
        n_part = int(np.prod(mesh.devices.shape))
        census = observatory.aggregate_census(
            observatory.census_collectives(hlo, mesh))
        results[name] = {
            "mesh": {a: int(s) for a, s in
                     zip(mesh.axis_names, mesh.devices.shape) if s > 1},
            "requested": requested,
            "collectives": census,
            "xla": xla,
        }
        findings += _lint_hlo(name, hlo, stderr_text, n_part)
        if not census:
            findings.append(f"{name}: zero collectives censused on a "
                            f"{n_part}-device mesh — the graph is not "
                            "actually partitioned (mesh plumbing broke?)")
        if golden is not None:
            gentry = golden["graphs"].get(name)
            if gentry is None:
                findings.append(f"{name}: not in the golden — run "
                                "--update-golden to pin it")
            else:
                msgs, compared = diff_graph(name, gentry, results[name])
                findings += msgs
                if not compared:
                    not_compared.append(name)

    if not_compared:
        print("check_spmd_sharding: golden earned on another XLA, census "
              f"not compared: {', '.join(not_compared)}")
    for f in findings:
        print(f"check_spmd_sharding: {f}", file=sys.stderr)
    if findings:
        if update:
            # never pin a census the warning/remat channel rejects — a
            # tainted golden would pass cleanly on the next plain run
            print("check_spmd_sharding: golden NOT updated — fix the "
                  "findings above first", file=sys.stderr)
        return 1

    if update:
        # a subset update (--graphs) merges into the existing golden —
        # re-earning one graph must not drop the other pinned ones; a
        # FULL update replaces the table, so a graph dropped from PINNED
        # can be pruned through the documented re-earn flow
        merged = dict(results)
        if graphs_arg is not None and golden_path.exists():
            merged = {**load_golden(golden_path)["graphs"], **results}
        payload = {"schema": GOLDEN_SCHEMA, "graphs": merged,
                   "bytes_tol": BYTES_TOL}
        golden_path.parent.mkdir(parents=True, exist_ok=True)
        golden_path.write_text(json.dumps(payload, indent=1,
                                          sort_keys=True) + "\n")
        print(f"check_spmd_sharding: golden updated ({len(results)} of "
              f"{len(merged)} graphs) -> {golden_path}")
    n_coll = sum(c["count"] for r in results.values()
                 for c in r["collectives"].values())
    print(f"check_spmd_sharding: OK ({len(results)} multichip graphs, "
          f"{n_coll} collectives censused, no remat pattern)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
