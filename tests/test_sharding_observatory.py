"""Sharding observatory + SPMD regression guard (tier-1).

Covers: the multichip collective census (post-SPMD HLO on a dp2 x tp2
virtual-CPU mesh) with its comm-roofline leg and gauges, the
single-device zero-collective pin, the HLO census parser on doctored
text (explicit + iota replica groups, async pairs, permutes), the
replicate-then-partition detector firing on doctored HLO, the golden
census diff going red on an injected collective, and the live
``scripts/check_spmd_sharding.py`` lint on three pinned graphs (the full
set runs standalone via the script itself).
"""

import importlib.util
import json
import os
from pathlib import Path

import pytest

from neuronx_distributed_inference_tpu import telemetry
from neuronx_distributed_inference_tpu.config import TpuConfig
from neuronx_distributed_inference_tpu.models.application import \
    CausalLMApplication
from neuronx_distributed_inference_tpu.models.llama import (
    LlamaFamily, LlamaInferenceConfig)
from neuronx_distributed_inference_tpu.parallel.mesh import (MeshConfig,
                                                             build_mesh)
from neuronx_distributed_inference_tpu.telemetry import metrics as tmetrics
from neuronx_distributed_inference_tpu.telemetry import observatory

from conftest import tiny_llama_hf_config

REPO = Path(__file__).resolve().parent.parent
LINT = REPO / "scripts" / "check_spmd_sharding.py"
GOLDEN = REPO / "artifacts" / "spmd_golden.json"

_spec = importlib.util.spec_from_file_location("check_spmd_sharding", LINT)
lint_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(lint_mod)


def _tiny_hf():
    return tiny_llama_hf_config(num_hidden_layers=2)


@pytest.fixture(scope="module")
def mesh_report():
    """The exact dp2 x tp2 paged app the lint pins (one shared config —
    the golden guards what this module asserts on), analyzed once for
    every census assertion (single compile set for the whole module)."""
    app = lint_mod._serving_app(paged=True)
    reg = telemetry.enable()
    try:
        report = observatory.analyze_app(app, registry=reg)
    finally:
        telemetry.disable()
    return report, reg


# ---------------------------------------------------------------------------
# multichip census + comm roofline
# ---------------------------------------------------------------------------

def test_mesh_census_collectives_and_comm_roofline(mesh_report):
    report, _ = mesh_report
    assert report["mesh"] == {"devices": 4, "axes": {"dp": 2, "tp": 2}}
    kinds = {(g["kind"], g["bucket"]) for g in report["graphs"]}
    # serving graph set: prefill-chunk/ctx widths, w1 decode, fused loop
    assert ("paged", "w16xb2") in kinds and ("paged", "w1xb2") in kinds
    assert ("paged_loop", "k4xb2") in kinds
    for g in report["graphs"]:
        assert g["collective_count"] > 0 and g["collective_bytes"] > 0
        for key, slot in g["collectives"].items():
            ckind, comm, dtype = key.split("@")
            assert ckind in ("all_reduce", "all_gather", "reduce_scatter",
                             "collective_permute", "all_to_all")
            # every comm group maps back to real mesh axes — nothing
            # "other"/"unmapped" on the serving graphs
            assert set(comm.split("+")) <= {"dp", "tp"}, key
            assert dtype, key                  # dtype leg always present
            assert slot["count"] > 0 and slot["bytes"] >= 0
        rl = g["roofline"]
        assert rl["bound"] in ("compute", "memory", "comm")
        assert rl["t_comm_ms"] > 0.0
        assert rl["est_step_ms"] >= max(rl["t_compute_ms"],
                                        rl["t_memory_ms"], rl["t_comm_ms"])
    # the decode step moves tp all-reduces (row-parallel matmul psums)
    w1 = next(g for g in report["graphs"] if g["bucket"] == "w1xb2")
    assert w1["collectives"]["all_reduce@tp@f32"]["count"] > 0
    assert report["totals"]["collective_bytes"] > 0
    json.dumps(report)                              # artifact-ready


def test_mesh_census_gauges(mesh_report):
    _, reg = mesh_report
    assert reg.get(tmetrics.GRAPH_COLLECTIVES_TOTAL).get(
        kind="all_reduce", comm="tp", dtype="f32") > 0
    assert reg.get(tmetrics.GRAPH_COLLECTIVE_BYTES).get(
        kind="all_gather", comm="dp", dtype="f32") > 0


def test_comm_roofline_prices_dp_at_dcn():
    entries = [{"kind": "all_gather", "comm": "dp", "bytes": 1 << 20,
                "group_size": 2},
               {"kind": "all_gather", "comm": "tp", "bytes": 1 << 20,
                "group_size": 2}]
    t = observatory.comm_roofline_seconds(entries, ici_gbps=200,
                                          dcn_gbps=25)
    t_ici_only = observatory.comm_roofline_seconds(
        [entries[1]], ici_gbps=200, dcn_gbps=25)
    # dp leg is 8x slower than the identical tp leg at these assumptions
    assert t == pytest.approx(t_ici_only * 9, rel=1e-6)


# ---------------------------------------------------------------------------
# single-device collective pin (satellite: no shard_map/psum leaks)
# ---------------------------------------------------------------------------

def test_single_device_graphs_have_zero_collectives():
    tcfg = TpuConfig(batch_size=2, seq_len=64, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_continuous_batching=True)
    app = CausalLMApplication(None, LlamaInferenceConfig(
        tcfg, **_tiny_hf()), LlamaFamily)
    app.init_random_weights(seed=0).init_cache()
    report = observatory.analyze_app(app)
    assert report["mesh"]["devices"] == 1
    assert report["totals"]["collectives"] == 0
    for g in report["graphs"]:
        assert g["collectives"] == {} and g["collective_bytes"] == 0


def test_single_device_collective_leak_raises(monkeypatch):
    tcfg = TpuConfig(batch_size=2, seq_len=64, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_continuous_batching=True)
    app = CausalLMApplication(None, LlamaInferenceConfig(
        tcfg, **_tiny_hf()), LlamaFamily)
    app.init_random_weights(seed=0).init_cache()
    monkeypatch.setattr(
        observatory, "census_collectives",
        lambda hlo, mesh=None: [{"kind": "all_reduce", "comm": "other",
                                 "bytes": 64, "group_size": 2}])
    with pytest.raises(RuntimeError, match="single-device graph.*psum"):
        observatory.analyze_app(app)


# ---------------------------------------------------------------------------
# census parser on doctored HLO (both replica-group formats, async pairs)
# ---------------------------------------------------------------------------

DOCTORED_HLO = """\
HloModule doctored, is_scheduled=true

ENTRY %main (p0: f32[4,8]) -> f32[4,8] {
  %p0 = f32[4,8]{1,0} parameter(0)
  %all-reduce.1 = f32[4,8]{1,0} all-reduce(f32[4,8]{1,0} %p0), channel_id=1, replica_groups={{0,1},{2,3}}, use_global_device_ids=true, to_apply=%add
  %all-gather.1 = f32[8,8]{1,0} all-gather(f32[4,8]{1,0} %all-reduce.1), channel_id=2, replica_groups=[2,2]<=[4], dimensions={0}, use_global_device_ids=true
  %ag-start = (f32[4,8]{1,0}, f32[8,8]{1,0}) all-gather-start(f32[4,8]{1,0} %p0), channel_id=3, replica_groups={{0,2},{1,3}}, dimensions={0}
  %ag-done = f32[8,8]{1,0} all-gather-done((f32[4,8]{1,0}, f32[8,8]{1,0}) %ag-start)
  %collective-permute.1 = f32[4,8]{1,0} collective-permute(f32[4,8]{1,0} %p0), channel_id=4, source_target_pairs={{0,1},{1,0},{2,3},{3,2}}
  %all-reduce.2 = (f32[4,8]{1,0}, f32[4,8]{1,0}) all-reduce(f32[4,8]{1,0} %p0, f32[4,8]{1,0} %p0), channel_id=6, replica_groups={{0,1},{2,3}}, use_global_device_ids=true, to_apply=%add
  %reduce-scatter.1 = bf16[2,8]{1,0} reduce-scatter(bf16[4,8]{1,0} %p0), channel_id=5, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={0}, to_apply=%add
  ROOT %out = f32[4,8]{1,0} copy(%all-reduce.1)
}
"""


def test_census_parser_doctored_hlo():
    mesh = build_mesh(MeshConfig(tp=2, dp=2))      # logical ids [[0,1],[2,3]]
    entries = observatory.census_collectives(DOCTORED_HLO, mesh)
    agg = observatory.aggregate_census(entries)
    # async pair counted once, at the -start
    assert agg["all_gather@dp@f32"]["count"] == 1
    # the sync VARIADIC combiner (tuple result) transfers every element:
    # one plain all-reduce (128B) + one 2-way combined (2 x 128B)
    assert agg["all_reduce@tp@f32"] == {"count": 2, "bytes": 3 * 4 * 8 * 4}
    # iota groups [2,2]<=[4] = rows {0,1},{2,3} = tp
    assert agg["all_gather@tp@f32"] == {"count": 1, "bytes": 8 * 8 * 4}
    # -start result tuple: LAST element (the gathered output) is counted
    assert agg["all_gather@dp@f32"]["bytes"] == 8 * 8 * 4
    # permute pairs stay inside tp groups; bf16 keys its OWN dtype bucket
    # sized at 2 bytes, and the transposed iota [2,2]<=[2,2]T(1,0) =
    # columns {0,2},{1,3} = dp
    assert agg["collective_permute@tp@f32"] == {"count": 1,
                                                "bytes": 4 * 8 * 4}
    assert agg["reduce_scatter@dp@bf16"] == {"count": 1, "bytes": 2 * 8 * 2}
    # without a mesh the kinds/bytes still parse, comm is unmapped
    assert all(e["comm"] == "unmapped"
               for e in observatory.census_collectives(DOCTORED_HLO))
    # dtype tokens with mixed digit/letter runs (fp8 fnuz) size correctly
    assert observatory._shape_bytes("f8e4m3b11fnuz[2,8]{1,0}") == 16
    # legacy 4-element permute-start tuples trail u32[] context scalars
    # after the result — the payload, not 4 bytes of context, is counted
    assert observatory._shape_bytes(
        "(f32[4,8]{1,0}, f32[4,8]{1,0}, u32[], u32[])", True) == 4 * 8 * 4


# ---------------------------------------------------------------------------
# replicate-then-partition detector (doctored-HLO negative test)
# ---------------------------------------------------------------------------

REMAT_HLO = """\
HloModule remat, is_scheduled=true

ENTRY %main (p0: f32[2,8]) -> f32[2,8] {
  %p0 = f32[2,8]{1,0} parameter(0)
  %pid = u32[] partition-id()
  %idx = s32[] convert(u32[] %pid)
  %zero = s32[] constant(0)
  %all-gather.9 = f32[8,8]{1,0} all-gather(f32[2,8]{1,0} %p0), channel_id=2, replica_groups={{0,1,2,3}}, dimensions={0}, use_global_device_ids=true
  ROOT %dynamic-slice.3 = f32[2,8]{1,0} dynamic-slice(f32[8,8]{1,0} %all-gather.9, s32[] %idx, s32[] %zero), dynamic_slice_sizes={2,8}
}
"""


def test_remat_detector_fires_on_doctored_hlo(tmp_path):
    findings = lint_mod.find_replicate_then_partition(REMAT_HLO, 4)
    assert len(findings) == 1 and "replicate-then-partition" in findings[0]
    # dump flavors without the '%' name sigil must fire identically
    unsigiled = lint_mod.find_replicate_then_partition(
        REMAT_HLO.replace("%", ""), 4)
    assert len(unsigiled) == 1 and "replicate-then-partition" in unsigiled[0]
    # async form: the dynamic-slice consumes the -done instruction's
    # value, never the -start's — the alias pass must bridge the pair
    async_hlo = REMAT_HLO.replace(
        "%all-gather.9 = f32[8,8]{1,0} all-gather(f32[2,8]{1,0} %p0), "
        "channel_id=2, replica_groups={{0,1,2,3}}, dimensions={0}, "
        "use_global_device_ids=true",
        "%ag-s = (f32[2,8]{1,0}, f32[8,8]{1,0}) all-gather-start("
        "f32[2,8]{1,0} %p0), channel_id=2, replica_groups={{0,1,2,3}}, "
        "dimensions={0}\n"
        "  %all-gather.9 = f32[8,8]{1,0} all-gather-done("
        "(f32[2,8]{1,0}, f32[8,8]{1,0}) %ag-s)")
    assert "all-gather-done" in async_hlo      # the replace really landed
    assert any("replicate-then-partition" in f for f in
               lint_mod.find_replicate_then_partition(async_hlo, 4))
    # a subset-axis gather + slice (the legit MoE ep-gather shape) is NOT
    # flagged: groups of 2 on a 4-partition mesh
    legit = REMAT_HLO.replace("replica_groups={{0,1,2,3}}",
                              "replica_groups={{0,1},{2,3}}")
    assert lint_mod.find_replicate_then_partition(legit, 4) == []
    # end to end through the script's doctored mode
    bad = tmp_path / "remat.hlo.txt"
    bad.write_text(REMAT_HLO)
    assert lint_mod.main(["--hlo-file", str(bad),
                          "--num-partitions", "4"]) == 1
    good = tmp_path / "clean.hlo.txt"
    good.write_text(legit)
    assert lint_mod.main(["--hlo-file", str(good),
                          "--num-partitions", "4"]) == 0


def test_capture_compiler_stderr_tees_through(capfd):
    # bytes reach the REAL stderr as they arrive (not re-emitted at
    # exit), so a hard kill mid-compile still leaves the live tail in
    # the multichip runner's log; counts accumulate at exit
    counts = {"spmd_warnings": 0, "involuntary_remat": 0}
    with observatory.capture_compiler_stderr(counts) as cap:
        os.write(2, b"E0803 spmd_partitioner.cc:613] [spmd] Involuntary "
                    b"full rematerialization. doctored\n")
    assert "Involuntary full rematerialization" in cap[0]
    assert counts == {"spmd_warnings": 1, "involuntary_remat": 1}
    assert "Involuntary full rematerialization" in capfd.readouterr().err


def test_remat_warning_channel_both_spellings():
    old = ("W0730 spmd_partitioner.cc:652] [SPMD] Involuntary full "
           "rematerialization. ... SPMD will replicate the tensor and "
           "then partition it")
    new = ("E0803 spmd_partitioner.cc:613] [spmd] Involuntary full "
           "rematerialization. The compiler was not able to go from "
           "sharding A to B without doing a full rematerialization")
    for text in (old, new):
        findings = lint_mod._lint_hlo("g", "", text, 4)
        assert any("involuntary full" in f for f in findings)
    assert lint_mod._lint_hlo("g", "", "all quiet", 4) == []


# ---------------------------------------------------------------------------
# golden census diff (an added/doubled collective is a red test)
# ---------------------------------------------------------------------------

def test_golden_census_diff_red_on_new_collective(tmp_path):
    golden = json.loads(GOLDEN.read_text())
    assert golden["schema"] == "nxdi-spmd-golden-v2"
    assert set(lint_mod.PINNED) == set(golden["graphs"])
    snap = {"graphs": {name: {"collectives": dict(g["collectives"])}
                       for name, g in golden["graphs"].items()}}
    # identical snapshot passes
    ok = tmp_path / "census_ok.json"
    ok.write_text(json.dumps(snap))
    assert lint_mod.main(["--census-json", str(ok),
                          "--golden", str(GOLDEN)]) == 0
    # a collective added to a pinned graph goes red
    doctored = json.loads(ok.read_text())
    target = doctored["graphs"]["cb_decode_dp2tp2"]["collectives"]
    target["all_to_all@tp"] = {"count": 1, "bytes": 4096}
    bad = tmp_path / "census_new.json"
    bad.write_text(json.dumps(doctored))
    assert lint_mod.main(["--census-json", str(bad),
                          "--golden", str(GOLDEN)]) == 1
    # a doubled collective (the silent 2x regression class) goes red too
    doubled = json.loads(ok.read_text())
    t2 = doubled["graphs"]["moe_tkg_dp2ep2tp2"]["collectives"]
    key = sorted(t2)[0]
    t2[key] = {"count": t2[key]["count"] * 2, "bytes": t2[key]["bytes"]}
    bad2 = tmp_path / "census_doubled.json"
    bad2.write_text(json.dumps(doubled))
    assert lint_mod.main(["--census-json", str(bad2),
                          "--golden", str(GOLDEN)]) == 1
    # a pinned graph missing from the snapshot (partial census) is red
    partial = json.loads(ok.read_text())
    del partial["graphs"]["moe_tkg_dp2ep2tp2"]
    bad3 = tmp_path / "census_partial.json"
    bad3.write_text(json.dumps(partial))
    assert lint_mod.main(["--census-json", str(bad3),
                          "--golden", str(GOLDEN)]) == 1
    # wrong-schema input (no graphs table) is a usage error, not a crash
    notasnap = tmp_path / "not_a_snapshot.json"
    notasnap.write_text(json.dumps({"details": {}}))
    assert lint_mod.main(["--census-json", str(notasnap),
                          "--golden", str(GOLDEN)]) == 2


def test_diff_census_units():
    golden = {"all_reduce@tp": {"count": 2, "bytes": 1000}}
    assert lint_mod.diff_census("g", golden, dict(golden)) == []
    msgs = lint_mod.diff_census(
        "g", golden, {"all_reduce@tp": {"count": 2, "bytes": 1300}})
    assert msgs and "1.30x" in msgs[0]              # bytes drift past tol
    assert lint_mod.diff_census(
        "g", golden, {"all_reduce@tp": {"count": 2, "bytes": 1200}}) == []
    assert lint_mod.diff_census("g", golden, {})    # disappearance is red


def test_diff_graph_requests_always_census_only_on_its_own_xla():
    name = "paged_decode_dp2tp2"
    entry = json.loads(GOLDEN.read_text())["graphs"][name]
    assert lint_mod.diff_graph(name, entry, entry) == ([], True)
    # a census another XLA emitted is not this code's to answer for ...
    key = sorted(entry["collectives"])[0]
    other = {**entry, "xla": {"jax": "0.0.1", "jaxlib": "0.0.1"},
             "collectives": {**entry["collectives"],
                             key: {"count": 99, "bytes": 1}}}
    assert lint_mod.diff_graph(name, other, entry) == ([], False)
    # ... on the same XLA it is
    assert lint_mod.diff_graph(name, {**other, "xla": entry["xla"]},
                               entry)[0]
    # what the code REQUESTS is held whatever compiled it: a dropped
    # constraint and a new one are both red
    asked = {**entry["requested"], "constraint:PartitionSpec('cp',)": 1}
    del asked[next(k for k in asked if k.startswith("constraint:"))]
    msgs, compared = lint_mod.diff_graph(
        name, {**other, "requested": asked}, entry)
    assert not compared and len(msgs) == 2
    assert all("requested sharding" in m for m in msgs)


# ---------------------------------------------------------------------------
# live lint (three pinned graphs; the full set runs via the script)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("graph", ["paged_decode_dp2tp2",
                                   "paged_ragged_dp2tp2",
                                   "cb_decode_dp2tp2"])
def test_spmd_lint_live_subset(graph, capsys):
    """In-process (jax is up with 8 virtual devices): the graph partitions
    with no full rematerialization or [SPMD] warning and requests the
    golden's shardings; its census is held to the golden where that was
    earned on this XLA, and the lint says so where it was not."""
    assert lint_mod.main(["--graphs", graph]) == 0
    out = capsys.readouterr().out
    assert "OK" in out and "collectives censused" in out
    entry = json.loads(GOLDEN.read_text())["graphs"][graph]
    assert ("census not compared" in out) == \
        (entry["xla"] != lint_mod.running_xla())


def test_update_golden_with_a_subset_merges(tmp_path):
    """``--update-golden --graphs g`` re-earns g (requests, census, XLA
    stamp) and leaves every other pinned graph as it was."""
    committed = json.loads(GOLDEN.read_text())["graphs"]
    before = {name: {**entry, "xla": {"jax": "0.0.1", "jaxlib": "0.0.1"}}
              for name, entry in committed.items()}
    before["cb_decode_dp2tp2"]["requested"] = {"input:None": 1}
    g2 = tmp_path / "golden_copy.json"
    g2.write_text(json.dumps({"schema": lint_mod.GOLDEN_SCHEMA,
                              "graphs": before}))
    assert lint_mod.main(["--update-golden", "--graphs",
                          "cb_decode_dp2tp2", "--golden", str(g2)]) == 0
    merged = json.loads(g2.read_text())["graphs"]
    assert set(merged) == set(lint_mod.PINNED)
    target = merged.pop("cb_decode_dp2tp2")
    assert target["xla"] == lint_mod.running_xla() and \
        target["requested"] == committed["cb_decode_dp2tp2"]["requested"]
    assert merged == {k: before[k] for k in merged}
