"""A whole cell at a toy size through ``run.py``'s own functions, on the CPU,
with the device check passed in; the last line is held to the contract."""

import argparse
import json
import os

import pytest

import run
from harness import build

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
FAKE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


@pytest.fixture()
def toy(monkeypatch, tmp_path):
    monkeypatch.setattr(build, "DATA_ROOT", TOY)
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    # float32 toys on XLA:CPU need exact matmuls to meet the toy gate
    gate = build.logit_gate
    monkeypatch.setattr(build, "logit_gate",
                        lambda cfg, seed: gate(cfg, seed, "highest"))


def _run(workload, trace, seconds=2.0):
    args = argparse.Namespace(workload=workload, seed=2**31 + 11,
                              seconds=seconds, trace=trace)
    out = run.run_cell(args, require_chips=lambda chips: dict(FAKE))
    return json.loads(json.dumps(out))       # it must serialise


def _held_to_contract(out, names):
    assert set(out) - {"breakdown"} == {"correct", "attempted", "failed",
                                        "metrics", "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == set(names)
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(out["device"])


@pytest.mark.parametrize("workload,names", [
    ("toy-open", {"ttft_p50_ms", "itl_p50_ms", "itl_p95_ms", "setup_s"}),
    ("toy-closed", {"tokens_per_s", "itl_p50_ms", "itl_p95_ms", "setup_s"}),
])
def test_untraced_cell_reports_its_end_to_end_metrics(toy, workload, names):
    out = _run(workload, trace=0)
    _held_to_contract(out, names)
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_traced_cell_reports_what_its_readers_find(toy):
    from neuronx_distributed_inference_tpu import telemetry
    from neuronx_distributed_inference_tpu.telemetry.trace import \
        disable_recorder
    try:
        out = _run("toy-closed", trace=1)
    finally:
        telemetry.disable()
        disable_recorder()
    # no device plane on the CPU: the trace readers return nothing and
    # their metrics are left out; the counter readers all report
    _held_to_contract(out, {"sched.live_batch_mean",
                            "adapter.dispatches_per_token",
                            "adapter.prefill_pad_share",
                            "warmup.graphs_built"})
    m = out["metrics"]
    assert 1.0 <= m["sched.live_batch_mean"]["value"] <= 4.0
    assert 0.0 < m["adapter.dispatches_per_token"]["value"] <= 2.0
    assert 0.0 <= m["adapter.prefill_pad_share"]["value"] < 100.0


def test_every_real_cell_resolves_its_data_files():
    with open(os.path.join(build.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"] + [{"name": "olmoe-chat-closed"}]:
        spec = run.load_cell(w["name"])
        assert spec["config"]["chips"] == spec["cell"]["chips"]
        assert "setup_s" in [m["name"] for m in spec["end_to_end"]]
        assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]
        for m in spec["per_layer"]:
            file = build.load_json("layer_metrics", m["name"] + ".json")
            for key in ("layer", "unit", "better", "source", "moves"):
                assert file[key] == m[key], (m["name"], key)
            assert file.get("workloads") == m.get("workloads")
        widths = build.warm_widths(spec["config"], spec["mix"])
        assert widths == [1, 64, 256]


def test_catalog_numbers_are_kept():
    """Every number of the catalog row of OLMoE-1B-7B is in the configuration
    file under the same key, but for what ``reduced`` lists."""
    cfg = build.load_json("configs", "olmoe-1b-7b.json")
    catalog = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
               "hidden_size": 2048, "intermediate_size": 1024,
               "max_position_embeddings": 4096, "model_type": "olmoe",
               "norm_topk_prob": False, "num_attention_heads": 16,
               "num_experts": 64, "num_experts_per_tok": 8,
               "num_hidden_layers": 16, "num_key_value_heads": 16,
               "rms_norm_eps": 1e-05, "rope_scaling": None,
               "rope_theta": 10000, "tie_word_embeddings": False,
               "vocab_size": 50304}
    differs = [k for k, v in catalog.items() if cfg.get(k, "absent") != v]
    assert differs == cfg["reduced"] == ["num_hidden_layers"]


def test_no_chip_is_an_error_not_a_fallback():
    with pytest.raises(build.NoChip, match="no TPU"):
        build.require_chips(1)


def test_unknown_device_kind_has_no_peaks():
    assert build.peaks_for("TPU v5 lite")["hbm_gbps"] == 819.0
    with pytest.raises(KeyError, match="no peaks"):
        build.peaks_for("TPU v9")
