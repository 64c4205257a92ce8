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
    # another architecture: its reference, its gate's cut and its cell are
    # files added beside the toy's own (README, "Adding things ...")
    ("toy-granite-closed", {"tokens_per_s", "itl_p50_ms", "itl_p95_ms",
                            "setup_s"}),
])
def test_untraced_cell_reports_its_end_to_end_metrics(toy, workload, names):
    out = _run(workload, trace=0)
    _held_to_contract(out, names)
    assert all(m["value"] > 0 for m in out["metrics"].values())


@pytest.mark.parametrize("workload", ["toy-closed", "toy-granite-closed"])
def test_traced_cell_reports_what_its_readers_find(toy, workload):
    from neuronx_distributed_inference_tpu import telemetry
    from neuronx_distributed_inference_tpu.telemetry.trace import \
        disable_recorder
    try:
        out = _run(workload, trace=1)
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


def test_the_toy_gate_goes_through_the_reference_found_by_name(
        toy, monkeypatch):
    """The granite toy's gate passes against ``references/granite.py``, and
    fails once that reference computes something else (its residual
    multiplier dropped): the served twin is held to the file found."""
    import types
    cfg = build.load_json("configs", "toy-granite.json")
    assert cfg["model_type"] not in build.BUILTIN_REFERENCES
    assert build.logit_gate(cfg, 2**31 + 5)["passed"] is True
    ref = build.load_reference("granite")
    monkeypatch.setattr(build, "load_reference", lambda model_type: (
        types.SimpleNamespace(
            weight_shapes=ref.weight_shapes,
            forward=lambda c, w, ids, with_margins=False: ref.forward(
                dict(c, residual_multiplier=1.0), w, ids, with_margins))))
    out = build.logit_gate(cfg, 2**31 + 5)
    assert out["passed"] is False and out["worst_ratio"] > 4.0


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
            # a metric's cells are listed in BENCHMARK.json alone
            assert "workloads" not in file, m["name"]
            for key in set(file) & set(m):
                assert file[key] == m[key], (m["name"], key)
            assert {"layer", "unit", "better", "source", "moves"} <= \
                set(file) & set(m)
        # warm: decode, and every prefill bucket of the cell's own that a
        # chunk of its mix's prompts can land in
        buckets = sorted(spec["config"]["serve"]["context_encoding_buckets"])
        lens = spec["mix"]["prompt_len"]
        widths = build.warm_widths(spec["config"], spec["mix"])
        assert widths[0] == 1 and set(widths[1:]) <= set(buckets)
        assert widths == sorted(set(widths))
        for n in (lens["lo"], lens["hi"]):
            last = (n - 1) % buckets[-1] + 1
            assert next(b for b in buckets if b >= last) in widths
        if lens["hi"] > buckets[-1]:
            assert buckets[-1] in widths


@pytest.mark.parametrize("gate,twin", [
    ({"layers": 2}, {"num_hidden_layers": 2}),
    ({"config": {"num_hidden_layers": 3, "layer_types": ["m", "a", "m"]}},
     {"num_hidden_layers": 3, "layer_types": ["m", "a", "m"]}),
])
def test_the_gates_cut_is_data(gate, twin):
    """``gate.layers`` is the short form; ``gate.config`` replaces any
    published key, and the twin's dict is the file's with those in place."""
    cfg = {"model_type": "x", "num_hidden_layers": 40, "layer_types": ["m"],
           "hidden_size": 8, "family": "x", "gate": gate}
    assert build.gate_overrides(gate) == twin
    hf = build.hf_config(cfg, build.gate_overrides(gate))
    assert hf == dict({"model_type": "x", "hidden_size": 8,
                       "layer_types": ["m"]}, **twin)
    assert build.hf_config(cfg)["num_hidden_layers"] == 40   # file untouched


@pytest.mark.parametrize("gate", [{}, {"layers": 2, "config": {}}])
def test_a_gate_gives_one_form_of_its_cut(gate):
    with pytest.raises(ValueError, match="either 'config'"):
        build.gate_overrides(gate)


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
