"""Config system tests (reference analog: test/unit config tests)."""

import json

import pytest

from neuronx_distributed_inference_tpu.config import (
    InferenceConfig, OnDeviceSamplingConfig, SpeculationConfig, TpuConfig)


def test_defaults_derive():
    c = TpuConfig(batch_size=2, seq_len=256)
    assert c.ctx_batch_size == 2
    assert c.tkg_batch_size == 2
    assert c.kv_cache_batch_size == 2
    assert c.max_context_length == 256
    assert c.kv_cache_dtype == "bfloat16"


def test_continuous_batching_ctx_batch():
    c = TpuConfig(batch_size=4, is_continuous_batching=True)
    assert c.ctx_batch_size == 1
    assert c.kv_cache_batch_size == 4


def test_validation_errors():
    with pytest.raises(ValueError):
        TpuConfig(seq_len=128, max_context_length=256)
    with pytest.raises(ValueError):
        TpuConfig(tp_degree=8, cp_degree=3)
    with pytest.raises(ValueError):
        TpuConfig(is_chunked_prefill=True)


def test_json_round_trip(tmp_path):
    c = TpuConfig(batch_size=2, seq_len=128, tp_degree=4,
                  on_device_sampling_config=OnDeviceSamplingConfig(
                      do_sample=True, top_k=50),
                  speculation_config=SpeculationConfig(
                      speculation_length=5))
    cfg = InferenceConfig(c, hidden_size=64, num_attention_heads=4,
                          vocab_size=512)
    p = tmp_path / "cfg.json"
    cfg.save(str(p))
    loaded = InferenceConfig.load(str(p))
    assert loaded.tpu_config.batch_size == 2
    assert loaded.tpu_config.tp_degree == 4
    assert loaded.tpu_config.on_device_sampling_config.top_k == 50
    assert loaded.tpu_config.speculation_config.speculation_length == 5
    assert loaded.hidden_size == 64


def test_unknown_keys_warn_not_raise():
    c = TpuConfig.from_dict({"batch_size": 1, "definitely_not_a_knob": 7})
    assert c.batch_size == 1


def test_dead_knobs_raise_or_work():
    """Every accepted knob changes behavior or errors (reference parity
    audit): pp_degree is refused (there is no inference pipeline schedule,
    so there is no such field: the constructor's own refusal of an argument
    it does not know), vocab_parallel switches the embed sharding (next
    test)."""
    with pytest.raises(TypeError, match="pp_degree"):
        TpuConfig(pp_degree=2, tp_degree=2)


# Keys a parent's ``to_dict`` wrote that this tree has no field for (ROADMAP
# C4): of PR 31's, thirteen nothing read and fifteen more the guard below
# found; of PR 45's, the eleven only config.py consumed or only tests set.
_RETIRED = {
    None: "n_active_tokens mlp_cp_degree start_rank_id local_ranks_size "
          "kv_cache_padding_size bucket_n_active_tokens qkv_kernel_enabled "
          "mlp_kernel_enabled attn_block_tkg_nki_kernel_enabled async_mode "
          "rpl_reduce_dtype cast_type skip_sharding rope_dtype "
          "quantized_checkpoints_path "
          "max_batch_size logits_dtype pp_degree world_size",
    "on_device_sampling_config": "on_device dynamic",
    "chunked_prefill_config": "max_num_seqs kernel_kv_tile_size",
    "moe_config": "capacity_factor glu_mlp glu_type fused_shared_experts "
                  "early_expert_affinity_modulation "
                  "normalize_top_k_affinities moe_tp_degree moe_ep_degree",
    "lora_config": "lora_dtype",
    "speculation_config": "spec_batch_size is_eagle_draft draft_model_module "
                          "enable_fused_speculation enable_eagle_speculation "
                          "enable_eagle_draft_input_norm num_medusa_heads"}


def test_a_config_saved_by_the_parent_still_loads():
    """The retired keys are dropped with a warning (``from_dict``'s rule for
    any key it does not know) and every field that remains round-trips."""
    from neuronx_distributed_inference_tpu.config import (
        ChunkedPrefillConfig, LoraServingConfig, MoEConfig)
    c = TpuConfig(batch_size=2, seq_len=128, tp_degree=4, cp_degree=2,
                  sequence_parallel_enabled=True, is_block_kv_layout=True,
                  on_device_sampling_config=OnDeviceSamplingConfig(
                      do_sample=True, top_k=50, stream_seed=3),
                  chunked_prefill_config=ChunkedPrefillConfig(
                      kernel_q_tile_size=64),
                  moe_config=MoEConfig(moe_tkg_ep_degree=1),
                  lora_config=LoraServingConfig(max_loras=3),
                  speculation_config=SpeculationConfig(speculation_length=5))
    mine = c.to_dict()
    saved = json.loads(json.dumps(mine))
    for sub, names in _RETIRED.items():
        into = saved if sub is None else saved[sub]
        assert not set(names.split()) & set(into)
        into.update(dict.fromkeys(names.split(), 0))
    loaded = TpuConfig.from_dict(saved)
    assert loaded == c and loaded.to_dict() == mine


def test_every_config_field_has_a_reader():
    """An option a user can set to no effect is not an option: some module
    of the package other than config.py reads every field of TpuConfig and
    its sub-configs (an attribute, or its name as a string for getattr)."""
    import ast
    import dataclasses
    from pathlib import Path

    from neuronx_distributed_inference_tpu import config as config_mod
    here = Path(config_mod.__file__)
    read = set()
    for path in here.parent.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if path != here and isinstance(node, ast.Attribute):
                read.add(node.attr)
            elif path != here and isinstance(node, ast.Constant):
                read.add(node.value)
    unread = {f"{cls.__name__}.{f.name}"
              for cls in (TpuConfig, *config_mod._SUBCONFIG_TYPES.values())
              for f in dataclasses.fields(cls) if f.name not in read}
    assert unread == set(), f"no module reads {sorted(unread)}"


def test_vocab_parallel_controls_embed_sharding():
    from jax.sharding import PartitionSpec as P
    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models import model_base
    from conftest import tiny_llama_hf_config
    from neuronx_distributed_inference_tpu.models.llama import \
        LlamaInferenceConfig

    def embed_pspec(vocab_parallel):
        tcfg = TpuConfig(tp_degree=2, vocab_parallel=vocab_parallel)
        icfg = LlamaInferenceConfig(tcfg, **tiny_llama_hf_config())
        spec = model_base.spec_from_config(icfg)
        return model_base.decoder_param_specs(spec)["embed"].pspec

    assert embed_pspec(True) == P(("ep", "tp"), None)
    assert embed_pspec(False) == P()


def test_save_converted_checkpoint_roundtrip(tmp_path):
    import numpy as np
    from neuronx_distributed_inference_tpu.config import TpuConfig
    from neuronx_distributed_inference_tpu.models.application import \
        CausalLMApplication
    from neuronx_distributed_inference_tpu.models.llama import (
        LlamaFamily, LlamaInferenceConfig)
    from conftest import tiny_llama_hf_config
    tcfg = TpuConfig(batch_size=2, seq_len=32, dtype="float32",
                     enable_bucketing=False, save_sharded_checkpoint=True)
    icfg = LlamaInferenceConfig(tcfg, **tiny_llama_hf_config())
    app = CausalLMApplication(None, icfg, LlamaFamily)
    app.init_random_weights(seed=3)
    app.init_cache()
    prompt = np.arange(1, 9, dtype=np.int32)[None].repeat(2, 0)
    ref = app.generate(prompt, max_new_tokens=4)["sequences"]
    app.compile(str(tmp_path / "artifact"))   # saves the converted ckpt

    app2 = CausalLMApplication(None, icfg, LlamaFamily)
    app2.load_converted_checkpoint(str(tmp_path / "artifact"))
    app2.init_cache()
    got = app2.generate(prompt, max_new_tokens=4)["sequences"]
    np.testing.assert_array_equal(got, ref)
