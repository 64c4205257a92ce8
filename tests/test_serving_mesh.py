"""CPU-mesh tier-1 coverage for the SERVING path (ROADMAP item 2a start):
block-KV + continuous-batching decode driven through
``PagedEngineAdapter`` over a dp2 x tp2 mesh of virtual CPU devices.

Correctness gate mirrors test_parallelism.py: sharded execution must
reproduce the single-device token stream bit-identically (GSPMD only
changes the schedule, not the math) — checkpoint-loaded weights, because
the padding/replication invariants only hold for converted checkpoints.

Budget: one ctx bucket (16) + the w1 decode shape — two compiles of one
tiny 2-layer graph per mesh config, <20s warm for the whole module.
"""

import numpy as np
import pytest
import torch

from neuronx_distributed_inference_tpu.config import (TpuConfig,
                                                      load_pretrained_config)
from neuronx_distributed_inference_tpu.models.application import \
    PagedCausalLMApplication
from neuronx_distributed_inference_tpu.models.llama import (
    LlamaFamily, LlamaInferenceConfig)
from neuronx_distributed_inference_tpu.parallel.mesh import mesh_from_config
from neuronx_distributed_inference_tpu.serving import PagedEngineAdapter

from conftest import tiny_llama_hf_config


@pytest.fixture(scope="module")
def ckpt_dir(tmp_path_factory):
    from transformers import LlamaConfig, LlamaForCausalLM
    torch.manual_seed(0)
    model = LlamaForCausalLM(LlamaConfig(**tiny_llama_hf_config(
        num_hidden_layers=2)))
    model.eval()
    d = tmp_path_factory.mktemp("tiny_llama_mesh")
    model.save_pretrained(d, safe_serialization=True)
    return str(d)


def _drive_adapter(ckpt_dir, tcfg_over):
    """One serving scenario: admit two ragged prompts, decode, then a
    continuous-batching slot swap (release one row, admit a new request
    into the freed capacity) — every dispatch at already-compiled shapes."""
    tcfg = TpuConfig(batch_size=2, seq_len=64, dtype="float32",
                     enable_bucketing=True, context_encoding_buckets=[16],
                     is_block_kv_layout=True, pa_block_size=8,
                     is_prefix_caching=True, **tcfg_over)
    icfg = LlamaInferenceConfig(tcfg, load_config=load_pretrained_config(
        ckpt_dir))
    mesh = mesh_from_config(tcfg)
    app = PagedCausalLMApplication(ckpt_dir, icfg, LlamaFamily, mesh=mesh)
    app.load_weights().init_cache()
    eng = PagedEngineAdapter(app)
    rng = np.random.default_rng(7)
    prompts = {0: rng.integers(1, 500, size=5).tolist(),
               1: rng.integers(1, 500, size=9).tolist(),
               2: rng.integers(1, 500, size=7).tolist()}
    toks = {sid: [] for sid in prompts}

    def collect(out):
        for sid, t in out.items():
            toks[sid].append(t)

    collect(eng.add_requests([0, 1], [prompts[0], prompts[1]]))
    for _ in range(3):
        collect(eng.step())
    # continuous batching: free row 0's blocks, admit request 2 into the
    # freed slot, keep decoding the mixed batch
    eng.release([0])
    collect(eng.add_requests([2], [prompts[2]]))
    for _ in range(2):
        collect(eng.step())
    eng.release([1, 2])
    assert not app.kv_mgr.tables
    return toks, app, mesh


def test_paged_adapter_on_dp_tp_mesh_matches_single_device(ckpt_dir):
    base, _, _ = _drive_adapter(ckpt_dir, {"tp_degree": 1})
    sharded, app, mesh = _drive_adapter(
        ckpt_dir, {"tp_degree": 4, "attention_dp_degree": 2})
    assert (mesh.shape["dp"], mesh.shape["tp"]) == (2, 2)
    # params really are sharded over the model axis
    assert any("tp" in str(x.sharding.spec)
               for x in app.params["layers"].values()
               if hasattr(x, "sharding"))
    assert base == sharded
    # every row generated through both phases of the swap
    assert all(len(v) >= 3 for v in base.values())
