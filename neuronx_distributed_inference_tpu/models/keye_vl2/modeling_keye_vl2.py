"""Keye-VL-2.0 language model, ``model_type`` ``KeyeVL2`` (registry name
``keye_vl2``; Kwai-Keye/Keye-VL-2.0-30B-A3B): a Qwen3-MoE-shaped decoder
(per-head q / k RMSNorm, GQA, a softmax router with the top k renormalised,
SwiGLU experts, no shared expert: ``models/qwen3_moe``, whose ``build_spec``,
share and loader it uses) whose every attention layer carries a LEARNED
SPARSE SELECTION, the lightning indexer of DeepSeek-V3.2-Exp's sparse
attention (``sa_config``; ``model_base.SparseSpec`` has the equations):
``indexer_num_heads`` index heads of ``indexer_head_dim`` score each cached
token's ONE index key, and the attention reads the ``topk`` best alone.

The index keys are a third paged pool on the K / V pools' block table, so
the family serves through the paged path only
(``model_base.SPARSE_UNSUPPORTED`` names what it does not run under).

Assumed, where the published config does not say (the benchmark's
``configs/keye-vl-2.0-30b-a3b.json`` lists each with its reason): the
indexer reads the layer's normed input; a LayerNorm (weight and bias) on the
index key and rotary over all lanes of index queries and key, halves
convention, at the model's ``rope_theta``; the tensor names
``self_attn.indexer.{wq, wk, k_norm, weights_proj}``; ``q_chunk_size`` /
``kv_chunk_size`` are a kernel's tile sizes and change no result.

Left out: the vision tower (its config is not in the repository) and
multimodal positions - with text ids the three ``mrope_section`` parts
carry one position and the rotary is the ordinary one.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from ...config import InferenceConfig
from ...ops.rope import RopeConfig
from ..family import register_family
from ..model_base import DecoderSpec, SparseSpec
from ..qwen3_moe.modeling_qwen3_moe import (Qwen3MoeFamily,
                                            Qwen3MoeInferenceConfig)


class KeyeVL2InferenceConfig(Qwen3MoeInferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return super().get_required_attributes() + ["sa_config"]

    def get_text_config(self):
        return self


@register_family("keye_vl2", "KeyeVL2")
class KeyeVL2Family(Qwen3MoeFamily):
    config_cls = KeyeVL2InferenceConfig

    @classmethod
    def attention_overrides(cls, config: InferenceConfig) -> Dict[str, Any]:
        sa = dict(config.sa_config)
        if int(sa.get("indexer_num_kv_heads", 1)) != 1:
            raise NotImplementedError(
                "keye_vl2: indexer_num_kv_heads "
                f"{sa['indexer_num_kv_heads']}; ONE index key a token is "
                "what the index-key pool holds")
        dim = int(sa["indexer_head_dim"])
        return {"sparse": SparseSpec(
            index_heads=int(sa["indexer_num_heads"]), index_dim=dim,
            topk=int(sa["topk"]),
            rope=RopeConfig(head_dim=dim, rope_theta=float(
                getattr(config, "rope_theta", 10000.0))))}

    @classmethod
    def convert_extra_layer_weights(cls, get, layer_stack, spec: DecoderSpec
                                    ) -> Dict[str, np.ndarray]:
        """``self_attn.indexer``: ``wq.weight`` (heads x dim, H),
        ``wk.weight`` (dim, H), ``weights_proj.weight`` (heads, H) fused
        into ``idx_proj`` (H, [qI | kI | w]); ``k_norm.weight`` / ``.bias``."""
        x = cls.hf_prefix + ".layers.{i}.self_attn.indexer."

        def t(w):
            return np.ascontiguousarray(np.asarray(w).T)
        return {
            "idx_proj": np.concatenate(
                [layer_stack(x + f"{name}.weight", t)
                 for name in ("wq", "wk", "weights_proj")], axis=-1),
            "idx_k_norm": layer_stack(x + "k_norm.weight", np.asarray),
            "idx_k_norm_b": layer_stack(x + "k_norm.bias", np.asarray),
        }
