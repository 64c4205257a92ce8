"""Qwen3-MoE family (reference: models/qwen3_moe/modeling_qwen3_moe.py
``NeuronQwen3MoeForCausalLM`` — MoE + EP flagship of the reference hub).

Qwen3 attention (per-head q/k RMSNorm, decoupled head_dim) + Mixtral-style
routing (softmax, top-k, optional renormalization via ``norm_topk_prob``).

ONE CHIP'S SHARE of the expert layers, spelt as ``models/qwen3_next`` spells
it: with ``router_num_experts`` in the config, ``num_experts`` is what the
weights HOLD (from ``first_expert`` on) and the router still scores
``router_num_experts``; the block computes the held experts' part of the sum
and no code stands in for the other chips (``modules/moe.py``). Without the
key every expert is held. :func:`moe_share` is the one reading of the keys,
``models/keye_vl2`` its second user.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from ...config import InferenceConfig
from ...modules.moe import MoESpec
from ..family import DecoderFamily, register_family
from ..model_base import DecoderSpec, spec_from_config


class Qwen3MoeInferenceConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["hidden_size", "num_attention_heads", "num_hidden_layers",
                "num_key_value_heads", "vocab_size", "head_dim",
                "num_experts", "num_experts_per_tok", "moe_intermediate_size"]


def moe_share(config: InferenceConfig) -> Dict[str, int]:
    """``MoESpec``'s ``num_experts`` / ``held_experts`` / ``first_expert``
    from ``num_experts`` (held), ``router_num_experts`` (routed over; absent
    = every expert is held) and ``first_expert``."""
    held = int(config.num_experts)
    routed = int(getattr(config, "router_num_experts", None) or held)
    first = int(getattr(config, "first_expert", 0) or 0)
    if not 0 <= first <= routed - held:
        raise ValueError(
            f"experts {first}..{first + held - 1} held of a router over "
            f"{routed}")
    return dict(num_experts=routed,
                held_experts=held if held < routed else 0,
                first_expert=first)


@register_family("qwen3_moe")
class Qwen3MoeFamily(DecoderFamily):
    config_cls = Qwen3MoeInferenceConfig

    @classmethod
    def build_spec(cls, config: InferenceConfig,
                   tp_degree: Optional[int] = None) -> DecoderSpec:
        if getattr(config, "mlp_only_layers", None):
            raise NotImplementedError(
                "qwen3_moe mlp_only_layers (mixed dense/MoE stacks) not "
                "supported yet")
        if getattr(config, "decoder_sparse_step", 1) != 1:
            raise NotImplementedError("decoder_sparse_step != 1 not supported")
        moe = MoESpec(
            **moe_share(config),
            top_k=config.num_experts_per_tok,
            intermediate_size=config.moe_intermediate_size,
            normalize_topk=bool(getattr(config, "norm_topk_prob", True)),
            act=getattr(config, "hidden_act", "silu"),
        )
        return spec_from_config(config, tp_degree, moe=moe, qk_norm=True,
                                intermediate_size=config.moe_intermediate_size,
                                **cls.attention_overrides(config))

    @classmethod
    def attention_overrides(cls, config: InferenceConfig) -> Dict[str, Any]:
        """What a family on this ``build_spec`` adds to the attention
        (``models/keye_vl2``: its learned sparse selection); nothing here."""
        return {}

    @classmethod
    def convert_mlp_weights(cls, get, layer_stack, spec: DecoderSpec
                            ) -> Dict[str, np.ndarray]:
        """HF names: mlp.gate.weight (E,H) router;
        mlp.experts.{e}.gate_proj/up_proj/down_proj."""
        p = cls.hf_prefix
        return cls.convert_moe_weights(
            get, spec,
            router_name=p + ".layers.{i}.mlp.gate.weight",
            expert_fmt=p + ".layers.{i}.mlp.experts.{e}.{name}.weight",
            gate="gate_proj", up="up_proj", down="down_proj")


def TpuQwen3MoeForCausalLM(model_path: str, config: InferenceConfig):
    from ..application import CausalLMApplication
    return CausalLMApplication(model_path, config, Qwen3MoeFamily)
