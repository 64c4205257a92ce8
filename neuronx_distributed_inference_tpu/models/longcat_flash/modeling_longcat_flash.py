"""LongCat-Flash family, ``model_type`` ``longcat_flash`` (meituan-longcat/
LongCat-Flash-Chat and the language model of LongCat-Flash-Omni; HF
``modeling_longcat_flash.py``). A layer is NOT "attention then MLP"
(``DecoderSpec.sub_blocks`` = 2; ``model_base.run_layers_shortcut``, its
block on the walks' one scan, ``scan_layers``): with
``N`` an RMSNorm and ``x`` the layer's input,

    a0 = x + MLA_0(N(x));    u = N(a0);    s = MoE(u)
    b0 = a0 + MLP_0(u);      a1 = b0 + MLA_1(N(b0))
    y  = a1 + MLP_1(N(a1)) + s

two latent-attention sub-blocks (each its own cache layer: ``num_layers``
layers are 2 x ``num_layers`` attention layers), two dense SwiGLU MLPs of
``ffn_hidden_size`` and ONE routed block whose output joins the residual at
the END of the layer (the shortcut, which upstream hides the experts'
exchange behind ``MLP_0`` and ``MLA_1``).

* MLA as DeepSeek's, with the whole query scaled by ``sqrt(hidden /
  q_lora_rank)`` and the normed latent by ``sqrt(hidden / kv_lora_rank)``
  (``MLASpec.q_scale`` / ``kv_scale``), rotary on interleaved pairs, no
  rope scaling; the paged pool keeps a token's latent row
  (``modules/block_kv_cache.latent_lanes``).
* The router scores ``n_routed_experts + zero_expert_num`` columns in
  float32, softmax, top ``moe_topk`` of ``p + e_score_correction_bias``
  (selection only), weights ``routed_scaling_factor x p`` NOT renormalised;
  the last ``zero_expert_num`` columns are identity experts
  (``MoESpec.zero_experts``).

ONE CHIP'S SHARE of the expert layers: with ``router_n_routed_experts`` in
the config, ``n_routed_experts`` is what the weights HOLD (from
``first_expert`` on) and the router still scores ``router_n_routed_experts +
zero_expert_num``; the block computes the held experts' part of the sum plus
the identity term of the rows it computes, and no code stands in for the
other chips. Without the key every routed expert is held. One chip:
``tp > 1`` and ``ep > 1`` are refused (the share runs without its exchange).

Left out of LongCat-Flash-Omni: the audio and vision encoders and the codec
decoder (the catalog row's config is the language model's).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np

from ...config import InferenceConfig
from ...modules.moe import MoESpec
from ..family import DecoderFamily, register_family
from ..model_base import (DecoderSpec, MLASpec, mla_q_columns,
                          spec_from_config)

#: attention + dense-MLP pairs of a layer (HF hard-codes ``for i in [0, 1]``)
SUB_BLOCKS = 2


class LongcatFlashInferenceConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["hidden_size", "num_attention_heads", "num_layers",
                "vocab_size", "ffn_hidden_size", "expert_ffn_hidden_size",
                "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
                "moe_topk"]

    def get_text_config(self):
        return self


@register_family("longcat_flash")
class LongcatFlashFamily(DecoderFamily):
    config_cls = LongcatFlashInferenceConfig

    @classmethod
    def build_spec(cls, config: InferenceConfig,
                   tp_degree: Optional[int] = None) -> DecoderSpec:
        tcfg = config.tpu_config
        tp = tp_degree if tp_degree is not None else tcfg.tp_degree
        if tp > 1 or getattr(tcfg, "ep_degree", 1) > 1:
            raise NotImplementedError(
                "longcat_flash is served on one chip (tp_degree 1, ep_degree "
                "1): a chip's share of the expert layers "
                "(router_n_routed_experts) runs without the exchange that "
                "would join it to the other shares, and the latent pool has "
                "not run sharded (PERF.md section 7)")
        for key, want in (("attention_bias", False), ("rope_scaling", None),
                          ("router_bias", False),
                          ("zero_expert_type", "identity"),
                          ("attention_method", "MLA"),
                          ("hidden_act", "silu")):
            got = getattr(config, key, want)
            if (got or want) != want:
                raise NotImplementedError(
                    f"longcat_flash with {key} = {got!r}: the published "
                    f"value is {want!r} and nothing else has been walked")
        hidden = int(config.hidden_size)
        mla = MLASpec(
            kv_lora_rank=int(config.kv_lora_rank),
            qk_nope_head_dim=int(config.qk_nope_head_dim),
            qk_rope_head_dim=int(config.qk_rope_head_dim),
            v_head_dim=int(config.v_head_dim),
            q_lora_rank=int(config.q_lora_rank),
            q_scale=((hidden / int(config.q_lora_rank)) ** 0.5
                     if getattr(config, "mla_scale_q_lora", True) else 1.0),
            kv_scale=((hidden / int(config.kv_lora_rank)) ** 0.5
                      if getattr(config, "mla_scale_kv_lora", True) else 1.0))
        held = int(config.n_routed_experts)
        routed = int(getattr(config, "router_n_routed_experts", None) or held)
        first = int(getattr(config, "first_expert", 0) or 0)
        zero = int(getattr(config, "zero_expert_num", 0) or 0)
        if not 0 <= first <= routed - held:
            raise ValueError(
                f"experts {first}..{first + held - 1} held of {routed} "
                "routed ones")
        spec = spec_from_config(
            config, tp_degree,
            num_layers=int(config.num_layers),
            num_kv_heads=int(config.num_attention_heads),
            intermediate_size=int(config.ffn_hidden_size),
            sub_blocks=SUB_BLOCKS,
            mla=mla,
            head_dim=mla.qk_head_dim,
            attn_scale=mla.qk_head_dim ** -0.5,
            rope_interleaved=True,
            moe=MoESpec(
                num_experts=routed + zero, top_k=int(config.moe_topk),
                intermediate_size=int(config.expert_ffn_hidden_size),
                normalize_topk=bool(getattr(config, "norm_topk_prob",
                                            False)),
                routed_scaling=float(getattr(config, "routed_scaling_factor",
                                             1.0)),
                has_router_bias=True,          # e_score_correction_bias
                router_bias_mode="select",
                act="silu",
                held_experts=held if held < routed else 0,
                first_expert=first, zero_experts=zero),
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             False)),
        )
        # rope operates on the dedicated rope head only
        return dataclasses.replace(
            spec, rope=dataclasses.replace(spec.rope,
                                           head_dim=mla.qk_rope_head_dim))

    @classmethod
    def convert_hf_state_dict(cls, sd, spec: DecoderSpec) -> Dict[str, Any]:
        """"layers" = the ``2 x num_layers`` [MLA, dense MLP] pairs,
        layer-major (pair ``2 i + j`` is ``self_attn.{j}`` / ``mlps.{j}`` /
        ``input_layernorm.{j}`` / ``post_attention_layernorm.{j}`` of layer
        ``i``); "moe_layers" = the routed block of every layer.

        Experts: a checkpoint that holds every routed expert is read at
        ``first_expert + e``; one that holds the share alone (the
        benchmark's seeded weights) at ``e``. The router keeps all its
        columns either way."""
        moe = spec.moe
        L, n = spec.num_layers, spec.sub_blocks

        def get(name):
            if name in sd:
                return np.asarray(sd[name])
            raise KeyError(f"missing checkpoint tensor {name}")

        def t(w):
            return np.ascontiguousarray(np.asarray(w).T)

        def pairs(fmt, tr=np.asarray):
            return np.stack([tr(get(fmt.format(i=i, j=j)))
                             for i in range(L) for j in range(n)])

        def every(fmt, tr=np.asarray):
            return np.stack([tr(get(fmt.format(i=i))) for i in range(L)])

        p = "model.layers.{i}."
        a = p + "self_attn.{j}."
        x = p + "mlp."
        whole = (x + f"experts.{moe.num_routed - 1}.up_proj.weight"
                 ).format(i=0) in sd
        first = moe.first_expert if whole else 0

        def experts(name):
            return np.stack([np.stack([
                t(get((x + f"experts.{first + e}.{name}.weight").format(i=i)))
                for e in range(moe.num_held)]) for i in range(L)])

        out = {
            "embed": get("model.embed_tokens.weight"),
            "final_norm": get("model.norm.weight"),
            "layers": {
                "input_norm": pairs(p + "input_layernorm.{j}.weight"),
                "post_norm": pairs(p + "post_attention_layernorm.{j}.weight"),
                "q_a_proj": pairs(a + "q_a_proj.weight", t),
                "q_a_norm": pairs(a + "q_a_layernorm.weight"),
                "q_b_proj": pairs(a + "q_b_proj.weight", lambda w: (
                    mla_q_columns(t(w), spec.num_q_heads,
                                  spec.mla.qk_nope_head_dim,
                                  spec.mla.qk_rope_head_dim))),
                "kv_a_proj": pairs(a + "kv_a_proj_with_mqa.weight", t),
                "kv_a_norm": pairs(a + "kv_a_layernorm.weight"),
                "kv_b_proj": pairs(a + "kv_b_proj.weight", t),
                "o_proj": pairs(a + "o_proj.weight", t),
                "gate_proj": pairs(p + "mlps.{j}.gate_proj.weight", t),
                "up_proj": pairs(p + "mlps.{j}.up_proj.weight", t),
                "down_proj": pairs(p + "mlps.{j}.down_proj.weight", t),
            },
            "moe_layers": {
                "router": every(x + "router.classifier.weight",
                                t).astype(np.float32),
                "router_bias": every(
                    x + "router.e_score_correction_bias").astype(np.float32),
                "expert_gate": experts("gate_proj"),
                "expert_up": experts("up_proj"),
                "expert_down": experts("down_proj"),
            },
        }
        pad = spec.padded_vocab - out["embed"].shape[0]
        if pad:
            out["embed"] = np.pad(out["embed"], [(0, pad), (0, 0)])
        if not spec.tie_word_embeddings:
            out["lm_head"] = t(np.pad(get("lm_head.weight"),
                                      [(0, pad), (0, 0)]))
        return out


def TpuLongcatFlashForCausalLM(model_path: str, config: InferenceConfig):
    from ..application import PagedCausalLMApplication
    return PagedCausalLMApplication(model_path, config, LongcatFlashFamily)
