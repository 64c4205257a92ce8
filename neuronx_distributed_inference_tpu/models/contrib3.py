"""Contrib hub wave 3 (reference: contrib/models/ — SURVEY §2.7):
openai-gpt (post-LN GPT-1), VaultGemma, Apertus (xIELU), Phi-3.5-MoE
(sparsemixer routing). LFM2 moved to models/lfm2/ with its expert sibling."""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np

from ..config import InferenceConfig
from ..modules.moe import MoESpec
from .contrib import GPT2Family, _SimpleConfig, _ident, _t
from .family import DecoderFamily, register_family
from .model_base import spec_from_config
from .contrib import StableLmFamily
from .olmo2.modeling_olmo2 import Olmo2Family
from ..ops.rope import RopeConfig


@register_family("openai-gpt")
class OpenAIGPTFamily(GPT2Family):
    """GPT-1 (reference: contrib/models/openai-gpt): gpt2-shaped fused
    Conv1D attention + learned positions, but POST-layernorm blocks
    (x = ln(x + sublayer(x))) and no final norm."""

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.n_embd
        nh = config.n_head
        return spec_from_config(
            config, tp_degree,
            num_layers=config.n_layer,
            hidden_size=H,
            num_q_heads=nh, num_kv_heads=nh, head_dim=H // nh,
            intermediate_size=getattr(config, "n_inner", None) or 4 * H,
            rms_eps=float(getattr(config, "layer_norm_epsilon", 1e-5)),
            act={"gelu": "gelu_new", "gelu_new": "gelu_new",
                 "relu": "relu", "silu": "silu"}.get(
                getattr(config, "afn", "gelu"), "gelu_new"),
            norm_type="layernorm", norm_bias=True,
            norm_position="post_residual", skip_final_norm=True,
            mlp_glu=False, mlp_bias=True,
            qkv_bias=True, o_bias=True,
            no_rope=True,
            learned_pos=int(getattr(config, "n_positions", 512)),
            tie_word_embeddings=True,
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        sd = dict(sd)
        p = cls.hf_prefix
        # tokens_embed/positions_embed -> the gpt2 wte/wpe names the base
        # converter consumes; no ln_f exists (skip_final_norm)
        sd[p + ".wte.weight"] = sd.pop(p + ".tokens_embed.weight")
        sd[p + ".wpe.weight"] = sd.pop(p + ".positions_embed.weight")
        H = spec.hidden_size
        sd[p + ".ln_f.weight"] = np.ones((H,), np.float32)
        sd[p + ".ln_f.bias"] = np.zeros((H,), np.float32)
        out = super().convert_hf_state_dict(sd, spec)
        out.pop("final_norm", None)
        out.pop("final_norm_b", None)
        return out


@register_family("vaultgemma")
class VaultGemmaFamily(DecoderFamily):
    """VaultGemma (reference: contrib/models/vaultgemma-1b): gemma2-style
    soft caps + alternating sliding/full layers, but only two pre-norms
    per layer (no sandwich norms)."""

    config_cls = _SimpleConfig
    post_norm_src = "pre_feedforward_layernorm"

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.hidden_size
        lt = list(getattr(config, "layer_types", []) or [])
        pattern = (tuple(t == "sliding_attention" for t in lt)
                   if lt and not all(t == lt[0] for t in lt) else None)
        window = int(getattr(config, "sliding_window", 0) or 0)
        qpre = getattr(config, "query_pre_attn_scalar", None)
        return spec_from_config(
            config, tp_degree,
            act=getattr(config, "hidden_activation", "gelu_pytorch_tanh"),
            embed_scale=math.sqrt(H),
            norm_offset=1.0,
            attn_scale=(float(qpre) ** -0.5 if qpre else None),
            attn_soft_cap=getattr(config, "attn_logit_softcapping", None),
            logits_soft_cap=getattr(config, "final_logit_softcapping", None),
            sliding_window=window,
            layer_pattern=pattern,
            qkv_bias=bool(getattr(config, "attention_bias", False)),
            o_bias=bool(getattr(config, "attention_bias", False)),
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             True)),
        )


class ApertusInferenceConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["hidden_size", "num_attention_heads", "num_hidden_layers",
                "num_key_value_heads", "vocab_size", "intermediate_size"]

    def get_text_config(self):
        return self


@register_family("apertus")
class ApertusFamily(DecoderFamily):
    """Swiss AI Apertus (reference: contrib/models/Apertus-8B-Instruct-2509):
    llama attention + per-head q/k RMSNorm before rope + plain up/down MLP
    with the learned-alpha xIELU activation."""

    config_cls = ApertusInferenceConfig
    input_norm_src = "attention_layernorm"
    post_norm_src = "feedforward_layernorm"

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        return spec_from_config(
            config, tp_degree,
            qk_norm=True,
            mlp_glu=False,
            act="xielu",
            qkv_bias=bool(getattr(config, "attention_bias", False)),
            o_bias=bool(getattr(config, "attention_bias", False)),
        )

    @classmethod
    def convert_mlp_weights(cls, get, layer_stack, spec):
        p = cls.hf_prefix
        return {
            # plain-MLP slots: gate_proj/down_proj hold fc1/fc2
            "gate_proj": layer_stack(p + ".layers.{i}.mlp.up_proj.weight",
                                     _t),
            "down_proj": layer_stack(p + ".layers.{i}.mlp.down_proj.weight",
                                     _t),
        }

    @classmethod
    def convert_extra_layer_weights(cls, get, layer_stack, spec):
        p = "model.layers.{i}.mlp.act_fn."

        def scalar(name):
            def tr(i):
                return np.float32(np.asarray(get(p.format(i=i) + name))
                                  .reshape(-1)[0])
            return tr

        xi = np.stack([
            np.array([scalar("alpha_p")(i), scalar("alpha_n")(i),
                      scalar("beta")(i), scalar("eps")(i)], np.float32)
            for i in range(spec.num_layers)])
        return {"xielu": xi}


@register_family("phimoe")
class PhimoeFamily(DecoderFamily):
    """Phi-3.5-MoE (reference: contrib/models/Phi-3.5-MoE-instruct):
    mixtral-shaped 16-expert top-2 MoE with the sparsemixer inference
    routing, LayerNorm (with bias) norms, and an optional lm-head bias."""

    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        moe = MoESpec(
            num_experts=config.num_local_experts,
            top_k=config.num_experts_per_tok,
            intermediate_size=config.intermediate_size,
            normalize_topk=False,
            router_act="sparsemixer",
            sparsemixer_eps=float(getattr(config, "router_jitter_noise",
                                          0.01)),
            act=getattr(config, "hidden_act", "silu"),
        )
        bias = bool(getattr(config, "attention_bias", False))
        window = getattr(config, "sliding_window", None) or 0
        return spec_from_config(
            config, tp_degree, moe=moe,
            norm_type="layernorm", norm_bias=True,
            qkv_bias=bias, o_bias=bias,
            lm_head_bias=bool(getattr(config, "lm_head_bias", False)),
            sliding_window=int(window),
        )

    @classmethod
    def convert_mlp_weights(cls, get, layer_stack, spec):
        p = cls.hf_prefix
        return cls.convert_moe_weights(
            get, spec,
            router_name=p + ".layers.{i}.block_sparse_moe.gate.weight",
            expert_fmt=(p + ".layers.{i}.block_sparse_moe.experts.{e}."
                        "{name}.weight"),
            gate="w1", up="w3", down="w2")

    @classmethod
    def convert_extra_layer_weights(cls, get, layer_stack, spec):
        p = cls.hf_prefix
        return {
            "input_norm_b": layer_stack(
                p + ".layers.{i}.input_layernorm.bias", _ident),
            "post_norm_b": layer_stack(
                p + ".layers.{i}.post_attention_layernorm.bias", _ident),
        }

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        out = super().convert_hf_state_dict(sd, spec)
        out["final_norm_b"] = np.asarray(sd["model.norm.bias"])
        if spec.lm_head_bias and "lm_head.bias" in sd:
            b = np.asarray(sd["lm_head.bias"])
            if b.shape[0] < spec.padded_vocab:
                b = np.pad(b, (0, spec.padded_vocab - b.shape[0]))
            out["lm_head_b"] = b
        return out

    @classmethod
    def load_hf_model(cls, model_path: str):
        from transformers.models.phimoe import PhimoeForCausalLM
        return PhimoeForCausalLM.from_pretrained(model_path)


@register_family("minicpm", "minicpm4")
class MiniCPMFamily(DecoderFamily):
    """MiniCPM / MiniCPM4 (reference: contrib/models/MiniCPM4-8B/src/
    modeling_minicpm.py): llama shape with MuP-style scalings — embeddings
    x scale_emb, every sublayer residual x scale_depth/sqrt(L), lm-head
    input / (hidden/dim_model_base) — and longrope scaling for the 4-series.
    The scalings map 1:1 onto existing spec knobs (embed_scale,
    residual_multiplier, logits_divide)."""

    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        L = config.num_hidden_layers
        H = config.hidden_size
        dmb = float(getattr(config, "dim_model_base", H) or H)
        return spec_from_config(
            config, tp_degree,
            embed_scale=float(getattr(config, "scale_emb", 1.0)),
            residual_multiplier=float(
                getattr(config, "scale_depth", 1.0)) / math.sqrt(L),
            logits_divide=H / dmb,
        )


@register_family("orion")
class OrionFamily(StableLmFamily):
    """Orion-14B (reference: contrib/models/orion-14b-chat/src/
    modeling_orion.py): llama shape with biased LayerNorm everywhere —
    structurally stablelm at full rotary without qkv biases, so the
    LayerNorm-bias conversion is inherited."""

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        return spec_from_config(
            config, tp_degree,
            rms_eps=float(getattr(config, "rms_norm_eps", 1e-5)),
            norm_type="layernorm", norm_bias=True,
        )


@register_family("internlm3")
class InternLM3Family(DecoderFamily):
    """InternLM3 (reference: contrib/models/internlm3-8b-instruct/src/
    modeling_internlm3.py): llama shape with independent qkv_bias /
    (o+mlp) bias knobs."""

    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        return spec_from_config(
            config, tp_degree,
            qkv_bias=bool(getattr(config, "qkv_bias", False)),
            o_bias=bool(getattr(config, "bias", False)),
            mlp_bias=bool(getattr(config, "bias", False)),
        )


@register_family("olmo3")
class Olmo3Family(Olmo2Family):
    """OLMo-3 (reference: contrib/models/OLMo-3-7B-Think/src/
    modeling_olmo3.py): olmo2's post-norm blocks + full-width q/k RMSNorm,
    plus an alternating sliding/full layer pattern."""

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        lt = list(getattr(config, "layer_types", []) or [])
        pattern = (tuple(t == "sliding_attention" for t in lt)
                   if lt and not all(t == lt[0] for t in lt) else None)
        all_sliding = bool(lt) and all(t == "sliding_attention" for t in lt)
        window = int(getattr(config, "sliding_window", 0) or 0)
        # HF olmo3 rotates sliding layers with PLAIN rope regardless of the
        # config's rope_scaling (two rotary embeddings, rope_type="default"
        # for sliding_attention)
        local = None
        if pattern is not None and getattr(config, "rope_scaling", None):
            H = config.hidden_size
            hd = (getattr(config, "head_dim", None)
                  or H // config.num_attention_heads)
            local = RopeConfig(head_dim=hd, rope_theta=float(
                getattr(config, "rope_theta", 500000.0)))
        return spec_from_config(
            config, tp_degree,
            norm_position="post",
            sandwich_norm=True,
            qk_norm_full=True,
            sliding_window=window if (pattern is not None
                                      or all_sliding) else 0,
            layer_pattern=pattern,
            local_rope=local,
        )
