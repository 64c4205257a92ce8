"""Contrib model hub, wave 2 (reference: contrib/models/ — SURVEY §2.7).
Each family is a thin DecoderSpec mapping + checkpoint conversion over the
shared layer machinery, like wave 1 (contrib.py).

Families: gptj, gpt_neo, gpt_bigcode, opt, xglm, biogpt, helium, ernie4_5,
seed_oss, arcee, nemotron, smollm3, cohere2 (command-r7b), exaone4,
hunyuan_v1_dense, granitemoe."""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import numpy as np

from ..config import InferenceConfig
from .contrib import (_SimpleConfig, _ident, _split_interleaved_qkv, _t, _vpad, _vpad1)
from .family import DecoderFamily, register_family
from .model_base import DecoderSpec, spec_from_config
from ..modules.moe import MoESpec
from ..parallel.layers import place_q_weight, replicate_kv_weight


# ---------------------------------------------------------------------------
# GPT-J (reference: contrib/models/gpt-j)
# ---------------------------------------------------------------------------

@register_family("gptj")
class GPTJFamily(DecoderFamily):
    """Parallel-shared residual (single ln_1), partial INTERLEAVED rotary
    (rotate_every_two), plain gelu MLP, biased untied lm_head."""
    config_cls = _SimpleConfig
    hf_prefix = "transformer"

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.n_embd
        nh = config.n_head
        return spec_from_config(
            config, tp_degree,
            num_layers=config.n_layer,
            hidden_size=H, num_q_heads=nh, num_kv_heads=nh,
            head_dim=H // nh,
            intermediate_size=getattr(config, "n_inner", None) or 4 * H,
            rms_eps=float(getattr(config, "layer_norm_epsilon", 1e-5)),
            act=getattr(config, "activation_function", "gelu_new"),
            norm_type="layernorm", norm_bias=True,
            mlp_glu=False, mlp_bias=True,
            rotary_dim=int(getattr(config, "rotary_dim", None)
                           or (H // nh)),
            rope_interleaved=True,
            block_style="parallel_shared",
            lm_head_bias=True,
            tie_word_embeddings=False,
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        g, D = spec.gqa, spec.head_dim
        p = cls.hf_prefix

        def get(n):
            return np.asarray(sd[n])

        def stack(fmt, tr):
            return np.stack([tr(get(fmt.format(i=i)))
                             for i in range(spec.num_layers)])

        L, H = spec.num_layers, spec.hidden_size
        layers = {
            "input_norm": stack(p + ".h.{i}.ln_1.weight", _ident),
            "input_norm_b": stack(p + ".h.{i}.ln_1.bias", _ident),
            # parallel_shared: post_norm unused
            "post_norm": np.ones((L, H), np.float32),
            "post_norm_b": np.zeros((L, H), np.float32),
            "q_proj": stack(p + ".h.{i}.attn.q_proj.weight",
                            lambda w: place_q_weight(_t(w), g, D, axis=-1)),
            "k_proj": stack(p + ".h.{i}.attn.k_proj.weight",
                            lambda w: replicate_kv_weight(_t(w), g, D,
                                                          axis=-1)),
            "v_proj": stack(p + ".h.{i}.attn.v_proj.weight",
                            lambda w: replicate_kv_weight(_t(w), g, D,
                                                          axis=-1)),
            "o_proj": stack(p + ".h.{i}.attn.out_proj.weight",
                            lambda w: place_q_weight(_t(w), g, D, axis=0)),
            "gate_proj": stack(p + ".h.{i}.mlp.fc_in.weight", _t),
            "gate_bias": stack(p + ".h.{i}.mlp.fc_in.bias", _ident),
            "down_proj": stack(p + ".h.{i}.mlp.fc_out.weight", _t),
            "down_bias": stack(p + ".h.{i}.mlp.fc_out.bias", _ident),
        }
        layers["qkv_proj"] = np.concatenate(
            [layers.pop("q_proj"), layers.pop("k_proj"),
             layers.pop("v_proj")], axis=-1)
        return {
            "embed": _vpad(get(p + ".wte.weight"), spec.padded_vocab),
            "layers": layers,
            "final_norm": get(p + ".ln_f.weight"),
            "final_norm_b": get(p + ".ln_f.bias"),
            "lm_head": _t(_vpad(get("lm_head.weight"), spec.padded_vocab)),
            "lm_head_b": _vpad1(get("lm_head.bias"), spec.padded_vocab),
        }


# ---------------------------------------------------------------------------
# GPT-Neo (reference: contrib/models/gpt-neo)
# ---------------------------------------------------------------------------

@register_family("gpt_neo")
class GPTNeoFamily(DecoderFamily):
    """Alternating global/local (sliding-window) attention, learned
    positions, no rope, plain gelu MLP, LN+bias. Attention projections have
    no bias; output projection does."""
    config_cls = _SimpleConfig
    hf_prefix = "transformer"

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.hidden_size
        nh = config.num_heads
        pattern = tuple(t == "local" for t in config.attention_layers)
        return spec_from_config(
            config, tp_degree,
            num_layers=config.num_layers,
            hidden_size=H, num_q_heads=nh, num_kv_heads=nh,
            head_dim=H // nh,
            intermediate_size=getattr(config, "intermediate_size", None)
            or 4 * H,
            rms_eps=float(getattr(config, "layer_norm_epsilon", 1e-5)),
            act=getattr(config, "activation_function", "gelu_new"),
            norm_type="layernorm", norm_bias=True,
            mlp_glu=False, mlp_bias=True,
            o_bias=True,
            no_rope=True,
            learned_pos=int(getattr(config, "max_position_embeddings", 2048)),
            layer_pattern=pattern if any(pattern) else None,
            sliding_window=int(getattr(config, "window_size", 256)),
            # gpt-neo attention has NO 1/sqrt(d) scaling
            attn_scale=1.0,
            tie_word_embeddings=True,
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        g, D = spec.gqa, spec.head_dim
        p = cls.hf_prefix

        def get(n):
            return np.asarray(sd[n])

        def stack(fmt, tr):
            return np.stack([tr(get(fmt.format(i=i)))
                             for i in range(spec.num_layers)])

        a = ".attn.attention"
        layers = {
            "input_norm": stack(p + ".h.{i}.ln_1.weight", _ident),
            "input_norm_b": stack(p + ".h.{i}.ln_1.bias", _ident),
            "post_norm": stack(p + ".h.{i}.ln_2.weight", _ident),
            "post_norm_b": stack(p + ".h.{i}.ln_2.bias", _ident),
            "q_proj": stack(p + ".h.{i}" + a + ".q_proj.weight",
                            lambda w: place_q_weight(_t(w), g, D, axis=-1)),
            "k_proj": stack(p + ".h.{i}" + a + ".k_proj.weight",
                            lambda w: replicate_kv_weight(_t(w), g, D,
                                                          axis=-1)),
            "v_proj": stack(p + ".h.{i}" + a + ".v_proj.weight",
                            lambda w: replicate_kv_weight(_t(w), g, D,
                                                          axis=-1)),
            "o_proj": stack(p + ".h.{i}" + a + ".out_proj.weight",
                            lambda w: place_q_weight(_t(w), g, D, axis=0)),
            "o_bias": stack(p + ".h.{i}" + a + ".out_proj.bias", _ident),
            "gate_proj": stack(p + ".h.{i}.mlp.c_fc.weight", _t),
            "gate_bias": stack(p + ".h.{i}.mlp.c_fc.bias", _ident),
            "down_proj": stack(p + ".h.{i}.mlp.c_proj.weight", _t),
            "down_bias": stack(p + ".h.{i}.mlp.c_proj.bias", _ident),
        }
        layers["qkv_proj"] = np.concatenate(
            [layers.pop("q_proj"), layers.pop("k_proj"),
             layers.pop("v_proj")], axis=-1)
        return {
            "embed": _vpad(get(p + ".wte.weight"), spec.padded_vocab),
            "pos_embed": get(p + ".wpe.weight"),
            "layers": layers,
            "final_norm": get(p + ".ln_f.weight"),
            "final_norm_b": get(p + ".ln_f.bias"),
        }


# ---------------------------------------------------------------------------
# GPT-BigCode / StarCoder v1 (reference: contrib/models/gpt_bigcode)
# ---------------------------------------------------------------------------

@register_family("gpt_bigcode")
class GPTBigCodeFamily(DecoderFamily):
    """Multi-query attention (1 kv head) with a fused c_attn, learned
    positions, plain gelu MLP, LN+bias."""
    config_cls = _SimpleConfig
    hf_prefix = "transformer"

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.n_embd
        nh = config.n_head
        return spec_from_config(
            config, tp_degree,
            num_layers=config.n_layer,
            hidden_size=H, num_q_heads=nh,
            num_kv_heads=1 if getattr(config, "multi_query", True) else nh,
            head_dim=H // nh,
            intermediate_size=getattr(config, "n_inner", None) or 4 * H,
            rms_eps=float(getattr(config, "layer_norm_epsilon", 1e-5)),
            act=getattr(config, "activation_function", "gelu_pytorch_tanh"),
            norm_type="layernorm", norm_bias=True,
            mlp_glu=False, mlp_bias=True,
            qkv_bias=True, o_bias=True,
            no_rope=True,
            learned_pos=int(getattr(config, "n_positions", 2048)),
            tie_word_embeddings=True,
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        g, D = spec.gqa, spec.head_dim
        H = spec.hidden_size
        kvd = spec.num_kv_heads * D
        p = cls.hf_prefix

        def get(n):
            return np.asarray(sd[n])

        def stack(fmt, tr):
            return np.stack([tr(get(fmt.format(i=i)))
                             for i in range(spec.num_layers)])

        qs, ks, vs, qb, kb, vb = [], [], [], [], [], []
        for i in range(spec.num_layers):
            w = get(f"{p}.h.{i}.attn.c_attn.weight")     # (H+2*kvd, H)
            b = get(f"{p}.h.{i}.attn.c_attn.bias")
            qs.append(place_q_weight(_t(w[:H]), g, D, axis=-1))
            ks.append(replicate_kv_weight(_t(w[H:H + kvd]), g, D, axis=-1))
            vs.append(replicate_kv_weight(_t(w[H + kvd:]), g, D, axis=-1))
            qb.append(place_q_weight(b[:H], g, D))
            kb.append(replicate_kv_weight(b[H:H + kvd], g, D))
            vb.append(replicate_kv_weight(b[H + kvd:], g, D))
        layers = {
            "input_norm": stack(p + ".h.{i}.ln_1.weight", _ident),
            "input_norm_b": stack(p + ".h.{i}.ln_1.bias", _ident),
            "post_norm": stack(p + ".h.{i}.ln_2.weight", _ident),
            "post_norm_b": stack(p + ".h.{i}.ln_2.bias", _ident),
            "qkv_proj": np.concatenate(
                [np.stack(qs), np.stack(ks), np.stack(vs)], axis=-1),
            "qkv_bias": np.concatenate(
                [np.stack(qb), np.stack(kb), np.stack(vb)], axis=-1),
            "o_proj": stack(p + ".h.{i}.attn.c_proj.weight",
                            lambda w: place_q_weight(_t(w), g, D, axis=0)),
            "o_bias": stack(p + ".h.{i}.attn.c_proj.bias", _ident),
            "gate_proj": stack(p + ".h.{i}.mlp.c_fc.weight", _t),
            "gate_bias": stack(p + ".h.{i}.mlp.c_fc.bias", _ident),
            "down_proj": stack(p + ".h.{i}.mlp.c_proj.weight", _t),
            "down_bias": stack(p + ".h.{i}.mlp.c_proj.bias", _ident),
        }
        return {
            "embed": _vpad(get(p + ".wte.weight"), spec.padded_vocab),
            "pos_embed": get(p + ".wpe.weight"),
            "layers": layers,
            "final_norm": get(p + ".ln_f.weight"),
            "final_norm_b": get(p + ".ln_f.bias"),
        }


# ---------------------------------------------------------------------------
# OPT / BioGPT / XGLM — fairseq-style decoders (learned/sinusoidal positions
# with a +2 offset, pre-LN, biased projections)
# ---------------------------------------------------------------------------

class _FairseqStyleFamily(DecoderFamily):
    """Shared conversion for OPT-shaped decoders: self_attn.{q,k,v,out}_proj
    (+bias), fc1/fc2, self_attn_layer_norm / final_layer_norm per layer.
    Position table handling differs per family (offset-2 learned table for
    OPT/BioGPT, synthesized sinusoidal for XGLM)."""
    config_cls = _SimpleConfig
    layers_fmt = "model.decoder.layers.{i}"

    @classmethod
    def _convert_layers(cls, sd, spec):
        g, D = spec.gqa, spec.head_dim

        def get(n):
            return np.asarray(sd[n])

        def stack(fmt, tr):
            return np.stack([tr(get(fmt.format(i=i)))
                             for i in range(spec.num_layers)])

        f = cls.layers_fmt
        layers = {
            "input_norm": stack(f + ".self_attn_layer_norm.weight", _ident),
            "input_norm_b": stack(f + ".self_attn_layer_norm.bias", _ident),
            "post_norm": stack(f + ".final_layer_norm.weight", _ident),
            "post_norm_b": stack(f + ".final_layer_norm.bias", _ident),
            "q_proj": stack(f + ".self_attn.q_proj.weight",
                            lambda w: place_q_weight(_t(w), g, D, axis=-1)),
            "k_proj": stack(f + ".self_attn.k_proj.weight",
                            lambda w: replicate_kv_weight(_t(w), g, D,
                                                          axis=-1)),
            "v_proj": stack(f + ".self_attn.v_proj.weight",
                            lambda w: replicate_kv_weight(_t(w), g, D,
                                                          axis=-1)),
            "o_proj": stack(f + ".self_attn.out_proj.weight",
                            lambda w: place_q_weight(_t(w), g, D, axis=0)),
            "o_bias": stack(f + ".self_attn.out_proj.bias", _ident),
            "gate_proj": stack(f + ".fc1.weight", _t),
            "gate_bias": stack(f + ".fc1.bias", _ident),
            "down_proj": stack(f + ".fc2.weight", _t),
            "down_bias": stack(f + ".fc2.bias", _ident),
            "q_bias": stack(f + ".self_attn.q_proj.bias",
                            lambda b: place_q_weight(b, g, D)),
            "k_bias": stack(f + ".self_attn.k_proj.bias",
                            lambda b: replicate_kv_weight(b, g, D)),
            "v_bias": stack(f + ".self_attn.v_proj.bias",
                            lambda b: replicate_kv_weight(b, g, D)),
        }
        layers["qkv_proj"] = np.concatenate(
            [layers.pop("q_proj"), layers.pop("k_proj"),
             layers.pop("v_proj")], axis=-1)
        layers["qkv_bias"] = np.concatenate(
            [layers.pop("q_bias"), layers.pop("k_bias"),
             layers.pop("v_bias")], axis=-1)
        return layers


@register_family("opt")
class OPTFamily(_FairseqStyleFamily):
    @classmethod
    def build_spec(cls, config, tp_degree=None):
        if getattr(config, "word_embed_proj_dim",
                   config.hidden_size) != config.hidden_size:
            raise NotImplementedError(
                "OPT word_embed_proj_dim != hidden_size (350m-style "
                "embedding projections) is not supported")
        if not getattr(config, "do_layer_norm_before", True):
            raise NotImplementedError("OPT post-norm variant not supported")
        return spec_from_config(
            config, tp_degree,
            num_kv_heads=config.num_attention_heads,
            rms_eps=1e-5,
            act=getattr(config, "activation_function", "relu"),
            norm_type="layernorm", norm_bias=True,
            mlp_glu=False, mlp_bias=True,
            qkv_bias=True, o_bias=True,
            intermediate_size=config.ffn_dim,
            no_rope=True,
            learned_pos=int(config.max_position_embeddings),
            tie_word_embeddings=True,
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        layers = cls._convert_layers(sd, spec)
        return {
            "embed": _vpad(np.asarray(sd["model.decoder.embed_tokens.weight"]),
                           spec.padded_vocab),
            # OPT's learned position table is indexed position+2
            "pos_embed": np.asarray(
                sd["model.decoder.embed_positions.weight"])[2:],
            "layers": layers,
            "final_norm": np.asarray(
                sd["model.decoder.final_layer_norm.weight"]),
            "final_norm_b": np.asarray(
                sd["model.decoder.final_layer_norm.bias"]),
        }


@register_family("biogpt")
class BioGptFamily(_FairseqStyleFamily):
    layers_fmt = "biogpt.layers.{i}"

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.hidden_size
        return spec_from_config(
            config, tp_degree,
            num_kv_heads=config.num_attention_heads,
            rms_eps=1e-5,
            act=getattr(config, "hidden_act", "gelu"),
            norm_type="layernorm", norm_bias=True,
            mlp_glu=False, mlp_bias=True,
            qkv_bias=True, o_bias=True,
            intermediate_size=config.intermediate_size,
            no_rope=True,
            embed_scale=(math.sqrt(H)
                         if getattr(config, "scale_embedding", True)
                         else None),
            learned_pos=int(config.max_position_embeddings),
            tie_word_embeddings=True,
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        layers = cls._convert_layers(sd, spec)
        return {
            "embed": _vpad(np.asarray(sd["biogpt.embed_tokens.weight"]),
                           spec.padded_vocab),
            "pos_embed": np.asarray(sd["biogpt.embed_positions.weight"])[2:],
            "layers": layers,
            "final_norm": np.asarray(sd["biogpt.layer_norm.weight"]),
            "final_norm_b": np.asarray(sd["biogpt.layer_norm.bias"]),
        }


def _sinusoidal_table(n_pos: int, dim: int, padding_idx: int = 1
                      ) -> np.ndarray:
    """fairseq/XGLM sinusoidal position table ([sin | cos], padding row
    zeroed) — XGLM registers it as a non-persistent buffer, so the
    checkpoint may not carry it."""
    half = dim // 2
    emb = math.log(10000.0) / (half - 1)
    freqs = np.exp(np.arange(half, dtype=np.float64) * -emb)
    args = np.arange(n_pos, dtype=np.float64)[:, None] * freqs[None, :]
    table = np.concatenate([np.sin(args), np.cos(args)], axis=1)
    if dim % 2 == 1:
        table = np.pad(table, [(0, 0), (0, 1)])
    table[padding_idx] = 0.0
    return table.astype(np.float32)


@register_family("xglm")
class XGLMFamily(_FairseqStyleFamily):
    layers_fmt = "model.layers.{i}"

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.d_model
        return spec_from_config(
            config, tp_degree,
            hidden_size=H,
            num_q_heads=config.attention_heads,
            num_kv_heads=config.attention_heads,
            head_dim=H // config.attention_heads,
            num_layers=config.num_layers,
            intermediate_size=config.ffn_dim,
            rms_eps=1e-5,
            act=getattr(config, "activation_function", "gelu"),
            norm_type="layernorm", norm_bias=True,
            mlp_glu=False, mlp_bias=True,
            qkv_bias=True, o_bias=True,
            no_rope=True,
            embed_scale=(math.sqrt(H)
                         if getattr(config, "scale_embedding", True)
                         else None),
            learned_pos=int(config.max_position_embeddings),
            tie_word_embeddings=True,
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        layers = cls._convert_layers(sd, spec)
        key = "model.embed_positions.weights"
        if key in sd:
            table = np.asarray(sd[key])[2:]
        else:
            table = _sinusoidal_table(spec.learned_pos + 2,
                                      spec.hidden_size)[2:]
        return {
            "embed": _vpad(np.asarray(sd["model.embed_tokens.weight"]),
                           spec.padded_vocab),
            "pos_embed": table,
            "layers": layers,
            "final_norm": np.asarray(sd["model.layer_norm.weight"]),
            "final_norm_b": np.asarray(sd["model.layer_norm.bias"]),
        }


# ---------------------------------------------------------------------------
# Llama-shaped quick wins
# ---------------------------------------------------------------------------

@register_family("helium")
class HeliumFamily(DecoderFamily):
    """kyutai Helium — llama-shaped (rms, rope, bias-free GLU)."""
    config_cls = _SimpleConfig


@register_family("ernie4_5")
class Ernie45Family(DecoderFamily):
    """Baidu ERNIE 4.5 dense — llama-shaped."""
    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        # Ernie4_5Config serializes its tie_word_embeddings=True default as
        # null; None must mean tied here
        tie = getattr(config, "tie_word_embeddings", None)
        return spec_from_config(config, tp_degree,
                                tie_word_embeddings=tie is not False)


@register_family("seed_oss")
class SeedOssFamily(DecoderFamily):
    """ByteDance Seed-OSS — llama + attention biases + explicit head_dim."""
    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        bias = bool(getattr(config, "attention_bias", True))
        return spec_from_config(config, tp_degree, qkv_bias=bias,
                                o_bias=bias)

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        if spec.o_bias and "model.layers.0.self_attn.o_proj.bias" not in sd:
            # seed-oss ships q/k/v biases but a bias-free o_proj
            sd = dict(sd)
            for i in range(spec.num_layers):
                sd[f"model.layers.{i}.self_attn.o_proj.bias"] = np.zeros(
                    (spec.hidden_size,), np.float32)
        return super().convert_hf_state_dict(sd, spec)


@register_family("arcee")
class ArceeFamily(DecoderFamily):
    """Arcee AFM — llama attention + plain ReLU^2 MLP (up/down, no gate)."""
    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        return spec_from_config(
            config, tp_degree,
            mlp_glu=False,
            act=getattr(config, "hidden_act", "relu2"),
        )

    @classmethod
    def convert_mlp_weights(cls, get, layer_stack, spec):
        p = cls.hf_prefix
        return {
            "gate_proj": layer_stack(p + ".layers.{i}.mlp.up_proj.weight",
                                     _t),
            "down_proj": layer_stack(p + ".layers.{i}.mlp.down_proj.weight",
                                     _t),
        }


@register_family("nemotron")
class NemotronFamily(DecoderFamily):
    """NVIDIA Nemotron — LayerNorm1P (zero-centered gamma, folded to w+1 at
    conversion), partial rotary, plain ReLU^2 MLP."""
    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.hidden_size
        nh = config.num_attention_heads
        hd = getattr(config, "head_dim", None) or H // nh
        return spec_from_config(
            config, tp_degree,
            head_dim=hd,
            rms_eps=float(getattr(config, "norm_eps", 1e-5)),
            norm_type="layernorm", norm_bias=True,
            mlp_glu=False,
            mlp_bias=bool(getattr(config, "mlp_bias", False)),
            qkv_bias=bool(getattr(config, "attention_bias", False)),
            o_bias=bool(getattr(config, "attention_bias", False)),
            act=getattr(config, "hidden_act", "relu2"),
            rotary_dim=int(hd * getattr(config, "partial_rotary_factor",
                                        0.5)),
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             False)),
        )

    @classmethod
    def convert_mlp_weights(cls, get, layer_stack, spec):
        p = cls.hf_prefix
        out = {
            "gate_proj": layer_stack(p + ".layers.{i}.mlp.up_proj.weight",
                                     _t),
            "down_proj": layer_stack(p + ".layers.{i}.mlp.down_proj.weight",
                                     _t),
        }
        if spec.mlp_bias:
            out["gate_bias"] = layer_stack(
                p + ".layers.{i}.mlp.up_proj.bias", _ident)
            out["down_bias"] = layer_stack(
                p + ".layers.{i}.mlp.down_proj.bias", _ident)
        return out

    @classmethod
    def convert_extra_layer_weights(cls, get, layer_stack, spec):
        p = cls.hf_prefix

        def plus1(w):   # LayerNorm1P: norm uses (1 + gamma)
            return np.asarray(w) + 1.0

        return {
            "input_norm": layer_stack(
                p + ".layers.{i}.input_layernorm.weight", plus1),
            "input_norm_b": layer_stack(
                p + ".layers.{i}.input_layernorm.bias", _ident),
            "post_norm": layer_stack(
                p + ".layers.{i}.post_attention_layernorm.weight", plus1),
            "post_norm_b": layer_stack(
                p + ".layers.{i}.post_attention_layernorm.bias", _ident),
        }

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        out = super().convert_hf_state_dict(sd, spec)
        out["final_norm"] = np.asarray(sd["model.norm.weight"]) + 1.0
        out["final_norm_b"] = np.asarray(sd["model.norm.bias"])
        return out


@register_family("smollm3")
class SmolLM3Family(DecoderFamily):
    """SmolLM3 — llama + NoPE on every no_rope_layers[i]==0 layer."""
    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        rope_on = [bool(x) for x in getattr(config, "no_rope_layers", [])]
        pattern = tuple(rope_on) if rope_on and not all(rope_on) else None
        # SmolLM3Config serializes its tie_word_embeddings=True default as
        # null; None must mean tied here
        tie = getattr(config, "tie_word_embeddings", None)
        return spec_from_config(
            config, tp_degree,
            qkv_bias=bool(getattr(config, "attention_bias", False)),
            # pattern: "local" layers keep rope; global layers are NoPE
            layer_pattern=pattern,
            nope_global=pattern is not None,
            tie_word_embeddings=tie is not False,
        )


def cohere2_block(config) -> dict:
    """What Cohere's second generation fixes of a decoder, as
    ``spec_from_config`` keywords - ``cohere2`` (Command-R7B) and
    ``cohere2_moe`` (``models/cohere2_moe``) alike: cohere v1 (one bias-free
    LayerNorm feeding a parallel block, logit scaling, tied embeddings,
    rotary over INTERLEAVED pairs ``(2i, 2i+1)``: HF ``rotate_half`` takes
    ``x[..., ::2]`` / ``x[..., 1::2]``) + sliding-window layers by
    ``layer_types`` among full layers that carry NO rotary."""
    scale = float(getattr(config, "logit_scale", 1.0))
    types = list(getattr(config, "layer_types", []) or [])
    pattern = tuple(t == "sliding_attention" for t in types)
    return dict(
        rms_eps=float(getattr(config, "layer_norm_eps", 1e-5)),
        norm_type="layernorm",
        block_style="parallel_shared",
        rope_interleaved=True,
        logits_divide=1.0 / scale if scale not in (0.0, 1.0) else None,
        layer_pattern=pattern if any(pattern) else None,
        sliding_window=(int(getattr(config, "sliding_window", 0) or 0)
                        if any(pattern) else 0),
        nope_global=any(pattern),
        # a stack of full layers only rotates nowhere
        no_rope=not any(pattern),
        tie_word_embeddings=True,
    )


@register_family("cohere2")
class Cohere2Family(DecoderFamily):
    """Command-R7B — :func:`cohere2_block` around a dense SwiGLU MLP."""
    config_cls = _SimpleConfig
    post_norm_src = "input_layernorm"   # parallel_shared: post_norm unused

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        return spec_from_config(config, tp_degree, **cohere2_block(config))

    @classmethod
    def convert_extra_layer_weights(cls, get, layer_stack, spec):
        L, H = spec.num_layers, spec.hidden_size
        return {"post_norm": np.ones((L, H), np.float32)}


@register_family("exaone4")
class Exaone4Family(DecoderFamily):
    """EXAONE 4.0 — POST-norm blocks (norms on the outputs, olmo2-style),
    per-head q/k RMSNorm, optional hybrid sliding/global layers with NoPE
    global layers."""
    config_cls = _SimpleConfig
    post_norm_src = "post_attention_layernorm"

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        types = list(getattr(config, "layer_types", []) or [])
        pattern = tuple(t == "sliding_attention" for t in types)
        hybrid = any(pattern)
        return spec_from_config(
            config, tp_degree,
            norm_position="post",
            sandwich_norm=True,
            qk_norm=True,
            layer_pattern=pattern if hybrid else None,
            sliding_window=int(getattr(config, "sliding_window", 0) or 0)
            if hybrid else 0,
            nope_global=hybrid,
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             False)),
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        aug = dict(sd)
        H = spec.hidden_size
        for i in range(spec.num_layers):   # unused pre-norm slots load ones
            aug[f"model.layers.{i}.input_layernorm.weight"] = np.ones(
                (H,), np.float32)
        return super().convert_hf_state_dict(aug, spec)

    @classmethod
    def convert_extra_layer_weights(cls, get, layer_stack, spec):
        p = cls.hf_prefix
        return {
            "post_attn_norm": layer_stack(
                p + ".layers.{i}.post_attention_layernorm.weight", _ident),
            "post_ff_norm": layer_stack(
                p + ".layers.{i}.post_feedforward_layernorm.weight", _ident),
        }


@register_family("hunyuan_v1_dense")
class HunYuanDenseFamily(DecoderFamily):
    """Tencent HunYuan dense — llama + per-head q/k RMSNorm applied AFTER
    rope (query/key_layernorm)."""
    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        return spec_from_config(
            config, tp_degree,
            qk_norm=True, qk_norm_after_rope=True,
            qkv_bias=bool(getattr(config, "attention_bias", False)),
            o_bias=bool(getattr(config, "attention_bias", False)),
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             False)),
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        sd = dict(sd)
        # the base converter's qk_norm branch reads q_norm/k_norm names;
        # alias hunyuan's query/key_layernorm onto them
        for i in range(spec.num_layers):
            sd[f"model.layers.{i}.self_attn.q_norm.weight"] = np.asarray(
                sd[f"model.layers.{i}.self_attn.query_layernorm.weight"])
            sd[f"model.layers.{i}.self_attn.k_norm.weight"] = np.asarray(
                sd[f"model.layers.{i}.self_attn.key_layernorm.weight"])
        return super().convert_hf_state_dict(sd, spec)


# ---------------------------------------------------------------------------
# GraniteMoE (reference: contrib MoE families)
# ---------------------------------------------------------------------------

@register_family("granitemoe")
class GraniteMoeFamily(DecoderFamily):
    """IBM Granite MoE — granite multipliers + MoE MLP with fused
    input_linear (gate|up stacked per expert)."""
    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        return spec_from_config(
            config, tp_degree,
            attn_scale=float(getattr(config, "attention_multiplier", 1.0)),
            embed_scale=float(getattr(config, "embedding_multiplier", 1.0)),
            residual_multiplier=float(getattr(config, "residual_multiplier",
                                              1.0)),
            logits_divide=float(getattr(config, "logits_scaling", 1.0)),
            moe=MoESpec(
                num_experts=int(config.num_local_experts),
                top_k=int(config.num_experts_per_tok),
                intermediate_size=int(config.intermediate_size),
                # granitemoe gating: top-k on raw logits, softmax over the k
                pre_softmax_topk=True,
            ),
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             True)),
        )

    @classmethod
    def convert_mlp_weights(cls, get, layer_stack, spec):
        L, E = spec.num_layers, spec.moe.num_experts
        I = spec.moe.intermediate_size
        p = cls.hf_prefix
        gates, ups, downs, routers = [], [], [], []
        for i in range(L):
            w_in = np.asarray(get(
                f"{p}.layers.{i}.block_sparse_moe.input_linear.weight"))
            w_out = np.asarray(get(
                f"{p}.layers.{i}.block_sparse_moe.output_linear.weight"))
            # input_linear (E, 2I, H): rows [0:I] gate, [I:2I] up
            gates.append(np.stack([_t(w_in[e, :I]) for e in range(E)]))
            ups.append(np.stack([_t(w_in[e, I:]) for e in range(E)]))
            downs.append(np.stack([_t(w_out[e]) for e in range(E)]))
            routers.append(_t(np.asarray(get(
                f"{p}.layers.{i}.block_sparse_moe.router.layer.weight"))
                .astype(np.float32)))
        return {
            "router": np.stack(routers),
            "expert_gate": np.stack(gates),
            "expert_up": np.stack(ups),
            "expert_down": np.stack(downs),
        }


# ---------------------------------------------------------------------------
# OLMoE (reference: contrib MoE families)
# ---------------------------------------------------------------------------

@register_family("olmoe")
class OlmoeFamily(DecoderFamily):
    """AllenAI OLMoE — llama attention + full-width q/k RMSNorm (olmo2
    style) + softmax-all-then-topk MoE without renormalization."""
    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        return spec_from_config(
            config, tp_degree,
            qk_norm_full=True,
            moe=MoESpec(
                num_experts=int(config.num_experts),
                top_k=int(config.num_experts_per_tok),
                intermediate_size=int(config.intermediate_size),
                normalize_topk=bool(getattr(config, "norm_topk_prob",
                                            False)),
            ),
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             False)),
        )

    @classmethod
    def convert_mlp_weights(cls, get, layer_stack, spec):
        p = cls.hf_prefix
        return cls.convert_moe_weights(
            get, spec,
            router_name=p + ".layers.{i}.mlp.gate.weight",
            expert_fmt=p + ".layers.{i}.mlp.experts.{e}.{name}.weight",
            gate="gate_proj", up="up_proj", down="down_proj")

    @classmethod
    def convert_extra_layer_weights(cls, get, layer_stack, spec):
        g, D = spec.gqa, spec.head_dim
        p = cls.hf_prefix
        return {
            "q_norm": layer_stack(
                p + ".layers.{i}.self_attn.q_norm.weight",
                lambda w: place_q_weight(np.asarray(w), g, D)),
            "k_norm": layer_stack(
                p + ".layers.{i}.self_attn.k_norm.weight",
                lambda w: replicate_kv_weight(np.asarray(w), g, D)),
        }


# ---------------------------------------------------------------------------
# GLM-4.5 / GLM-4-MoE (reference: contrib MoE families)
# ---------------------------------------------------------------------------

@register_family("glm4_moe")
class Glm4MoeFamily(DecoderFamily):
    """Zhipu GLM-4-MoE — GQA attention (partial rotary, optional per-head
    qk-norm, qkv bias) + DeepSeek-V3-style MoE: sigmoid router with
    e_score_correction_bias (selection only), shared experts, leading
    dense layers."""
    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.hidden_size
        nh = config.num_attention_heads
        hd = getattr(config, "head_dim", None) or H // nh
        moe = MoESpec(
            num_experts=int(config.n_routed_experts),
            top_k=int(config.num_experts_per_tok),
            intermediate_size=int(config.moe_intermediate_size),
            normalize_topk=bool(getattr(config, "norm_topk_prob", True)),
            routed_scaling=float(getattr(config, "routed_scaling_factor",
                                         1.0)),
            router_act="sigmoid",
            has_router_bias=True,
            router_bias_mode="select",
            shared_intermediate=(int(config.moe_intermediate_size)
                                 * int(getattr(config, "n_shared_experts",
                                               0) or 0)),
            n_group=int(getattr(config, "n_group", 1) or 1),
            topk_group=int(getattr(config, "topk_group", 1) or 1),
        )
        return spec_from_config(
            config, tp_degree,
            head_dim=hd,
            moe=moe,
            first_dense=int(getattr(config, "first_k_dense_replace", 0)),
            qkv_bias=bool(getattr(config, "attention_bias", False)),
            qk_norm=bool(getattr(config, "use_qk_norm", False)),
            rotary_dim=int(hd * getattr(config, "partial_rotary_factor",
                                        0.5)),
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             False)),
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        g, D = spec.gqa, spec.head_dim
        p = cls.hf_prefix
        L = spec.num_layers
        nd = spec.first_dense

        def get(n):
            return np.asarray(sd[n])

        def attn_layer(i):
            base = f"{p}.layers.{i}.self_attn"
            out = {
                "input_norm": _ident(get(
                    f"{p}.layers.{i}.input_layernorm.weight")),
                "post_norm": _ident(get(
                    f"{p}.layers.{i}.post_attention_layernorm.weight")),
                "q_proj": place_q_weight(_t(get(f"{base}.q_proj.weight")),
                                         g, D, axis=-1),
                "k_proj": replicate_kv_weight(
                    _t(get(f"{base}.k_proj.weight")), g, D, axis=-1),
                "v_proj": replicate_kv_weight(
                    _t(get(f"{base}.v_proj.weight")), g, D, axis=-1),
                "o_proj": place_q_weight(_t(get(f"{base}.o_proj.weight")),
                                         g, D, axis=0),
            }
            if spec.qkv_bias:
                out["q_bias"] = place_q_weight(get(f"{base}.q_proj.bias"),
                                               g, D)
                out["k_bias"] = replicate_kv_weight(
                    get(f"{base}.k_proj.bias"), g, D)
                out["v_bias"] = replicate_kv_weight(
                    get(f"{base}.v_proj.bias"), g, D)
            if spec.qk_norm:
                out["q_norm"] = _ident(get(f"{base}.q_norm.weight"))
                out["k_norm"] = _ident(get(f"{base}.k_norm.weight"))
            return out

        def dense_layer(i):
            out = attn_layer(i)
            for k in ("gate_proj", "up_proj", "down_proj"):
                out[k] = _t(get(f"{p}.layers.{i}.mlp.{k}.weight"))
            return out

        def moe_layer(i):
            from .deepseek.modeling_deepseek import deepseek_style_moe_weights
            out = attn_layer(i)
            out.update(deepseek_style_moe_weights(get, p, i, spec, _t))
            return out

        def stack_dicts(dicts):
            return {k: np.stack([d[k] for d in dicts]) for k in dicts[0]}

        out = {
            "embed": _vpad(get(p + ".embed_tokens.weight"),
                           spec.padded_vocab),
            "final_norm": _ident(get(p + ".norm.weight")),
        }
        if nd > 0:
            out["layers"] = stack_dicts([dense_layer(i) for i in range(nd)])
            out["moe_layers"] = stack_dicts([moe_layer(i)
                                             for i in range(nd, L)])
        else:
            out["layers"] = stack_dicts([moe_layer(i) for i in range(L)])
        if not spec.tie_word_embeddings:
            out["lm_head"] = _t(_vpad(get("lm_head.weight"),
                                      spec.padded_vocab))
        return out


# ---------------------------------------------------------------------------
# BLOOM / MPT — ALiBi decoders (no rope, additive per-head position bias)
# ---------------------------------------------------------------------------

@register_family("bloom")
class BloomFamily(DecoderFamily):
    """BigScience BLOOM — ALiBi, per-head-interleaved fused QKV, embedding
    LayerNorm, plain gelu MLP, LN+bias."""
    config_cls = _SimpleConfig
    hf_prefix = "transformer"

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.hidden_size
        nh = config.n_head
        return spec_from_config(
            config, tp_degree,
            num_layers=config.n_layer,
            hidden_size=H, num_q_heads=nh, num_kv_heads=nh,
            head_dim=H // nh,
            intermediate_size=4 * H,
            rms_eps=float(getattr(config, "layer_norm_epsilon", 1e-5)),
            act="gelu_pytorch_tanh",    # bloom_gelu_forward is the tanh form
            norm_type="layernorm", norm_bias=True,
            mlp_glu=False, mlp_bias=True,
            qkv_bias=True, o_bias=True,
            no_rope=True, alibi=True, embed_norm=True,
            tie_word_embeddings=True,
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        g, D = spec.gqa, spec.head_dim
        nh = spec.num_q_heads
        p = cls.hf_prefix
        from ..ops.attention import alibi_slopes

        def get(n):
            return np.asarray(sd[n])

        def stack(fmt, tr):
            return np.stack([tr(get(fmt.format(i=i)))
                             for i in range(spec.num_layers)])

        fused = _split_interleaved_qkv(
            get, p + ".h.{i}.self_attention.query_key_value",
            spec.num_layers, nh, g, D)
        slopes = place_q_weight(alibi_slopes(nh, "bloom"), g, 1)
        layers = {
            "input_norm": stack(p + ".h.{i}.input_layernorm.weight", _ident),
            "input_norm_b": stack(p + ".h.{i}.input_layernorm.bias", _ident),
            "post_norm": stack(
                p + ".h.{i}.post_attention_layernorm.weight", _ident),
            "post_norm_b": stack(
                p + ".h.{i}.post_attention_layernorm.bias", _ident),
            **fused,
            "o_proj": stack(p + ".h.{i}.self_attention.dense.weight",
                            lambda w: place_q_weight(_t(w), g, D, axis=0)),
            "o_bias": stack(p + ".h.{i}.self_attention.dense.bias", _ident),
            "gate_proj": stack(p + ".h.{i}.mlp.dense_h_to_4h.weight", _t),
            "gate_bias": stack(p + ".h.{i}.mlp.dense_h_to_4h.bias", _ident),
            "down_proj": stack(p + ".h.{i}.mlp.dense_4h_to_h.weight", _t),
            "down_bias": stack(p + ".h.{i}.mlp.dense_4h_to_h.bias", _ident),
            "alibi_slopes": np.broadcast_to(
                slopes, (spec.num_layers,) + slopes.shape).copy(),
        }
        return {
            "embed": _vpad(get(p + ".word_embeddings.weight"),
                           spec.padded_vocab),
            "embed_norm": get(p + ".word_embeddings_layernorm.weight"),
            "embed_norm_b": get(p + ".word_embeddings_layernorm.bias"),
            "layers": layers,
            "final_norm": get(p + ".ln_f.weight"),
            "final_norm_b": get(p + ".ln_f.bias"),
        }


@register_family("mpt")
class MptFamily(DecoderFamily):
    """MosaicML MPT — ALiBi, fused third-split Wqkv, bias-free everything,
    plain gelu MLP."""
    config_cls = _SimpleConfig
    hf_prefix = "transformer"

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.d_model
        nh = config.n_heads
        ac = getattr(config, "attn_config", None) or {}
        if not isinstance(ac, dict):      # MptConfig object vs raw JSON dict
            ac = {k: getattr(ac, k) for k in
                  ("alibi", "alibi_bias_max", "qk_ln", "clip_qkv")
                  if hasattr(ac, k)}
        if not ac.get("alibi", True):
            raise NotImplementedError("MPT without ALiBi (learned "
                                      "positions) is not supported")
        if ac.get("alibi_bias_max", 8) != 8:
            raise NotImplementedError("MPT alibi_bias_max != 8")
        if not getattr(config, "no_bias", True):
            raise NotImplementedError("MPT with biases is not supported")
        if ac.get("qk_ln", False) or ac.get("clip_qkv", None):
            raise NotImplementedError("MPT qk_ln / clip_qkv variants")
        return spec_from_config(
            config, tp_degree,
            num_layers=config.n_layers,
            hidden_size=H, num_q_heads=nh, num_kv_heads=nh,
            head_dim=H // nh,
            intermediate_size=int(H * getattr(config, "expansion_ratio", 4)),
            rms_eps=float(getattr(config, "layer_norm_epsilon", 1e-5)),
            act="gelu",
            norm_type="layernorm", norm_bias=False,
            mlp_glu=False, mlp_bias=False,
            no_rope=True, alibi=True,
            tie_word_embeddings=True,
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        g, D = spec.gqa, spec.head_dim
        H = spec.hidden_size
        p = cls.hf_prefix
        from ..ops.attention import alibi_slopes

        def get(n):
            return np.asarray(sd[n])

        def stack(fmt, tr):
            return np.stack([tr(get(fmt.format(i=i)))
                             for i in range(spec.num_layers)])

        qs, ks, vs = [], [], []
        for i in range(spec.num_layers):
            w = get(f"{p}.blocks.{i}.attn.Wqkv.weight")    # (3H, H) thirds
            qs.append(place_q_weight(_t(w[:H]), g, D, axis=-1))
            ks.append(replicate_kv_weight(_t(w[H:2 * H]), g, D, axis=-1))
            vs.append(replicate_kv_weight(_t(w[2 * H:]), g, D, axis=-1))
        slopes = place_q_weight(alibi_slopes(spec.num_q_heads, "mpt"), g, 1)
        layers = {
            "input_norm": stack(p + ".blocks.{i}.norm_1.weight", _ident),
            "post_norm": stack(p + ".blocks.{i}.norm_2.weight", _ident),
            "qkv_proj": np.concatenate(
                [np.stack(qs), np.stack(ks), np.stack(vs)], axis=-1),
            "o_proj": stack(p + ".blocks.{i}.attn.out_proj.weight",
                            lambda w: place_q_weight(_t(w), g, D, axis=0)),
            "gate_proj": stack(p + ".blocks.{i}.ffn.up_proj.weight", _t),
            "down_proj": stack(p + ".blocks.{i}.ffn.down_proj.weight", _t),
            "alibi_slopes": np.broadcast_to(
                slopes, (spec.num_layers,) + slopes.shape).copy(),
        }
        return {
            "embed": _vpad(get(p + ".wte.weight"), spec.padded_vocab),
            "layers": layers,
            "final_norm": get(p + ".norm_f.weight"),
        }


# ---------------------------------------------------------------------------
# Persimmon (reference: contrib/models/persimmon)
# ---------------------------------------------------------------------------

@register_family("persimmon")
class PersimmonFamily(DecoderFamily):
    """Adept Persimmon — per-head-interleaved fused QKV with bias, per-head
    q/k LayerNorm (with bias), partial rotary, ReLU^2 MLP, LN+bias."""
    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.hidden_size
        nh = config.num_attention_heads
        hd = H // nh
        return spec_from_config(
            config, tp_degree,
            num_kv_heads=nh,
            head_dim=hd,
            rms_eps=float(getattr(config, "layer_norm_eps", 1e-5)),
            act=getattr(config, "hidden_act", "relu2"),
            norm_type="layernorm", norm_bias=True,
            mlp_glu=False, mlp_bias=True,
            qkv_bias=True, o_bias=True,
            qk_norm=bool(getattr(config, "qk_layernorm", True)),
            qk_norm_type="layernorm",
            rotary_dim=int(hd * getattr(config, "partial_rotary_factor",
                                        0.5)),
            tie_word_embeddings=False,
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        g, D = spec.gqa, spec.head_dim
        nh = spec.num_q_heads
        p = cls.hf_prefix

        def get(n):
            return np.asarray(sd[n])

        def stack(fmt, tr):
            return np.stack([tr(get(fmt.format(i=i)))
                             for i in range(spec.num_layers)])

        fused = _split_interleaved_qkv(
            get, p + ".layers.{i}.self_attn.query_key_value",
            spec.num_layers, nh, g, D)
        layers = {
            "input_norm": stack(
                p + ".layers.{i}.input_layernorm.weight", _ident),
            "input_norm_b": stack(
                p + ".layers.{i}.input_layernorm.bias", _ident),
            "post_norm": stack(
                p + ".layers.{i}.post_attention_layernorm.weight", _ident),
            "post_norm_b": stack(
                p + ".layers.{i}.post_attention_layernorm.bias", _ident),
            **fused,
            "o_proj": stack(p + ".layers.{i}.self_attn.dense.weight",
                            lambda w: place_q_weight(_t(w), g, D, axis=0)),
            "o_bias": stack(p + ".layers.{i}.self_attn.dense.bias", _ident),
            "gate_proj": stack(
                p + ".layers.{i}.mlp.dense_h_to_4h.weight", _t),
            "gate_bias": stack(
                p + ".layers.{i}.mlp.dense_h_to_4h.bias", _ident),
            "down_proj": stack(
                p + ".layers.{i}.mlp.dense_4h_to_h.weight", _t),
            "down_bias": stack(
                p + ".layers.{i}.mlp.dense_4h_to_h.bias", _ident),
        }
        if spec.qk_norm:
            layers["q_norm"] = stack(
                p + ".layers.{i}.self_attn.q_layernorm.weight", _ident)
            layers["q_norm_b"] = stack(
                p + ".layers.{i}.self_attn.q_layernorm.bias", _ident)
            layers["k_norm"] = stack(
                p + ".layers.{i}.self_attn.k_layernorm.weight", _ident)
            layers["k_norm_b"] = stack(
                p + ".layers.{i}.self_attn.k_layernorm.bias", _ident)
        return {
            "embed": _vpad(get(p + ".embed_tokens.weight"),
                           spec.padded_vocab),
            "layers": layers,
            "final_norm": get(p + ".final_layernorm.weight"),
            "final_norm_b": get(p + ".final_layernorm.bias"),
            "lm_head": _t(_vpad(get("lm_head.weight"), spec.padded_vocab)),
        }


# ---------------------------------------------------------------------------
# dots.llm1 (rednote) — GLM-4-MoE-shaped with full rotary + per-head qk RMS
# ---------------------------------------------------------------------------

@register_family("dots1")
class Dots1Family(Glm4MoeFamily):
    """rednote dots.llm1 — DeepSeek-V3-style MoE (sigmoid router +
    e_score_correction_bias, shared experts, leading dense layers) with
    standard-GQA attention, FULL rotary and per-head q/k RMSNorm."""

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.hidden_size
        nh = config.num_attention_heads
        hd = getattr(config, "head_dim", None) or H // nh
        moe = MoESpec(
            num_experts=int(config.n_routed_experts),
            top_k=int(config.num_experts_per_tok),
            intermediate_size=int(config.moe_intermediate_size),
            normalize_topk=bool(getattr(config, "norm_topk_prob", True)),
            routed_scaling=float(getattr(config, "routed_scaling_factor",
                                         1.0)),
            router_act="sigmoid",
            has_router_bias=True,
            router_bias_mode="select",
            shared_intermediate=(int(config.moe_intermediate_size)
                                 * int(getattr(config, "n_shared_experts",
                                               0) or 0)),
            n_group=int(getattr(config, "n_group", 1) or 1),
            topk_group=int(getattr(config, "topk_group", 1) or 1),
        )
        return spec_from_config(
            config, tp_degree,
            head_dim=hd,
            moe=moe,
            first_dense=int(getattr(config, "first_k_dense_replace", 0)),
            qkv_bias=bool(getattr(config, "attention_bias", False)),
            qk_norm=True,
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             False)),
        )


# ---------------------------------------------------------------------------
# CodeGen (Salesforce) — GPT-J sibling with mp_num-blocked fused QKV
# ---------------------------------------------------------------------------

@register_family("codegen")
class CodeGenFamily(GPTJFamily):
    """CodeGen — GPT-J architecture (parallel-shared residual, interleaved
    partial rotary, gelu MLP, biased untied lm_head) with the fused
    qkv_proj laid out as mp_num=4 blocks of [q | v | k] head groups."""

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        # de-block the mp_num=4 fused qkv into the synthetic per-projection
        # names GPT-J uses, then delegate to its converter
        nh = spec.num_q_heads
        D = spec.head_dim
        p = cls.hf_prefix
        mp_num = 4
        local = nh * D // mp_num
        sd = dict(sd)
        for i in range(spec.num_layers):
            w = np.asarray(sd[f"{p}.h.{i}.attn.qkv_proj.weight"])
            w = w.reshape(mp_num, 3 * local, -1)
            sd[f"{p}.h.{i}.attn.q_proj.weight"] = \
                w[:, :local].reshape(nh * D, -1)
            sd[f"{p}.h.{i}.attn.v_proj.weight"] = \
                w[:, local:2 * local].reshape(nh * D, -1)
            sd[f"{p}.h.{i}.attn.k_proj.weight"] = \
                w[:, 2 * local:].reshape(nh * D, -1)
        return super().convert_hf_state_dict(sd, spec)
