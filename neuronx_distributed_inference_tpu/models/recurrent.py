"""Recurrent / hybrid model families on the SSM state axis:

  * Falcon-H1 — parallel hybrid: every layer runs a Mamba-2 mixer NEXT TO
    standard attention, plus MuP multipliers throughout (reference:
    contrib/models/Falcon-H1-0.5B-Instruct/src/modeling_falcon_h1.py).
    All MuP multipliers are folded into the WEIGHTS at conversion time
    (they are all linear pre/post scalings), so the traced graph carries
    zero extra multiplies; the tied embedding/lm-head pair is untied at
    conversion because the two carry different multipliers.
  * RecurrentGemma (Griffin) — interleaved rec/rec/attn pattern of RG-LRU
    recurrent blocks and sliding-window MQA attention (reference:
    contrib/models/recurrentgemma-2b-it/src/modeling_recurrent_gemma.py).
"""

from __future__ import annotations

import math
from typing import List

import jax.numpy as jnp
import numpy as np

from ..config import InferenceConfig
from ..modules.ssm import SSMSpec
from ..parallel.layers import place_q_weight, replicate_kv_weight
from .family import DecoderFamily, register_family
from .model_base import spec_from_config


def _t(w):
    return np.ascontiguousarray(np.asarray(w).T)


class FalconH1InferenceConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["hidden_size", "num_attention_heads", "num_hidden_layers",
                "num_key_value_heads", "vocab_size", "intermediate_size",
                "mamba_d_ssm", "mamba_n_heads", "mamba_d_state"]

    def get_text_config(self):
        return self


@register_family("falcon_h1")
class FalconH1Family(DecoderFamily):
    """Falcon-H1 hybrid attention+mamba2
    (reference: contrib/models/Falcon-H1-0.5B-Instruct/src/)."""

    config_cls = FalconH1InferenceConfig
    post_norm_src = "pre_ff_layernorm"

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.hidden_size
        d_ssm = getattr(config, "mamba_d_ssm", None) \
            or getattr(config, "mamba_expand", 2) * H
        extras = (
            ("embedding_multiplier",
             float(getattr(config, "embedding_multiplier", 1.0))),
            ("lm_head_multiplier",
             float(getattr(config, "lm_head_multiplier", 1.0))),
            ("key_multiplier", float(getattr(config, "key_multiplier", 1.0))),
            ("attention_in_multiplier",
             float(getattr(config, "attention_in_multiplier", 1.0))),
            ("attention_out_multiplier",
             float(getattr(config, "attention_out_multiplier", 1.0))),
            ("mlp_multipliers",
             tuple(float(x) for x in
                   getattr(config, "mlp_multipliers", (1.0, 1.0)))),
            ("ssm_multipliers",
             tuple(float(x) for x in
                   getattr(config, "ssm_multipliers", (1.0,) * 5))),
            ("ssm_in_multiplier",
             float(getattr(config, "ssm_in_multiplier", 1.0))),
            ("ssm_out_multiplier",
             float(getattr(config, "ssm_out_multiplier", 1.0))),
        )
        return spec_from_config(
            config, tp_degree,
            ssm=SSMSpec(
                kind="mamba2",
                d_inner=int(d_ssm),
                num_heads=int(config.mamba_n_heads),
                head_dim=int(getattr(config, "mamba_d_head",
                                     d_ssm // config.mamba_n_heads)),
                d_state=int(config.mamba_d_state),
                n_groups=int(getattr(config, "mamba_n_groups", 1)),
                d_conv=int(getattr(config, "mamba_d_conv", 4)),
                chunk_size=int(getattr(config, "mamba_chunk_size", 128)),
                conv_bias=bool(getattr(config, "mamba_conv_bias", True)),
                gated_norm=bool(getattr(config, "mamba_rms_norm", False)),
                norm_before_gate=bool(
                    getattr(config, "mamba_norm_before_gate", True)),
                norm_eps=float(getattr(config, "rms_norm_eps", 1e-5)),
            ),
            ssm_parallel=True,
            qkv_bias=bool(getattr(config, "attention_bias", False)),
            o_bias=bool(getattr(config, "attention_bias", False)),
            # embedding and lm-head carry DIFFERENT MuP multipliers — the
            # pair is untied at conversion even when the checkpoint ties it
            tie_word_embeddings=False,
            extras=extras,
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        """Fold every MuP multiplier into the weights, rename the falcon-h1
        module names onto the base-converter layout, then let the base
        handle attention/MLP/norms; the mamba weights land via
        ``convert_extra_layer_weights``."""
        sd = dict(sd)
        aim = spec.extra("attention_in_multiplier", 1.0)
        km = spec.extra("key_multiplier", 1.0)
        aom = spec.extra("attention_out_multiplier", 1.0)
        mm = spec.extra("mlp_multipliers", (1.0, 1.0))
        em = spec.extra("embedding_multiplier", 1.0)
        lm = spec.extra("lm_head_multiplier", 1.0)

        def scale(key, m):
            if key in sd and m != 1.0:
                sd[key] = np.asarray(sd[key]) * np.asarray(sd[key]).dtype.type(m)

        embed_raw = np.asarray(sd["model.embed_tokens.weight"])
        if "lm_head.weight" not in sd:          # tied checkpoint: untie
            sd["lm_head.weight"] = embed_raw.copy()
        scale("lm_head.weight", lm)
        scale("model.embed_tokens.weight", em)
        for i in range(spec.num_layers):
            p = f"model.layers.{i}."
            scale(p + "self_attn.q_proj.weight", aim)
            scale(p + "self_attn.k_proj.weight", aim * km)
            scale(p + "self_attn.v_proj.weight", aim)
            scale(p + "self_attn.o_proj.weight", aom)
            for src, dst, m in (("gate_proj", "gate_proj", mm[0]),
                                ("up_proj", "up_proj", 1.0),
                                ("down_proj", "down_proj", mm[1])):
                k = p + f"feed_forward.{src}.weight"
                if k in sd:
                    scale(k, m)
                    sd[p + f"mlp.{dst}.weight"] = sd.pop(k)
        if "model.final_layernorm.weight" in sd:
            sd["model.norm.weight"] = sd.pop("model.final_layernorm.weight")
        return super().convert_hf_state_dict(sd, spec)

    @classmethod
    def convert_extra_layer_weights(cls, get, layer_stack, spec,
                                    p="model.layers.{i}.mamba."):
        """The Mamba-2 mixer's tensors under the prefix ``p``, ``in_proj`` /
        ``conv1d`` split by destination (another family's names: its
        prefix)."""
        s = spec.ssm
        d = s.d_inner
        gn = s.n_groups * s.d_state
        nh = s.num_heads
        sim = spec.extra("ssm_in_multiplier", 1.0)
        m0, m1, m2, m3, m4 = spec.extra("ssm_multipliers", (1.0,) * 5)

        def in_part(lo, hi, mult):
            # in_proj rows [gate d | x d | B gn | C gn | dt nh] with the
            # section's mup multiplier and ssm_in_multiplier folded in
            def tr(w):
                w = np.asarray(w)[lo:hi].T
                return np.ascontiguousarray(w * w.dtype.type(sim * mult))
            return tr

        def conv_part(lo, hi):
            return lambda w: np.ascontiguousarray(np.asarray(w)[lo:hi, 0, :])

        def conv_bias_part(lo, hi):
            return lambda b: np.ascontiguousarray(np.asarray(b)[lo:hi])

        def f32(w):
            return np.asarray(w).astype(np.float32)

        def out_t(w):
            w = _t(w)
            som = spec.extra("ssm_out_multiplier", 1.0)
            return np.ascontiguousarray(w * w.dtype.type(som))

        out = {
            "ssm_in_gate": layer_stack(p + "in_proj.weight", in_part(0, d, m0)),
            "ssm_in_x": layer_stack(p + "in_proj.weight",
                                    in_part(d, 2 * d, m1)),
            "ssm_in_bc": np.concatenate([
                layer_stack(p + "in_proj.weight",
                            in_part(2 * d, 2 * d + gn, m2)),
                layer_stack(p + "in_proj.weight",
                            in_part(2 * d + gn, 2 * d + 2 * gn, m3)),
            ], axis=-1),
            "ssm_in_dt": layer_stack(p + "in_proj.weight",
                                     in_part(2 * d + 2 * gn,
                                             2 * d + 2 * gn + nh, m4)),
            "ssm_conv_x": layer_stack(p + "conv1d.weight", conv_part(0, d)),
            "ssm_conv_bc": layer_stack(p + "conv1d.weight",
                                       conv_part(d, d + 2 * gn)),
            "ssm_dt_bias": layer_stack(p + "dt_bias", f32),
            "ssm_A_log": layer_stack(p + "A_log", f32),
            "ssm_D": layer_stack(p + "D", f32),
            "ssm_out": layer_stack(p + "out_proj.weight", out_t),
        }
        if s.conv_bias:
            out["ssm_conv_x_b"] = layer_stack(p + "conv1d.bias",
                                              conv_bias_part(0, d))
            out["ssm_conv_bc_b"] = layer_stack(p + "conv1d.bias",
                                               conv_bias_part(d, d + 2 * gn))
        if s.gated_norm:
            out["ssm_norm"] = layer_stack(p + "norm.weight",
                                          lambda w: np.asarray(w))
        return out

    @classmethod
    def load_hf_model(cls, model_path: str):
        import transformers
        return transformers.FalconH1ForCausalLM.from_pretrained(model_path)


class RecurrentGemmaInferenceConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["hidden_size", "num_attention_heads", "num_hidden_layers",
                "vocab_size", "lru_width", "block_types"]

    def get_text_config(self):
        return self


@register_family("recurrent_gemma")
class RecurrentGemmaFamily(DecoderFamily):
    """RecurrentGemma / Griffin: rec/rec/attn interleave of RG-LRU blocks
    and sliding-window MQA (reference: contrib/models/recurrentgemma-2b-it/
    src/modeling_recurrent_gemma.py)."""

    config_cls = RecurrentGemmaInferenceConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.hidden_size
        nh = config.num_attention_heads
        hd = getattr(config, "head_dim", None) or H // nh
        W = int(getattr(config, "lru_width", None) or H)
        bt = list(getattr(config, "block_types",
                          ("recurrent", "recurrent", "attention")))
        pattern = tuple((bt * config.num_hidden_layers)[
            :config.num_hidden_layers])
        return spec_from_config(
            config, tp_degree,
            head_dim=hd,
            ssm=SSMSpec(
                kind="rglru",
                d_inner=W,
                num_heads=nh,
                head_dim=W // nh,
                d_conv=int(getattr(config, "conv1d_width", 4)),
            ),
            ssm_pattern=tuple(x == "recurrent" for x in pattern),
            ssm_parallel=False,
            sliding_window=int(getattr(config, "attention_window_size",
                                       2048)),
            qkv_bias=bool(getattr(config, "attention_bias", False)),
            o_bias=True,                      # rgemma o_proj always has bias
            rotary_dim=int(hd * float(getattr(config,
                                              "partial_rotary_factor", 0.5))),
            act=getattr(config, "hidden_activation", "gelu_pytorch_tanh"),
            # HF halves the config intermediate for the actual MLP width
            intermediate_size=config.intermediate_size // 2,
            mlp_bias=True,
            # HF rounds the sqrt(H) embedding normalizer through bfloat16
            embed_scale=float(jnp.bfloat16(math.sqrt(H))),
            norm_offset=1.0,                  # gemma (1+w) RMSNorm
            logits_soft_cap=float(getattr(config, "logits_soft_cap", 30.0)),
            rms_eps=float(getattr(config, "rms_norm_eps", 1e-6)),
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             True)),
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        """Interleaved layout: "layers" = every layer's norms + MLP;
        "attn_layers"/"ssm_layers" = the temporal blocks, stacked in order
        of appearance (reference weight names:
        modeling_recurrent_gemma.py RecurrentGemmaDecoderLayer)."""
        g = spec.gqa
        D = spec.head_dim
        pat = spec.resolved_ssm_pattern

        def get(n):
            return np.asarray(sd[n])

        def stack(idx, fmt, tr):
            return np.stack([tr(get(fmt.format(i=i))) for i in idx])

        all_i = list(range(spec.num_layers))
        attn_i = [i for i in all_i if not pat[i]]
        ssm_i = [i for i in all_i if pat[i]]
        p = "model.layers.{i}."
        tb = p + "temporal_block."

        layers = {
            "input_norm": stack(all_i, p + "temporal_pre_norm.weight",
                                np.asarray),
            "post_norm": stack(all_i, p + "channel_pre_norm.weight",
                               np.asarray),
        }
        for w in ("gate", "up", "down"):
            layers[w + "_proj"] = stack(
                all_i, p + f"mlp_block.{w}_proj.weight", _t)
            layers[w + "_bias"] = stack(
                all_i, p + f"mlp_block.{w}_proj.bias", np.asarray)

        def q_t(w):
            return place_q_weight(_t(w), g, D, axis=-1)

        def kv_t(w):
            return replicate_kv_weight(_t(w), g, D, axis=-1)

        attn_layers = {} if not attn_i else {
            "qkv_proj": np.concatenate([
                stack(attn_i, tb + "q_proj.weight", q_t),
                stack(attn_i, tb + "k_proj.weight", kv_t),
                stack(attn_i, tb + "v_proj.weight", kv_t)], axis=-1),
            "o_proj": stack(attn_i, tb + "o_proj.weight",
                            lambda w: place_q_weight(_t(w), g, D, axis=0)),
            "o_bias": stack(attn_i, tb + "o_proj.bias", np.asarray),
        }

        def f32(w):
            return np.asarray(w).astype(np.float32)

        ssm_layers = {} if not ssm_i else {
            "rg_y": stack(ssm_i, tb + "linear_y.weight", _t),
            "rg_y_b": stack(ssm_i, tb + "linear_y.bias", np.asarray),
            "rg_x": stack(ssm_i, tb + "linear_x.weight", _t),
            "rg_x_b": stack(ssm_i, tb + "linear_x.bias", np.asarray),
            "rg_out": stack(ssm_i, tb + "linear_out.weight", _t),
            "rg_out_b": stack(ssm_i, tb + "linear_out.bias", np.asarray),
            "rg_conv": stack(ssm_i, tb + "conv_1d.weight",
                             lambda w: np.asarray(w)[:, 0, :]),
            "rg_conv_b": stack(ssm_i, tb + "conv_1d.bias", np.asarray),
            "rg_param": stack(ssm_i, tb + "rg_lru.recurrent_param", f32),
            "rg_igate_w": stack(ssm_i, tb + "rg_lru.input_gate_weight",
                                np.asarray),
            "rg_igate_b": stack(ssm_i, tb + "rg_lru.input_gate_bias",
                                np.asarray),
            "rg_rgate_w": stack(ssm_i, tb + "rg_lru.recurrent_gate_weight",
                                np.asarray),
            "rg_rgate_b": stack(ssm_i, tb + "rg_lru.recurrent_gate_bias",
                                np.asarray),
        }

        def vpad(w):
            if w.shape[0] < spec.padded_vocab:
                w = np.pad(w, [(0, spec.padded_vocab - w.shape[0]), (0, 0)])
            return w

        out = {
            "embed": vpad(get("model.embed_tokens.weight")),
            "layers": layers,
            "final_norm": get("model.final_norm.weight"),
        }
        if attn_layers:
            out["attn_layers"] = attn_layers
        if ssm_layers:
            out["ssm_layers"] = ssm_layers
        if not spec.tie_word_embeddings:
            out["lm_head"] = np.ascontiguousarray(
                vpad(get("lm_head.weight")).T)
        return out

    @classmethod
    def load_hf_model(cls, model_path: str):
        import transformers
        return transformers.RecurrentGemmaForCausalLM.from_pretrained(
            model_path)
