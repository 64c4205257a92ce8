"""Contrib model hub — the breadth wave of community decoder families
(reference: contrib/models/, 64 community models each with src + tests —
SURVEY §2.7). Every family here is a thin DecoderSpec mapping + checkpoint
conversion over the shared layer machinery (model_base.py), mirroring how
the reference's contrib models subclass its L5 bases.

Families: gpt2, gpt_neox (pythia), falcon, starcoder2, phi (phi-1/2),
gemma (v1), olmo (v1), glm4, stablelm, cohere (command-r)."""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import numpy as np

from ..config import InferenceConfig
from .family import DecoderFamily, register_family
from .model_base import DecoderSpec, pad_vocab, spec_from_config
from ..parallel.layers import place_q_weight, replicate_kv_weight


class _SimpleConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["vocab_size"]

    def get_text_config(self):
        return self


def _t(w):
    return np.ascontiguousarray(np.asarray(w).T)


def _ident(w):
    return np.asarray(w)


def _split_interleaved_qkv(get, key_fmt, num_layers, nh, g, D,
                           with_bias=True):
    """Split a per-head-interleaved fused QKV stack — weights (nh, 3, D, H)
    per layer (gpt_neox / bloom / persimmon layout) — into placed q/k/v
    weight (and bias) stacks."""
    qs, ks, vs, qb, kb, vb = [], [], [], [], [], []
    for i in range(num_layers):
        w = np.asarray(get(key_fmt.format(i=i) + ".weight"))
        w = w.reshape(nh, 3, D, -1)
        qs.append(place_q_weight(_t(w[:, 0].reshape(nh * D, -1)), g, D,
                                 axis=-1))
        ks.append(replicate_kv_weight(_t(w[:, 1].reshape(nh * D, -1)), g, D,
                                      axis=-1))
        vs.append(replicate_kv_weight(_t(w[:, 2].reshape(nh * D, -1)), g, D,
                                      axis=-1))
        if with_bias:
            b = np.asarray(get(key_fmt.format(i=i) + ".bias")).reshape(
                nh, 3, D)
            qb.append(place_q_weight(b[:, 0].reshape(-1), g, D))
            kb.append(replicate_kv_weight(b[:, 1].reshape(-1), g, D))
            vb.append(replicate_kv_weight(b[:, 2].reshape(-1), g, D))
    out = {"qkv_proj": np.concatenate(
        [np.stack(qs), np.stack(ks), np.stack(vs)], axis=-1)}
    if with_bias:
        out["qkv_bias"] = np.concatenate(
            [np.stack(qb), np.stack(kb), np.stack(vb)], axis=-1)
    return out


# ---------------------------------------------------------------------------
# GPT-2 (reference: contrib/models/gpt2)
# ---------------------------------------------------------------------------

@register_family("gpt2")
class GPT2Family(DecoderFamily):
    """Learned positions, fused Conv1D c_attn, plain gelu MLP, LN+bias."""
    config_cls = _SimpleConfig
    hf_prefix = "transformer"

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.n_embd
        nh = config.n_head
        inner = getattr(config, "n_inner", None) or 4 * H
        return spec_from_config(
            config, tp_degree,
            num_layers=config.n_layer,
            hidden_size=H,
            num_q_heads=nh,
            num_kv_heads=nh,
            head_dim=H // nh,
            intermediate_size=inner,
            rms_eps=float(getattr(config, "layer_norm_epsilon", 1e-5)),
            act={"gelu_new": "gelu_new", "gelu": "gelu",
                 "gelu_pytorch_tanh": "gelu_pytorch_tanh"}.get(
                getattr(config, "activation_function", "gelu_new"),
                "gelu_new"),
            norm_type="layernorm", norm_bias=True,
            mlp_glu=False, mlp_bias=True,
            qkv_bias=True, o_bias=True,
            no_rope=True,
            learned_pos=int(getattr(config, "n_positions", 1024)),
            tie_word_embeddings=True,
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        g, D = spec.gqa, spec.head_dim
        H = spec.hidden_size
        p = cls.hf_prefix

        def get(n):
            return np.asarray(sd[n])

        def stack(fmt, tr):
            return np.stack([tr(get(fmt.format(i=i)))
                             for i in range(spec.num_layers)])

        def split_cattn(w):   # Conv1D weight (H, 3H) already (in, out)
            return np.asarray(w)[:, :H], np.asarray(w)[:, H:2 * H], \
                np.asarray(w)[:, 2 * H:]

        qs, ks, vs, qb, kb, vb = [], [], [], [], [], []
        for i in range(spec.num_layers):
            wq, wk, wv = split_cattn(get(f"{p}.h.{i}.attn.c_attn.weight"))
            bq, bk, bv = np.split(get(f"{p}.h.{i}.attn.c_attn.bias"), 3)
            qs.append(place_q_weight(wq, g, D, axis=-1))
            ks.append(replicate_kv_weight(wk, g, D, axis=-1))
            vs.append(replicate_kv_weight(wv, g, D, axis=-1))
            qb.append(place_q_weight(bq, g, D))
            kb.append(replicate_kv_weight(bk, g, D))
            vb.append(replicate_kv_weight(bv, g, D))
        layers = {
            "input_norm": stack(p + ".h.{i}.ln_1.weight", _ident),
            "input_norm_b": stack(p + ".h.{i}.ln_1.bias", _ident),
            "post_norm": stack(p + ".h.{i}.ln_2.weight", _ident),
            "post_norm_b": stack(p + ".h.{i}.ln_2.bias", _ident),
            "q_proj": np.stack(qs), "k_proj": np.stack(ks),
            "v_proj": np.stack(vs),
            "q_bias": np.stack(qb), "k_bias": np.stack(kb),
            "v_bias": np.stack(vb),
            # c_proj is Conv1D: already (in, out); pad the q-sized input axis
            "o_proj": stack(p + ".h.{i}.attn.c_proj.weight",
                            lambda w: place_q_weight(np.asarray(w), g, D,
                                                     axis=0)),
            "o_bias": stack(p + ".h.{i}.attn.c_proj.bias", _ident),
            "gate_proj": stack(p + ".h.{i}.mlp.c_fc.weight", _ident),
            "gate_bias": stack(p + ".h.{i}.mlp.c_fc.bias", _ident),
            "down_proj": stack(p + ".h.{i}.mlp.c_proj.weight", _ident),
            "down_bias": stack(p + ".h.{i}.mlp.c_proj.bias", _ident),
        }
        # fuse q/k/v (+biases) like the shared path
        layers["qkv_proj"] = np.concatenate(
            [layers.pop("q_proj"), layers.pop("k_proj"),
             layers.pop("v_proj")], axis=-1)
        layers["qkv_bias"] = np.concatenate(
            [layers.pop("q_bias"), layers.pop("k_bias"),
             layers.pop("v_bias")], axis=-1)

        def vpad(w):
            if w.shape[0] < spec.padded_vocab:
                w = np.pad(w, [(0, spec.padded_vocab - w.shape[0]), (0, 0)])
            return w

        return {
            "embed": vpad(get(p + ".wte.weight")),
            "pos_embed": get(p + ".wpe.weight"),
            "layers": layers,
            "final_norm": get(p + ".ln_f.weight"),
            "final_norm_b": get(p + ".ln_f.bias"),
        }


# ---------------------------------------------------------------------------
# GPT-NeoX / Pythia (reference: contrib/models gpt_neox-style families)
# ---------------------------------------------------------------------------

@register_family("gpt_neox")
class GPTNeoXFamily(DecoderFamily):
    """Per-head-interleaved fused QKV, partial rotary, parallel-dual
    residual, plain gelu MLP, LN+bias."""
    config_cls = _SimpleConfig
    hf_prefix = "gpt_neox"

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
            act=getattr(config, "hidden_act", "gelu"),
            norm_type="layernorm", norm_bias=True,
            mlp_glu=False, mlp_bias=True,
            qkv_bias=True, o_bias=True,
            rotary_dim=int(hd * getattr(config, "rotary_pct", 0.25)),
            block_style=("parallel_dual"
                         if getattr(config, "use_parallel_residual", True)
                         else "sequential"),
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
            get, p + ".layers.{i}.attention.query_key_value",
            spec.num_layers, nh, g, D)
        layers = {
            "input_norm": stack(p + ".layers.{i}.input_layernorm.weight", _ident),
            "input_norm_b": stack(p + ".layers.{i}.input_layernorm.bias", _ident),
            "post_norm": stack(p + ".layers.{i}.post_attention_layernorm.weight", _ident),
            "post_norm_b": stack(p + ".layers.{i}.post_attention_layernorm.bias", _ident),
            **fused,
            "o_proj": stack(p + ".layers.{i}.attention.dense.weight",
                            lambda w: place_q_weight(_t(w), g, D, axis=0)),
            "o_bias": stack(p + ".layers.{i}.attention.dense.bias", _ident),
            "gate_proj": stack(p + ".layers.{i}.mlp.dense_h_to_4h.weight", _t),
            "gate_bias": stack(p + ".layers.{i}.mlp.dense_h_to_4h.bias", _ident),
            "down_proj": stack(p + ".layers.{i}.mlp.dense_4h_to_h.weight", _t),
            "down_bias": stack(p + ".layers.{i}.mlp.dense_4h_to_h.bias", _ident),
        }

        def vpad(w):
            if w.shape[0] < spec.padded_vocab:
                w = np.pad(w, [(0, spec.padded_vocab - w.shape[0]), (0, 0)])
            return w

        return {
            "embed": vpad(get(p + ".embed_in.weight")),
            "layers": layers,
            "final_norm": get(p + ".final_layer_norm.weight"),
            "final_norm_b": get(p + ".final_layer_norm.bias"),
            "lm_head": _t(vpad(get("embed_out.weight"))),
        }


# ---------------------------------------------------------------------------
# Falcon (reference: contrib/models/falcon)
# ---------------------------------------------------------------------------

@register_family("falcon")
class FalconFamily(DecoderFamily):
    """Fused grouped QKV, parallel-shared residual (falcon-7B style),
    plain gelu MLP, LN+bias."""
    config_cls = _SimpleConfig
    hf_prefix = "transformer"

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.hidden_size
        nh = config.num_attention_heads
        new_arch = bool(getattr(config, "new_decoder_architecture", False))
        n_kv = (config.num_kv_heads if new_arch
                else (1 if getattr(config, "multi_query", True) else nh))
        parallel = bool(getattr(config, "parallel_attn", True))
        # old arch (falcon-7b): ONE shared norm feeds attn and MLP;
        # new arch (falcon-40b/180b): separate ln_attn / ln_mlp, both over
        # the block input -> parallel_dual
        if new_arch:
            style = "parallel_dual"
        elif parallel:
            style = "parallel_shared"
        else:
            style = "sequential"
        return spec_from_config(
            config, tp_degree,
            num_kv_heads=n_kv,
            head_dim=H // nh,
            intermediate_size=getattr(config, "ffn_hidden_size", None)
            or 4 * H,
            rms_eps=float(getattr(config, "layer_norm_epsilon", 1e-5)),
            act="gelu",
            norm_type="layernorm", norm_bias=True,
            mlp_glu=False, mlp_bias=bool(getattr(config, "bias", False)),
            qkv_bias=bool(getattr(config, "bias", False)),
            o_bias=bool(getattr(config, "bias", False)),
            block_style=style,
            tie_word_embeddings=True,
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        g, D = spec.gqa, spec.head_dim
        nh = spec.num_q_heads
        nkv = spec.num_kv_heads
        p = cls.hf_prefix

        def get(n):
            return np.asarray(sd[n])

        def stack(fmt, tr):
            return np.stack([tr(get(fmt.format(i=i)))
                             for i in range(spec.num_layers)])

        gsize = nh // nkv
        qs, ks, vs = [], [], []
        for i in range(spec.num_layers):
            w = get(f"{p}.h.{i}.self_attention.query_key_value.weight")
            # falcon fused layout: (nkv, g+2, hd, H) — q heads of each kv
            # group, then that group's k and v
            w = w.reshape(nkv, gsize + 2, D, -1)
            q = w[:, :gsize].reshape(nkv * gsize * D, -1)
            k = w[:, gsize].reshape(nkv * D, -1)
            v = w[:, gsize + 1].reshape(nkv * D, -1)
            qs.append(place_q_weight(_t(q), g, D, axis=-1))
            ks.append(replicate_kv_weight(_t(k), g, D, axis=-1))
            vs.append(replicate_kv_weight(_t(v), g, D, axis=-1))
        new_arch = any(".ln_attn." in k for k in sd)
        ln = "ln_attn" if new_arch else "input_layernorm"
        if new_arch:
            # falcon-40b style: separate MLP norm over the block input
            post_norm = stack(p + ".h.{i}.ln_mlp.weight", _ident)
            post_norm_b = stack(p + ".h.{i}.ln_mlp.bias", _ident)
        else:
            # parallel_shared never reads post_norm; keep identity
            post_norm = np.ones((spec.num_layers, spec.hidden_size),
                                np.float32)
            post_norm_b = np.zeros((spec.num_layers, spec.hidden_size),
                                   np.float32)
        layers = {
            "input_norm": stack(p + ".h.{i}." + ln + ".weight", _ident),
            "input_norm_b": stack(p + ".h.{i}." + ln + ".bias", _ident),
            "post_norm": post_norm,
            "post_norm_b": post_norm_b,
            "qkv_proj": np.concatenate(
                [np.stack(qs), np.stack(ks), np.stack(vs)], axis=-1),
            "o_proj": stack(p + ".h.{i}.self_attention.dense.weight",
                            lambda w: place_q_weight(_t(w), g, D, axis=0)),
            "gate_proj": stack(p + ".h.{i}.mlp.dense_h_to_4h.weight", _t),
            "down_proj": stack(p + ".h.{i}.mlp.dense_4h_to_h.weight", _t),
        }
        if spec.qkv_bias:
            qbs, kbs, vbs = [], [], []
            for i in range(spec.num_layers):
                b = get(f"{p}.h.{i}.self_attention.query_key_value.bias")
                b = b.reshape(nkv, gsize + 2, D)
                qbs.append(place_q_weight(
                    b[:, :gsize].reshape(-1), g, D))
                kbs.append(replicate_kv_weight(b[:, gsize].reshape(-1), g, D))
                vbs.append(replicate_kv_weight(
                    b[:, gsize + 1].reshape(-1), g, D))
            layers["qkv_bias"] = np.concatenate(
                [np.stack(qbs), np.stack(kbs), np.stack(vbs)], axis=-1)
        if spec.o_bias:
            layers["o_bias"] = stack(
                p + ".h.{i}.self_attention.dense.bias", _ident)
        if spec.mlp_bias:
            layers["gate_bias"] = stack(
                p + ".h.{i}.mlp.dense_h_to_4h.bias", _ident)
            layers["down_bias"] = stack(
                p + ".h.{i}.mlp.dense_4h_to_h.bias", _ident)

        def vpad(w):
            if w.shape[0] < spec.padded_vocab:
                w = np.pad(w, [(0, spec.padded_vocab - w.shape[0]), (0, 0)])
            return w

        return {
            "embed": vpad(get(p + ".word_embeddings.weight")),
            "layers": layers,
            "final_norm": get(p + ".ln_f.weight"),
            "final_norm_b": get(p + ".ln_f.bias"),
        }


# ---------------------------------------------------------------------------
# StarCoder2 (reference: contrib/models/starcoder2)
# ---------------------------------------------------------------------------

@register_family("starcoder2")
class Starcoder2Family(DecoderFamily):
    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        bias = bool(getattr(config, "use_bias", True))
        return spec_from_config(
            config, tp_degree,
            rms_eps=float(getattr(config, "norm_epsilon", 1e-5)),
            act=getattr(config, "hidden_act", "gelu_pytorch_tanh"),
            norm_type="layernorm", norm_bias=True,
            mlp_glu=False, mlp_bias=bias,
            qkv_bias=bias, o_bias=bias,
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             True)),
        )

    @classmethod
    def convert_mlp_weights(cls, get, layer_stack, spec):
        p = cls.hf_prefix
        out = {
            "gate_proj": layer_stack(p + ".layers.{i}.mlp.c_fc.weight", _t),
            "down_proj": layer_stack(p + ".layers.{i}.mlp.c_proj.weight", _t),
        }
        if spec.mlp_bias:
            out["gate_bias"] = layer_stack(p + ".layers.{i}.mlp.c_fc.bias",
                                           _ident)
            out["down_bias"] = layer_stack(p + ".layers.{i}.mlp.c_proj.bias",
                                           _ident)
        return out

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
        return out


# ---------------------------------------------------------------------------
# Phi (phi-1 / phi-2) (reference: contrib/models/phi)
# ---------------------------------------------------------------------------

@register_family("phi")
class PhiFamily(DecoderFamily):
    """Parallel-shared residual, partial rotary, plain gelu MLP, LN+bias,
    biased lm_head."""
    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.hidden_size
        nh = config.num_attention_heads
        hd = H // nh
        return spec_from_config(
            config, tp_degree,
            head_dim=hd,
            rms_eps=float(getattr(config, "layer_norm_eps", 1e-5)),
            act=getattr(config, "hidden_act", "gelu_new"),
            norm_type="layernorm", norm_bias=True,
            mlp_glu=False, mlp_bias=True,
            qkv_bias=True, o_bias=True, lm_head_bias=True,
            rotary_dim=int(hd * getattr(config, "partial_rotary_factor",
                                        0.5)),
            block_style="parallel_shared",
            tie_word_embeddings=False,
        )

    @classmethod
    def convert_mlp_weights(cls, get, layer_stack, spec):
        p = cls.hf_prefix
        return {
            "gate_proj": layer_stack(p + ".layers.{i}.mlp.fc1.weight", _t),
            "gate_bias": layer_stack(p + ".layers.{i}.mlp.fc1.bias", _ident),
            "down_proj": layer_stack(p + ".layers.{i}.mlp.fc2.weight", _t),
            "down_bias": layer_stack(p + ".layers.{i}.mlp.fc2.bias", _ident),
        }

    @classmethod
    def convert_extra_layer_weights(cls, get, layer_stack, spec):
        p = cls.hf_prefix
        L, H = spec.num_layers, spec.hidden_size
        return {
            "input_norm_b": layer_stack(
                p + ".layers.{i}.input_layernorm.bias", _ident),
            # parallel_shared: post_norm unused
            "post_norm": np.ones((L, H), np.float32),
            "post_norm_b": np.zeros((L, H), np.float32),
        }

    # phi has no post_attention_layernorm; base conversion must not fetch it
    post_norm_src = "input_layernorm"
    attn_o_src = "self_attn.dense"

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        sd = dict(sd)
        # phi names its final norm "final_layernorm"
        sd.setdefault("model.norm.weight",
                      np.asarray(sd["model.final_layernorm.weight"]))
        out = super().convert_hf_state_dict(sd, spec)
        out["final_norm"] = np.asarray(sd["model.final_layernorm.weight"])
        out["final_norm_b"] = np.asarray(sd["model.final_layernorm.bias"])
        out["lm_head_b"] = _vpad1(np.asarray(sd["lm_head.bias"]),
                                  spec.padded_vocab)
        return out


# ---------------------------------------------------------------------------
# Gemma v1 (reference: contrib/models/gemma)
# ---------------------------------------------------------------------------

@register_family("gemma")
class GemmaFamily(DecoderFamily):
    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        return spec_from_config(
            config, tp_degree,
            head_dim=config.head_dim,
            norm_offset=1.0,
            embed_scale=math.sqrt(config.hidden_size),
            act=getattr(config, "hidden_activation", None)
            or "gelu_pytorch_tanh",
            tie_word_embeddings=True,
        )


# ---------------------------------------------------------------------------
# OLMo v1 (reference: contrib/models/olmo)
# ---------------------------------------------------------------------------

@register_family("olmo")
class OlmoFamily(DecoderFamily):
    """Non-parametric LayerNorm (no weight/bias in the checkpoint)."""
    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        return spec_from_config(
            config, tp_degree,
            norm_type="layernorm",
            rms_eps=1e-5,
            qkv_clip=getattr(config, "clip_qkv", None),
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             False)),
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        # synthesize unit norm weights: OLMo's LayerNorm has no params
        L, H = spec.num_layers, spec.hidden_size
        ones = np.ones((H,), np.float32)
        sd = dict(sd)
        for i in range(L):
            sd.setdefault(f"model.layers.{i}.input_layernorm.weight", ones)
            sd.setdefault(f"model.layers.{i}.post_attention_layernorm.weight",
                          ones)
        sd.setdefault("model.norm.weight", ones)
        return super().convert_hf_state_dict(sd, spec)


# ---------------------------------------------------------------------------
# GLM-4 (reference: contrib/models/glm)
# ---------------------------------------------------------------------------

@register_family("glm4")
class Glm4Family(DecoderFamily):
    """Fused gate_up MLP, sandwich norms, partial interleaved rotary."""
    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.hidden_size
        nh = config.num_attention_heads
        hd = getattr(config, "head_dim", None) or H // nh
        return spec_from_config(
            config, tp_degree,
            head_dim=hd,
            qkv_bias=bool(getattr(config, "attention_bias", True)),
            rotary_dim=int(hd * getattr(config, "partial_rotary_factor",
                                        0.5)),
            rope_interleaved=True,
            sandwich_norm=True,
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             False)),
        )

    post_norm_src = "post_attention_layernorm"

    @classmethod
    def convert_mlp_weights(cls, get, layer_stack, spec):
        p = cls.hf_prefix
        I = spec.intermediate_size

        def gate(w):
            return _t(np.asarray(w)[:I])

        def up(w):
            return _t(np.asarray(w)[I:])

        return {
            "gate_proj": layer_stack(p + ".layers.{i}.mlp.gate_up_proj.weight",
                                     gate),
            "up_proj": layer_stack(p + ".layers.{i}.mlp.gate_up_proj.weight",
                                   up),
            "down_proj": layer_stack(p + ".layers.{i}.mlp.down_proj.weight",
                                     _t),
        }

    @classmethod
    def convert_extra_layer_weights(cls, get, layer_stack, spec):
        p = cls.hf_prefix
        return {
            "post_attn_norm": layer_stack(
                p + ".layers.{i}.post_self_attn_layernorm.weight", _ident),
            "post_ff_norm": layer_stack(
                p + ".layers.{i}.post_mlp_layernorm.weight", _ident),
        }


# ---------------------------------------------------------------------------
# StableLM (reference: contrib/models/stablelm)
# ---------------------------------------------------------------------------

@register_family("stablelm")
class StableLmFamily(DecoderFamily):
    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        H = config.hidden_size
        nh = config.num_attention_heads
        hd = H // nh
        return spec_from_config(
            config, tp_degree,
            head_dim=hd,
            rms_eps=float(getattr(config, "layer_norm_eps", 1e-5)),
            norm_type="layernorm", norm_bias=True,
            qkv_bias=bool(getattr(config, "use_qkv_bias", False)),
            rotary_dim=int(hd * getattr(config, "partial_rotary_factor",
                                        0.25)),
            tie_word_embeddings=False,
        )

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
        out["final_norm"] = np.asarray(sd["model.norm.weight"])
        out["final_norm_b"] = np.asarray(sd["model.norm.bias"])
        return out


# ---------------------------------------------------------------------------
# Cohere / Command-R (reference: contrib/models/cohere)
# ---------------------------------------------------------------------------

@register_family("cohere")
class CohereFamily(DecoderFamily):
    """Parallel-shared residual, bias-free LayerNorm, logit scaling,
    tied embeddings, rotary over interleaved pairs (HF ``rotate_half`` of
    modeling_cohere takes ``x[..., ::2]`` / ``x[..., 1::2]``)."""
    config_cls = _SimpleConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        scale = float(getattr(config, "logit_scale", 1.0))
        return spec_from_config(
            config, tp_degree,
            rms_eps=float(getattr(config, "layer_norm_eps", 1e-5)),
            norm_type="layernorm",
            block_style="parallel_shared",
            rope_interleaved=True,
            logits_divide=1.0 / scale if scale else None,
            tie_word_embeddings=True,
        )

    @classmethod
    def convert_extra_layer_weights(cls, get, layer_stack, spec):
        L, H = spec.num_layers, spec.hidden_size
        return {"post_norm": np.ones((L, H), np.float32)}

    post_norm_src = "input_layernorm"   # parallel_shared: post_norm unused


def _vpad(w: np.ndarray, padded: int) -> np.ndarray:
    if w.shape[0] < padded:
        w = np.pad(w, [(0, padded - w.shape[0])] + [(0, 0)] * (w.ndim - 1))
    return w


def _vpad1(b: np.ndarray, padded: int) -> np.ndarray:
    if b.shape[0] < padded:
        b = np.pad(b, (0, padded - b.shape[0]))
    return b
