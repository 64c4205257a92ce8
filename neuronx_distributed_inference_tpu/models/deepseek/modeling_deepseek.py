"""DeepSeek-V2/V3 family (reference: models/deepseek/modeling_deepseek.py
``DeepseekV3*`` — SURVEY §2.7: MLA attention, custom rope_util, 493 LoC).

Covered deltas:
  * MLA (multi-head latent attention): q-lora + kv-lora compression with a
    shared rope head (model_base._mla_project); K dim = nope+rope, V dim =
    v_head_dim. The paged pool keeps a token's LATENT row (kv_lora_rank +
    rope values, modules/block_kv_cache.latent_lanes) and a decode step
    attends in the latent space (ops/mla_decode.py); the contiguous cache
    holds the expanded heads
  * yarn rope with mscale attention factor; softmax scale *= mscale(all_dim)^2
  * sigmoid router with e_score_correction_bias (selection only),
    group-limited greedy routing (n_group/topk_group), routed_scaling_factor
  * mixed stacks: first_k_dense_replace dense layers then MoE layers with
    shared experts

ONE CHIP'S SHARE of the expert layers, spelt as ``models/longcat_flash/``
and ``models/qwen3_next/`` spell it: with ``router_n_routed_experts`` in the
config, ``n_routed_experts`` is what the weights HOLD (from ``first_expert``
on) and the router, its selection bias, the groups and the top k still run
over ``router_n_routed_experts``; the block computes the held experts' part
of the sum plus the shared expert, and no code stands in for the other
chips. Without the key every routed expert is held. A share runs on one
chip: ``tp > 1`` and ``ep > 1`` are refused with it.

Left out: the multi-token-prediction module (``num_nextn_predict_layers``).
A checkpoint's ``model.layers.<num_hidden_layers>.*`` are its tensors and
are never read.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import numpy as np

from ...config import InferenceConfig
from ...modules.moe import MoESpec
from ..family import DecoderFamily, register_family
from ..model_base import (DecoderSpec, MLASpec, mla_q_columns,
                          spec_from_config)
from ...parallel.layers import ParamSpec


class DeepseekInferenceConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["hidden_size", "num_attention_heads", "num_hidden_layers",
                "vocab_size", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim"]


def deepseek_style_moe_weights(get, prefix: str, i: int, spec,
                              transpose) -> Dict[str, Any]:
    """DeepSeek-V3-shaped MoE weights for layer ``i``: sigmoid/softmax
    router (+ optional e_score_correction_bias), per-expert gate/up/down,
    optional shared experts. Shared by every family with this checkpoint
    shape (deepseek v2/v3, glm4_moe). A share of the experts
    (``MoESpec.held_experts``) is read at ``first_expert + e`` from a
    checkpoint that holds every routed expert, and at ``e`` from one that
    holds the share alone (the benchmark's seeded weights); the router keeps
    all its columns either way."""
    moe = spec.moe
    last = f"{prefix}.layers.{i}.mlp.experts.{moe.num_routed - 1}"
    first = moe.first_expert if moe.holds_share and _has(
        get, last + ".gate_proj.weight") else 0
    out: Dict[str, Any] = {
        "router": transpose(get(
            f"{prefix}.layers.{i}.mlp.gate.weight")).astype(np.float32),
    }
    if spec.moe.has_router_bias:
        out["router_bias"] = np.asarray(get(
            f"{prefix}.layers.{i}.mlp.gate.e_score_correction_bias")).astype(
            np.float32)
    for key, name in (("expert_gate", "gate_proj"),
                      ("expert_up", "up_proj"),
                      ("expert_down", "down_proj")):
        out[key] = np.stack([
            transpose(get(
                f"{prefix}.layers.{i}.mlp.experts.{first + e}.{name}.weight"))
            for e in range(moe.num_held)])
    if spec.moe.shared_intermediate:
        for key, name in (("shared_gate", "gate_proj"),
                          ("shared_up", "up_proj"),
                          ("shared_down", "down_proj")):
            out[key] = transpose(get(
                f"{prefix}.layers.{i}.mlp.shared_experts.{name}.weight"))
    return out


def _has(get, name: str) -> bool:
    try:
        get(name)
    except KeyError:
        return False
    return True


@register_family("deepseek_v3", "deepseek_v2")
class DeepseekFamily(DecoderFamily):
    config_cls = DeepseekInferenceConfig

    @classmethod
    def build_spec(cls, config: InferenceConfig, tp_degree: Optional[int] = None
                   ) -> DecoderSpec:
        mla = MLASpec(
            kv_lora_rank=config.kv_lora_rank,
            qk_nope_head_dim=config.qk_nope_head_dim,
            qk_rope_head_dim=config.qk_rope_head_dim,
            v_head_dim=config.v_head_dim,
            q_lora_rank=getattr(config, "q_lora_rank", None),
            # no LoRA scaling of the query or the latent (LongCat-Flash's)
            q_scale=1.0, kv_scale=1.0,
        )
        scale = mla.qk_head_dim ** -0.5
        rope_scaling = getattr(config, "rope_scaling", None) or {}
        mscale_all_dim = rope_scaling.get("mscale_all_dim", 0) or 0
        if mscale_all_dim:
            f = float(rope_scaling["factor"])
            m = (1.0 if f <= 1 else
                 0.1 * mscale_all_dim * math.log(f) + 1.0)
            scale = scale * m * m
        moe = None
        first_dense = 0
        if getattr(config, "n_routed_experts", None):
            held = int(config.n_routed_experts)
            routed = int(getattr(config, "router_n_routed_experts", None)
                         or held)
            first = int(getattr(config, "first_expert", 0) or 0)
            if not 0 <= first <= routed - held:
                raise ValueError(
                    f"experts {first}..{first + held - 1} held of {routed} "
                    "routed ones")
            tcfg = config.tpu_config
            tp = tp_degree if tp_degree is not None else tcfg.tp_degree
            if held < routed and (tp > 1
                                  or getattr(tcfg, "ep_degree", 1) > 1):
                raise NotImplementedError(
                    f"{getattr(config, 'model_type', 'deepseek')}: a chip's "
                    "share of the expert layers (router_n_routed_experts) "
                    "is served on one chip "
                    "(tp_degree 1, ep_degree 1): it runs without the "
                    "exchange that would join it to the other shares "
                    "(PERF.md section 7)")
            moe = MoESpec(
                num_experts=routed,
                top_k=config.num_experts_per_tok,
                intermediate_size=config.moe_intermediate_size,
                normalize_topk=bool(getattr(config, "norm_topk_prob", True)),
                routed_scaling=float(getattr(config, "routed_scaling_factor",
                                             1.0)),
                router_act="sigmoid",
                has_router_bias=True,          # e_score_correction_bias
                router_bias_mode="select",
                shared_intermediate=(config.moe_intermediate_size
                                     * getattr(config, "n_shared_experts", 0)),
                n_group=int(getattr(config, "n_group", 1) or 1),
                topk_group=int(getattr(config, "topk_group", 1) or 1),
                held_experts=held if held < routed else 0,
                first_expert=first,
            )
            first_dense = int(getattr(config, "first_k_dense_replace", 0))
        spec = spec_from_config(
            config, tp_degree,
            mla=mla,
            moe=moe,
            first_dense=first_dense,
            head_dim=mla.qk_head_dim,
            attn_scale=scale,
            rope_interleaved=bool(getattr(config, "rope_interleave", True)),
        )
        # rope operates on the dedicated rope head only
        import dataclasses
        return dataclasses.replace(
            spec, rope=dataclasses.replace(spec.rope,
                                           head_dim=mla.qk_rope_head_dim))

    @classmethod
    def convert_hf_state_dict(cls, sd: Dict[str, np.ndarray], spec: DecoderSpec
                              ) -> Dict[str, Any]:
        """Layers ``0 .. num_layers - 1`` are read by name; a checkpoint's
        ``model.layers.<num_layers>.*`` (DeepSeek-V3's multi-token-prediction
        module: its own embedding, head, projection and one block) is the
        next index and is never asked for."""
        p = cls.hf_prefix
        L = spec.num_layers
        nd = spec.first_dense if spec.moe is not None else L

        def get(name):
            if name in sd:
                return np.asarray(sd[name])
            raise KeyError(f"missing checkpoint tensor {name}")

        def t(w):
            return np.ascontiguousarray(np.asarray(w).T)

        def ident(w):
            return np.asarray(w)

        def q_t(w):
            # (in, heads x [nope | rope]) -> [all nope | all rope]
            return mla_q_columns(t(w), spec.gqa.num_q_heads,
                                 spec.mla.qk_nope_head_dim,
                                 spec.mla.qk_rope_head_dim)

        def attn_layer(i: int) -> Dict[str, np.ndarray]:
            base = f"{p}.layers.{i}.self_attn"
            out = {
                "input_norm": ident(get(f"{p}.layers.{i}.input_layernorm.weight")),
                "post_norm": ident(get(
                    f"{p}.layers.{i}.post_attention_layernorm.weight")),
                "kv_a_proj": t(get(f"{base}.kv_a_proj_with_mqa.weight")),
                "kv_a_norm": ident(get(f"{base}.kv_a_layernorm.weight")),
                "kv_b_proj": t(get(f"{base}.kv_b_proj.weight")),
                "o_proj": t(get(f"{base}.o_proj.weight")),
            }
            if spec.mla.q_lora_rank:
                out["q_a_proj"] = t(get(f"{base}.q_a_proj.weight"))
                out["q_a_norm"] = ident(get(f"{base}.q_a_layernorm.weight"))
                out["q_b_proj"] = q_t(get(f"{base}.q_b_proj.weight"))
            else:
                out["q_proj"] = q_t(get(f"{base}.q_proj.weight"))
            return out

        def dense_layer(i: int) -> Dict[str, np.ndarray]:
            out = attn_layer(i)
            for k, n in (("gate_proj", "gate_proj"), ("up_proj", "up_proj"),
                         ("down_proj", "down_proj")):
                out[k] = t(get(f"{p}.layers.{i}.mlp.{n}.weight"))
            return out

        def moe_layer(i: int) -> Dict[str, np.ndarray]:
            out = attn_layer(i)
            out.update(deepseek_style_moe_weights(get, p, i, spec, t))
            return out

        def stack(dicts: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
            return {k: np.stack([d[k] for d in dicts]) for k in dicts[0]}

        def vpad(w):
            if w.shape[0] < spec.padded_vocab:
                w = np.pad(w, [(0, spec.padded_vocab - w.shape[0])] +
                           [(0, 0)] * (w.ndim - 1))
            return w

        out: Dict[str, Any] = {
            "embed": vpad(get(p + ".embed_tokens.weight")),
            "final_norm": ident(get(p + ".norm.weight")),
        }
        if spec.moe is not None and spec.first_dense > 0:
            out["layers"] = stack([dense_layer(i) for i in range(nd)])
            out["moe_layers"] = stack([moe_layer(i) for i in range(nd, L)])
        elif spec.moe is not None:
            out["layers"] = stack([moe_layer(i) for i in range(L)])
        else:
            out["layers"] = stack([dense_layer(i) for i in range(L)])
        if not spec.tie_word_embeddings:
            out["lm_head"] = np.ascontiguousarray(vpad(get("lm_head.weight")).T)
        return out


def TpuDeepseekForCausalLM(model_path: str, config: InferenceConfig):
    from ..application import CausalLMApplication
    return CausalLMApplication(model_path, config, DeepseekFamily)
