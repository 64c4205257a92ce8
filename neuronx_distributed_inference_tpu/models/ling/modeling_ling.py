"""Ling-3.0-flash's language model (inclusionAI/Ling-3.0-flash-VL's
``config.json``; served on token ids, its vision tower is not part of this
family), registered as ``ling_kda``: an INTERLEAVED hybrid of two temporal
blocks under pre-norm residual layers ``h += T(N(h)); h += F(N(h))``.

* ``T``, five layers in six (``layer_group_size``: layer ``l`` is latent
  attention where ``(l + 1) % layer_group_size == 0``): Kimi Delta Attention
  (arXiv:2510.26692; ``modules/ssm.py`` kind ``kda``), the delta rule with its
  decay BY CHANNEL, ``g = kda_lower_bound * sigmoid(exp(A_log) * (W_a u +
  dt_bias))`` through a full-rank projection (``no_kda_lora``), short
  convolutions of ``short_conv_kernel_size`` with ``silu`` (``linear_silu``)
  on q, k, v, l2-normalised q and k (``use_qk_norm``), the output RMSNorm by
  head (``group_norm_size`` 1) and then ONE sigmoid gate a head
  (``gated_attention_proj_granularity_type`` ``head_wise``). No rotary.
* ``T``, every sixth layer: DeepSeek-V2-Lite's form of Multi-head Latent
  Attention (``q_lora_rank`` null: the query is one projection), rotary in
  the half-split layout on ``qk_rope_head_dim`` lanes, the same head-wise
  gate (``model_base.MLASpec.head_gate``). On the paged path its layers keep
  a LATENT pool (``modules/block_kv_cache.latent_page``) beside the linear
  layers' state slots, in one cache.
* ``F``: a dense SwiGLU of ``intermediate_size`` on the ``first_k_dense_
  replace`` leading layers, then DeepSeek-V3's group-limited sigmoid router
  (``n_group`` / ``topk_group``, a selection bias, renormalised, times
  ``routed_scaling_factor``) over ``num_experts`` SwiGLU experts of
  ``moe_intermediate_size`` and one shared expert.

ONE CHIP'S SHARE, spelt as ``models/deepseek/`` spells it: with
``router_num_experts`` in the config, ``num_experts`` is what the weights
HOLD (from ``first_expert`` on) and the router still scores
``router_num_experts`` columns.

What the published keys do not define is REFUSED by name, not guessed
(:data:`REFUSED`, :func:`_refuse_undefined`): a non-zero entry of
``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list`` (a clamped
SwiGLU whose form the config does not give) and the switches below. What was
taken by convention is listed under ``assumed`` in
``benchmark/configs/ling-3.0-flash.json``, the checkpoint's tensor names
among it. One chip: tensor and expert parallelism are refused.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from ...config import InferenceConfig
from ...modules.moe import MoESpec
from ...modules.ssm import SSMSpec, kda_chunk_tokens
from ..contrib import _ident, _t, _vpad
from ..deepseek.modeling_deepseek import deepseek_style_moe_weights
from ..family import DecoderFamily, register_family
from ..model_base import (DecoderSpec, MLASpec, mla_q_columns,
                          spec_from_config)

#: switches the family runs at ONE value only, with that value: any other is
#: refused by the key's name (the published config has each at this value)
REFUSED = (("use_nGPT", False), ("value_norm", False), ("up_proj_norm", False),
           ("scale_router_input", False), ("use_kda_lora", False),
           ("no_kda_lora", True), ("mtp_use_kda", False),
           ("kda_safe_gate", True), ("linear_silu", True),
           ("use_mla_nope", False), ("use_qk_norm", True),
           ("group_norm_size", 1), ("q_lora_rank", None),
           ("gated_attention_proj_granularity_type", "head_wise"),
           ("score_function", "sigmoid"), ("norm_topk_prob", True),
           ("moe_router_enable_expert_bias", True), ("use_bias", False),
           ("use_qkv_bias", False), ("rope_scaling", None))
LIMIT_LISTS = ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list")


class LingKdaInferenceConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["hidden_size", "num_attention_heads", "num_hidden_layers",
                "vocab_size", "intermediate_size", "layer_group_size",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "head_dim", "short_conv_kernel_size",
                "kda_lower_bound", "num_experts", "num_experts_per_tok",
                "moe_intermediate_size", "first_k_dense_replace"]

    def get_text_config(self):
        return self


def _refuse_undefined(config) -> None:
    """Raise ``NotImplementedError`` naming the first key whose value this
    family does not define a computation for."""
    for key, want in REFUSED:
        got = getattr(config, key, want)
        if got != want:
            raise NotImplementedError(
                f"ling_kda with {key} = {got!r}: the published value is "
                f"{want!r} and the config gives no equation for another")
    n = int(config.num_hidden_layers)
    for key in LIMIT_LISTS:
        limits = list(getattr(config, key, None) or [])
        if limits and len(limits) != n:
            raise ValueError(
                f"{key} names {len(limits)} layers, num_hidden_layers is "
                f"{n}: cut the list with the depth")
        hot = [i for i, x in enumerate(limits) if x]
        if hot:
            raise NotImplementedError(
                f"ling_kda with {key}[{hot[0]}] = {limits[hot[0]]}: a "
                "clamped SwiGLU whose form (of the gate, of silu(gate), or "
                "of the product) the config does not give; only 0 = no "
                "clamp is computed")
    kv_lin = int(getattr(config, "num_kv_heads_for_linear_attn", 0) or 0)
    if kv_lin not in (0, int(config.num_attention_heads)):
        raise NotImplementedError(
            f"ling_kda with num_kv_heads_for_linear_attn = {kv_lin}: 0 (as "
            "many as the query heads) or the head count")
    if int(config.num_key_value_heads) != int(config.num_attention_heads):
        raise NotImplementedError(
            "ling_kda with num_key_value_heads != num_attention_heads: "
            "latent attention expands a K and V head a query head")


def temporal_pattern(config) -> List[bool]:
    """True where layer ``l``'s temporal block is the linear (KDA) one: every
    layer but each ``layer_group_size``-th, counted from 1."""
    period = int(config.layer_group_size)
    if period < 2:
        raise ValueError(f"layer_group_size {period}: at least 2")
    return [(l + 1) % period != 0
            for l in range(int(config.num_hidden_layers))]


@register_family("ling_kda")
class LingKdaFamily(DecoderFamily):
    config_cls = LingKdaInferenceConfig

    @classmethod
    def build_spec(cls, config: InferenceConfig,
                   tp_degree: Optional[int] = None) -> DecoderSpec:
        tcfg = config.tpu_config
        tp = tp_degree if tp_degree is not None else tcfg.tp_degree
        if tp > 1 or getattr(tcfg, "ep_degree", 1) > 1:
            raise NotImplementedError(
                "ling_kda is served on one chip (tp_degree 1, ep_degree 1): "
                "a recurrent stack has never run sharded, and a chip's share "
                "of the experts runs without its exchange (PERF.md section "
                "7)")
        _refuse_undefined(config)
        pattern = temporal_pattern(config)
        if all(pattern):
            raise NotImplementedError(
                "ling_kda with no latent-attention layer within "
                "num_hidden_layers: keep a whole layer_group_size period")
        nh, hd = int(config.num_attention_heads), int(config.head_dim)
        mla = MLASpec(
            kv_lora_rank=int(config.kv_lora_rank),
            qk_nope_head_dim=int(config.qk_nope_head_dim),
            qk_rope_head_dim=int(config.qk_rope_head_dim),
            v_head_dim=int(config.v_head_dim), q_lora_rank=None,
            head_gate=True)
        held = int(config.num_experts)
        routed = int(getattr(config, "router_num_experts", None) or held)
        first = int(getattr(config, "first_expert", 0) or 0)
        if not 0 <= first <= routed - held:
            raise ValueError(f"experts {first}..{first + held - 1} held of "
                             f"{routed} routed ones")
        inter = int(config.moe_intermediate_size)
        moe = MoESpec(
            num_experts=routed, top_k=int(config.num_experts_per_tok),
            intermediate_size=inter, normalize_topk=True,
            routed_scaling=float(getattr(config, "routed_scaling_factor",
                                         1.0)),
            router_act="sigmoid", has_router_bias=True,
            router_bias_mode="select",
            shared_intermediate=int(getattr(
                config, "moe_shared_expert_intermediate_size", inter))
            * int(getattr(config, "num_shared_experts", 1)),
            n_group=int(getattr(config, "n_group", 1) or 1),
            topk_group=int(getattr(config, "topk_group", 1) or 1),
            held_experts=held if held < routed else 0, first_expert=first)
        bound = float(config.kda_lower_bound)
        spec = spec_from_config(
            config, tp_degree,
            mla=mla, moe=moe,
            first_dense=int(config.first_k_dense_replace),
            head_dim=mla.qk_head_dim, rotary_dim=None,
            attn_scale=mla.qk_head_dim ** -0.5,
            rope_interleaved=False,       # assumed A6: the half-split layout
            ssm=SSMSpec(
                kind="kda", d_inner=nh * hd, num_heads=nh, head_dim=hd,
                d_state=hd, d_conv=int(config.short_conv_kernel_size),
                # the largest chunk whose decays factor exactly in float32
                chunk_size=kda_chunk_tokens(bound), conv_bias=False,
                norm_eps=float(getattr(config, "rms_norm_eps", 1e-6)),
                decay_lower_bound=bound),
            ssm_pattern=tuple(pattern), ssm_parallel=False,
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             False)))
        # rope operates on the dedicated rope head only (as DeepSeek's)
        return dataclasses.replace(spec, rope=dataclasses.replace(
            spec.rope, head_dim=mla.qk_rope_head_dim, rotary_dim=None))

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        """Interleaved layout (``decoder_param_specs``): "layers" the leading
        dense layers' norms + MLP, "moe_layers" the expert layers' norms +
        router + experts, "attn_layers" / "ssm_layers" the temporal blocks in
        order of appearance. Tensor names (assumed: the published modelling
        file is not in this tree): ``model.layers.{i}.input_layernorm``,
        ``.post_attention_layernorm``; ``.linear_attn.{q,k,v}_proj``,
        ``{q,k,v}_conv1d``, ``f_proj`` (the decay), ``b_proj``, ``g_proj``,
        ``A_log``, ``dt_bias``, ``o_norm``, ``o_proj``; ``.self_attn.q_proj``,
        ``kv_a_proj_with_mqa``, ``kv_a_layernorm``, ``kv_b_proj``, ``g_proj``,
        ``o_proj``; ``.mlp.*`` as DeepSeek-V3's."""
        pat = spec.resolved_ssm_pattern
        m = spec.mla
        L, nd = spec.num_layers, spec.first_dense
        p = "model.layers.{i}."

        def get(n):
            if n in sd:
                return np.asarray(sd[n])
            raise KeyError(f"missing checkpoint tensor {n}")

        def stack_over(idx):
            return lambda fmt, tr=_ident: np.stack(
                [tr(get(fmt.format(i=i))) for i in idx])

        def norms_and(idx, mlp_of):
            every = stack_over(idx)
            out = {"input_norm": every(p + "input_layernorm.weight"),
                   "post_norm": every(p + "post_attention_layernorm.weight")}
            per_layer = [mlp_of(i) for i in idx]
            out.update({k: np.stack([d[k] for d in per_layer])
                        for k in per_layer[0]})
            return out

        def dense_mlp(i):
            return {k: _t(get(f"model.layers.{i}.mlp.{k}.weight"))
                    for k in ("gate_proj", "up_proj", "down_proj")}

        out = {"embed": _vpad(get("model.embed_tokens.weight"),
                              spec.padded_vocab),
               "final_norm": get("model.norm.weight")}
        if nd:
            out["layers"] = norms_and(range(nd), dense_mlp)
        out["moe_layers" if nd else "layers"] = norms_and(
            range(nd, L), lambda i: deepseek_style_moe_weights(
                get, "model", i, spec, _t))
        attn = stack_over([i for i in range(L) if not pat[i]])
        a = p + "self_attn."
        out["attn_layers"] = {
            "q_proj": attn(a + "q_proj.weight", lambda w: mla_q_columns(
                _t(w), spec.gqa.num_q_heads, m.qk_nope_head_dim,
                m.qk_rope_head_dim)),
            "kv_a_proj": attn(a + "kv_a_proj_with_mqa.weight", _t),
            "kv_a_norm": attn(a + "kv_a_layernorm.weight"),
            "kv_b_proj": attn(a + "kv_b_proj.weight", _t),
            "g_proj": attn(a + "g_proj.weight", _t),
            "o_proj": attn(a + "o_proj.weight", _t),
        }
        lin = stack_over([i for i in range(L) if pat[i]])
        k = p + "linear_attn."

        def fused(names, suffix, tr, axis):
            return np.concatenate(
                [lin(k + n + suffix, tr) for n in names], axis=axis)
        out["ssm_layers"] = {
            "kda_in": fused("qkv", "_proj.weight", _t, 2),
            "kda_in_a": lin(k + "f_proj.weight", _t),
            "kda_in_bg": fused("bg", "_proj.weight", _t, 2),
            # Conv1d.weight (C, 1, K) -> (C, K), channels [q | k | v]
            "kda_conv": fused("qkv", "_conv1d.weight",
                              lambda w: np.asarray(w)[:, 0, :], 1),
            "kda_dt_bias": lin(k + "dt_bias").astype(np.float32),
            "kda_A_log": lin(k + "A_log").astype(np.float32),
            "kda_norm": lin(k + "o_norm.weight"),
            "kda_out": lin(k + "o_proj.weight", _t),
        }
        if not spec.tie_word_embeddings:
            out["lm_head"] = np.ascontiguousarray(
                _vpad(get("lm_head.weight"), spec.padded_vocab).T)
        return out

    @classmethod
    def load_hf_model(cls, model_path: str):
        raise NotImplementedError(
            "the installed transformers has no Ling-3.0 (bailing_hybrid) "
            "model; load the checkpoint's state dict and "
            "convert_hf_state_dict it")
