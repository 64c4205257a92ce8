"""Olmo-Hybrid family, ``model_type`` ``olmo_hybrid`` (allenai/Olmo-Hybrid-7B):
an INTERLEAVED hybrid — each layer's temporal block is gated delta-rule
linear attention (``modules/ssm.py`` kind ``gated_delta``; the Gated DeltaNet
that HF ships as ``Qwen3NextGatedDeltaNet``, with a write strength in (0, 2)
where ``linear_allow_neg_eigval``) or full softmax attention, as
``layer_types`` says — in the Olmo 2 / Olmo 3 block of :mod:`..olmo2`: no
input norms, an RMSNorm on each sub-block's OUTPUT before its residual add,
a full-width q/k RMSNorm before the head split on the attention layers, an
untied head.

What ``config.json`` cannot say is taken by convention and listed under
``assumed`` in ``benchmark/configs/olmo-hybrid-7b.json``: where the norms
sit, ``rope_parameters.rope_theta: null`` read as NO positional embedding,
and the checkpoint's tensor names (Olmo 3's for attention and MLP;
``linear_attn.{q,k,v,a,b,g,o}_proj``, ``{q,k,v}_conv1d``, ``A_log``,
``dt_bias``, ``o_norm`` for the mixer).

The state (a float32 ``(d_k, d_v)`` matrix a head, and the conv tail over
[q|k|v]) is the second per-sequence cache beside the KV pool, so the family
serves through the paged path (``PagedCausalLMApplication`` ->
``PagedEngineAdapter``) as well as the contiguous one. One chip: tensor
parallelism is refused.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ...config import InferenceConfig
from ...modules.ssm import SSMSpec
from ...parallel.layers import place_q_weight, replicate_kv_weight
from ..family import DecoderFamily, register_family
from ..model_base import DecoderSpec, spec_from_config

LINEAR, FULL = "linear_attention", "full_attention"
#: the delta rule's prefill chunk: any chunking gives the token-by-token
#: result; 64 is the published kernels' (the triangular solve is 64 wide)
SCAN_CHUNK = 64


class OlmoHybridInferenceConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["hidden_size", "num_attention_heads", "num_hidden_layers",
                "num_key_value_heads", "vocab_size", "intermediate_size",
                "layer_types", "linear_num_key_heads",
                "linear_num_value_heads", "linear_key_head_dim",
                "linear_value_head_dim", "linear_conv_kernel_dim"]

    def get_text_config(self):
        return self


@register_family("olmo_hybrid")
class OlmoHybridFamily(DecoderFamily):
    config_cls = OlmoHybridInferenceConfig

    @classmethod
    def build_spec(cls, config: InferenceConfig,
                   tp_degree: Optional[int] = None) -> DecoderSpec:
        tp = tp_degree if tp_degree is not None \
            else config.tpu_config.tp_degree
        if tp > 1:
            raise NotImplementedError(
                "olmo_hybrid is served on one chip (tp_degree 1): its 30 "
                "heads divide over neither 4 nor 8 chips, and a recurrent "
                "stack has never run sharded (PERF.md section 7)")
        layer_types = list(config.layer_types)
        if len(layer_types) != config.num_hidden_layers or \
                set(layer_types) - {LINEAR, FULL}:
            raise ValueError(
                f"layer_types must name {config.num_hidden_layers} layers, "
                f"each {LINEAR!r} or {FULL!r}; got {layer_types}")
        theta = (getattr(config, "rope_parameters", None)
                 or {}).get("rope_theta")
        if theta is not None:
            raise NotImplementedError(
                f"olmo_hybrid with rope_parameters.rope_theta = {theta}: "
                "the published value is null, read as no positional "
                "embedding; a rotary member has not been checked")
        if getattr(config, "attention_bias", False):
            raise NotImplementedError(
                "olmo_hybrid with attention_bias: the published value is "
                "false and the loader reads no bias")
        heads = int(config.linear_num_value_heads)
        key_heads = int(config.linear_num_key_heads)
        if heads % key_heads:
            raise ValueError(
                f"linear_num_value_heads {heads} is not a multiple of "
                f"linear_num_key_heads {key_heads}")
        d_k, d_v = (int(config.linear_key_head_dim),
                    int(config.linear_value_head_dim))
        return spec_from_config(
            config, tp_degree,
            ssm=SSMSpec(
                kind="gated_delta", d_inner=heads * d_v, num_heads=heads,
                num_key_heads=key_heads, head_dim=d_v, d_state=d_k,
                d_conv=int(config.linear_conv_kernel_dim),
                chunk_size=SCAN_CHUNK, conv_bias=False,
                # y = w * rmsnorm(o) * silu(g): the norm first, then the gate
                gated_norm=True, norm_before_gate=True,
                norm_eps=float(getattr(config, "rms_norm_eps", 1e-6)),
                beta_scale=2.0 if getattr(config, "linear_allow_neg_eigval",
                                          False) else 1.0),
            ssm_pattern=tuple(t == LINEAR for t in layer_types),
            ssm_parallel=False,
            no_rope=True,
            norm_position="post",
            sandwich_norm=True,       # the post_attn / post_ff norm slots
            qk_norm_full=True,
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             False)),
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        """Interleaved layout (``decoder_param_specs``): "layers" = every
        layer's two output norms + MLP; "attn_layers" / "ssm_layers" = the
        temporal blocks, stacked in order of appearance. The mixer's
        projections are fused by destination: ``gdn_in`` = [q | k | v | g]
        columns (the three depthwise convolutions stacked in the same
        [q | k | v] order), ``gdn_in_ab`` = [a | b]."""
        g, D = spec.gqa, spec.head_dim
        pat = spec.resolved_ssm_pattern

        def get(n):
            if n in sd:
                return np.asarray(sd[n])
            raise KeyError(f"missing checkpoint tensor {n}")

        def t(w):
            return np.ascontiguousarray(np.asarray(w).T)

        def stack_over(idx):
            return lambda fmt, tr=np.asarray: np.stack(
                [tr(get(fmt.format(i=i))) for i in idx])

        all_i = list(range(spec.num_layers))
        every = stack_over(all_i)
        attn = stack_over([i for i in all_i if not pat[i]])
        lin = stack_over([i for i in all_i if pat[i]])
        p = "model.layers.{i}."
        out = {
            "embed": get("model.embed_tokens.weight"),
            "final_norm": get("model.norm.weight"),
            "layers": {
                "post_attn_norm": every(
                    p + "post_attention_layernorm.weight"),
                "post_ff_norm": every(
                    p + "post_feedforward_layernorm.weight"),
                "gate_proj": every(p + "mlp.gate_proj.weight", t),
                "up_proj": every(p + "mlp.up_proj.weight", t),
                "down_proj": every(p + "mlp.down_proj.weight", t),
            },
        }
        pad = spec.padded_vocab - out["embed"].shape[0]
        if pad:
            out["embed"] = np.pad(out["embed"], [(0, pad), (0, 0)])
        if spec.num_attn_layers:
            a = p + "self_attn."
            out["attn_layers"] = {
                "qkv_proj": np.concatenate([
                    attn(a + "q_proj.weight",
                         lambda w: place_q_weight(t(w), g, D, axis=-1)),
                    attn(a + "k_proj.weight",
                         lambda w: replicate_kv_weight(t(w), g, D, axis=-1)),
                    attn(a + "v_proj.weight",
                         lambda w: replicate_kv_weight(t(w), g, D, axis=-1)),
                ], axis=-1),
                "o_proj": attn(a + "o_proj.weight",
                               lambda w: place_q_weight(t(w), g, D, axis=0)),
                "q_norm": attn(a + "q_norm.weight",
                               lambda w: place_q_weight(np.asarray(w), g, D)),
                "k_norm": attn(a + "k_norm.weight",
                               lambda w: replicate_kv_weight(np.asarray(w),
                                                             g, D)),
            }
        if spec.num_ssm_layers:
            m = p + "linear_attn."

            def fused(names, suffix, tr, axis):
                return np.concatenate(
                    [lin(m + n + suffix, tr) for n in names], axis=axis)
            out["ssm_layers"] = {
                "gdn_in": fused("qkvg", "_proj.weight", t, 2),
                "gdn_in_ab": fused("ab", "_proj.weight", t, 2),
                # Conv1d.weight (C, 1, K) -> (C, K), channels [q | k | v]
                "gdn_conv": fused("qkv", "_conv1d.weight",
                                  lambda w: np.asarray(w)[:, 0, :], 1),
                "gdn_dt_bias": lin(m + "dt_bias").astype(np.float32),
                "gdn_A_log": lin(m + "A_log").astype(np.float32),
                "gdn_norm": lin(m + "o_norm.weight"),
                "gdn_out": lin(m + "o_proj.weight", t),
            }
        if not spec.tie_word_embeddings:
            lm = get("lm_head.weight")
            out["lm_head"] = t(np.pad(lm, [(0, pad), (0, 0)]))
        return out
