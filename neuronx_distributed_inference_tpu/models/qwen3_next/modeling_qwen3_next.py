"""Qwen3-Next family, ``model_type`` ``qwen3_next``
(Qwen/Qwen3-Next-80B-A3B-Instruct; HF ``modeling_qwen3_next.py``): an
INTERLEAVED hybrid of pre-norm blocks whose every norm is ``x * rsqrt(mean
x^2 + eps) * (1 + w)``. Layer ``i`` is full attention where ``(i + 1) %
full_attention_interval == 0`` (or as ``layer_types`` says), else gated
delta-rule linear attention (``modules/ssm.py`` kind ``gated_delta``) with
``linear_num_key_heads`` key heads serving groups of value heads; every
layer's MLP is the sparse block: a softmax router over ``num_experts`` with
the top ``num_experts_per_tok`` renormalised, plus a shared expert behind a
per-token sigmoid gate.

* Full layers: ``q_proj`` is doubled, per head ``[query | gate]``; per-head
  RMSNorm ``(1 + w)`` on q and k; rotary on the first ``partial_rotary_factor``
  of a head; ``o_proj(attn * sigmoid(gate))``.
* Linear layers: ``in_proj_qkvz`` / ``in_proj_ba`` are stored interleaved per
  KEY head (``fix_query_key_value_ordering``); the loader lays them out by
  destination.

ONE CHIP'S SHARE of the expert layers: with ``router_num_experts`` in the
config, ``num_experts`` is what the weights HOLD (from ``first_expert`` on)
and the router still scores ``router_num_experts``; the block computes the
held experts' part of the sum and no code stands in for the other chips
(``modules/moe.py``). Without the key every expert is held.

Left out: the multi-token-prediction head (``mtp.*``: HF's
``Qwen3NextForCausalLM`` loads none either). The state (a float32 ``(d_k,
d_v)`` matrix a value head and the conv tail over [q|k|v]) is the second
per-sequence cache beside the KV pool, so the family serves through the
paged path. One chip: ``tp > 1`` and ``ep > 1`` are refused.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ...config import InferenceConfig
from ...modules.moe import MoESpec
from ...modules.ssm import SSMSpec
from ...parallel.layers import place_q_weight, replicate_kv_weight
from ..family import DecoderFamily, register_family
from ..model_base import DecoderSpec, spec_from_config
from ..olmo_hybrid.modeling_olmo_hybrid import FULL, LINEAR, SCAN_CHUNK
from ..qwen3_moe.modeling_qwen3_moe import moe_share


class Qwen3NextInferenceConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["hidden_size", "num_attention_heads", "num_hidden_layers",
                "num_key_value_heads", "head_dim", "vocab_size",
                "num_experts", "num_experts_per_tok",
                "moe_intermediate_size", "shared_expert_intermediate_size",
                "linear_num_key_heads", "linear_num_value_heads",
                "linear_key_head_dim", "linear_value_head_dim",
                "linear_conv_kernel_dim"]

    def get_text_config(self):
        return self


def layer_types_of(config) -> List[str]:
    """``layer_types`` as given, else from ``full_attention_interval``."""
    types = getattr(config, "layer_types", None)
    if types:
        return list(types)
    every = int(getattr(config, "full_attention_interval", 4))
    return [FULL if (i + 1) % every == 0 else LINEAR
            for i in range(config.num_hidden_layers)]


@register_family("qwen3_next")
class Qwen3NextFamily(DecoderFamily):
    config_cls = Qwen3NextInferenceConfig

    @classmethod
    def build_spec(cls, config: InferenceConfig,
                   tp_degree: Optional[int] = None) -> DecoderSpec:
        tcfg = config.tpu_config
        tp = tp_degree if tp_degree is not None else tcfg.tp_degree
        if tp > 1 or getattr(tcfg, "ep_degree", 1) > 1:
            raise NotImplementedError(
                "qwen3_next is served on one chip (tp_degree 1, ep_degree "
                "1): no recurrent stack has run sharded, and a chip's share "
                "of the expert layers (router_num_experts) runs without the "
                "exchange that would join it to the other shares (PERF.md "
                "section 7)")
        layer_types = layer_types_of(config)
        if len(layer_types) != config.num_hidden_layers or \
                set(layer_types) - {LINEAR, FULL}:
            raise ValueError(
                f"layer_types must name {config.num_hidden_layers} layers, "
                f"each {LINEAR!r} or {FULL!r}; got {layer_types}")
        for key, want in (("decoder_sparse_step", 1), ("mlp_only_layers", []),
                          ("attention_bias", False), ("rope_scaling", None),
                          ("use_sliding_window", False)):
            got = getattr(config, key, want)
            if (got or want) != want:
                raise NotImplementedError(
                    f"qwen3_next with {key} = {got!r}: the published value "
                    f"is {want!r} and nothing else has been walked")
        heads = int(config.linear_num_value_heads)
        key_heads = int(config.linear_num_key_heads)
        if heads % key_heads:
            raise ValueError(
                f"linear_num_value_heads {heads} is not a multiple of "
                f"linear_num_key_heads {key_heads}")
        d_k, d_v = (int(config.linear_key_head_dim),
                    int(config.linear_value_head_dim))
        head_dim = int(config.head_dim)
        eps = float(getattr(config, "rms_norm_eps", 1e-6))
        return spec_from_config(
            config, tp_degree,
            rotary_dim=int(head_dim * float(
                getattr(config, "partial_rotary_factor", 1.0))),
            intermediate_size=int(config.moe_intermediate_size),
            moe=MoESpec(
                **moe_share(config), top_k=int(config.num_experts_per_tok),
                intermediate_size=int(config.moe_intermediate_size),
                normalize_topk=bool(getattr(config, "norm_topk_prob", True)),
                shared_intermediate=int(
                    config.shared_expert_intermediate_size),
                shared_gated=True,
                act=getattr(config, "hidden_act", "silu")),
            ssm=SSMSpec(
                kind="gated_delta", d_inner=heads * d_v, num_heads=heads,
                num_key_heads=key_heads, head_dim=d_v, d_state=d_k,
                d_conv=int(config.linear_conv_kernel_dim),
                chunk_size=SCAN_CHUNK, conv_bias=False,
                gated_norm=True, norm_before_gate=True, norm_eps=eps),
            ssm_pattern=tuple(t == LINEAR for t in layer_types),
            ssm_parallel=False,
            qk_norm=True,
            attn_out_gate=True,
            norm_offset=1.0,
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             False)),
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        """Interleaved layout (``decoder_param_specs``): "layers" = every
        layer's two input norms + the sparse block; "attn_layers" /
        "ssm_layers" = the temporal blocks in order of appearance.
        ``qkv_proj`` = [q | k | v | gate] (the doubled ``q_proj`` taken
        apart per head); ``gdn_in`` = [q | k | v | z] and ``gdn_in_ab`` =
        [a | b], each out of its per-key-head interleaving.

        Experts: a checkpoint that holds the router's every expert is read
        at ``first_expert + e``; one that holds the share alone (the
        benchmark's seeded weights) at ``e``."""
        g, D = spec.gqa, spec.head_dim
        pat = spec.resolved_ssm_pattern
        moe, s = spec.moe, spec.ssm

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
        x = p + "mlp."
        whole = (x + f"experts.{moe.num_experts - 1}.up_proj.weight"
                 ).format(i=0) in sd
        first = moe.first_expert if whole else 0

        def experts(name, tr):
            return np.stack([np.stack([
                tr(get((x + f"experts.{first + e}.{name}.weight")
                       .format(i=i))) for e in range(moe.num_held)])
                for i in all_i])
        out = {
            "embed": get("model.embed_tokens.weight"),
            "final_norm": get("model.norm.weight"),
            "layers": {
                "input_norm": every(p + "input_layernorm.weight"),
                "post_norm": every(p + "post_attention_layernorm.weight"),
                "router": every(x + "gate.weight", t).astype(np.float32),
                "expert_gate": experts("gate_proj", t),
                "expert_up": experts("up_proj", t),
                "expert_down": experts("down_proj", t),
                "shared_gate": every(x + "shared_expert.gate_proj.weight", t),
                "shared_up": every(x + "shared_expert.up_proj.weight", t),
                "shared_down": every(x + "shared_expert.down_proj.weight", t),
                "shared_gate_w": every(x + "shared_expert_gate.weight",
                                       lambda w: np.asarray(w)[0]),
            },
        }
        pad = spec.padded_vocab - out["embed"].shape[0]
        if pad:
            out["embed"] = np.pad(out["embed"], [(0, pad), (0, 0)])
        if spec.num_attn_layers:
            a = p + "self_attn."
            nq = spec.num_q_heads

            def q_half(half):
                # q_proj.weight (nq * 2D, H): per head [query D | gate D]
                def tr(w):
                    w = np.asarray(w).reshape(nq, 2, D, -1)[:, half]
                    return place_q_weight(t(w.reshape(nq * D, -1)), g, D,
                                          axis=-1)
                return tr

            def kv(w):
                return replicate_kv_weight(t(w), g, D, axis=-1)
            out["attn_layers"] = {
                "qkv_proj": np.concatenate([
                    attn(a + "q_proj.weight", q_half(0)),
                    attn(a + "k_proj.weight", kv),
                    attn(a + "v_proj.weight", kv),
                    attn(a + "q_proj.weight", q_half(1)),
                ], axis=-1),
                "o_proj": attn(a + "o_proj.weight",
                               lambda w: place_q_weight(t(w), g, D, axis=0)),
                "q_norm": attn(a + "q_norm.weight"),
                "k_norm": attn(a + "k_norm.weight"),
            }
        if spec.num_ssm_layers:
            m = p + "linear_attn."
            nk, nv = s.key_heads, s.num_heads
            r, dk, dv = nv // nk, s.d_state, s.head_dim

            def qkvz(w):
                # (nk * (2 dk + 2 r dv), H): a key head's [q | k | v | z]
                w = np.asarray(w).reshape(nk, 2 * dk + 2 * r * dv, -1)
                cuts = np.cumsum([dk, dk, r * dv])
                return t(np.concatenate(
                    [part.reshape(-1, w.shape[-1])
                     for part in np.split(w, cuts, axis=1)], axis=0))

            def ba(w):
                # (nk * 2r, H): a key head's [b | a]; stored as [a | b]
                w = np.asarray(w).reshape(nk, 2, r, -1)
                return t(np.concatenate(
                    [w[:, 1].reshape(nv, -1), w[:, 0].reshape(nv, -1)],
                    axis=0))
            out["ssm_layers"] = {
                "gdn_in": lin(m + "in_proj_qkvz.weight", qkvz),
                "gdn_in_ab": lin(m + "in_proj_ba.weight", ba),
                # Conv1d.weight (C, 1, K) -> (C, K), channels [q | k | v]
                "gdn_conv": lin(m + "conv1d.weight",
                                lambda w: np.asarray(w)[:, 0, :]),
                "gdn_dt_bias": lin(m + "dt_bias").astype(np.float32),
                "gdn_A_log": lin(m + "A_log").astype(np.float32),
                "gdn_norm": lin(m + "norm.weight"),
                "gdn_out": lin(m + "out_proj.weight", t),
            }
        if not spec.tie_word_embeddings:
            lm = get("lm_head.weight")
            out["lm_head"] = t(np.pad(lm, [(0, pad), (0, 0)]))
        return out
