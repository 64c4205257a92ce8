"""Liquid LFM2 families, ``model_type`` ``lfm2`` (reference:
contrib/models/lfm2-2.6b; HF ``modeling_lfm2.py``) and ``lfm2_moe``
(LiquidAI/LFM2-8B-A1B; the published ``modeling_lfm2_moe.py``): an
INTERLEAVED hybrid of pre-norm blocks. Layer ``i`` is, by ``layer_types``,

* ``conv``: the gated short convolution (``modules/ssm.py`` kind
  ``shortconv``): ``[B | C | X] = W_in h``, a depthwise causal convolution of
  width ``conv_L_cache`` over ``B * X`` with no activation, ``W_out (C *
  conv)``. Its state is the conv tail alone, ``conv_L_cache - 1`` products a
  channel: the second per-sequence cache beside the KV pool, so both families
  serve through the paged path;
* ``full_attention``: grouped-query attention with a per-head RMSNorm on q
  and on k BEFORE the rotary embedding (the whole head rotates).

``lfm2``: every MLP is the dense ``w2 (silu(w1 g) * w3 g)``. ``lfm2_moe``:
the first ``num_dense_layers`` layers keep that MLP at ``intermediate_size``;
every later layer routes over ``num_experts`` experts of
``moe_intermediate_size``: ``s = sigmoid(g W_r)`` in float32, the top
``num_experts_per_tok`` of ``s + expert_bias`` (``use_expert_bias``: the bias
picks, it does not weigh), ``w = s[picked] / (sum + 1e-6)``
(``norm_topk_prob``), times ``routed_scaling_factor``. No shared expert.

The two share ONE converter: operator, norms, attention and conv are the same
tensors; the expert family adds ``feed_forward.gate``,
``feed_forward.expert_bias`` and ``feed_forward.experts.{e}.w1/w3/w2``.
"""

from __future__ import annotations

from typing import List

import numpy as np

from ...config import InferenceConfig
from ...modules.moe import MoESpec
from ...modules.ssm import SSMSpec
from ...parallel.layers import place_q_weight, replicate_kv_weight
from ..contrib import _ident, _t, _vpad
from ..family import DecoderFamily, register_family
from ..model_base import mlp_stack, spec_from_config

CONV, FULL = "conv", "full_attention"
#: added to the sum the picked sigmoids are renormalised by (the published
#: ``Lfm2MoeSparseMoeBlock.route_tokens_to_experts``)
TOPK_NORM_EPS = 1e-6


class Lfm2InferenceConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["hidden_size", "num_attention_heads", "num_hidden_layers",
                "vocab_size", "layer_types", "conv_L_cache"]

    def get_text_config(self):
        return self


class Lfm2MoeInferenceConfig(Lfm2InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return super().get_required_attributes() + [
            "intermediate_size", "num_dense_layers", "num_experts",
            "num_experts_per_tok", "moe_intermediate_size"]


def _layer_types(config) -> List[str]:
    types = list(config.layer_types)
    if len(types) != config.num_hidden_layers or set(types) - {CONV, FULL}:
        raise ValueError(
            f"layer_types must name {config.num_hidden_layers} layers, each "
            f"{CONV!r} or {FULL!r}; got {types}")
    return types


def _common_spec(config, tp_degree, **kw):
    H = config.hidden_size
    return spec_from_config(
        config, tp_degree,
        rms_eps=float(getattr(config, "norm_eps", 1e-5)),
        qk_norm=True,
        ssm=SSMSpec(kind="shortconv", d_inner=H, num_heads=1, head_dim=H,
                    d_conv=int(config.conv_L_cache),
                    conv_bias=bool(getattr(config, "conv_bias", False))),
        ssm_pattern=tuple(t == CONV for t in _layer_types(config)),
        ssm_parallel=False,
        tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                         True)),
        **kw)


@register_family("lfm2")
class Lfm2Family(DecoderFamily):
    config_cls = Lfm2InferenceConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        inter = config.intermediate_size
        if getattr(config, "block_auto_adjust_ff_dim", False):
            inter = int(2 * inter / 3)
            mult = getattr(config, "block_ffn_dim_multiplier", None)
            if mult is not None:
                inter = int(mult * inter)
            mo = int(getattr(config, "block_multiple_of", 256))
            inter = mo * ((inter + mo - 1) // mo)
        return _common_spec(config, tp_degree, intermediate_size=inter)

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        """Interleaved layout (``decoder_param_specs``): "layers" = the dense
        layers' two norms + MLP, "moe_layers" = the expert layers' norms +
        routed block (one stack, "layers", where the model has one kind:
        ``model_base.mlp_stack``); "attn_layers" / "ssm_layers" = the
        temporal blocks in order of appearance."""
        g, D = spec.gqa, spec.head_dim
        H = spec.hidden_size
        pat = spec.resolved_ssm_pattern
        moe = spec.moe

        def get(n):
            if n in sd:
                return np.asarray(sd[n])
            raise KeyError(f"missing checkpoint tensor {n}")

        def stack(idx, fmt, tr=_ident):
            return np.stack([tr(get(fmt.format(i=i))) for i in idx])

        all_i = list(range(spec.num_layers))
        attn_i = [i for i in all_i if not pat[i]]
        conv_i = [i for i in all_i if pat[i]]
        n_dense = spec.num_layers - spec.num_moe_layers
        p = "model.layers.{i}."
        f = p + "feed_forward."

        def norms(idx):
            return {"input_norm": stack(idx, p + "operator_norm.weight"),
                    "post_norm": stack(idx, p + "ffn_norm.weight")}

        out = {
            "embed": _vpad(get("model.embed_tokens.weight"),
                           spec.padded_vocab),
            "final_norm": get("model.embedding_norm.weight"),
        }
        if n_dense:
            idx = all_i[:n_dense]
            out["layers"] = {
                **norms(idx),
                "gate_proj": stack(idx, f + "w1.weight", _t),
                "up_proj": stack(idx, f + "w3.weight", _t),
                "down_proj": stack(idx, f + "w2.weight", _t)}
        if spec.num_moe_layers:
            idx = all_i[n_dense:]

            def experts(name):
                return np.stack([np.stack([
                    _t(get((f + f"experts.{e}.{name}.weight").format(i=i)))
                    for e in range(moe.num_held)]) for i in idx])
            out[mlp_stack(spec, idx[0])[0]] = {
                **norms(idx),
                "router": stack(idx, f + "gate.weight", _t).astype(
                    np.float32),
                "router_bias": stack(idx, f + "expert_bias").astype(
                    np.float32),
                "expert_gate": experts("w1"),
                "expert_up": experts("w3"),
                "expert_down": experts("w2")}
        if attn_i:
            a = p + "self_attn."

            def kv(w):
                return replicate_kv_weight(_t(w), g, D, axis=-1)
            out["attn_layers"] = {
                "qkv_proj": np.concatenate([
                    stack(attn_i, a + "q_proj.weight",
                          lambda w: place_q_weight(_t(w), g, D, axis=-1)),
                    stack(attn_i, a + "k_proj.weight", kv),
                    stack(attn_i, a + "v_proj.weight", kv)], axis=-1),
                "o_proj": stack(attn_i, a + "out_proj.weight",
                                lambda w: place_q_weight(_t(w), g, D, axis=0)),
                "q_norm": stack(attn_i, a + "q_layernorm.weight"),
                "k_norm": stack(attn_i, a + "k_layernorm.weight"),
            }
        if conv_i:
            c = p + "conv."

            def rows(lo):
                # in_proj rows [B | C | x] (HF BCx chunk order)
                return lambda w: _t(np.asarray(w)[lo:lo + H])
            ssm_layers = {
                "sc_in_b": stack(conv_i, c + "in_proj.weight", rows(0)),
                "sc_in_c": stack(conv_i, c + "in_proj.weight", rows(H)),
                "sc_in_x": stack(conv_i, c + "in_proj.weight", rows(2 * H)),
                # Conv1d.weight (C, 1, K) -> (C, K)
                "sc_conv": stack(conv_i, c + "conv.weight",
                                 lambda w: np.asarray(w)[:, 0, :]),
                "sc_out": stack(conv_i, c + "out_proj.weight", _t),
            }
            if spec.ssm.conv_bias:
                ssm_layers["sc_conv_b"] = stack(conv_i, c + "conv.bias")
                ssm_layers["sc_out_b"] = stack(conv_i, c + "out_proj.bias")
                for key, lo in (("sc_in_b_b", 0), ("sc_in_c_b", H),
                                ("sc_in_x_b", 2 * H)):
                    ssm_layers[key] = stack(
                        conv_i, c + "in_proj.bias",
                        lambda b, lo=lo: np.asarray(b)[lo:lo + H])
            out["ssm_layers"] = ssm_layers
        if not spec.tie_word_embeddings:
            out["lm_head"] = np.ascontiguousarray(
                _vpad(get("lm_head.weight"), spec.padded_vocab).T)
        return out

    @classmethod
    def load_hf_model(cls, model_path: str):
        import transformers
        return transformers.Lfm2ForCausalLM.from_pretrained(model_path)


@register_family("lfm2_moe")
class Lfm2MoeFamily(Lfm2Family):
    config_cls = Lfm2MoeInferenceConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        tcfg = config.tpu_config
        tp = tp_degree if tp_degree is not None else tcfg.tp_degree
        if tp > 1 or getattr(tcfg, "ep_degree", 1) > 1:
            raise NotImplementedError(
                "lfm2_moe is served on one chip (tp_degree 1, ep_degree 1): "
                "no recurrent stack has run sharded (the conv channels' "
                "specs are sharded over the model axis and have never run "
                "so; PERF.md section 7)")
        if getattr(config, "block_auto_adjust_ff_dim", False):
            raise NotImplementedError(
                "lfm2_moe with block_auto_adjust_ff_dim: the published "
                "config has no such key and the dense layers' "
                "intermediate_size is used as it stands")
        if not getattr(config, "use_expert_bias", True):
            raise NotImplementedError(
                "lfm2_moe with use_expert_bias false: the published value "
                "is true and nothing else has been walked")
        n_dense = int(config.num_dense_layers)
        if not 0 <= n_dense < config.num_hidden_layers:
            raise ValueError(
                f"num_dense_layers {n_dense} leaves no expert layer of "
                f"{config.num_hidden_layers}; a stack without experts is "
                "the lfm2 family")
        return _common_spec(
            config, tp_degree,
            intermediate_size=int(config.intermediate_size),
            first_dense=n_dense,
            moe=MoESpec(
                num_experts=int(config.num_experts),
                top_k=int(config.num_experts_per_tok),
                intermediate_size=int(config.moe_intermediate_size),
                normalize_topk=bool(getattr(config, "norm_topk_prob", True)),
                topk_norm_eps=TOPK_NORM_EPS,
                routed_scaling=float(getattr(
                    config, "routed_scaling_factor", 1.0)),
                router_act="sigmoid",
                has_router_bias=True,
                router_bias_mode="select"))

    @classmethod
    def load_hf_model(cls, model_path: str):
        raise NotImplementedError(
            "the installed transformers has no Lfm2MoeForCausalLM; load the "
            "checkpoint's state dict and convert_hf_state_dict it")
