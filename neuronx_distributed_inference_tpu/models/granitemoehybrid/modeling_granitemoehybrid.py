"""Granite 4.0-H family, ``model_type`` ``granitemoehybrid`` (reference: HF
``modeling_granitemoehybrid.py``): an INTERLEAVED hybrid — each layer's
temporal block is a Mamba-2 mixer or GQA attention, as ``layer_types``
says — with the IBM multiplier set of :mod:`..granite` (embedding
multiplier, attention multiplier as the softmax scale, residual multiplier
on every block output, logits scaling), no positional embedding
(``position_embedding_type: "nope"``), a fused ``shared_mlp.input_linear``
([gate | up]) and a tied head.

Only the dense members are served (``num_local_experts == 0``,
granite-4.0-h-micro): the larger siblings add a routed expert block next to
the shared MLP, which this family refuses.

The recurrent state is a first-class cache beside the KV pool (per-sequence
slots, ``modules/ssm.py``), so the family serves through the paged path
(``PagedCausalLMApplication`` -> ``PagedEngineAdapter``) as well as the
contiguous one.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ...config import InferenceConfig
from ...modules.ssm import SSMSpec
from ...parallel.layers import place_q_weight, replicate_kv_weight
from ..family import DecoderFamily, register_family
from ..model_base import DecoderSpec, spec_from_config
from ..recurrent import FalconH1Family


class GraniteMoeHybridInferenceConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["hidden_size", "num_attention_heads", "num_hidden_layers",
                "num_key_value_heads", "vocab_size",
                "shared_intermediate_size", "layer_types", "mamba_n_heads",
                "mamba_d_head", "mamba_d_state"]

    def get_text_config(self):
        return self


@register_family("granitemoehybrid")
class GraniteMoeHybridFamily(DecoderFamily):
    config_cls = GraniteMoeHybridInferenceConfig

    @classmethod
    def build_spec(cls, config: InferenceConfig,
                   tp_degree: Optional[int] = None) -> DecoderSpec:
        if int(getattr(config, "num_local_experts", 0) or 0) > 0:
            raise NotImplementedError(
                "granitemoehybrid with num_local_experts > 0 routes experts "
                "next to the shared MLP; only the dense members "
                "(num_local_experts == 0, granite-4.0-h-micro) are served")
        if int(getattr(config, "mamba_n_groups", 1)) != 1:
            raise NotImplementedError(
                "granitemoehybrid normalises the gated mixer output over "
                "its whole width; modules/ssm.py normalises per B/C group, "
                "the form the nemotron_h family runs (8 groups): the two "
                "agree for mamba_n_groups == 1 only")
        layer_types = list(config.layer_types)
        if len(layer_types) != config.num_hidden_layers or \
                set(layer_types) - {"mamba", "attention"}:
            raise ValueError(
                f"layer_types must name {config.num_hidden_layers} layers, "
                f"each 'mamba' or 'attention'; got {layer_types}")
        pos = getattr(config, "position_embedding_type", "nope")
        if pos not in ("nope", "rope"):
            raise ValueError(f"position_embedding_type {pos!r}: expected "
                             "'nope' or 'rope'")
        H = config.hidden_size
        nh, hd = int(config.mamba_n_heads), int(config.mamba_d_head)
        d_inner = int(getattr(config, "mamba_expand", 2) * H)
        if d_inner != nh * hd:
            raise ValueError(f"mamba_expand * hidden_size = {d_inner} is not "
                             f"mamba_n_heads * mamba_d_head = {nh * hd}")
        return spec_from_config(
            config, tp_degree,
            intermediate_size=int(config.shared_intermediate_size),
            ssm=SSMSpec(
                kind="mamba2", d_inner=d_inner, num_heads=nh, head_dim=hd,
                d_state=int(config.mamba_d_state), n_groups=1,
                d_conv=int(getattr(config, "mamba_d_conv", 4)),
                # any chunking gives the published chunk's result: 64 keeps
                # the (heads, chunk, chunk) float32 intra-chunk tensors of
                # a full-batch pack small (34 MB a layer at 32 rows x 256)
                chunk_size=min(int(getattr(config, "mamba_chunk_size", 256)),
                               64),
                conv_bias=bool(getattr(config, "mamba_conv_bias", True)),
                # y = w * rmsnorm(y * silu(z)): gate first, then the norm
                gated_norm=True, norm_before_gate=False,
                norm_eps=float(getattr(config, "rms_norm_eps", 1e-5)),
            ),
            ssm_pattern=tuple(t == "mamba" for t in layer_types),
            ssm_parallel=False,
            no_rope=(pos == "nope"),
            embed_scale=float(getattr(config, "embedding_multiplier", 1.0)),
            attn_scale=float(getattr(config, "attention_multiplier",
                                     None) or 0) or None,
            residual_multiplier=float(getattr(config, "residual_multiplier",
                                              1.0)),
            logits_divide=float(getattr(config, "logits_scaling", 0) or 0)
            or None,
            qkv_bias=bool(getattr(config, "attention_bias", False)),
            o_bias=bool(getattr(config, "attention_bias", False)),
            tie_word_embeddings=bool(getattr(config, "tie_word_embeddings",
                                             True)),
        )

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        """Interleaved layout (``decoder_param_specs``): "layers" = every
        layer's norms + shared MLP; "attn_layers" / "ssm_layers" = the
        temporal blocks, stacked in order of appearance. The mixer's
        ``in_proj`` / ``conv1d`` are split by destination exactly as
        Falcon-H1's (same [gate | x | B | C | dt] row order)."""
        g, D = spec.gqa, spec.head_dim
        pat = spec.resolved_ssm_pattern
        inter = spec.intermediate_size

        def get(n):
            if n in sd:
                return np.asarray(sd[n])
            raise KeyError(f"missing checkpoint tensor {n}")

        def t(w):
            return np.ascontiguousarray(np.asarray(w).T)

        def stack_over(idx):
            return lambda fmt, tr: np.stack(
                [tr(get(fmt.format(i=i))) for i in idx])

        all_i = list(range(spec.num_layers))
        attn_i = [i for i in all_i if not pat[i]]
        ssm_i = [i for i in all_i if pat[i]]
        every, attn, ssm = (stack_over(all_i), stack_over(attn_i),
                            stack_over(ssm_i))
        p = "model.layers.{i}."
        layers = {
            "input_norm": every(p + "input_layernorm.weight", np.asarray),
            "post_norm": every(p + "post_attention_layernorm.weight",
                               np.asarray),
            # input_linear rows are [gate | up]
            "gate_proj": every(p + "shared_mlp.input_linear.weight",
                               lambda w: t(np.asarray(w)[:inter])),
            "up_proj": every(p + "shared_mlp.input_linear.weight",
                             lambda w: t(np.asarray(w)[inter:])),
            "down_proj": every(p + "shared_mlp.output_linear.weight", t),
        }
        out = {"embed": get("model.embed_tokens.weight"), "layers": layers,
               "final_norm": get("model.norm.weight")}
        if out["embed"].shape[0] < spec.padded_vocab:
            out["embed"] = np.pad(
                out["embed"],
                [(0, spec.padded_vocab - out["embed"].shape[0]), (0, 0)])
        if attn_i:
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
            }
            if spec.qkv_bias:
                out["attn_layers"]["qkv_bias"] = np.concatenate([
                    attn(a + "q_proj.bias",
                         lambda b: place_q_weight(b, g, D)),
                    attn(a + "k_proj.bias",
                         lambda b: replicate_kv_weight(b, g, D)),
                    attn(a + "v_proj.bias",
                         lambda b: replicate_kv_weight(b, g, D))], axis=-1)
                out["attn_layers"]["o_bias"] = attn(a + "o_proj.bias",
                                                    np.asarray)
        if ssm_i:
            out["ssm_layers"] = FalconH1Family.convert_extra_layer_weights(
                get, ssm, spec)
        if not spec.tie_word_embeddings:
            lm = get("lm_head.weight")
            lm = np.pad(lm, [(0, spec.padded_vocab - lm.shape[0]), (0, 0)])
            out["lm_head"] = t(lm)
        return out

    @classmethod
    def load_hf_model(cls, model_path: str):
        import transformers
        return transformers.GraniteMoeHybridForCausalLM.from_pretrained(
            model_path)
