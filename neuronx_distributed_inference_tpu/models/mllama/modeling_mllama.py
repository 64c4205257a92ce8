"""MLlama (Llama-3.2 Vision) family — cross-attention decoder + multimodal
KV manager (reference: models/mllama/ — modeling_mllama.py cross-attention
decoder layers, modules/kvcache/multimodal_kv_cache_manager.py,
model_wrapper_mllama.py; 3380 LoC).

TPU design:
  * The text stack interleaves standard self-attention layers (the shared
    DecoderSpec machinery, scanned per contiguous segment via
    model_base.run_layer_slice) with tanh-gated cross-attention layers that
    attend to vision states.
  * Cross-attention K/V is the multimodal KV cache: computed ONCE per
    request from the vision states (``compute_cross_kv``) and fed read-only
    into every prefill/decode step — the analog of the reference's
    MultimodalKVCacheManager holding cross-attention caches outside the
    autoregressive cache.
  * ``full_text_row_masked_out_mask`` semantics preserved: a text row whose
    cross-attention mask is fully off attends uniformly (its additive mask
    zeroes out) and its gated-MLP delta is suppressed
    (HF _prepare_cross_attention_mask).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...utils.host_loop import greedy_host_loop

from ...config import InferenceConfig, TpuConfig
from ...modules.kv_cache import KVCacheSpec, cache_len_of, init_cache
from ...ops import attention as attn_ops
from ...ops import sampling as sampling_ops
from ...ops.normalization import rms_norm
from ...parallel.layers import place_q_weight, replicate_kv_weight
from ...utils import checkpoint as ckpt
from ..family import DecoderFamily, register_family
from ..model_base import (DecoderSpec, _embed, _lm_head, attn_inputs,
                          run_layer_slice, spec_from_config)


@dataclass(frozen=True)
class MllamaSpec:
    """Layer interleave plan: walk ``segments`` = [(n_self, has_cross), ...]
    over the total stack (cross layer indices from HF
    ``cross_attention_layers``)."""
    segments: Tuple[Tuple[int, bool], ...]
    num_self: int
    num_cross: int


def build_mllama_plan(total_layers: int, cross_layers: Tuple[int, ...]
                      ) -> MllamaSpec:
    cross = set(int(c) for c in cross_layers)
    segments: List[Tuple[int, bool]] = []
    run = 0
    for i in range(total_layers):
        if i in cross:
            segments.append((run, True))
            run = 0
        else:
            run += 1
    if run:
        segments.append((run, False))
    return MllamaSpec(tuple(segments), total_layers - len(cross), len(cross))


class MllamaTextConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["hidden_size", "num_attention_heads", "num_hidden_layers",
                "num_key_value_heads", "vocab_size", "intermediate_size",
                "cross_attention_layers"]


@register_family("mllama_text")
class MllamaTextFamily(DecoderFamily):
    """Self-attention side of the stack (llama-shaped); cross layers are
    converted separately by ``convert_cross_layers``."""
    config_cls = MllamaTextConfig

    @classmethod
    def build_spec(cls, config, tp_degree=None):
        from ..model_base import pad_vocab
        plan = build_mllama_plan(config.num_hidden_layers,
                                 tuple(config.cross_attention_layers))
        tcfg = config.tpu_config
        tp = tp_degree if tp_degree is not None else tcfg.tp_degree
        # HF mllama embeds vocab_size + 8 special image tokens; the embed
        # table (and input ids) cover them while lm_head stays vocab_size
        return spec_from_config(
            config, tp_degree, num_layers=plan.num_self,
            padded_vocab=pad_vocab(config.vocab_size + 8, tp))

    @classmethod
    def convert_hf_state_dict(cls, sd, spec):
        # remap the non-contiguous self-layer indices onto 0..num_self-1,
        # then run the standard llama conversion
        cross = set()
        i = 0
        remapped = dict(sd)
        # discover cross layers by key shape: cross layers have cross_attn.*
        total = 0
        for k in sd:
            if ".layers." in k:
                total = max(total, int(k.split(".layers.")[1].split(".")[0]) + 1)
            if ".cross_attn." in k:
                cross.add(int(k.split(".layers.")[1].split(".")[0]))
        self_ids = [i for i in range(total) if i not in cross]
        out = {}
        for k, v in sd.items():
            if ".layers." in k:
                li = int(k.split(".layers.")[1].split(".")[0])
                if li in cross:
                    continue
                k = k.replace(f".layers.{li}.",
                              f".layers.{self_ids.index(li)}.")
            out[k] = v
        return super().convert_hf_state_dict(out, spec)


def convert_cross_layers(sd: Dict[str, np.ndarray], spec: DecoderSpec,
                         cross_ids: List[int], prefix: str = "model"
                         ) -> Dict[str, np.ndarray]:
    g, D = spec.gqa, spec.head_dim

    def get(n):
        return np.asarray(sd[n])

    def q_t(w):
        return place_q_weight(np.ascontiguousarray(w.T), g, D, axis=-1)

    def kv_t(w):
        return replicate_kv_weight(np.ascontiguousarray(w.T), g, D, axis=-1)

    def o_t(w):
        return place_q_weight(np.ascontiguousarray(w.T), g, D, axis=0)

    def t(w):
        return np.ascontiguousarray(w.T)

    def stack(fmt, tr):
        return np.stack([tr(get(fmt.format(i=i))) for i in cross_ids])

    p = prefix
    return {
        "input_norm": stack(p + ".layers.{i}.input_layernorm.weight",
                            np.asarray),
        "q_proj": stack(p + ".layers.{i}.cross_attn.q_proj.weight", q_t),
        "k_proj": stack(p + ".layers.{i}.cross_attn.k_proj.weight", kv_t),
        "v_proj": stack(p + ".layers.{i}.cross_attn.v_proj.weight", kv_t),
        "o_proj": stack(p + ".layers.{i}.cross_attn.o_proj.weight", o_t),
        "q_norm": stack(p + ".layers.{i}.cross_attn.q_norm.weight",
                        np.asarray),
        "k_norm": stack(p + ".layers.{i}.cross_attn.k_norm.weight",
                        np.asarray),
        "attn_gate": stack(p + ".layers.{i}.cross_attn_attn_gate",
                           np.asarray),
        "mlp_gate": stack(p + ".layers.{i}.cross_attn_mlp_gate", np.asarray),
        "post_norm": stack(p + ".layers.{i}.post_attention_layernorm.weight",
                           np.asarray),
        "gate_proj": stack(p + ".layers.{i}.mlp.gate_proj.weight", t),
        "up_proj": stack(p + ".layers.{i}.mlp.up_proj.weight", t),
        "down_proj": stack(p + ".layers.{i}.mlp.down_proj.weight", t),
    }


def compute_cross_kv(spec: DecoderSpec, cross_params, vision_states):
    """The multimodal KV cache fill (reference:
    multimodal_kv_cache_manager.py): per cross layer,
    k = k_norm(k_proj(vision)), v = v_proj(vision).
    vision_states (B, S_vis, H_text) -> k/v (Lc, B, S_vis, Hkv, D)."""
    b, s, _ = vision_states.shape
    g = spec.gqa

    def one(lw):
        k = (vision_states @ lw["k_proj"]).reshape(b, s, g.num_kv_heads,
                                                   spec.head_dim)
        k = rms_norm(k, lw["k_norm"], spec.rms_eps)
        v = (vision_states @ lw["v_proj"]).reshape(b, s, g.num_kv_heads,
                                                   spec.head_dim)
        return k, v

    ks, vs = jax.lax.map(one, cross_params)
    return {"k": ks, "v": vs}


def _cross_block(spec: DecoderSpec, hidden, lw, ck, cv, cross_mask):
    """One tanh-gated cross-attention decoder layer (HF
    MllamaCrossAttentionDecoderLayer semantics).

    hidden (B, T, H); ck/cv (B, S_vis, Hkv, D); cross_mask (B, T, S_vis)
    bool. HF row semantics (_prepare_cross_attention_mask): a text row whose
    mask is fully off attends ALL keys uniformly (its additive mask zeroes
    out), and only its gated-MLP delta is suppressed."""
    b, t, _ = hidden.shape
    g = spec.gqa
    row_any = cross_mask.any(axis=-1, keepdims=True)        # (B, T, 1)
    eff_mask = jnp.where(row_any, cross_mask, True)
    r = rms_norm(hidden, lw["input_norm"], spec.rms_eps)
    q = (r @ lw["q_proj"]).reshape(b, t, g.num_q_heads, spec.head_dim)
    q = rms_norm(q, lw["q_norm"], spec.rms_eps)
    a = attn_ops.mha(q, ck, cv, eff_mask, spec.scale)
    a = a.reshape(b, t, -1) @ lw["o_proj"]
    hidden = hidden + jnp.tanh(lw["attn_gate"]) * a
    r = rms_norm(hidden, lw["post_norm"], spec.rms_eps)
    m = (jax.nn.silu(r @ lw["gate_proj"]) * (r @ lw["up_proj"])) \
        @ lw["down_proj"]
    m = m * row_any.astype(m.dtype)
    return hidden + jnp.tanh(lw["mlp_gate"]) * m


def mllama_forward(spec: DecoderSpec, mspec: MllamaSpec, tcfg: TpuConfig,
                   params, cache, cross_kv, input_ids, position_ids, seq_ids,
                   seq_lens, cross_mask, sampling_params, rng,
                   phase: str):
    """One prefill or decode step through the interleaved stack.

    phase "prefill": causal in-window self attention; cross_mask covers the
    padded window. phase "decode": T=1 over the self cache."""
    if phase == "prefill":
        ai = attn_inputs(spec, position_ids,
                         lambda w, c=0: attn_ops.prefill_causal_mask(
                             input_ids.shape[1], position_ids, window=w, chunk=c))
    else:
        cache_len = cache_len_of(cache)
        ai = attn_inputs(spec, position_ids,
                         lambda w, c=0: attn_ops.decode_mask(position_ids,
                                                        cache_len, window=w, chunk=c))
    hidden = _embed(spec, params, input_ids)
    si = ci = 0
    empty_local = jnp.zeros((0,), bool)
    for n_self, has_cross in mspec.segments:
        if n_self:
            seg = jax.tree.map(lambda a: a[si:si + n_self], params["layers"])
            hidden, cache, _ = run_layer_slice(
                spec, seg, cache, hidden, ai, cache_offset=si,
                is_local=jnp.zeros((n_self,), bool), rep={}, mlp_kind=None,
                seq_ids=seq_ids, positions=position_ids, phase=phase,
                identity_seq_ids=not tcfg.is_continuous_batching,
                arange_positions=(phase == "prefill"))
            si += n_self
        if has_cross:
            lw = jax.tree.map(lambda a: a[ci], params["cross_layers"])
            hidden = _cross_block(spec, hidden, lw, cross_kv["k"][ci],
                                  cross_kv["v"][ci], cross_mask)
            ci += 1
    out: Dict[str, Any] = {"cache": cache}
    if phase == "prefill":
        idx = jnp.maximum(seq_lens - 1, 0)
        last_h = jnp.take_along_axis(hidden, idx[:, None, None].astype(jnp.int32),
                                     axis=1)
        logits = _lm_head(spec, params, last_h)[:, 0, :]
        if tcfg.output_logits:
            out["logits"] = _lm_head(spec, params,
                                     hidden)[..., :spec.vocab_size]
    else:
        full = _lm_head(spec, params, hidden)
        logits = full[:, -1, :]
        if tcfg.output_logits:
            out["logits"] = full[..., :spec.vocab_size]
    out["tokens"] = sampling_ops.sample(
        logits, tcfg.on_device_sampling_config, sampling_params, rng)
    return out


class MllamaInferenceConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["text_config", "vision_config", "image_token_index"]


class MllamaApplication:
    """Cross-attention text application (reference: NeuronMllamaForCausalLM +
    its dedicated ModelWrapper, model_wrapper_mllama.py). Vision states come
    either from the vision tower or directly (``vision_states=`` argument —
    the reference supports the same split via its two builders)."""

    def __init__(self, model_path: Optional[str], config, mesh=None):
        from ...parallel.mesh import mesh_from_config
        self.config = config
        self.tpu_config: TpuConfig = config.tpu_config
        self.model_path = model_path
        tc = dict(config.text_config) if hasattr(config, "text_config") \
            else {}
        self.text_config = MllamaTextConfig(self.tpu_config, **tc)
        self.mesh = mesh or mesh_from_config(self.tpu_config)
        mp = self.mesh.shape["tp"] * self.mesh.shape["ep"]
        self.spec = MllamaTextFamily.build_spec(self.text_config, mp)
        self.plan = build_mllama_plan(
            self.text_config.num_hidden_layers,
            tuple(self.text_config.cross_attention_layers))
        self.params = None
        self.cache = None
        self._rng = jax.random.PRNGKey(self.tpu_config.seed)
        self._cross_fn = jax.jit(partial(compute_cross_kv, self.spec))
        self._steps: Dict[str, Any] = {}

    def load_weights(self):
        sd = ckpt.load_state_dict(self.model_path)
        text_sd = {}
        for k, v in sd.items():
            if k.endswith("lm_head.weight"):
                text_sd["lm_head.weight"] = v
            for pre in ("model.language_model.", "language_model.model.",
                        "language_model."):
                if k.startswith(pre):
                    text_sd["model." + k[len(pre):]] = v
                    break
            else:
                if k.startswith("model.layers."):
                    text_sd[k] = v
                elif k.startswith("model.") and ".layers." not in k:
                    text_sd[k] = v
        from .. import model_base
        host = model_base.fuse_qkv_host(
            MllamaTextFamily.convert_hf_state_dict(text_sd, self.spec))
        cross_ids = sorted(
            int(c) for c in self.text_config.cross_attention_layers)
        host["cross_layers"] = convert_cross_layers(text_sd, self.spec,
                                                    cross_ids)
        self.params = jax.tree.map(jnp.asarray, host)
        # vision tower + projector, when the checkpoint ships them
        vis_prefix = next((p for p in ("model.vision_model", "vision_model")
                           if any(k.startswith(p + ".") for k in sd)), None)
        if vis_prefix is not None and hasattr(self.config, "vision_config"):
            self.vis_spec = mllama_vision_spec(dict(self.config.vision_config))
            self.vision_params = jax.tree.map(
                jnp.asarray,
                convert_mllama_vision(sd, self.vis_spec, vis_prefix))
            proj = next(p for p in ("model.multi_modal_projector",
                                    "multi_modal_projector")
                        if f"{p}.weight" in sd)
            self.projector_w = jnp.asarray(
                np.ascontiguousarray(np.asarray(sd[f"{proj}.weight"],
                                                np.float32).T))
            self.projector_b = jnp.asarray(
                np.asarray(sd[f"{proj}.bias"], np.float32))
            self._vis_fn = jax.jit(partial(mllama_vision_forward,
                                           self.vis_spec))
        return self

    def encode_images(self, pixel_values: np.ndarray,
                      aspect_ratio_ids: np.ndarray,
                      aspect_ratio_mask: np.ndarray) -> jnp.ndarray:
        """HF-processor-layout pixels -> projected cross-attention states
        (B, M*T*(P+1), H_text) (reference: vision builder of the mllama
        wrapper + multi_modal_projector)."""
        feats = self._vis_fn(self.vision_params,
                             jnp.asarray(pixel_values, jnp.float32),
                             jnp.asarray(aspect_ratio_ids),
                             jnp.asarray(aspect_ratio_mask))
        b, m, t, p1, _ = feats.shape
        proj = feats @ self.projector_w + self.projector_b
        return proj.reshape(b, m * t * p1, -1)

    def generate_from_images(self, input_ids: np.ndarray,
                             pixel_values: np.ndarray,
                             aspect_ratio_ids: np.ndarray,
                             aspect_ratio_mask: np.ndarray,
                             cross_attention_mask: Optional[np.ndarray] = None,
                             **kw) -> Dict[str, Any]:
        """End-to-end image->text: cross_attention_mask arrives in the HF
        processor layout (B, S_text, M, T) and is expanded per patch
        (reference: _prepare_cross_attention_mask)."""
        states = self.encode_images(pixel_values, aspect_ratio_ids,
                                    aspect_ratio_mask)
        if cross_attention_mask is not None:
            cm = np.asarray(cross_attention_mask)
            b, s, m, t = cm.shape
            p1 = (self.vis_spec["image_size"] //
                  self.vis_spec["patch_size"]) ** 2 + 1
            cross_attention_mask = np.repeat(
                cm.reshape(b, s, m * t), p1, axis=2).astype(bool)
        return self.generate(input_ids, np.asarray(states),
                             cross_attention_mask=cross_attention_mask, **kw)

    def init_cache(self):
        cfg = self.tpu_config
        kvspec = KVCacheSpec(
            num_layers=self.spec.num_layers, batch_size=cfg.kv_cache_batch_size,
            max_seq_len=cfg.seq_len, num_kv_heads=self.spec.gqa.num_kv_heads,
            head_dim=self.spec.head_dim, dtype=self.spec.kv_dtype)
        self.cache = init_cache(kvspec, self.mesh)
        return self

    def _step(self, phase):
        if phase not in self._steps:
            self._steps[phase] = jax.jit(
                partial(mllama_forward, self.spec, self.plan,
                        self.tpu_config, phase=phase), donate_argnums=(1,))
        return self._steps[phase]

    def generate(self, input_ids: np.ndarray, vision_states: np.ndarray,
                 cross_attention_mask: Optional[np.ndarray] = None,
                 attention_mask: Optional[np.ndarray] = None,
                 max_new_tokens: int = 16,
                 eos_token_id: Optional[int] = None) -> Dict[str, Any]:
        """vision_states (B, S_vis, H_text): flattened projected vision
        hidden states; cross_attention_mask (B, S_text, S_vis) bool (True =
        attend) — defaults to all-on."""
        input_ids = np.asarray(input_ids)
        b, s = input_ids.shape
        if attention_mask is None:
            attention_mask = np.ones_like(input_ids)
        seq_lens = attention_mask.astype(np.int32).sum(axis=1)
        if self.cache is None:
            self.init_cache()
        s_vis = vision_states.shape[1]
        if cross_attention_mask is None:
            cross_attention_mask = np.ones((b, s, s_vis), bool)
        cross_kv = self._cross_fn(params_cross(self.params),
                                  jnp.asarray(vision_states,
                                              self.spec.dtype))

        self._rng, k1 = jax.random.split(self._rng)
        pos = np.broadcast_to(np.arange(s, dtype=np.int32), (b, s))
        out = self._step("prefill")(
            self.params, self.cache, cross_kv, jnp.asarray(input_ids),
            jnp.asarray(pos), jnp.arange(b, dtype=jnp.int32),
            jnp.asarray(seq_lens), jnp.asarray(cross_attention_mask),
            None, k1)
        self.cache = out["cache"]
        tokens = [np.asarray(out["tokens"]).reshape(b, 1)]
        logits = [np.asarray(out["logits"])] if "logits" in out else []

        # decode: the new token reuses the LAST text row's cross mask (HF
        # extends the mask with the final row during generation)
        dec_mask = jnp.asarray(cross_attention_mask[:, -1:, :])
        eos_ids = (None if eos_token_id is None
                   else np.atleast_1d(np.asarray(eos_token_id)))
        state = {"pos": seq_lens.astype(np.int32)}
        rows = jnp.arange(b, dtype=jnp.int32)

        def step(last):
            self._rng, k1 = jax.random.split(self._rng)
            o = self._step("decode")(
                self.params, self.cache, cross_kv, last[:, None],
                jnp.asarray(state["pos"][:, None]), rows, None, dec_mask,
                None, k1)
            self.cache = o["cache"]
            state["pos"] = state["pos"] + 1
            if "logits" in o:
                logits.append(o["logits"])   # device array; fetched below
            return o["tokens"].reshape(b).astype(jnp.int32)

        # shared chunked host loop (utils/host_loop.py): no per-token fetch
        first = jnp.asarray(tokens[0].reshape(b).astype(np.int32))
        gen = greedy_host_loop(step, first, max_new_tokens, eos_ids=eos_ids)
        res = {"sequences": np.concatenate([input_ids, gen], axis=1),
               "generated": gen}
        if logits:
            res["logits"] = [np.asarray(lg) for lg in logits]
        return res

    def reset(self):
        self.init_cache()
        return self


def params_cross(params):
    return params["cross_layers"]


# ---------------------------------------------------------------------------
# Vision tower (reference: models/mllama/modeling_mllama_vision.py +
# encoder_utils.py — tiled ViT with gated positional embeddings, local +
# gated-global encoders, intermediate-layer feature concat) and the
# aspect-ratio / image-transform host pipeline (reference:
# models/mllama/image_transform.py, aspect_ratio_utils.py).
# ---------------------------------------------------------------------------

from ...ops.normalization import layer_norm as _ln


def mllama_vision_spec(vc: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "image_size": int(vc["image_size"]),
        "patch_size": int(vc["patch_size"]),
        "hidden": int(vc["hidden_size"]),
        "heads": int(vc["attention_heads"]),
        "layers": int(vc["num_hidden_layers"]),
        "global_layers": int(vc["num_global_layers"]),
        "max_tiles": int(vc["max_num_tiles"]),
        "norm_eps": float(vc.get("norm_eps", 1e-5)),
        "intermediate_indices": tuple(
            int(i) for i in vc["intermediate_layers_indices"]),
        "act": vc.get("hidden_act", "gelu"),
    }


def _vision_mha(h, lw, nh, mask_add):
    b, n, dim = h.shape
    hd = dim // nh
    q = (h @ lw["q"]).reshape(b, n, nh, hd)
    k = (h @ lw["k"]).reshape(b, n, nh, hd)
    v = (h @ lw["v"]).reshape(b, n, nh, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (hd ** -0.5)
    if mask_add is not None:
        s = s + mask_add
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return (a.reshape(b, n, dim).astype(h.dtype)) @ lw["o"]


def _vision_layer(vs, h, lw, mask_add, gated):
    eps = vs["norm_eps"]
    # HF ACT2FN["gelu"] is the exact erf GELU
    act = (partial(jax.nn.gelu, approximate=False) if vs["act"] == "gelu"
           else partial(jax.nn.gelu, approximate=True))
    r = _ln(h, lw["ln1_w"], lw["ln1_b"], eps)
    a = _vision_mha(r, lw, vs["heads"], mask_add)
    if gated:
        a = jnp.tanh(lw["gate_attn"]) * a
    h = h + a
    r = _ln(h, lw["ln2_w"], lw["ln2_b"], eps)
    m = act((r @ lw["fc1"] + lw["fc1_b"]).astype(jnp.float32)).astype(h.dtype)
    m = m @ lw["fc2"] + lw["fc2_b"]
    if gated:
        m = jnp.tanh(lw["gate_ffn"]) * m
    return h + m


def mllama_vision_forward(vs: Dict[str, Any], params: Dict[str, Any],
                          pixel_values: jnp.ndarray,
                          aspect_ratio_ids: jnp.ndarray,
                          aspect_ratio_mask: jnp.ndarray) -> jnp.ndarray:
    """HF MllamaVisionModel.forward parity. pixel_values
    (B, M, T, C, H, W); aspect_ratio_ids (B, M); aspect_ratio_mask
    (B, M, T). Returns (B, M, T, P+1, hidden*(1+len(intermediate)))."""
    b, m, t, c, hh, ww = pixel_values.shape
    p = vs["patch_size"]
    dim = vs["hidden"]
    grid = hh // p
    npatch = grid * grid
    x = pixel_values.reshape(b * m * t, c, grid, p, grid, p)
    x = jnp.transpose(x, (0, 2, 4, 1, 3, 5)).reshape(b * m * t, npatch, -1)
    x = x @ params["patch_proj"]                      # (BMT, P, dim)

    ar = aspect_ratio_ids.reshape(b * m)
    # pre-tile positional embedding (gated)
    pre = params["pre_tile_embed"][ar].reshape(b * m, vs["max_tiles"], 1, dim)
    x = x.reshape(b * m, t, npatch, dim) + jnp.tanh(params["pre_tile_gate"]) \
        * pre[:, :t]
    # cls token FIRST (HF cat([class, patches]))
    cls = jnp.broadcast_to(params["class_embedding"][None, None, None, :],
                           (b * m, t, 1, dim))
    x = jnp.concatenate([cls, x.reshape(b * m, t, npatch, dim)], axis=2)
    np1 = npatch + 1
    # gated positional embedding: (1-tanh(g))*pos + tanh(g)*tile_pos[ar]
    g = jnp.tanh(params["pos_gate"])
    x = x + (1.0 - g) * params["pos_embed"][None, None]
    tile_pos = params["tile_pos_embed"][ar].reshape(
        b * m, vs["max_tiles"], np1, dim)
    x = x + g * tile_pos[:, :t]
    x = _ln(x, params["ln_pre_w"], params["ln_pre_b"], 1e-5)

    # pad patches to a multiple of 8 (HF does; the zero-content pad rows ARE
    # attendable under HF's mask semantics, so parity requires the pad)
    pad = (8 - np1 % 8) % 8
    x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    L = np1 + pad
    # additive mask (HF _prepare_aspect_ratio_attention_mask): mark pad
    # TILES and pad PATCH rows, mask only pairs where both sides are pad
    mask = jnp.broadcast_to(
        aspect_ratio_mask.reshape(b * m, t, 1).astype(jnp.float32),
        (b * m, t, L))
    if pad:
        mask = mask.at[:, :, -pad:].set(0.0)
    inv = (1.0 - mask).reshape(b * m, t * L, 1)
    mask_add = (inv @ jnp.swapaxes(inv, 1, 2)) * jnp.finfo(jnp.float32).min
    mask_add = mask_add[:, None]                      # (BM, 1, TL, TL)

    h = x.reshape(b * m, t * L, dim)
    inter = []
    for i in range(vs["layers"]):
        if i in vs["intermediate_indices"]:
            inter.append(h)
        lw = jax.tree.map(lambda a, i=i: a[i], params["layers"])
        h = _vision_layer(vs, h, lw, mask_add, gated=False)
    if vs["layers"] in vs["intermediate_indices"]:
        inter.append(h)
    h = _ln(h, params["ln_post_w"], params["ln_post_b"], 1e-5)

    # global encoder with post-tile embedding
    post = params["post_tile_embed"][ar].reshape(
        b * m, vs["max_tiles"], 1, dim)
    h = h.reshape(b * m, t, L, dim) + jnp.tanh(params["post_tile_gate"]) \
        * post[:, :t]
    h = h.reshape(b * m, t * L, dim)
    for i in range(vs["global_layers"]):
        lw = jax.tree.map(lambda a, i=i: a[i], params["global_layers"])
        h = _vision_layer(vs, h, lw, mask_add, gated=True)

    h = h.reshape(b * m, t, L, dim)[:, :, :np1]
    inter = jnp.stack([y.reshape(b * m, t, L, dim)[:, :, :np1]
                       for y in inter], axis=-1)
    inter = inter.reshape(b * m, t, np1, -1)
    out = jnp.concatenate([h, inter], axis=-1)
    return out.reshape(b, m, t, np1, -1)


def convert_mllama_vision(sd: Dict[str, np.ndarray], vs: Dict[str, Any],
                          prefix: str = "vision_model") -> Dict[str, Any]:
    def get(n):
        return np.asarray(sd[f"{prefix}.{n}"], np.float32)

    def t(w):
        return np.ascontiguousarray(np.asarray(w, np.float32).T)

    def enc_layers(base, n, gated):
        def lw(i):
            b = f"{base}.layers.{i}"
            d = {
                "ln1_w": get(f"{b}.input_layernorm.weight"),
                "ln1_b": get(f"{b}.input_layernorm.bias"),
                "ln2_w": get(f"{b}.post_attention_layernorm.weight"),
                "ln2_b": get(f"{b}.post_attention_layernorm.bias"),
                "q": t(get(f"{b}.self_attn.q_proj.weight")),
                "k": t(get(f"{b}.self_attn.k_proj.weight")),
                "v": t(get(f"{b}.self_attn.v_proj.weight")),
                "o": t(get(f"{b}.self_attn.o_proj.weight")),
                "fc1": t(get(f"{b}.mlp.fc1.weight")),
                "fc1_b": get(f"{b}.mlp.fc1.bias"),
                "fc2": t(get(f"{b}.mlp.fc2.weight")),
                "fc2_b": get(f"{b}.mlp.fc2.bias"),
            }
            if gated:
                d["gate_attn"] = get(f"{b}.gate_attn").reshape(())
                d["gate_ffn"] = get(f"{b}.gate_ffn").reshape(())
            return d

        ls = [lw(i) for i in range(n)]
        return {k: np.stack([d[k] for d in ls]) for k in ls[0]}

    return {
        "patch_proj": t(get("patch_embedding.weight").reshape(
            vs["hidden"], -1)),
        "class_embedding": get("class_embedding"),
        "pos_embed": get("gated_positional_embedding.embedding"),
        "pos_gate": get("gated_positional_embedding.gate").reshape(()),
        "tile_pos_embed": get("gated_positional_embedding.tile_embedding.weight"),
        "pre_tile_embed": get("pre_tile_positional_embedding.embedding.weight"),
        "pre_tile_gate": get("pre_tile_positional_embedding.gate").reshape(()),
        "post_tile_embed": get("post_tile_positional_embedding.embedding.weight"),
        "post_tile_gate": get("post_tile_positional_embedding.gate").reshape(()),
        "ln_pre_w": get("layernorm_pre.weight"),
        "ln_pre_b": get("layernorm_pre.bias"),
        "ln_post_w": get("layernorm_post.weight"),
        "ln_post_b": get("layernorm_post.bias"),
        "layers": enc_layers("transformer", vs["layers"], False),
        "global_layers": enc_layers("global_transformer",
                                    vs["global_layers"], True),
    }


# ---------------------------------------------------------------------------
# Host-side aspect-ratio / image-transform pipeline (reference:
# models/mllama/aspect_ratio_utils.py + image_transform.py): choose a tile
# arrangement for an arbitrary image, resize + pad onto the tile canvas,
# split into tiles, and produce aspect_ratio_ids/mask for the tower.
# ---------------------------------------------------------------------------

def supported_aspect_ratios(max_num_tiles: int):
    """All (w, h) tile arrangements with w*h <= max_num_tiles, in HF
    processor order (width-major)."""
    out = []
    for w in range(1, max_num_tiles + 1):
        for h in range(1, max_num_tiles + 1):
            if w * h <= max_num_tiles:
                out.append((w, h))
    return out


def choose_canvas(img_h: int, img_w: int, tile_size: int,
                  max_num_tiles: int):
    """Pick the (w_tiles, h_tiles) canvas: smallest upscale that fits, else
    the largest-area downscale (HF get_optimal_tiled_canvas semantics)."""
    best_up = None
    best_down = None
    for (tw, th) in supported_aspect_ratios(max_num_tiles):
        cw, ch = tw * tile_size, th * tile_size
        scale = min(cw / img_w, ch / img_h)
        if scale >= 1:
            key = (scale, cw * ch)
            if best_up is None or key < best_up[0]:
                best_up = (key, (tw, th))
        else:
            # largest scale first, then SMALLEST canvas area (HF
            # get_optimal_tiled_canvas tie-break)
            key = (-scale, cw * ch)
            if best_down is None or key < best_down[0]:
                best_down = (key, (tw, th))
    return (best_up or best_down)[1]


def image_to_tiles(img: np.ndarray, tile_size: int, max_num_tiles: int):
    """img (C, H, W) float -> (tiles (T, C, tile, tile), aspect_ratio_id,
    num_tiles). Bilinear resize preserving aspect, zero-pad, split."""
    c, h, w = img.shape
    tw, th = choose_canvas(h, w, tile_size, max_num_tiles)
    cw, ch = tw * tile_size, th * tile_size
    scale = min(cw / w, ch / h)
    nh, nw = max(1, int(round(h * scale))), max(1, int(round(w * scale)))
    # bilinear resize via jax.image (host-side, tiny)
    resized = np.asarray(jax.image.resize(jnp.asarray(img, jnp.float32),
                                          (c, nh, nw), "bilinear"))
    canvas = np.zeros((c, ch, cw), np.float32)
    canvas[:, :nh, :nw] = resized
    tiles = canvas.reshape(c, th, tile_size, tw, tile_size)
    tiles = np.transpose(tiles, (1, 3, 0, 2, 4)).reshape(
        th * tw, c, tile_size, tile_size)
    ar_id = supported_aspect_ratios(max_num_tiles).index((tw, th)) + 1
    return tiles, ar_id, th * tw
