"""IDEFICS (HuggingFace's open Flamingo) — CLIP vision tower + Perceiver
resampler + llama decoder with tanh-gated cross-attention every
``cross_layer_interval`` layers (reference: contrib/models/
idefics-9b-instruct).

TPU mapping mirrors the mllama stitching: standard llama segments run
through ``run_layer_slice`` (full KV-cache machinery), the gated cross
blocks sit between segments with their cross K/V precomputed ONCE from the
resampled image latents — decode steps touch only the self-attention
cache. The decoupled additional embeddings (the <image>/<fake_image>
token rows appended at fine-tuning time) are concatenated onto the base
tables at conversion."""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import InferenceConfig, TpuConfig
from ..modules.kv_cache import KVCacheSpec, cache_len_of, init_cache
from ..ops import attention as attn_ops
from ..ops import sampling as sampling_ops
from ..ops.normalization import layer_norm, rms_norm
from ..utils import checkpoint as ckpt
from ..utils.host_loop import greedy_host_loop
from . import vision
from .family import get_family
from .model_base import (DecoderSpec, _embed, _lm_head, attn_inputs,
                         init_params, param_shardings, run_layer_slice,
                         spec_from_config)


class IdeficsInferenceConfig(InferenceConfig):
    def get_required_attributes(self) -> List[str]:
        return ["hidden_size", "num_attention_heads", "num_hidden_layers",
                "vocab_size", "cross_layer_interval", "vision_config"]

    def get_text_config(self):
        return self


# ---------------------------------------------------------------------------
# Perceiver resampler (reference: HF IdeficsPerceiverResampler — Flamingo
# latents cross-attending the frozen vision sequence)
# ---------------------------------------------------------------------------

def perceiver_forward(params: Dict[str, Any], context: jnp.ndarray,
                      n_heads: int, head_dim: int, eps: float = 1e-5
                      ) -> jnp.ndarray:
    """context (B, S, E) -> latents (B, n_latents, E). Keys/values attend
    over [context ; latents] (Flamingo concat)."""
    b = context.shape[0]
    lat = jnp.broadcast_to(params["latents"],
                           (b,) + params["latents"].shape)
    for blk in params["blocks"]:
        c = layer_norm(context, blk["ctx_ln_w"], blk["ctx_ln_b"], eps)
        q_in = layer_norm(lat, blk["lat_ln_w"], blk["lat_ln_b"], eps)
        kv_in = jnp.concatenate([c, q_in], axis=1)
        q = (q_in @ blk["q_w"]).reshape(b, -1, n_heads, head_dim)
        k = (kv_in @ blk["k_w"]).reshape(b, -1, n_heads, head_dim)
        v = (kv_in @ blk["v_w"]).reshape(b, -1, n_heads, head_dim)
        s = jnp.einsum("bihd,bjhd->bhij", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * (head_dim ** -0.5)
        p = jax.nn.softmax(s, axis=-1)
        a = jnp.einsum("bhij,bjhd->bihd", p, v.astype(jnp.float32))
        a = a.reshape(b, lat.shape[1], -1).astype(lat.dtype)
        lat = lat + a @ blk["o_w"]
        m = layer_norm(lat, blk["mlp_ln_w"], blk["mlp_ln_b"], eps)
        m = jax.nn.relu(m @ blk["fc_w"]) @ blk["cproj_w"]
        lat = lat + m
    return layer_norm(lat, params["ln_w"], params["ln_b"], eps)


def convert_perceiver(sd, depth: int, prefix="model.perceiver_resampler"):
    def get(n):
        return np.asarray(sd[f"{prefix}.{n}"], np.float32)

    def t(w):
        return np.ascontiguousarray(np.asarray(w, np.float32).T)

    blocks = []
    for i in range(depth):
        a, m = f"blocks.{i}.0", f"blocks.{i}.1"
        blocks.append({
            "ctx_ln_w": get(f"{a}.context_layer_norm.weight"),
            "ctx_ln_b": get(f"{a}.context_layer_norm.bias"),
            "lat_ln_w": get(f"{a}.latents_layer_norm.weight"),
            "lat_ln_b": get(f"{a}.latents_layer_norm.bias"),
            "q_w": t(get(f"{a}.q_proj.weight")),
            "k_w": t(get(f"{a}.k_proj.weight")),
            "v_w": t(get(f"{a}.v_proj.weight")),
            "o_w": t(get(f"{a}.output_proj.weight")),
            "mlp_ln_w": get(f"{m}.ln.weight"),
            "mlp_ln_b": get(f"{m}.ln.bias"),
            "fc_w": t(get(f"{m}.fc.weight")),
            "cproj_w": t(get(f"{m}.c_proj.weight")),
        })
    return {"latents": get("latents"), "blocks": blocks,
            "ln_w": get("layer_norm.weight"), "ln_b": get("layer_norm.bias")}


# ---------------------------------------------------------------------------
# Gated cross-attention block (reference: HF IdeficsGatedCrossAttentionLayer)
# ---------------------------------------------------------------------------

def compute_cross_kv(cross_params, image_states, n_heads: int, head_dim: int):
    """Precompute per-cross-layer K/V from the (static) image latents:
    image_states (B, S_img, E_vis) -> k/v (Lc, B, S_img, H, D)."""
    b, s, _ = image_states.shape

    def one(lw):
        k = (image_states @ lw["k_proj"]).reshape(b, s, n_heads, head_dim)
        v = (image_states @ lw["v_proj"]).reshape(b, s, n_heads, head_dim)
        return k, v

    ks, vs = jax.lax.map(one, cross_params)
    return {"k": ks, "v": vs}


def _cross_block(spec: DecoderSpec, hidden, lw, ck, cv, img_mask):
    """x += tanh(alpha_ca) * cross_attn(ln(x), img) [zeroed for rows
    attending NO image latent — HF's cross_attention_gate is computed on
    the additive mask: any 0.0 entry = attends at least one latent];
    partial masks apply to the scores; x += tanh(alpha_d) * mlp(ln2(x))."""
    b, t, _ = hidden.shape
    nh, hd = spec.gqa.num_q_heads, spec.head_dim
    gate = img_mask.any(axis=-1, keepdims=True)             # (B, T, 1)
    eff_mask = jnp.where(gate, img_mask, True)              # avoid all -inf
    r = rms_norm(hidden, lw["input_norm"], spec.rms_eps)
    q = (r @ lw["q_proj"]).reshape(b, t, nh, hd)
    a = attn_ops.mha(q, ck, cv, eff_mask, spec.scale)
    a = a.reshape(b, t, -1) @ lw["o_proj"]
    a = a * gate.astype(a.dtype)
    hidden = hidden + jnp.tanh(lw["alpha_ca"]) * a
    r = rms_norm(hidden, lw["post_norm"], spec.rms_eps)
    m = (jax.nn.silu(r @ lw["gate_proj"]) * (r @ lw["up_proj"])) \
        @ lw["down_proj"]
    return hidden + jnp.tanh(lw["alpha_d"]) * m


def convert_cross_layers(sd, n_cross: int):
    def get(n):
        return np.asarray(sd[n], np.float32)

    def t(w):
        return np.ascontiguousarray(np.asarray(w, np.float32).T)

    def lw(i):
        p = f"model.gated_cross_attn_layers.{i}."
        return {
            "input_norm": get(p + "input_layernorm.weight"),
            "q_proj": t(get(p + "cross_attn.q_proj.weight")),
            "k_proj": t(get(p + "cross_attn.k_proj.weight")),
            "v_proj": t(get(p + "cross_attn.v_proj.weight")),
            "o_proj": t(get(p + "cross_attn.o_proj.weight")),
            "alpha_ca": get(p + "alpha_cross_attn"),
            "alpha_d": get(p + "alpha_dense"),
            "post_norm": get(p + "post_attention_layernorm.weight"),
            "gate_proj": t(get(p + "mlp.gate_proj.weight")),
            "up_proj": t(get(p + "mlp.up_proj.weight")),
            "down_proj": t(get(p + "mlp.down_proj.weight")),
        }

    layers = [lw(i) for i in range(n_cross)]
    return {k: np.stack([d[k] for d in layers]) for k in layers[0]}


# ---------------------------------------------------------------------------
# Interleaved forward
# ---------------------------------------------------------------------------

def idefics_forward(spec: DecoderSpec, interval: int, tcfg: TpuConfig,
                    params, cache, cross_kv, input_ids, position_ids,
                    seq_ids, seq_lens, img_mask, sampling_params, rng,
                    phase: str):
    if phase == "prefill":
        ai = attn_inputs(spec, position_ids,
                         lambda w, c=0: attn_ops.prefill_causal_mask(
                             input_ids.shape[1], position_ids, window=w,
                             chunk=c))
    else:
        ai = attn_inputs(spec, position_ids,
                         lambda w, c=0: attn_ops.decode_mask(
                             position_ids, cache_len_of(cache), window=w,
                             chunk=c))
    hidden = _embed(spec, params, input_ids)
    L = spec.num_layers
    si = 0
    for start in range(0, L, interval):
        ci = start // interval
        lw = jax.tree.map(lambda a: a[ci], params["cross_layers"])
        hidden = _cross_block(spec, hidden, lw, cross_kv["k"][ci],
                              cross_kv["v"][ci], img_mask)
        n_self = min(interval, L - start)
        seg = jax.tree.map(lambda a: a[si:si + n_self], params["layers"])
        hidden, cache, _ = run_layer_slice(
            spec, seg, cache, hidden, ai, cache_offset=si,
            is_local=jnp.zeros((n_self,), bool), rep={}, mlp_kind=None,
            seq_ids=seq_ids, positions=position_ids, phase=phase,
            identity_seq_ids=True, arange_positions=(phase == "prefill"))
        si += n_self
    out: Dict[str, Any] = {"cache": cache}
    if phase == "prefill":
        idx = jnp.maximum(seq_lens - 1, 0)
        last_h = jnp.take_along_axis(
            hidden, idx[:, None, None].astype(jnp.int32), axis=1)
        logits = _lm_head(spec, params, last_h)[:, 0, :]
    else:
        full = _lm_head(spec, params, hidden)
        logits = full[:, -1, :]
    if tcfg.output_logits:
        out["logits"] = _lm_head(spec, params, hidden)[..., :spec.vocab_size]
    out["tokens"] = sampling_ops.sample(
        logits, tcfg.on_device_sampling_config, sampling_params, rng)
    return out


# ---------------------------------------------------------------------------
# Application
# ---------------------------------------------------------------------------

class IdeficsApplication:
    """Vision tower + perceiver + gated-cross-attention llama LM."""

    def __init__(self, model_path: Optional[str],
                 config: IdeficsInferenceConfig, mesh=None):
        self.config = config
        self.tpu_config = config.tpu_config
        self.model_path = model_path
        self.mesh = mesh
        extra = int(getattr(config, "additional_vocab_size", 0) or 0)
        # decoupled additional embeddings extend the vocab; padded_vocab
        # must cover the concatenated table
        from .model_base import pad_vocab
        v_total = int(config.vocab_size) + extra
        self.spec = spec_from_config(
            config, None,
            vocab_size=v_total,
            padded_vocab=pad_vocab(v_total, config.tpu_config.tp_degree),
            rms_eps=float(getattr(config, "rms_norm_eps", 1e-6)))
        vc = dict(config.vision_config)
        self.vit_spec = vision.VitSpec(
            hidden_size=int(vc.get("embed_dim", vc.get("hidden_size"))),
            num_layers=int(vc["num_hidden_layers"]),
            num_heads=int(vc["num_attention_heads"]),
            intermediate_size=int(vc["intermediate_size"]),
            patch_size=int(vc["patch_size"]),
            image_size=int(vc["image_size"]),
            use_cls_token=True, pre_layernorm=True, post_layernorm=True,
            act=vc.get("hidden_act", "gelu"),
            eps=float(vc.get("layer_norm_eps", 1e-5)),
            feature_layer=-1)
        pc = dict(getattr(config, "perceiver_config", {}) or {})
        self.use_resampler = bool(getattr(config, "use_resampler", False)
                                  or pc.get("use_resampler", False))
        self.perceiver_cfg = pc
        if pc.get("qk_layer_norms_perceiver") or getattr(
                config, "qk_layer_norms", False):
            raise NotImplementedError(
                "idefics qk_layer_norms variants are not supported")
        self.interval = int(config.cross_layer_interval)
        self.params = None
        self.cache = None
        self.vision_params = None
        self.perceiver_params = None
        self._steps: Dict[str, Any] = {}
        self._rng = jax.random.PRNGKey(0)
        self._vit = jax.jit(partial(vision.vit_forward, self.vit_spec))
        self._cross_fn = jax.jit(partial(
            compute_cross_kv, n_heads=self.spec.gqa.num_q_heads,
            head_dim=self.spec.head_dim))

    def load_weights(self):
        sd = ckpt.load_state_dict(self.model_path)
        fam = get_family("llama")
        text_sd = {k: v for k, v in sd.items()
                   if k.startswith("model.layers.")
                   or k in ("model.norm.weight",)}
        embed = np.asarray(sd["model.embed_tokens.weight"], np.float32)
        head = np.asarray(sd["lm_head.weight"], np.float32)
        if "model.embed_tokens.additional_embedding.weight" in sd:
            embed = np.concatenate([embed, np.asarray(
                sd["model.embed_tokens.additional_embedding.weight"],
                np.float32)])
        if "lm_head.additional_fc.weight" in sd:
            head = np.concatenate([head, np.asarray(
                sd["lm_head.additional_fc.weight"], np.float32)])
        text_sd["model.embed_tokens.weight"] = embed
        text_sd["lm_head.weight"] = head
        host = fam.convert_hf_state_dict(text_sd, self.spec)
        host["cross_layers"] = convert_cross_layers(
            sd, (self.spec.num_layers + self.interval - 1) // self.interval)
        from .model_base import fuse_qkv_host
        host = fuse_qkv_host(host)
        self.params = jax.tree.map(jnp.asarray, host)
        self.vision_params = jax.tree.map(
            jnp.asarray, vision.convert_clip_vision_tower(
                sd, self.vit_spec, "model.vision_model", bare_prefix=True))
        if self.use_resampler:
            self.perceiver_params = jax.tree.map(
                jnp.asarray,
                convert_perceiver(sd, int(self.perceiver_cfg.get(
                    "resampler_depth", 6))))
        return self

    def init_cache(self):
        cfg = self.tpu_config
        self.cache = init_cache(KVCacheSpec(
            num_layers=self.spec.num_layers, batch_size=cfg.batch_size,
            max_seq_len=cfg.seq_len,
            num_kv_heads=self.spec.gqa.num_kv_heads,
            head_dim=self.spec.head_dim, dtype=self.spec.kv_dtype),
            self.mesh)
        return self

    def encode_images(self, pixel_values: np.ndarray) -> jnp.ndarray:
        """(B, N_img, C, H, W) -> image latents (B, N_img * S_img, E_vis)."""
        b, n = pixel_values.shape[:2]
        feats = self._vit(self.vision_params,
                          jnp.asarray(pixel_values).reshape(
                              (b * n,) + pixel_values.shape[2:]))
        if self.use_resampler:
            pc = self.perceiver_cfg
            feats = perceiver_forward(
                self.perceiver_params, feats,
                int(pc.get("resampler_n_heads", 16)),
                int(pc.get("resampler_head_dim", 96)))
        s_img = feats.shape[1]
        return feats.reshape(b, n * s_img, feats.shape[-1]), s_img

    def _step(self, phase):
        if phase not in self._steps:
            self._steps[phase] = jax.jit(
                partial(idefics_forward, self.spec, self.interval,
                        self.tpu_config, phase=phase), donate_argnums=(1,))
        return self._steps[phase]

    def generate(self, input_ids: np.ndarray, pixel_values: np.ndarray,
                 image_attention_mask: Optional[np.ndarray] = None,
                 attention_mask: Optional[np.ndarray] = None,
                 max_new_tokens: int = 16,
                 eos_token_id: Optional[int] = None) -> Dict[str, Any]:
        """pixel_values (B, N_img, C, H, W); image_attention_mask
        (B, S_text, N_img) bool/int (True = that token attends that image)
        — defaults to all-on."""
        input_ids = np.asarray(input_ids)
        b, s = input_ids.shape
        if attention_mask is None:
            attention_mask = np.ones_like(input_ids)
        seq_lens = attention_mask.astype(np.int32).sum(axis=1)
        if self.cache is None:
            self.init_cache()
        latents, s_img = self.encode_images(pixel_values)
        n_img = pixel_values.shape[1]
        if image_attention_mask is None:
            image_attention_mask = np.ones((b, s, n_img), bool)
        # expand per-image mask over that image's latent slots
        img_mask = np.repeat(image_attention_mask.astype(bool), s_img,
                             axis=2)
        cross_kv = self._cross_fn(self.params["cross_layers"],
                                  latents.astype(self.spec.dtype))

        self._rng, k1 = jax.random.split(self._rng)
        pos = np.broadcast_to(np.arange(s, dtype=np.int32), (b, s))
        out = self._step("prefill")(
            self.params, self.cache, cross_kv, jnp.asarray(input_ids),
            jnp.asarray(pos), jnp.arange(b, dtype=jnp.int32),
            jnp.asarray(seq_lens), jnp.asarray(img_mask), None, k1)
        self.cache = out["cache"]
        logits = [np.asarray(out["logits"])] if "logits" in out else []

        dec_mask = jnp.asarray(img_mask[:, -1:, :])
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
                logits.append(o["logits"])
            return o["tokens"].reshape(b).astype(jnp.int32)

        first = jnp.asarray(np.asarray(out["tokens"]).reshape(b)
                            .astype(np.int32))
        gen = greedy_host_loop(step, first, max_new_tokens, eos_ids=eos_ids)
        res = {"sequences": np.concatenate([input_ids, gen], axis=1),
               "generated": gen}
        if logits:
            res["logits"] = [np.asarray(lg) for lg in logits]
        return res

    def reset(self):
        self.init_cache()
        return self
