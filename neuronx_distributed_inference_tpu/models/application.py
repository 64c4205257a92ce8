"""Application layer — compile/load/warmup lifecycle + host generation loop
(reference: models/application_base.py ``NeuronApplicationBase``,
models/model_wrapper.py ``ModelWrapper``, models/model_base.py
``NeuronBaseForCausalLM``:3024).

TPU redesign of the three reference classes into one:
  * compile()  -> ``jax.jit(...).lower().compile()`` per (submodel, bucket);
    the persistent XLA compilation cache (utils/compile_cache.py)
    replaces the NEFF artifact dir.
  * load()     -> checkpoint load + convert + device_put with shardings.
  * generate() -> host loop; with ``decode_chunk_tokens`` > 1 a device call
    runs that many decode steps in one ``lax.scan`` (model_base.decode_loop:
    the step graph scanned, the cache its carry), which is the TPU
    replacement for async double-buffering
    (reference: modules/async_execution.py). The serving path's fused loop
    is ``paged_decode_loop``.
KV cache buffers are donated every call (reference I/O aliasing,
model_wrapper.py:1578-1627).
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
import time
from functools import partial
from typing import Any, Dict, List, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import InferenceConfig, TpuConfig
from ..modules import autobucketing
from ..telemetry import get_registry
from ..telemetry import metrics as tmetrics
from ..telemetry import trace as trace_mod
from ..modules.kv_cache import KVCacheSpec, cache_pspec, init_cache
from ..ops import kernel_mode
from ..ops.sampling import prepare_sampling_params
from ..parallel.mesh import AXIS_DP, AXIS_TP, MeshConfig, build_mesh, mesh_from_config
from ..utils import checkpoint as ckpt
from ..utils.compile_cache import configure_compile_cache
from .family import DecoderFamily, family_for_config
from . import model_base

logger = logging.getLogger("nxdi_tpu")

# Submodel tags (reference: models/model_wrapper.py:37-42)
CONTEXT_ENCODING_MODEL_TAG = "context_encoding_model"
TOKEN_GENERATION_MODEL_TAG = "token_generation_model"
SPECULATION_MODEL_TAG = "speculation_model"
FUSED_SPECULATION_MODEL_TAG = "fused_speculation_model"


def _named_partial(fn, *args, **kw):
    """``functools.partial`` that keeps ``fn``'s name: ``jax.jit`` names the
    XLA module after it (``jit_paged_forward_step`` on the profiler's
    ``XLA Modules`` line, not ``jit__unknown``). Only the name is copied —
    a ``__wrapped__`` would make jit resolve ``donate_argnums`` against the
    unbound signature."""
    bound = partial(fn, *args, **kw)
    bound.__name__ = bound.__qualname__ = fn.__name__
    return bound


class CausalLMApplication:
    """Compile/load/run a causal LM on a TPU mesh."""

    def __init__(self, model_path: Optional[str], config: InferenceConfig,
                 family: Optional[Type[DecoderFamily]] = None,
                 mesh: Optional[Mesh] = None):
        self.model_path = model_path
        self.config = config
        self.tpu_config: TpuConfig = config.tpu_config
        self.family = family or family_for_config(config)
        self.mesh = mesh if mesh is not None else mesh_from_config(self.tpu_config)
        # heads/vocab/mlp shard over the COMBINED ("ep","tp") axes, so GQA
        # padding and vocab padding resolve against ep*tp (the reference's
        # full tp_degree; ep subdivides it, moe_v2.py:135-161)
        mp_degree = self.mesh.shape["tp"] * self.mesh.shape["ep"]
        self.spec = self.family.build_spec(config, tp_degree=mp_degree)
        self.params = None
        self.cache = None
        self._compiled: Dict[Tuple[str, int], Any] = {}
        # telemetry: None = follow the process-global registry (disabled by
        # default); assign app.telemetry = reg to pin one. _jit_seen tracks
        # (kind, bucket, shape) signatures for the recompile counter — it
        # never feeds the jit cache key itself.
        self._telemetry_override = None
        self._jit_seen: set = set()
        # cold-start discipline (serving/warmup.py): after precompile()
        # declares steady state, any first-seen signature is a tracked
        # incident. _trace_ctx carries the request trace ids of the
        # dispatch currently executing so the incident is attributed.
        self._steady_state = False
        self._steady_incidents: List[Dict[str, Any]] = []
        self._trace_ctx: Tuple[str, ...] = ()
        self._warmup_report: Optional[Dict[str, Any]] = None
        # (site, path, reason) notes of every attention-kernel decision
        # traced into this app's graphs (ops/kernel_mode.py)
        self._kernel_notes: set = set()
        # the same notes by paged program, keyed by its (rows, width): what
        # the trace of THAT program took (``paged_program_notes``)
        self._paged_notes: Dict[Tuple[int, int], frozenset] = {}
        self._rng = jax.random.PRNGKey(self.tpu_config.seed)
        self.ctx_buckets = autobucketing.context_encoding_buckets(self.tpu_config)
        self.tkg_buckets = autobucketing.token_generation_buckets(self.tpu_config)
        # 2-D bucketing: allowed compiled batch sizes (reference: batch x
        # seq TKG buckets, autobucketing.py:203)
        self.batch_buckets = autobucketing.batch_buckets(self.tpu_config)
        # observability (reference: utils/snapshot.py env-driven capture;
        # utils/tensor_replacement/ golden injection)
        from ..utils.snapshot import SnapshotManager
        self.snapshot = SnapshotManager()
        self.replacements = None
        if self.tpu_config.tensor_replacement_config is not None:
            self.load_tensor_replacements()
        configure_compile_cache()

    # ------------------------------------------------------------------
    # weights
    # ------------------------------------------------------------------
    def load_weights(self, model_path: Optional[str] = None):
        """Load + convert + shard a HF checkpoint
        (reference: application_base.py:375-421 ``load_weights``)."""
        path = model_path or self.model_path
        sd = ckpt.load_state_dict(path)
        host = self.family.convert_hf_state_dict(sd, self.spec)
        self._put_params(host)
        return self

    def init_random_weights(self, seed: int = 0):
        """Synthetic weights (tiny-model tests / benches — reference:
        modules/checkpoint.py:202-287)."""
        if self.spec.quant is None and self.spec.low_rank is None:
            self.params = model_base.init_params(
                self.spec, jax.random.PRNGKey(seed), self.mesh)
        else:
            host = jax.device_get(model_base.init_params(
                self.spec, jax.random.PRNGKey(seed)))
            self._put_params(host)
        return self

    def _put_params(self, host: Dict[str, Any]):
        """Shard-on-load; factorize (SVD) and/or quantize first when the
        config asks for it (reference: application_base.py:746-799
        quantize-and-save path). Order matters: the SVD needs the fp
        weight, so low-rank factorization runs BEFORE quantization and
        quantizes its own factors (modules/low_rank.factorize_params)."""
        from ..modules import quantization as quant
        host = model_base.fuse_qkv_host(host)
        host = model_base.stack_lora_host(self.spec, host)
        fp_shardings = model_base.param_shardings(self.spec, self.mesh)
        if self.spec.quant is None and self.spec.low_rank is None:
            self.params = ckpt.device_put_params(host, fp_shardings,
                                                 dtype=self.spec.dtype)
            return
        host = jax.tree.map(
            lambda x: (np.asarray(x).astype(self.spec.dtype)
                       if np.issubdtype(np.asarray(x).dtype, np.floating)
                       else np.asarray(x)), host)
        if self.spec.low_rank is not None:
            from ..modules import low_rank as low_rank_mod
            host = low_rank_mod.factorize_params(
                host, self.spec.low_rank, quant=self.spec.quant)
        if self.spec.quant is not None:
            host = quant.quantize_params(host, self.spec.quant)
        shardings = quant.quantized_shardings(fp_shardings, host, self.mesh)
        self.params = ckpt.device_put_params(host, shardings, dtype=None)

    def save_quantized_state_dict(self, path: str):
        """Quantize the loaded/initialized weights and save them flat
        (reference: application_base.py:746-799
        ``save_quantized_state_dict``). Reload with
        :meth:`load_quantized_state_dict`."""
        if self.spec.quant is None:
            raise ValueError("config.tpu_config.quantized must be set")
        if self.params is None:
            raise RuntimeError("load_weights() first")
        host = jax.device_get(self.params)
        flat = _flatten_tree(host)
        ckpt.save_state_dict_safetensors(
            {k: np.asarray(v) for k, v in flat.items()}, path)
        self.config.save(path + os.sep)

    def load_quantized_state_dict(self, path: str):
        sd = ckpt.load_state_dict(path)
        host = _unflatten_tree(sd)
        from ..modules import quantization as quant
        fp_shardings = model_base.param_shardings(self.spec, self.mesh)
        shardings = quant.quantized_shardings(fp_shardings, host, self.mesh)
        self.params = ckpt.device_put_params(host, shardings, dtype=None)
        return self

    def save_converted_checkpoint(self, path: str):
        """Save the post-conversion param tree (fused qkv, padded heads,
        stacked layers) so reload skips HF conversion — the analog of the
        reference's pre-sharded per-rank checkpoints
        (application_base.py:389-399 save_sharded_checkpoint); triggered by
        ``save_sharded_checkpoint`` at compile()."""
        if self.params is None:
            raise RuntimeError("load_weights() first")
        host = jax.device_get(self.params)
        flat = _flatten_tree(host)
        ckpt.save_state_dict_safetensors(
            {k: np.asarray(v) for k, v in flat.items()},
            os.path.join(path, "converted"))
        self.config.save(path + os.sep)

    def load_converted_checkpoint(self, path: str):
        """Load a :meth:`save_converted_checkpoint` artifact (no HF
        conversion pass)."""
        sd = ckpt.load_state_dict(os.path.join(path, "converted"))
        host = _unflatten_tree(sd)
        shardings = model_base.param_shardings(self.spec, self.mesh)
        self.params = ckpt.device_put_params(host, shardings,
                                             dtype=self.spec.dtype)
        return self

    def init_cache(self):
        cfg = self.tpu_config
        spec = KVCacheSpec(
            # SSM-only layers carry no KV rows (recurrent/hybrid stacks)
            num_layers=self.spec.num_attn_layers,
            batch_size=cfg.kv_cache_batch_size,
            max_seq_len=cfg.seq_len,
            num_kv_heads=self.spec.gqa.num_kv_heads,
            head_dim=self.spec.head_dim,
            dtype=self.spec.kv_dtype,
            # rolling sliding-window cache: w slots instead of seq_len
            # (reference: kv_cache_manager.py:605-606)
            window=(self.spec.sliding_window if self.spec.rolling_window
                    else 0),
            v_head_dim=(self.spec.v_head_dim
                        if self.spec.v_head_dim != self.spec.head_dim else None),
        )
        if self.spec.mixed_kv:
            # per-layer cache sizes: local layers roll at W (reference:
            # gpt-oss per-layer KV, gpt_oss_kv_cache_manager.py)
            from ..modules.kv_cache import init_mixed_cache
            self.cache = init_mixed_cache(
                spec, self.spec.layer_pattern, self.spec.sliding_window,
                self.mesh)
        else:
            self.cache = init_cache(spec, self.mesh,
                                    flash_decoding=self.spec.flash_decoding)
        if self.spec.ssm is not None:
            # recurrent state pytree rides the same cache dict (reference
            # analog: the conv/ssm state tensors of
            # contrib Falcon-H1 FalconHybridMambaAttentionDynamicCache)
            from ..modules.ssm import init_ssm_state
            self.cache.update(init_ssm_state(
                self.spec.ssm, self.spec.num_ssm_layers,
                cfg.kv_cache_batch_size, self.spec.dtype, self.mesh))
        return self

    # ------------------------------------------------------------------
    # compile
    # ------------------------------------------------------------------
    def _io_shardings(self):
        repl = NamedSharding(self.mesh, P())
        cache_sh = NamedSharding(self.mesh, cache_pspec())
        return repl, cache_sh

    def _jit_prefill(self):
        fn = _named_partial(model_base.context_encoding_step, self.spec,
                            self.tpu_config)
        return jax.jit(fn, donate_argnums=(1,))

    def _jit_decode(self, kv_bucket: Optional[int] = None):
        fn = _named_partial(model_base.token_generation_step, self.spec,
                            self.tpu_config, kv_view=kv_bucket)
        return jax.jit(fn, donate_argnums=(1,))

    def _jit_decode_loop(self, num_steps: int,
                         kv_bucket: Optional[int] = None):
        fn = _named_partial(model_base.decode_loop, self.spec,
                            self.tpu_config, kv_view=kv_bucket)
        return jax.jit(fn, static_argnames=("num_steps",), donate_argnums=(1,))

    def _check_decode_fits(self, needed: int):
        """Decode writing KV slots up to ``needed - 1`` must stay inside
        the compiled seq_len — past it the scatter writes out of bounds
        (wrapping or dropping silently, depending on layout). Rolling
        caches store slot = pos % window, so they can never overflow."""
        if self.spec.rolling_window:
            return
        limit = self.tpu_config.seq_len
        if needed > limit:
            from ..resilience.errors import CapacityError
            raise CapacityError(
                f"decode would write KV at position {needed - 1} past the "
                f"compiled seq_len {limit}")

    def _kv_bucket(self, needed: int) -> Optional[int]:
        """Smallest TKG seq bucket covering ``needed`` cache slots — the
        decode graph compiled for bucket b reads cache[:b] only (reference:
        TKG seq buckets, autobucketing.py:226). None = full cache."""
        if self.spec.rolling_window:
            return None        # rolling cache: slot != position, no view cut
        buckets = self.tkg_buckets
        if len(buckets) <= 1:
            return None
        return autobucketing.get_target_bucket(buckets, needed, kind="tkg")

    def get_compiled(self, tag: str, bucket=0):
        key = (tag, bucket)
        if key not in self._compiled:
            if tag == CONTEXT_ENCODING_MODEL_TAG:
                self._compiled[key] = self._jit_prefill()
            elif tag == TOKEN_GENERATION_MODEL_TAG:
                self._compiled[key] = self._jit_decode(bucket or None)
            elif tag == "decode_loop":
                steps, kv_bucket = bucket if isinstance(bucket, tuple) \
                    else (bucket, None)
                self._compiled[key] = self._jit_decode_loop(steps, kv_bucket)
            elif tag == "windowed_cte":
                fn = _named_partial(model_base.token_generation_multi,
                                    self.spec, self.tpu_config)
                self._compiled[key] = jax.jit(fn, donate_argnums=(1,))
            else:
                raise KeyError(tag)
        return self._compiled[key]

    def compile(self, compiled_model_path: Optional[str] = None):
        """AOT warm the compilation cache for every (submodel, bucket)
        (reference: application_base.py:292-316 ``compile``).
        ``compiled_model_path`` receives the saved config (and the
        converted checkpoint under ``save_sharded_checkpoint``); the
        executables go to the process's one compilation cache
        (utils/compile_cache.py), not here."""
        if compiled_model_path:
            os.makedirs(compiled_model_path, exist_ok=True)
            self.config.save(compiled_model_path + os.sep)
            if self.tpu_config.save_sharded_checkpoint and \
                    self.params is not None:
                self.save_converted_checkpoint(compiled_model_path)
        self.warmup()
        return self

    def warmup(self):
        """Run every bucket once (reference: application_base.py:349-373)."""
        if self.params is None:
            self.init_random_weights()
        if self.cache is None:
            self.init_cache()
        cfg = self.tpu_config
        b = cfg.ctx_batch_size
        for s in self.ctx_buckets:
            self._run_prefill(np.zeros((b, s), np.int32),
                              np.zeros((b,), np.int32) + 1)
        chunk = max(cfg.decode_chunk_tokens, 1)
        # compile every TKG seq bucket (reference: warmup runs every bucket
        # of every submodel, application_base.py:349-373)
        starts = [1] if len(self.tkg_buckets) <= 1 else [
            max(b - chunk, 1) for b in self.tkg_buckets]
        warm_batches = sorted(set(self.batch_buckets)
                              | {cfg.tkg_batch_size or cfg.batch_size})
        for start in starts:
            for bb in warm_batches:           # 2-D: every batch bucket
                if chunk > 1:
                    self._run_decode_loop(np.zeros((bb,), np.int32),
                                          np.full((bb,), start, np.int32),
                                          chunk)
                # the chunk tail of generate() uses the single-step graph —
                # warm it per bucket too, or the first request reaching a
                # new bucket stalls on a mid-request compile
                self._run_decode(np.zeros((bb, 1), np.int32),
                                 np.full((bb, 1), start, np.int32))
        # 2-D batch buckets: warm each short-batch prefill at the smallest
        # ctx bucket (the remaining grid compiles lazily; the decode loop —
        # the stall that matters mid-request — is warmed above)
        for bb in self.batch_buckets:
            if bb != b:
                self._run_prefill(np.zeros((bb, self.ctx_buckets[0]),
                                           np.int32),
                                  np.ones((bb,), np.int32))
        return self

    # ------------------------------------------------------------------
    # execution helpers
    # ------------------------------------------------------------------
    @contextlib.contextmanager
    def _mesh_ctx(self):
        """Execute compiled fns inside the mesh context: bare-PartitionSpec
        sharding constraints in model code resolve against it, and
        ops/decode_attention.dispatch reads it to shard_map the Pallas
        kernel over the dp/mp axes. Whatever is TRACED in the body notes
        its kernel-vs-XLA attention choice onto this app
        (``warmup_state()["kernels"]``)."""
        with jax.sharding.set_mesh(self.mesh), \
                kernel_mode.recording(self._kernel_notes):
            yield

    # -- telemetry (host-boundary only; all no-ops while disabled) ---------
    @property
    def telemetry(self):
        return (self._telemetry_override
                if self._telemetry_override is not None else get_registry())

    @telemetry.setter
    def telemetry(self, reg):
        self._telemetry_override = reg

    @contextlib.contextmanager
    def _run_span(self, kind: str, n_rows: int):
        """``with self._run_span(kind, rows):`` around the host side of one
        _run_* call (entry -> the return of the asynchronous dispatch, RNG
        split included): a ``run.<kind>`` flight-recorder slice (and so a
        profiler TraceMe) and one ``nxdi_run_seconds{part="host"}``
        observation. Strictly OUTSIDE traced code, and it NEVER syncs the
        device: the blocking wait is measured where it really happens
        (``fetch.tokens``), device time belongs to the profiler's trace.
        With telemetry and recorder off the span is the shared no-op."""
        tel = self.telemetry
        t0 = time.perf_counter()
        with trace_mod.get_recorder().span(f"run.{kind}", cat="app",
                                           rows=n_rows):
            yield
        if tel.enabled:
            tmetrics.run_seconds_histogram(tel).observe(
                time.perf_counter() - t0, kind=kind, part="host")

    def _note_jit(self, kind: str, bucket, sig):
        """Recompile accounting: the first time a (kind, bucket, shape)
        signature runs it is a graph build (trace + XLA compile, or a
        persistent-cache load); afterwards it is a cache hit. The single
        most useful "why is serving slow" signal. Signatures are tracked
        even while telemetry is disabled (one set-add, no syncs) so that
        enabling the registry after warmup does not misreport every warm
        graph as a fresh compile. First-time signatures also land on the
        flight recorder as ``compile`` instants, so a trace timeline shows
        WHERE mid-serving compile stalls interleave with dispatches."""
        key = (kind, bucket, sig)
        seen = key in self._jit_seen
        if not seen:
            self._jit_seen.add(key)
            rec = trace_mod.get_recorder()
            if rec.enabled:
                rec.instant("compile", cat="app", kind=kind,
                            bucket=str(bucket), sig=str(sig))
            if self._steady_state:
                self._note_steady_recompile(kind, bucket, sig, rec)
        tel = self.telemetry
        if not tel.enabled:
            return
        if seen:
            tmetrics.jit_cache_hits_counter(tel).inc(kind=kind)
        else:
            tmetrics.jit_compiles_counter(tel).inc(kind=kind,
                                                   bucket=str(bucket))

    # -- steady-state compile discipline (serving/warmup.py) ---------------
    _MAX_STEADY_INCIDENTS = 256

    def _note_steady_recompile(self, kind: str, bucket, sig, rec) -> None:
        """A first-seen signature AFTER precompile() declared steady state:
        a tracked incident — counter, ``compile.unexpected`` flight-
        recorder event, and attribution onto the request traces packed
        into the triggering dispatch (``request_context``)."""
        traces = [t for t in self._trace_ctx if t]
        incident = {"kind": kind, "bucket": str(bucket), "sig": str(sig),
                    "traces": traces}
        self._steady_incidents.append(incident)
        if len(self._steady_incidents) > self._MAX_STEADY_INCIDENTS:
            del self._steady_incidents[0]
        if rec.enabled:
            rec.instant("compile.unexpected", cat="app", kind=kind,
                        bucket=str(bucket), sig=str(sig), traces=traces)
        tel = self.telemetry
        if tel.enabled:
            tmetrics.steady_state_recompiles_counter(tel).inc(
                kind=kind, bucket=str(bucket))

    def declare_steady_state(self, on: bool = True):
        """Flip the steady-state flag: ``precompile()`` (serving/warmup.py)
        declares it after walking the serving graph ladder; from then on
        every first-seen jit signature is a tracked incident."""
        self._steady_state = bool(on)
        return self

    def request_context(self, traces):
        """Context manager attributing any compile observed inside the
        body to ``traces`` (request trace ids of the dispatch being
        issued). Adapters wrap their ``_run_*`` calls in steady state."""
        @contextlib.contextmanager
        def _ctx():
            prev = self._trace_ctx
            self._trace_ctx = tuple(traces)
            try:
                yield
            finally:
                self._trace_ctx = prev
        return _ctx()

    def warmup_state(self) -> Dict[str, Any]:
        """JSON-able cold-start account: the precompile report summary,
        the steady-state flag, and every tracked recompile incident —
        served as ``/v1/debug/state["warmup"]``."""
        out: Dict[str, Any] = {
            "steady_state": self._steady_state,
            "graphs_seen": len(self._jit_seen),
            "incidents": list(self._steady_incidents),
            "kernels": [{"site": s, "path": p, "reason": r}
                        for s, p, r in sorted(self._kernel_notes)],
        }
        if self._warmup_report is not None:
            out["precompile"] = {
                k: self._warmup_report[k]
                for k in ("n_graphs", "n_compiles", "n_cache_loads",
                          "n_warm_hits", "total_seconds")
                if k in self._warmup_report}
        return out

    def _next_rng(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    def _default_sampling_params(self, batch: int):
        sc = self.tpu_config.on_device_sampling_config
        if sc is None:
            return None
        return jnp.asarray(prepare_sampling_params(
            batch, sc.top_k, sc.top_p, sc.temperature))

    def _run_prefill(self, input_ids: np.ndarray, seq_lens: np.ndarray,
                     seq_ids: Optional[np.ndarray] = None,
                     sampling_params=None, adapter_ids=None,
                     image_embeds=None, image_mask=None,
                     rope_position_ids=None, deepstack_embeds=None):
        b, s = input_ids.shape
        if seq_ids is None:
            seq_ids = np.arange(b, dtype=np.int32)
        elif (not self.tpu_config.is_continuous_batching
              and not np.array_equal(np.asarray(seq_ids), np.arange(b))):
            # the prefill graph takes the identity fast-path write under
            # this static config (kv_cache.write_prefill_at_layer), which
            # would silently ignore a row permutation — reject at the
            # boundary like _run_decode does
            raise ValueError("non-identity seq_ids require "
                             "is_continuous_batching=True")
        with self._run_span("prefill", b):
            position_ids = np.broadcast_to(np.arange(s, dtype=np.int32),
                                           (b, s))
            fn = self.get_compiled(CONTEXT_ENCODING_MODEL_TAG, s)
            self._note_jit("prefill", s, (b, s))
            if sampling_params is None:
                sampling_params = self._default_sampling_params(b)
            if self.snapshot.enabled:
                self.snapshot.save_step({"input_ids": input_ids,
                                         "position_ids": position_ids,
                                         "seq_ids": seq_ids,
                                         "seq_lens": seq_lens},
                                        weights=self.params)
            if image_mask is not None:
                image_mask = jnp.asarray(np.asarray(image_mask, bool))
            if rope_position_ids is not None:
                rope_position_ids = jnp.asarray(rope_position_ids)
            with self._mesh_ctx():
                out = fn(self.params, self.cache, jnp.asarray(input_ids),
                         jnp.asarray(position_ids), jnp.asarray(seq_ids),
                         jnp.asarray(seq_lens), sampling_params,
                         self._next_rng(), adapter_ids, self.replacements,
                         image_embeds, image_mask, rope_position_ids,
                         deepstack_embeds)
            self.cache = out["cache"]
        return out

    def _run_prefill_windowed(self, input_ids: np.ndarray,
                              seq_lens: np.ndarray, window: int,
                              sampling_params=None):
        """Windowed context encoding (reference: models/model_base.py:878-933
        + long-context mode, models/config.py:612-621): walk the prompt in
        fixed windows re-invoking ONE decode-phase multi-token graph with
        growing KV — the (S, S) one-shot prefill attention materialization
        becomes (W, S), which is what makes >=32k contexts feasible.
        Returns {"tokens", "cache"} like _run_prefill."""
        b, s = input_ids.shape
        if self.spec.rolling_window or self.spec.mixed_kv:
            # the windowed-CTE graph addresses cache slot == position; a
            # rolling cache stores slot = pos % W - silently wrong reads
            raise NotImplementedError(
                "windowed_context_encoding is incompatible with rolling / "
                "mixed per-layer KV caches (slot != position)")
        seq_ids = jnp.arange(b, dtype=jnp.int32)
        fn = self.get_compiled("windowed_cte", window)
        if sampling_params is None:
            sampling_params = self._default_sampling_params(b)
        vocab = self.spec.vocab_size
        last_logits = jnp.zeros((b, vocab), jnp.float32)
        lens_d = jnp.asarray(seq_lens.astype(np.int32))
        with self._mesh_ctx():
            for off in range(0, s, window):
                ids_w = jnp.asarray(input_ids[:, off:off + window])
                pos_w = off + jnp.arange(window, dtype=jnp.int32)[None, :]
                pos_w = jnp.broadcast_to(pos_w, (b, window))
                # padded rows past seq_len: positions pushed out of range so
                # their cache writes drop
                pos_w = jnp.where(pos_w < lens_d[:, None], pos_w,
                                  self.tpu_config.seq_len)
                out = fn(self.params, self.cache, ids_w, pos_w, seq_ids)
                self.cache = out["cache"]
                # keep each row's logits at its LAST real position
                idx = jnp.clip(lens_d - 1 - off, 0, window - 1)
                lg = jnp.take_along_axis(
                    out["logits_all"], idx[:, None, None], axis=1)[:, 0]
                hit = (lens_d - 1 >= off) & (lens_d - 1 < off + window)
                last_logits = jnp.where(hit[:, None],
                                        lg.astype(jnp.float32), last_logits)
            tokens = self._sample_logits(last_logits, sampling_params)
        return {"tokens": tokens, "cache": self.cache}

    def _sample_logits(self, logits, sampling_params):
        if "sample_last" not in self._compiled:
            from ..ops import sampling as sampling_ops
            cfg = self.tpu_config

            def fn(lg, sp, rng):
                return sampling_ops.sample_dp(
                    lg, cfg.on_device_sampling_config, sp, rng)
            self._compiled["sample_last"] = jax.jit(fn)
        return self._compiled["sample_last"](logits, sampling_params,
                                             self._next_rng())

    def _run_decode(self, input_ids: np.ndarray, position_ids: np.ndarray,
                    seq_ids: Optional[np.ndarray] = None, sampling_params=None,
                    adapter_ids=None, rope_position_ids=None):
        b = input_ids.shape[0]
        if seq_ids is None:
            seq_ids = np.arange(b, dtype=np.int32)
        elif (not self.tpu_config.is_continuous_batching
              and not np.array_equal(np.asarray(seq_ids), np.arange(b))):
            # the decode graph skips the cache row-gather under this static
            # config (model_base._layer_body), so non-identity seq_ids would
            # silently read the wrong rows — reject at the boundary
            raise ValueError("non-identity seq_ids require "
                             "is_continuous_batching=True")
        with self._run_span("decode", b * input_ids.shape[1]):
            needed = int(np.max(np.asarray(position_ids))) + input_ids.shape[1]
            self._check_decode_fits(needed)
            kv_bucket = self._kv_bucket(needed) or 0
            fn = self.get_compiled(TOKEN_GENERATION_MODEL_TAG, kv_bucket)
            self._note_jit("decode", kv_bucket, input_ids.shape)
            if sampling_params is None:
                sampling_params = self._default_sampling_params(b)
            if self.snapshot.enabled:
                self.snapshot.save_step({"input_ids": input_ids,
                                         "position_ids": position_ids,
                                         "seq_ids": seq_ids})
            if rope_position_ids is not None:
                rope_position_ids = jnp.asarray(rope_position_ids)
            with self._mesh_ctx():
                out = fn(self.params, self.cache, jnp.asarray(input_ids),
                         jnp.asarray(position_ids), jnp.asarray(seq_ids),
                         sampling_params, self._next_rng(), adapter_ids,
                         self.replacements, rope_position_ids)
            self.cache = out["cache"]
        return out

    def _run_decode_loop(self, first_tokens: np.ndarray, positions: np.ndarray,
                         num_steps: int, seq_ids: Optional[np.ndarray] = None,
                         sampling_params=None, adapter_ids=None,
                         rope_position_ids=None):
        b = first_tokens.shape[0]
        if seq_ids is None:
            seq_ids = np.arange(b, dtype=np.int32)
        elif (not self.tpu_config.is_continuous_batching
              and not np.array_equal(np.asarray(seq_ids), np.arange(b))):
            # same boundary guard as _run_decode: without continuous
            # batching the scanned decode graph skips the cache row-gather,
            # so non-identity seq_ids would silently read the wrong rows
            raise ValueError("non-identity seq_ids require "
                             "is_continuous_batching=True")
        with self._run_span("decode_loop", b * num_steps):
            needed = int(np.max(np.asarray(positions))) + num_steps
            self._check_decode_fits(needed)
            loop_bucket = (num_steps, self._kv_bucket(needed))
            fn = self.get_compiled("decode_loop", loop_bucket)
            self._note_jit("decode_loop", loop_bucket, first_tokens.shape)
            if sampling_params is None:
                sampling_params = self._default_sampling_params(b)
            if rope_position_ids is not None:
                rope_position_ids = jnp.asarray(rope_position_ids)
            with self._mesh_ctx():
                out = fn(self.params, self.cache, jnp.asarray(first_tokens),
                         jnp.asarray(positions), jnp.asarray(seq_ids),
                         sampling_params, self._next_rng(),
                         num_steps=num_steps, adapter_ids=adapter_ids,
                         rope_position_ids=rope_position_ids)
            self.cache = out["cache"]
        return out

    # ------------------------------------------------------------------
    # generation (reference: utils/hf_adapter.py _sample loop :139-258 +
    # NeuronBaseForCausalLM._get_model_outputs routing :3549-3735)
    # ------------------------------------------------------------------
    def _generate_repadded(self, input_ids: np.ndarray, **kw
                           ) -> Dict[str, Any]:
        """Batch-mismatch host shim (reference: model_wrapper.py
        ``_forward_with_pad`` :574-703 + sub-batching :1315-1440).

        b < batch bucket: pad every batchful input by REPEATING ROW 0 —
        pad rows recompute row 0's data and rewrite its cache rows with
        identical values, so they are harmless (the reference repeats the
        first batchline for exactly this reason); outputs are sliced back.
        b > max compiled batch: split into compiled-batch sub-batches run
        sequentially and re-concatenated. No seq_ids sort is needed: the
        decode graph addresses cache rows BY seq_id (gather), so request
        order is free."""
        b_in = input_ids.shape[0]
        cfg = self.tpu_config
        # explicit per-row kwargs — a shape heuristic would misclassify e.g.
        # a multi-valued eos_token_id list whose length happens to equal b
        per_row = ("attention_mask", "sampling_params", "teacher_tokens",
                   "adapter_ids", "image_mask", "rope_position_ids",
                   "decode_rope_start", "image_embeds")

        def _batchful(k, x):
            return k in per_row and x is not None

        if b_in > cfg.batch_size:
            # sub-batching: compiled-batch chunks (last padded recursively)
            outs = []
            for lo in range(0, b_in, cfg.batch_size):
                hi = min(lo + cfg.batch_size, b_in)
                sub = {k: (np.asarray(v)[lo:hi] if _batchful(k, v) else v)
                       for k, v in kw.items()}
                # deepstack stacks batch on axis 1
                if kw.get("deepstack_embeds") is not None:
                    sub["deepstack_embeds"] = \
                        np.asarray(kw["deepstack_embeds"])[:, lo:hi]
                outs.append(self.generate(input_ids[lo:hi], **sub))

            def _cat(key):
                # chunks may stop at different EOS points: right-pad each
                # chunk to the widest before concatenating (0 = the
                # post-EOS fill convention)
                arrs = [np.asarray(o[key]) for o in outs]
                w = max(a.shape[1] for a in arrs)
                return np.concatenate(
                    [np.pad(a, ((0, 0), (0, w - a.shape[1])))
                     for a in arrs])

            merged = {"sequences": _cat("sequences"),
                      "generated": _cat("generated")}
            if "seq_lens" in outs[0]:
                merged["seq_lens"] = np.concatenate(
                    [np.asarray(o["seq_lens"]) for o in outs])
            for extra in ("ttft_s",):
                if extra in outs[0]:
                    merged[extra] = outs[0][extra]
            if kw.get("return_logits") and "logits" in outs[0]:
                # keep the per-step list contract: step i concatenates all
                # chunks' step-i logits; chunks that stopped early repeat
                # their final step
                n_steps = max(len(o["logits"]) for o in outs)
                merged["logits"] = [
                    np.concatenate([np.asarray(
                        o["logits"][min(si, len(o["logits"]) - 1)])
                        for o in outs], axis=0)
                    for si in range(n_steps)]
            return merged

        pad = autobucketing.get_target_bucket(self.batch_buckets,
                                              b_in, kind="batch") - b_in

        def _pad0(k, x):
            if not _batchful(k, x):
                return x
            a = np.asarray(x)
            return np.concatenate([a, np.repeat(a[:1], pad, axis=0)])

        kw2 = {k: _pad0(k, v) for k, v in kw.items()}
        if kw.get("deepstack_embeds") is not None:
            ds = np.asarray(kw["deepstack_embeds"])
            kw2["deepstack_embeds"] = np.concatenate(
                [ds, np.repeat(ds[:, :1], pad, axis=1)], axis=1)
        padded_ids = np.concatenate(
            [input_ids, np.repeat(input_ids[:1], pad, axis=0)])
        out = self.generate(padded_ids, **kw2)
        res = dict(out)
        res["sequences"] = out["sequences"][:b_in]
        res["generated"] = out["generated"][:b_in]
        if "seq_lens" in out:
            res["seq_lens"] = np.asarray(out["seq_lens"])[:b_in]
        if "logits" in out:
            res["logits"] = [np.asarray(lg)[:b_in] for lg in out["logits"]]
        return res

    def generate(self, input_ids: np.ndarray,
                 attention_mask: Optional[np.ndarray] = None,
                 max_new_tokens: int = 128,
                 eos_token_id: Optional[int] = None,
                 sampling_params: Optional[np.ndarray] = None,
                 return_logits: bool = False,
                 teacher_tokens: Optional[np.ndarray] = None,
                 adapter_ids: Optional[np.ndarray] = None,
                 image_embeds=None,
                 image_mask: Optional[np.ndarray] = None,
                 deepstack_embeds=None,
                 rope_position_ids: Optional[np.ndarray] = None,
                 decode_rope_start: Optional[np.ndarray] = None
                 ) -> Dict[str, Any]:
        """Greedy/sampled generation. input_ids (B, S) right-padded;
        attention_mask (B, S) marks real tokens. Returns sequences including
        the prompt (HF convention).

        teacher_tokens (B, T): teacher-forcing for logit-matching accuracy —
        feed these instead of the sampled tokens (reference:
        utils/accuracy.py logit flow re-feeds golden tokens).
        adapter_ids (B,): per-request LoRA adapter slot (multi-LoRA serving,
        reference: modules/lora_serving/).
        rope_position_ids (B, S, 3) + decode_rope_start (B, 3): M-RoPE
        3-axis positions for the prompt and the first generated token
        (qwen2-VL; reference: rotary_position_ids plumbing,
        models/model_base.py:566-578). Decode advances all axes by 1/token."""
        input_ids = np.asarray(input_ids)
        b, s = input_ids.shape
        if b not in self.batch_buckets:
            # serving host shim (reference: model_wrapper.py:520-703
            # repeat-first-batchline pad + :1315-1440 sub-batching): pad a
            # short batch to the smallest BATCH bucket by repeating row 0
            # (2-D bucketing: the ladder may hold sizes below the full
            # compiled batch), or split an oversized batch into
            # compiled-batch chunks
            return self._generate_repadded(
                input_ids, attention_mask=attention_mask,
                max_new_tokens=max_new_tokens, eos_token_id=eos_token_id,
                sampling_params=sampling_params, return_logits=return_logits,
                teacher_tokens=teacher_tokens, adapter_ids=adapter_ids,
                image_embeds=image_embeds, image_mask=image_mask,
                deepstack_embeds=deepstack_embeds,
                rope_position_ids=rope_position_ids,
                decode_rope_start=decode_rope_start)
        if adapter_ids is not None:
            adapter_ids = jnp.asarray(np.asarray(adapter_ids, np.int32))
        if attention_mask is None:
            attention_mask = np.ones_like(input_ids)
        seq_lens = attention_mask.astype(np.int32).sum(axis=1)
        if self.cache is None:
            self.init_cache()
        if self.params is None:
            raise RuntimeError("load_weights() or init_random_weights() first")
        if sampling_params is not None:
            sampling_params = jnp.asarray(sampling_params)
        if self.snapshot.enabled:
            self.snapshot.on_request()

        if teacher_tokens is not None:
            # teacher forcing can feed at most T tokens, producing T+1 steps
            max_new_tokens = min(max_new_tokens,
                                 np.asarray(teacher_tokens).shape[1] + 1)
        wcte = self.tpu_config.windowed_context_encoding
        if wcte and s > wcte:
            # windowed CTE pads to a window multiple instead of a ctx bucket
            bucket = -(-s // wcte) * wcte
        else:
            wcte = None
            bucket = autobucketing.get_target_bucket(self.ctx_buckets, s,
                                                     kind="ctx")
        padded = np.zeros((b, bucket), input_ids.dtype)
        padded[:, :s] = input_ids
        padded_img_mask = None
        if image_mask is not None:
            padded_img_mask = np.zeros((b, bucket), bool)
            padded_img_mask[:, :s] = np.asarray(image_mask, bool)
        padded_rope = None
        if rope_position_ids is not None:
            padded_rope = np.zeros((b, bucket, 3), np.int32)
            padded_rope[:, :s] = np.asarray(rope_position_ids, np.int32)
        max_total = int(seq_lens.max()) + max_new_tokens
        if max_total > self.tpu_config.seq_len:
            max_new_tokens = self.tpu_config.seq_len - int(seq_lens.max())
            if max_new_tokens <= 0:
                raise ValueError("prompt exceeds seq_len")

        t0 = time.perf_counter()
        if wcte:
            if (image_embeds is not None or adapter_ids is not None
                    or rope_position_ids is not None or return_logits):
                raise NotImplementedError(
                    "windowed context encoding supports plain text prompts "
                    "without logits output")
            out = self._run_prefill_windowed(padded, seq_lens, wcte,
                                             sampling_params=sampling_params)
        else:
            out = self._run_prefill(padded, seq_lens,
                                    sampling_params=sampling_params,
                                    adapter_ids=adapter_ids,
                                    image_embeds=image_embeds,
                                    deepstack_embeds=deepstack_embeds,
                                    image_mask=padded_img_mask,
                                    rope_position_ids=padded_rope)
        first = out["tokens"]                     # device array (B,)
        try:
            first.copy_to_host_async()
        except AttributeError:
            pass
        logits_trace = [np.asarray(out["logits"])] if return_logits and "logits" in out else []

        # eos_token_id: int or list of ints (HF allows multiple stop ids)
        eos_ids = (None if eos_token_id is None
                   else np.atleast_1d(np.asarray(eos_token_id, dtype=np.int64)))
        # tokens stay ON DEVICE through the loop — a synchronous fetch per
        # step would drain the dispatch queue and leave the chip idle while
        # the host loops, so EOS checks run one chunk late on an overlapped
        # async copy (reference async_execution.py hides the same latency
        # with double-buffering).
        collected = [first[:, None]]
        pending = first[:, None]                  # device tokens not yet eos-checked
        ttft = None
        positions = seq_lens.astype(np.int32)  # position of the token just sampled
        rpos = (np.asarray(decode_rope_start, np.int32)
                if decode_rope_start is not None else None)
        n_generated = 1
        eos_seen = np.zeros((b,), bool) if eos_ids is not None else None
        chunk = max(self.tpu_config.decode_chunk_tokens, 1)
        while n_generated < max_new_tokens:
            remaining = max_new_tokens - n_generated
            # only the full-chunk loop graph is warmed; a partial remainder
            # would trigger a fresh XLA compile mid-request, so finish it with
            # the (already-compiled) single-step graph instead
            n = chunk if remaining >= chunk else 1
            cur = collected[-1][:, -1]
            if teacher_tokens is not None:
                cur = np.asarray(teacher_tokens[:, n_generated - 1],
                                 dtype=np.int32)
                n = 1
            if n == 1 or return_logits:
                o = self._run_decode(
                    cur[:, None], positions[:, None],
                    sampling_params=sampling_params, adapter_ids=adapter_ids,
                    rope_position_ids=(rpos[:, None, :] if rpos is not None
                                       else None))
                new = o["tokens"].reshape(b, 1)
                if return_logits and "logits" in o:
                    logits_trace.append(np.asarray(o["logits"]))
                positions = positions + 1
                if rpos is not None:
                    rpos = rpos + 1
                n_generated += 1
            else:
                o = self._run_decode_loop(cur, positions, n,
                                          sampling_params=sampling_params,
                                          adapter_ids=adapter_ids,
                                          rope_position_ids=rpos)
                new = o["tokens"]
                positions = positions + n
                if rpos is not None:
                    rpos = rpos + n
                n_generated += n
            try:
                new.copy_to_host_async()
            except AttributeError:
                pass
            collected.append(new)
            if ttft is None:
                # first token reached the host while the next chunk computes
                np.asarray(first)
                ttft = time.perf_counter() - t0
            if eos_seen is not None:
                eos_seen |= np.isin(np.asarray(pending), eos_ids).any(axis=1)
                pending = new
                if eos_seen.all():
                    break

        if ttft is None:
            np.asarray(first)
            ttft = time.perf_counter() - t0
        collected = [np.asarray(c) for c in collected]
        result = _finalize_generation(input_ids, collected, eos_ids, ttft,
                                      seq_lens)
        if return_logits:
            result["logits"] = logits_trace
        return result

    def reset(self):
        """Clear KV cache between requests."""
        self.init_cache()
        return self

    # ------------------------------------------------------------------
    # observability (reference: SURVEY §5)
    # ------------------------------------------------------------------
    def load_tensor_replacements(self, source_path: Optional[str] = None):
        """Build the golden-injection arrays from the configured .npz
        (reference: utils/tensor_replacement/registry.py + wiring
        model_wrapper.py:481-518). The npz holds one (L,B,T,H) array per
        target point; ``layers`` restricts which layer indices replace."""
        trc = self.tpu_config.tensor_replacement_config
        path = source_path or (trc.source_path if trc else None)
        if path is None:
            raise ValueError("tensor_replacement_config.source_path required")
        data = np.load(path)
        L = self.spec.num_layers
        layer_on = np.zeros((L,), bool)
        if trc and trc.layers is not None:
            layer_on[np.asarray(trc.layers, int)] = True
        else:
            layer_on[:] = True
        rep: Dict[str, Any] = {}
        targets = (trc.targets if trc and trc.targets else list(data.files))
        for name in targets:
            arr = np.asarray(data[name])
            if arr.shape[0] != L:
                raise ValueError(f"replacement {name!r} leading dim "
                                 f"{arr.shape[0]} != num_layers {L}")
            rep[name] = jnp.asarray(arr)
            rep[name + "_on"] = jnp.asarray(layer_on)
        self.replacements = rep
        return self

    # ------------------------------------------------------------------
    # multi-LoRA serving (reference: modules/lora_serving/)
    # ------------------------------------------------------------------
    def load_lora_adapters(self, ckpt_paths: Optional[Dict[str, str]] = None):
        """Load PEFT adapter checkpoints into slots 1..N (slot 0 stays the
        zero adapter = base model). ckpt_paths {name: dir}; defaults to
        tpu_config.lora_config.lora_ckpt_paths. Returns {name: slot}."""
        from ..modules import lora as lora_mod
        lc = self.tpu_config.lora_config
        if self.spec.lora is None or lc is None:
            raise ValueError("lora_config must be set on the TpuConfig")
        ckpt_paths = ckpt_paths or lc.lora_ckpt_paths or {}
        if self.params is None:
            raise RuntimeError("load_weights() first")
        slots: Dict[str, int] = {}
        for slot, (name, path) in enumerate(ckpt_paths.items(), start=1):
            if slot >= self.spec.lora.max_loras:
                raise ValueError(f"adapter {name!r}: slot {slot} exceeds "
                                 f"max_loras {self.spec.lora.max_loras}")
            self.set_lora_adapter(slot, path)
            slots[name] = slot
        self.lora_slots = slots
        return slots

    def lora_adapter_arrays(self, path: str) -> Dict[str, Any]:
        """Load + shard-transform one PEFT adapter dir into the host-side
        stacked layout: ``{module: (A (L,in,r), B (L,r,out))}`` — the
        same GQA head pad/replicate transforms the base weights get, so
        the arrays are slot-writable as-is (:meth:`write_lora_slot`).
        This is the pure LOAD half of the old ``set_lora_adapter``; the
        serving adapter pool (serving/lora_pool.py) caches these arrays
        host-side for spill/restore without re-reading the checkpoint."""
        from ..modules import lora as lora_mod
        from ..parallel.layers import place_q_weight, replicate_kv_weight
        sd, acfg = lora_mod.load_peft_adapter(path)
        lo = self.spec.lora
        g = self.spec.gqa
        D = self.spec.head_dim
        dims = {
            "q_proj": (self.spec.hidden_size, self.spec.q_size),
            "k_proj": (self.spec.hidden_size, self.spec.kv_size),
            "v_proj": (self.spec.hidden_size, self.spec.kv_size),
            "o_proj": (self.spec.q_size, self.spec.hidden_size),
            "gate_proj": (self.spec.hidden_size, self.spec.intermediate_size),
            "up_proj": (self.spec.hidden_size, self.spec.intermediate_size),
            "down_proj": (self.spec.intermediate_size, self.spec.hidden_size),
        }
        transforms = {
            "q_proj": lambda b: place_q_weight(b, g, D, -1),
            "k_proj": lambda b: replicate_kv_weight(b, g, D, -1),
            "v_proj": lambda b: replicate_kv_weight(b, g, D, -1),
        }
        arrays: Dict[str, Any] = {}
        for mod in lo.target_modules:
            d_in, d_out = dims[mod]
            # o_proj's A consumes the padded head layout on its input side
            in_transform = (lambda a: place_q_weight(a, g, D, 0)) \
                if mod == "o_proj" else None
            arrays[mod] = lora_mod.adapter_layer_arrays(
                sd, acfg, self.spec.num_layers, mod, d_in, d_out, lo.rank,
                out_transform=transforms.get(mod), in_transform=in_transform)
        return arrays

    def write_lora_slot(self, slot: int, arrays: Dict[str, Any]):
        """Write pre-transformed adapter ``arrays`` ({module: (A, B)},
        :meth:`lora_adapter_arrays` layout) into ``slot`` of the stacked
        device params — the pure WRITE half of adapter loading, so a
        caller can make the swap transactional by snapshotting the
        touched leaves first (serving/lora_pool.py does)."""
        from ..modules import lora as lora_mod
        for mod, (a, b) in arrays.items():
            lora_mod.set_adapter_slot(self.params, "layers", slot, mod, a, b)
        return self

    def set_lora_adapter(self, slot: int, path: str):
        """Dynamic multi-LoRA: (re)load one adapter dir into ``slot``
        (reference: host-side adapter swap, models/model_base.py:3349-3356)."""
        return self.write_lora_slot(slot, self.lora_adapter_arrays(path))


def _flatten_tree(tree: Dict[str, Any], prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in tree.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten_tree(v, key + "."))
        else:
            out[key] = v
    return out


def _unflatten_tree(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in flat.items():
        parts = k.split(".")
        node = out
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def _finalize_generation(input_ids: np.ndarray, collected, eos_ids,
                         ttft: float, seq_lens: np.ndarray) -> Dict[str, Any]:
    """Shared tail of the generation loops: concat steps, trim past the first
    eos per row (tokens after eos are garbage by HF convention), assemble the
    result dict."""
    gen = np.concatenate(collected, axis=1)
    if eos_ids is not None:
        for i in range(gen.shape[0]):
            hits = np.where(np.isin(gen[i], eos_ids))[0]
            if hits.size:
                gen[i, hits[0] + 1:] = eos_ids[0]
    return {"sequences": np.concatenate([input_ids, gen], axis=1),
            "generated": gen, "ttft_s": ttft, "seq_lens": seq_lens}


class PagedCausalLMApplication(CausalLMApplication):
    """Paged-KV (block layout) application with prefix caching
    (reference: BlockKVCacheManager + vLLM-facing surface;
    enabled by ``is_block_kv_layout`` / ``is_prefix_caching``,
    models/config.py:277-317).

    One jitted graph (model_base.paged_forward_step) serves prefill,
    prefix-cached continuation and decode; the host side owns the block
    allocator and tables.
    """

    def init_cache(self):
        from ..modules.block_kv_cache import (BlockKVCacheManager,
                                              init_block_cache, pool_spec,
                                              window_pool_spec)
        cfg = self.tpu_config
        # heads a page for attention, a latent row a token for MLA
        bspec = pool_spec(self.spec, cfg.pa_num_blocks, cfg.pa_block_size)
        self.kv_mgr = BlockKVCacheManager(
            bspec, self.mesh, enable_prefix_caching=cfg.is_prefix_caching)
        # single owner of the live (donated) buffers is the application; the
        # manager keeps allocator + tables only (its .cache would become a
        # stale donated alias after the first step)
        self.cache = self.kv_mgr.cache
        self.kv_mgr.cache = None
        if self.spec.window_pool:
            # the window layers' pool: a ring of pages a batch slot, sized
            # from the spec, the rows and the widest warmed step
            ring = init_block_cache(
                window_pool_spec(self.spec, self.state_slots,
                                 cfg.pa_block_size, max(self.warm_widths)),
                self.mesh)
            self.cache.update(k_w=ring["k"], v_w=ring["v"])
        if self.spec.sparse is not None:
            # a learned sparse selection's index keys: a THIRD pool on the
            # same block table (a block is block_size tokens of K, V and
            # index keys of every layer), in the same donated dict
            from ..modules.block_kv_cache import (index_pool_shape,
                                                  init_index_pool)
            self.cache["k_idx"] = init_index_pool(
                index_pool_shape(self.spec, cfg.pa_num_blocks,
                                 cfg.pa_block_size),
                self.spec.kv_dtype, self.mesh)
        if self.spec.ssm is not None:
            # the second per-sequence cache: the kind's conv tails + fp32
            # state, one SLOT per batch row beside the KV pool, in the same
            # donated dict so the step graphs update both in place
            from ..modules.ssm import init_ssm_state
            self.cache.update(init_ssm_state(
                self.spec.ssm, self.spec.num_ssm_layers, self.state_slots,
                self.spec.dtype, self.mesh))
        # static block-table width for the jitted graphs
        self.max_blocks = bspec.blocks_for(cfg.seq_len)
        # 2-D prefix x prefill bucketing: per-call block-table widths
        # (reference: autobucketing.py:22-64, selection
        # model_wrapper.py:923-1045)
        self._bt_buckets = autobucketing.block_table_buckets(
            cfg, self.max_blocks)
        return self

    def _jit_paged(self):
        fn = _named_partial(model_base.paged_forward_step, self.spec,
                            self.tpu_config)
        return jax.jit(fn, donate_argnums=(1,))

    @functools.cached_property
    def _decode_ids_sharding(self):
        return NamedSharding(self.mesh, P())

    @property
    def state_slots(self) -> int:
        """Per-sequence slots beside the KV pool: one per batch row for a
        recurrent/hybrid stack (its state) and for a stack with a window
        pool (its ring of pages: ``DecoderSpec.window_pool``); a full-batch
        step's rows ARE the slots. 0 for any other attention stack."""
        return (self.tpu_config.batch_size
                if self.spec.ssm is not None or self.spec.window_pool else 0)

    @property
    def window_ring_pages(self) -> int:
        """Pages of a batch slot's ring in the window layers' pool (0: the
        stack has none)."""
        if self.cache is None or "k_w" not in self.cache:
            return 0
        return self.cache["k_w"].shape[1] // self.state_slots

    @property
    def warm_widths(self) -> List[int]:
        """Token widths a paged step runs at, ascending: the decode step's
        1, the chunked-prefill tile and the prefill buckets (what
        ``precompile`` warms and a window pool's ring is sized from)."""
        cfg = self.tpu_config
        widths = {1, *self.ctx_buckets}
        if cfg.is_chunked_prefill and cfg.chunked_prefill_config is not None:
            widths.add(cfg.chunked_prefill_config.kernel_q_tile_size)
        return sorted(widths)

    @property
    def prefill_row_buckets(self) -> List[int]:
        """Row ladder of a two-phase prefill-chunk dispatch, two rungs
        ``[r_min, batch_size]``: a chunk carrying ``r_min`` prompts or
        fewer runs ``r_min`` rows, anything more the full batch (the
        adapter packs only what fills at least half of it). ``r_min``
        is 1, or the extent of the mesh axis the batch rows shard over
        ("dp"), the smallest row count that axis divides. Prefill chunks
        ONLY: decode, spec-verify and ragged dispatches pad to
        ``batch_buckets`` (the full batch)."""
        full = self.tpu_config.batch_size
        return sorted({min(self.mesh.shape[AXIS_DP], full), full})

    # -- positionally coupled sampling (ops/sampling.coupled_sample) -------
    def _coupled_sampling(self) -> bool:
        sc = self.tpu_config.on_device_sampling_config
        return (sc is not None and sc.do_sample
                and sc.stream_seed is not None)

    def _stream_seeds(self, row_seeds, batch: int):
        """Gate the per-row seed input of the paged graph family: None
        unless the coupled stream is on (an absent optional arg is an
        empty pytree, so off-knob graphs stay byte-identical). The
        serving adapters always thread their per-request seeds; this
        gate is what keeps greedy configs on the legacy graphs."""
        if not self._coupled_sampling():
            return None
        if row_seeds is None:
            # a host buffer, not jnp.zeros: that is a device program per
            # row count, and the warm-up walk dispatches step programs only
            row_seeds = np.zeros((batch,), np.int32)
        return jnp.asarray(row_seeds, jnp.int32)

    def _lora_adapter_ids(self, adapter_ids):
        """Gate the per-row LoRA slot input of the paged graph family:
        None (nothing attached a pool) keeps every graph byte-identical
        to a LoRA-free build — an absent optional arg is an empty pytree,
        exactly the ``_stream_seeds`` off-knob pattern. Negative ids
        clamp to slot 0 (the pinned zero adapter = base model)."""
        if adapter_ids is None:
            return None
        if self.spec.lora is None:
            raise ValueError(
                "adapter_ids passed but the model was built without "
                "lora_config — set TpuConfig.lora_config")
        return jnp.asarray(np.maximum(np.asarray(adapter_ids, np.int32), 0))

    def _jit_paged_loop(self, num_steps: int):
        fn = _named_partial(model_base.paged_decode_loop, self.spec,
                            self.tpu_config, num_steps=num_steps)
        return jax.jit(fn, donate_argnums=(1,))

    def _run_paged_loop(self, first_tokens, positions, block_table,
                        num_steps: int, sampling_params=None,
                        row_seeds=None, adapter_ids=None):
        # horizon guard: the fused loop writes KV at positions
        # [p, p+num_steps); past seq_len the in-graph slot advance would
        # index past the block table (mirrors _run_decode_loop's guard)
        self._check_decode_fits(
            int(np.max(np.asarray(positions))) + num_steps)
        with self._run_span("paged_loop", first_tokens.shape[0] * num_steps):
            key = ("paged_loop", num_steps)
            if key not in self._compiled:
                self._compiled[key] = self._jit_paged_loop(num_steps)
            aids = self._lora_adapter_ids(adapter_ids)
            self._note_jit("paged_loop", num_steps,
                           (first_tokens.shape[0], block_table.shape[1],
                            aids is not None))
            if sampling_params is None:
                sampling_params = self._default_sampling_params(
                    first_tokens.shape[0])
            seeds = self._stream_seeds(row_seeds, first_tokens.shape[0])
            kw = {"row_seeds": seeds} if seeds is not None else {}
            if aids is not None:
                kw["adapter_ids"] = aids
            with self._mesh_ctx():
                out = self._compiled[key](
                    self.params, self.cache, jnp.asarray(first_tokens),
                    jnp.asarray(positions), jnp.asarray(block_table),
                    sampling_params, self._next_rng(), **kw)
            self.cache = out["cache"]
        return out

    def get_compiled(self, tag: str, bucket: int = 0):
        if tag == "paged_forward":
            key = (tag, bucket)
            if key not in self._compiled:
                self._compiled[key] = self._jit_paged()
            return self._compiled[key]
        return super().get_compiled(tag, bucket)

    # -- speculative serving graphs (serving/speculation/) -----------------
    def _jit_spec_draft(self, num_steps: int):
        fn = _named_partial(model_base.paged_spec_draft_loop, self.spec,
                            self.tpu_config, num_steps=num_steps)
        return jax.jit(fn, donate_argnums=(1,))

    def _jit_spec_verify(self, want_hidden: bool):
        fn = _named_partial(model_base.paged_spec_verify, self.spec,
                            self.tpu_config, want_hidden=want_hidden)
        return jax.jit(fn, donate_argnums=(1,))

    def _run_spec_draft(self, first_tokens, positions, block_table, widths,
                        num_steps: int, sampling_params=None,
                        row_seeds=None, adapter_ids=None):
        """Masked greedy-k self-draft pass (one fused dispatch; see
        model_base.paged_spec_draft_loop). Frozen rows (width already
        reached) write nothing, so the per-row clamp in ``widths`` bounds
        every KV write."""
        self._check_decode_fits(
            int(np.max(np.asarray(positions) + np.asarray(widths) - 1)))
        with self._run_span("spec_draft", first_tokens.shape[0] * num_steps):
            key = ("spec_draft", num_steps)
            if key not in self._compiled:
                self._compiled[key] = self._jit_spec_draft(num_steps)
            aids = self._lora_adapter_ids(adapter_ids)
            self._note_jit("spec_draft", num_steps,
                           (first_tokens.shape[0], block_table.shape[1],
                            aids is not None))
            if sampling_params is None:
                sampling_params = self._default_sampling_params(
                    first_tokens.shape[0])
            seeds = self._stream_seeds(row_seeds, first_tokens.shape[0])
            kw = {"row_seeds": seeds} if seeds is not None else {}
            if aids is not None:
                kw["adapter_ids"] = aids
            with self._mesh_ctx():
                out = self._compiled[key](
                    self.params, self.cache, jnp.asarray(first_tokens),
                    jnp.asarray(positions), jnp.asarray(block_table),
                    jnp.asarray(widths), sampling_params, self._next_rng(),
                    **kw)
            self.cache = out["cache"]
        return out

    def _run_spec_verify(self, input_ids, position_ids, slot_mapping,
                         block_table, widths, want_hidden: bool = False,
                         sampling_params=None, row_seeds=None,
                         adapter_ids=None):
        """Speculative verify dispatch: ONE ragged k+1-wide paged forward
        with in-graph exact-match acceptance (model_base.paged_spec_verify
        — greedy argmax, or the coupled sampled draw when the stream-seed
        knob is on). ``input_ids`` may be a device array — drafts never
        round-trip through the host."""
        self._check_decode_fits(
            int(np.max(np.asarray(position_ids)[:, 0]
                       + np.asarray(widths))))
        with self._run_span("spec_verify", input_ids.shape[0]):
            key = ("spec_verify", input_ids.shape[1], want_hidden)
            if key not in self._compiled:
                self._compiled[key] = self._jit_spec_verify(want_hidden)
            aids = self._lora_adapter_ids(adapter_ids)
            self._note_jit("spec_verify", input_ids.shape[1],
                           (input_ids.shape, block_table.shape,
                            aids is not None))
            seeds = self._stream_seeds(row_seeds, input_ids.shape[0])
            kw = {}
            if seeds is not None:
                if sampling_params is None:
                    sampling_params = self._default_sampling_params(
                        input_ids.shape[0])
                kw = {"sampling_params": sampling_params, "row_seeds": seeds}
            if aids is not None:
                kw["adapter_ids"] = aids
            with self._mesh_ctx():
                out = self._compiled[key](
                    self.params, self.cache, jnp.asarray(input_ids),
                    jnp.asarray(position_ids), jnp.asarray(slot_mapping),
                    jnp.asarray(block_table), jnp.asarray(widths), **kw)
            self.cache = out["cache"]
        return out

    # -- ragged unified dispatch (serving/ragged/) -------------------------
    def _jit_ragged(self, want_hidden: bool):
        fn = _named_partial(model_base.paged_ragged_step, self.spec,
                            self.tpu_config, want_hidden=want_hidden)
        return jax.jit(fn, donate_argnums=(1,))

    def _run_ragged(self, input_ids, position_ids, slot_mapping,
                    block_table, widths, emit_modes,
                    want_hidden: bool = False, sampling_params=None,
                    row_seeds=None, adapter_ids=None):
        """ONE ragged mixed dispatch (model_base.paged_ragged_step): rows
        mix decode steps, prefill chunks and speculative verify windows,
        each at its own offset over its own block table. ``input_ids``
        may be a device array — verify-row drafts never round-trip
        through the host."""
        self._check_decode_fits(
            int(np.max(np.asarray(position_ids)[:, 0]
                       + np.asarray(widths))))
        with self._run_span("ragged", input_ids.shape[0]):
            key = ("ragged", input_ids.shape[1], want_hidden)
            if key not in self._compiled:
                self._compiled[key] = self._jit_ragged(want_hidden)
            aids = self._lora_adapter_ids(adapter_ids)
            self._note_jit("ragged", input_ids.shape[1],
                           (input_ids.shape, block_table.shape,
                            aids is not None))
            if sampling_params is None:
                sampling_params = self._default_sampling_params(
                    input_ids.shape[0])
            seeds = self._stream_seeds(row_seeds, input_ids.shape[0])
            kw = {"row_seeds": seeds} if seeds is not None else {}
            if aids is not None:
                kw["adapter_ids"] = aids
            with self._mesh_ctx():
                out = self._compiled[key](
                    self.params, self.cache, jnp.asarray(input_ids),
                    jnp.asarray(position_ids), jnp.asarray(slot_mapping),
                    jnp.asarray(block_table), jnp.asarray(widths),
                    jnp.asarray(emit_modes), sampling_params, self._next_rng(),
                    **kw)
            self.cache = out["cache"]
        return out

    def _bt_width(self, b: int) -> int:
        """Smallest block-table width bucket covering every live row's
        blocks (2-D prefix x prefill bucket selection)."""
        return self._bt_width_for(range(b))

    def _bt_width_for(self, seq_ids) -> int:
        live = max((len(self.kv_mgr.tables.get(i, ())) for i in seq_ids),
                   default=1)
        return autobucketing.get_target_bucket(self._bt_buckets,
                                               max(live, 1),
                                               kind="block_table")

    def _run_paged(self, input_ids, position_ids, slot_mapping, block_table,
                   last_idx, sampling_params=None, row_seeds=None,
                   adapter_ids=None, state_slots=None):
        """``state_slots`` (rows,): recurrent stacks only, the state slot
        of each row of a dispatch with FEWER rows than slots (the one-row
        chunk); a full-batch dispatch lays its rows out in slot order and
        passes none (``model_base.run_layers_ssm``)."""
        rec = trace_mod.get_recorder()
        # the phases of the host's side, as slices under run.paged:
        # prep.inputs (entry to the last host->device placement), prep.rng,
        # prep.enqueue (the jit call to the return of its dispatch). The
        # mesh context opens where it always did, before the placements of
        # the call's own arguments, and closes after the call
        with self._run_span("paged", input_ids.shape[0]), \
                contextlib.ExitStack() as mesh:
            with rec.span("prep.inputs", cat="app"):
                fn = self.get_compiled("paged_forward")
                aids = self._lora_adapter_ids(adapter_ids)
                if input_ids.shape[1] == 1 and not isinstance(input_ids,
                                                              jax.Array):
                    # the decode step's ids are placed as the step hands
                    # them on (``out["next_ids"]``: committed, replicated),
                    # so a step fed from the host and one fed the previous
                    # step's output on the device are ONE executable
                    input_ids = jax.device_put(input_ids,
                                               self._decode_ids_sharding)
                # one jitted graph serves every paged call; the shape
                # signature (prefill width x table width) is what
                # distinguishes compiles
                self._note_jit("paged", input_ids.shape[1],
                               (input_ids.shape, block_table.shape,
                                aids is not None))
                if sampling_params is None:
                    sampling_params = self._default_sampling_params(
                        input_ids.shape[0])
                seeds = self._stream_seeds(row_seeds, input_ids.shape[0])
                kw = {"row_seeds": seeds} if seeds is not None else {}
                if aids is not None:
                    kw["adapter_ids"] = aids
                if state_slots is not None:
                    kw["state_slots"] = jnp.asarray(state_slots, jnp.int32)
                mesh.enter_context(self._mesh_ctx())
                ids = jnp.asarray(input_ids)
                pos = jnp.asarray(position_ids)
                slots = jnp.asarray(slot_mapping)
                table = jnp.asarray(block_table)
                last = jnp.asarray(last_idx)
            with rec.span("prep.rng", cat="app"):
                rng = self._next_rng()
            with rec.span("prep.enqueue", cat="app"), \
                    self._noting_program(ids.shape):
                out = fn(self.params, self.cache, ids, pos, slots, table,
                         last, sampling_params, rng, **kw)
                self.cache = out["cache"]
        return out

    @contextlib.contextmanager
    def _noting_program(self, shape: Tuple[int, int]):
        """Around the call of the paged program of ``shape`` (rows, width):
        if the call traced it, keep the notes of THAT trace under its shape
        (``paged_program_notes``) beside the app's."""
        traced: set = set()
        with kernel_mode.recording(traced):
            yield
        if traced:
            self._kernel_notes |= traced
            self._paged_notes[shape] = frozenset(
                traced | self._paged_notes.get(shape, frozenset()))

    def paged_program_notes(self, rows: int, width: int) -> frozenset:
        """The engagement record (``kernel_mode.note`` triples) of the paged
        program of ``rows`` x ``width`` tokens, as its own trace left it;
        empty before that program has been traced."""
        return self._paged_notes.get((rows, width), frozenset())

    def _dummy_state_slots(self, rows: int):
        """The ``state_slots`` of a dummy (warm-up) dispatch of ``rows``
        rows: slot 0 for each row of a dispatch narrower than the slots
        (every token dead, so the slot is read and written back as it
        was), None otherwise."""
        if self.state_slots and rows != self.state_slots:
            return np.zeros((rows,), np.int32)
        return None

    def warmup(self):
        """AOT-compile the paged graph at each shape it will run: the T=1
        decode step at the full batch, and every prefill window (ctx bucket
        or chunk width) at both rungs of ``prefill_row_buckets`` — the
        full batch (``generate()``, a packed chunk) and ``r_min`` rows (a
        chunk carrying one prompt). Dummy calls write nothing (all slots
        negative → dropped)."""
        if self.params is None:
            self.init_random_weights()
        if not hasattr(self, "kv_mgr") or self.cache is None:
            self.init_cache()
        cfg = self.tpu_config
        b = cfg.batch_size
        widths = self.warm_widths
        # 2-D table-width buckets: every (rows x prefill width x table
        # width) triple plus the chunked decode loop at every table width —
        # the shapes generate() and the serving adapter actually run
        chunk = max(cfg.decode_chunk_tokens, 1)
        for tw in self._bt_buckets:
            for w in widths:
                for rows in ([b] if w == 1 else self.prefill_row_buckets):
                    self._run_paged(np.zeros((rows, w), np.int32),
                                    np.zeros((rows, w), np.int32),
                                    np.full((rows, w), -1, np.int32),
                                    np.zeros((rows, tw), np.int32),  # null block
                                    np.zeros((rows,), np.int32),
                                    state_slots=self._dummy_state_slots(rows))
            if chunk > 1:
                self._run_paged_loop(np.zeros((b,), np.int32),
                                     np.zeros((b,), np.int32),
                                     np.zeros((b, tw), np.int32), chunk)
        return self

    def generate(self, input_ids: np.ndarray,
                 attention_mask: Optional[np.ndarray] = None,
                 max_new_tokens: int = 128,
                 eos_token_id: Optional[int] = None,
                 sampling_params: Optional[np.ndarray] = None,
                 return_logits: bool = False,
                 teacher_tokens: Optional[np.ndarray] = None) -> Dict[str, Any]:
        """Paged generation. Prefix-cached prompt blocks are skipped
        (not recomputed); the rest mirrors CausalLMApplication.generate."""
        from ..modules.block_kv_cache import (cut_cached_at_unwritten,
                                              slots_from_table)
        logits_trace: List[np.ndarray] = []
        input_ids = np.asarray(input_ids)
        b, s = input_ids.shape
        if b not in self.batch_buckets:
            # batch-mismatch host shim (reference: model_wrapper.py:520-703
            # + sub-batching :1315-1440) — without it a b != compiled-batch
            # request would silently jit a fresh graph mid-request
            return self._generate_repadded(
                input_ids, attention_mask=attention_mask,
                max_new_tokens=max_new_tokens, eos_token_id=eos_token_id,
                sampling_params=sampling_params,
                return_logits=return_logits, teacher_tokens=teacher_tokens)
        if teacher_tokens is not None:
            # teacher forcing can feed at most T tokens, producing T+1 steps
            teacher_tokens = np.asarray(teacher_tokens, np.int32)
            max_new_tokens = min(max_new_tokens, teacher_tokens.shape[1] + 1)
        if attention_mask is None:
            attention_mask = np.ones_like(input_ids)
        seq_lens = attention_mask.astype(np.int32).sum(axis=1)
        if self.params is None:
            raise RuntimeError("load_weights() or init_random_weights() first")
        if not hasattr(self, "kv_mgr") or self.cache is None:
            self.init_cache()
        if sampling_params is not None:
            sampling_params = jnp.asarray(sampling_params)
        eos_ids = (None if eos_token_id is None
                   else np.atleast_1d(np.asarray(eos_token_id, dtype=np.int64)))

        # --- allocate blocks; discover cached prefix per row ---
        cfg = self.tpu_config
        chunked = (cfg.is_chunked_prefill
                   and cfg.chunked_prefill_config is not None)
        cached = np.zeros((b,), np.int32)
        bsz = self.kv_mgr.spec.block_size
        batch_fresh: set = set()      # blocks first written by THIS call
        for i in range(b):
            toks = input_ids[i, :seq_lens[i]].tolist()
            blocks, c = self.kv_mgr.begin_sequence(i, toks)
            if chunked:
                # chunked prefill writes sibling rows' blocks chunk by chunk,
                # so a prefix hit on a block allocated earlier in this SAME
                # batch may read slots the sibling hasn't written yet — cut
                # the cached prefix at the first such block (shared helper
                # with the serving adapter's packed-chunk path)
                c = cut_cached_at_unwritten(blocks, c, bsz, batch_fresh)
            batch_fresh.update(blocks[c // bsz:])
            # always recompute >= 1 token so there are logits to sample from
            cached[i] = min(c, seq_lens[i] - 1)
        bt = self.kv_mgr.block_table_array(range(b), self._bt_width(b))

        # --- prefill the uncached suffixes ---
        suffix_lens = seq_lens - cached
        t_max = int(suffix_lens.max())
        chunk_w = (cfg.chunked_prefill_config.kernel_q_tile_size
                   if chunked else 0)

        def _prefill_window(off_w, width, last_idx):
            """One paged-prefill call over window [off, off+width) of each
            row's uncached suffix (off_w: (B,) per-row offsets)."""
            ids_w = np.zeros((b, width), np.int32)
            pos_w = np.zeros((b, width), np.int32)
            for i in range(b):
                lo = cached[i] + off_w[i]
                n = int(np.clip(seq_lens[i] - lo, 0, width))
                ids_w[i, :n] = input_ids[i, lo:lo + n]
                pos_w[i] = lo + np.arange(width, dtype=np.int32)
            valid = (np.arange(width)[None, :]
                     < (seq_lens - cached - off_w)[:, None])
            # padded tail positions: writes dropped via negative slots,
            # outputs never sampled
            slot_pos = np.where(valid, pos_w, -1)
            slots = slots_from_table(bt, slot_pos, self.kv_mgr.spec.block_size)
            return self._run_paged(ids_w, pos_w, slots, bt, last_idx,
                                   sampling_params)

        t0 = time.perf_counter()
        if chunk_w and t_max > chunk_w:
            # chunked prefill (reference: windowed context encoding,
            # model_base.py:878-933 + ChunkedPrefillConfig): walk the suffix
            # in fixed windows re-invoking the same graph with growing KV
            n_chunks = -(-t_max // chunk_w)
            tokens = np.zeros((b, 1), np.int32)
            off = np.zeros((b,), np.int32)
            for c in range(n_chunks):
                last_idx = np.clip(suffix_lens - 1 - off, 0, chunk_w - 1)
                out = _prefill_window(off, chunk_w, last_idx)
                toks = np.asarray(out["tokens"]).reshape(b)
                final_here = ((suffix_lens - 1 >= off)
                              & (suffix_lens - 1 < off + chunk_w))
                tokens[final_here, 0] = toks[final_here]
                off = off + chunk_w
        else:
            # 2-D (prefill width x table width) selection: the table width
            # was already bucketed when bt was built (_bt_width) — this
            # picks the other axis (reference: 2-D prefix-caching bucket
            # selection, model_wrapper.py:923-1045)
            bucket = autobucketing.get_target_bucket(self.ctx_buckets, t_max,
                                                     kind="ctx")
            out = _prefill_window(np.zeros((b,), np.int32), bucket,
                                  np.maximum(suffix_lens - 1, 0))
            tokens = np.asarray(out["tokens"]).reshape(b, 1)
        ttft = time.perf_counter() - t0
        if return_logits and "logits" in out:
            logits_trace.append(np.asarray(out["logits"]))

        collected = [tokens]
        positions = seq_lens.astype(np.int32)
        n_generated = 1
        eos_seen = np.zeros((b,), bool) if eos_ids is not None else None
        if eos_seen is not None:
            eos_seen |= np.isin(tokens[:, 0], eos_ids)
        # fetch-free chunked paged decode: blocks for the whole chunk are
        # pre-allocated on the host, then ``decode_chunk_tokens`` steps run
        # in ONE device call with slot mappings computed in-graph
        # (model_base.paged_decode_loop; reference: in-graph tokengen
        # slot-mapping, block_kv_cache_manager.py:376-430). Zero per-token
        # host fetches; EOS is checked at chunk boundaries.
        # return_logits and teacher forcing keep the single-step path
        # (per-step logits / a host-chosen next token).
        chunk = (1 if return_logits or teacher_tokens is not None
                 else max(cfg.decode_chunk_tokens, 1))
        while n_generated < max_new_tokens:
            room = self.tpu_config.seq_len - int(positions.max())
            remaining = min(max_new_tokens - n_generated, room)
            # a partial chunk would jit a fresh ('paged_loop', n) graph
            # mid-request — finish remainders with the single-step graph
            steps = chunk if remaining >= chunk else 1
            steps = min(steps, remaining)
            if steps <= 0:
                break
            for i in range(b):
                self.kv_mgr.grow(i, steps)
            bt = self.kv_mgr.block_table_array(range(b), self._bt_width(b))
            cur = collected[-1][:, -1].astype(np.int32)
            if teacher_tokens is not None:
                cur = teacher_tokens[:, n_generated - 1]
            if steps == 1:
                pos = positions[:, None]
                slots = slots_from_table(bt, pos,
                                         self.kv_mgr.spec.block_size)
                o = self._run_paged(cur[:, None], pos, slots, bt,
                                    np.zeros((b,), np.int32),
                                    sampling_params)
                new = np.asarray(o["tokens"]).reshape(b, 1)
                if return_logits and "logits" in o:
                    logits_trace.append(np.asarray(o["logits"]))
            else:
                o = self._run_paged_loop(cur, positions, bt, steps,
                                         sampling_params)
                new = np.asarray(o["tokens"])
            collected.append(new)
            positions = positions + steps
            n_generated += steps
            if eos_seen is not None:
                eos_seen |= np.isin(new, eos_ids).any(axis=1)
                if eos_seen.all():
                    break

        result = _finalize_generation(input_ids, collected, eos_ids, ttft,
                                      seq_lens)
        result["cached_tokens"] = cached.copy()
        if return_logits:
            result["logits"] = logits_trace
        return result

    def release(self, seq_ids=None):
        """Return sequences' blocks to the allocator (prefix-cached blocks
        stay resident for reuse)."""
        ids = list(self.kv_mgr.tables) if seq_ids is None else list(seq_ids)
        for sid in ids:
            if sid in self.kv_mgr.tables:
                self.kv_mgr.end_sequence(sid)
        return self

    def reset(self):
        self.release()
        return self
