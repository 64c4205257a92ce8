"""Serving package: the engine adapter (``serving.adapter`` — the
importable continuous-batching contract over the paged application) and
the multi-tenant serving engine built on top of it (``serving.engine`` —
queue + scheduler + token streams + HTTP/SSE front door; see README
"Serving engine").

Importing ``neuronx_distributed_inference_tpu.serving`` exposes the
adapter surface (this module used to be ``serving.py``); the engine layer is imported explicitly from ``.engine``, the fleet layer
above it (replicated-engine router, host-RAM KV spill tier, disaggregated
prefill handoff — README "Fleet") explicitly from ``.fleet``, and the
ragged unified dispatch (one mixed prefill+decode+verify dispatch per
engine step, enabled with ``PagedEngineAdapter(app, ragged=True)`` —
README "Ragged dispatch") explicitly from ``.ragged``.
"""

from .adapter import PagedEngineAdapter
from .lora_pool import LoraAdapterPool

__all__ = ["LoraAdapterPool", "PagedEngineAdapter"]
