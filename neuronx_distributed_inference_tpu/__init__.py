"""TPU-native distributed inference framework.

A ground-up JAX/XLA/Pallas re-design with the capability surface of
aws-neuron/neuronx-distributed-inference (the reference implementation for
Trainium). See SURVEY.md at the repo root for the component-by-component map.
"""

__version__ = "0.1.0"

from .config import (ChunkedPrefillConfig, InferenceConfig, MoEConfig,
                     OnDeviceSamplingConfig, SpeculationConfig, TpuConfig,
                     load_pretrained_config)

__all__ = [
    "TpuConfig", "InferenceConfig", "OnDeviceSamplingConfig", "MoEConfig",
    "SpeculationConfig", "ChunkedPrefillConfig", "load_pretrained_config",
]
