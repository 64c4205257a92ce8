from .modeling_cohere2_moe import (Cohere2MoeFamily,
                                   Cohere2MoeInferenceConfig)

__all__ = ["Cohere2MoeFamily", "Cohere2MoeInferenceConfig"]
