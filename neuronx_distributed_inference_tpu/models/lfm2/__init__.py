from .modeling_lfm2 import (Lfm2Family, Lfm2InferenceConfig,  # noqa: F401
                            Lfm2MoeFamily, Lfm2MoeInferenceConfig)
