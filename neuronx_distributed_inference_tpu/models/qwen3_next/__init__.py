from .modeling_qwen3_next import (Qwen3NextFamily,  # noqa: F401
                                  Qwen3NextInferenceConfig)
