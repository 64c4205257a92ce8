from .modeling_ling import (LingKdaFamily,  # noqa: F401
                            LingKdaInferenceConfig)
