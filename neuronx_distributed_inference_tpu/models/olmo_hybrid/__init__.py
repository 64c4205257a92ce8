from .modeling_olmo_hybrid import (OlmoHybridFamily,
                                   OlmoHybridInferenceConfig)
