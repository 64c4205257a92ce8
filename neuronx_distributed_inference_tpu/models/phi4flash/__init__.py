from .modeling_phi4flash import (Phi4FlashFamily,  # noqa: F401
                                 Phi4FlashInferenceConfig)
