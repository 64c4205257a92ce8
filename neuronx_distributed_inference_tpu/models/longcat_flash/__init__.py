from .modeling_longcat_flash import (LongcatFlashFamily,  # noqa: F401
                                     LongcatFlashInferenceConfig)
