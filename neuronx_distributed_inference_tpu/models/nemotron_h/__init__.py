from .modeling_nemotron_h import (NemotronHFamily,  # noqa: F401
                                  NemotronHInferenceConfig)
