from .modeling_granitemoehybrid import (GraniteMoeHybridFamily,
                                        GraniteMoeHybridInferenceConfig)
