from .modeling_smallthinker import (SmallThinkerFamily,
                                    SmallThinkerInferenceConfig)

__all__ = ["SmallThinkerFamily", "SmallThinkerInferenceConfig"]
