from .modeling_keye_vl2 import KeyeVL2Family, KeyeVL2InferenceConfig

__all__ = ["KeyeVL2Family", "KeyeVL2InferenceConfig"]
