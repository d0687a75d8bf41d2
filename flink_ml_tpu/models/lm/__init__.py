"""Decoder-only language models that train through ``Estimator.fit``."""
from flink_ml_tpu.models.lm.decoder_lm import DecoderLM, DecoderLMModel

__all__ = ["DecoderLM", "DecoderLMModel"]
