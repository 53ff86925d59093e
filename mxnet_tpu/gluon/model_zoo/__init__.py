"""Model zoo (ref: python/mxnet/gluon/model_zoo/__init__.py; bert adds
GluonNLP-parity language models)."""
from . import (bert, brumby, deepseek_v2, granite_hybrid, nemotron_h, ssd,
               transformer, vision)
from .vision import get_model

__all__ = ["vision", "bert", "brumby", "deepseek_v2", "granite_hybrid",
           "nemotron_h", "get_model"]
