"""Model zoo (reference: python/mxnet/gluon/model_zoo/__init__.py).

Pretrained-weight downloads are not available in this environment; models are
constructed with random init and support ``load_parameters`` from local files.
"""
from . import vision
from . import bert
from . import smallthinker
from . import joyai
from . import nemotron_h
from . import lfm2
from .vision import get_model

__all__ = ["vision", "bert", "smallthinker", "joyai", "nemotron_h", "lfm2",
           "get_model"]
