"""Gluon neural-network layers (reference: python/mxnet/gluon/nn/)."""
from .basic_layers import *  # noqa: F401,F403
from .conv_layers import *  # noqa: F401,F403
from .transformer import *  # noqa: F401,F403
from .moe import *  # noqa: F401,F403
from .ssm import *  # noqa: F401,F403
from . import basic_layers, conv_layers, transformer, moe, ssm
from .basic_layers import __all__ as _b
from .conv_layers import __all__ as _c
from .transformer import __all__ as _t
from .moe import __all__ as _m
from .ssm import __all__ as _s

__all__ = list(_b) + list(_c) + list(_t) + list(_m) + list(_s)
