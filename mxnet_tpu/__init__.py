"""mxnet_tpu — a TPU-native deep learning framework.

A from-scratch rebuild of Apache MXNet's capabilities (NDArray + autograd +
Gluon + KVStore + data pipeline) designed for TPU hardware: XLA compiles and
fuses every op, ``jax.jit`` backs ``hybridize()``, ``jax.sharding`` meshes +
collectives back the KVStore, and Pallas supplies hand-tuned kernels where
XLA's defaults are not enough.

Import convention matches the reference: ``import mxnet_tpu as mx``.
"""
__version__ = "2.0.0a1"

from . import base
from .base import MXNetError
from .context import (Context, cpu, tpu, gpu, cpu_pinned, current_context,
                      num_tpus, num_gpus, device)
from . import engine
from . import ndarray
from . import ndarray as nd
from .ndarray import random
from . import autograd
from . import util
from .util import is_np_array, set_np, reset_np, use_np

# Every subsystem imports or `import mxnet_tpu` fails: a dropped module
# would otherwise surface far away as an AttributeError on `mx.<name>`.
_SUBSYSTEMS = [
    ("initializer", None), ("init", None), ("optimizer", None),
    ("lr_scheduler", None), ("kvstore", None), ("kvstore", "kv"),
    ("gluon", None),
    ("metric", None), ("profiler", None), ("numpy", "np"),
    ("numpy_extension", "npx"), ("symbol", None), ("symbol", "sym"),
    ("image", None), ("io", None), ("runtime", None), ("parallel", None),
    ("test_utils", None), ("amp", None), ("recordio", None),
    ("operator", None), ("rtc", None), ("contrib", None),
    ("subgraph", None), ("checkpoint", None), ("testing", None),
    ("analysis", None), ("telemetry", None), ("elastic", None),
    ("serving", None), ("library", None),
    ("inspector", None), ("visualization", None), ("visualization", "viz"),
    ("name", None), ("attribute", None), ("error", None), ("log", None),
    ("registry", None),
]
import importlib as _importlib

for _mod, _alias in _SUBSYSTEMS:
    globals()[_alias or _mod] = _importlib.import_module(f".{_mod}",
                                                         __name__)

from .kvstore import KVStore  # noqa: F401,E402

# persistent XLA compilation cache, at JAX_COMPILATION_CACHE_DIR or one
# fixed path in the checkout: restarts load executables from disk
# instead of recompiling (runtime.setup_compile_cache counts hits/misses)
runtime.setup_compile_cache()  # noqa: F821 - bound by the loop above

from .attribute import AttrScope  # noqa: F401,E402  (reference __init__:72)


def tpu_context_available() -> bool:
    return num_tpus() > 0
