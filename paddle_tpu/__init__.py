"""paddle_tpu — a TPU-native deep learning framework with PaddlePaddle's capabilities.

Blueprint: /root/repo/SURVEY.md (structural analysis of the reference).
The public surface mirrors `paddle.*` (reference: python/paddle/__init__.py)
while the implementation is an idiomatic XLA/PJRT/Pallas stack.
"""
from __future__ import annotations

import jax as _jax

# Paddle dtype semantics need int64 (default integer dtype). float64 stays out
# of the compute path via default-dtype coercion in to_tensor, so TPU (no f64)
# is safe.
_jax.config.update("jax_enable_x64", True)

# Core types ------------------------------------------------------------------
from .core.dtype import (  # noqa: F401
    DType,
    bfloat16,
    bool_,
    complex64,
    complex128,
    float8_e4m3fn,
    float8_e5m2,
    float16,
    float32,
    float64,
    get_default_dtype,
    int8,
    int16,
    int32,
    int64,
    set_default_dtype,
    uint8,
)
from .core.dtype import DType as dtype  # noqa: F401
from .core.place import (  # noqa: F401
    CPUPlace,
    CUDAPinnedPlace,
    CUDAPlace,
    Place,
    TPUPlace,
    get_device,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    is_compiled_with_xpu,
    set_device,
)
from .core.flags import get_flags, set_flags  # noqa: F401
from .core.rng import get_rng_state, seed, set_rng_state  # noqa: F401
from .core.rng import get_rng_state as get_cuda_rng_state  # noqa: F401
from .core.rng import set_rng_state as set_cuda_rng_state  # noqa: F401

bool = bool_  # noqa: A001 — paddle.bool is the dtype, as in the reference


def __getattr__(name):
    # lazy: paddle.DataParallel without importing distributed at package load
    if name == "DataParallel":
        from .distributed.parallel import DataParallel

        return DataParallel
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")
from .core.tensor import Parameter, Tensor, to_tensor  # noqa: F401
from .ops.dispatch import (  # noqa: F401
    enable_grad,
    is_grad_enabled,
    no_grad,
    set_grad_enabled,
)
from .autograd.engine import grad  # noqa: F401

# Op library → module-level functions (paddle.add, paddle.matmul, ...).
# Sourced from the YAML-generated binding surface (ops/generated_bindings),
# NOT the raw registry: an op without an ops.yaml entry is not public.
from .ops.dispatch import OPS as _OPS
from .ops import generated_bindings as _gen_bindings
from . import tensor as _tensor_methods  # noqa: F401  (patches Tensor methods)
from . import _C_ops  # noqa: F401

_globals = globals()
for _name in _gen_bindings.__all__:
    if _name not in _globals:
        _globals[_name] = getattr(_gen_bindings, _name)
del _name


# Creation / random wrappers with paddle signatures ---------------------------
def rand(shape, dtype=None):
    return _OPS["uniform"](shape, dtype, 0.0, 1.0)


def randn(shape, dtype=None):
    return _OPS["gaussian"](shape, 0.0, 1.0, dtype)


def normal(mean=0.0, std=1.0, shape=None):
    if shape is None:
        if isinstance(mean, Tensor):
            shape = mean.shape
        elif isinstance(std, Tensor):
            shape = std.shape
        else:
            shape = [1]
    return _OPS["gaussian"](shape, mean, std, None)


def ones_like(x, dtype=None):
    return _OPS["ones_like"](x, dtype)


def zeros_like(x, dtype=None):
    return _OPS["zeros_like"](x, dtype)


def clone(x):
    return _OPS["assign"](x)


def numel(x):
    return to_tensor(x.size, dtype="int64")


def shape(x):
    return to_tensor(x.shape, dtype="int32")


def is_tensor(x):
    return isinstance(x, Tensor)


def get_default_device():  # convenience
    from .core.place import current_place

    return current_place()


def in_dynamic_mode():
    from .jit.api import in_to_static_trace

    return not in_to_static_trace()


def device_count():
    import jax

    try:
        devs = [d for d in jax.devices() if d.platform != "cpu"]
        return len(devs) or jax.device_count()
    except RuntimeError:
        return 0


def synchronize():
    """Block until all dispatched device work completes (analog of
    DeviceContext Wait): drains the in-flight step pipeline, then fences
    the device."""
    from .core import async_engine

    async_engine.synchronize()


# Subpackages (populated as the framework grows; see SURVEY.md §7 build plan) -
from . import observability  # noqa: F401, E402  (flight recorder + metrics)

# SIGUSR1 -> flight-recorder dump: a hung process can be inspected with
# `kill -USR1 <pid>` (no-op when not installable, e.g. non-main thread)
observability.install_signal_handler()

from . import autograd  # noqa: F401, E402
from . import nn  # noqa: F401, E402
from . import optimizer  # noqa: F401, E402
from . import jit  # noqa: F401, E402
from . import amp  # noqa: F401, E402
from . import io  # noqa: F401, E402
from . import metric  # noqa: F401, E402
from . import static  # noqa: F401, E402
from .static import enable_static, disable_static  # noqa: F401, E402
from . import audio, hub, text, utils, version  # noqa: F401, E402
from . import vision  # noqa: F401, E402
from . import distributed  # noqa: F401, E402
from . import incubate  # noqa: F401, E402
from . import profiler  # noqa: F401, E402
from . import linalg  # noqa: F401, E402
from . import fft  # noqa: F401, E402
from . import signal  # noqa: F401, E402
from . import distribution  # noqa: F401, E402
from . import geometric  # noqa: F401, E402  (registers graph/segment ops)
from . import sparse  # noqa: F401, E402
from . import pir  # noqa: F401, E402
from . import inference  # noqa: F401, E402
from . import device  # noqa: F401, E402
from . import quantization  # noqa: F401, E402
from . import framework  # noqa: F401, E402
from .framework.io_api import load, save  # noqa: F401, E402
from .hapi.model import Model  # noqa: F401, E402
from . import hapi  # noqa: F401, E402

# Reference __all__ parity tail: compositions/aliases that aren't phi ops
# (numpy-style stacks/splits, predicates, in-place functional spellings,
# dlpack, utilities) — see tensor/compat_ext.py.
from .tensor import compat_ext as _compat_ext  # noqa: E402

for _name in _compat_ext.__all__:
    if _name not in _globals:
        _globals[_name] = getattr(_compat_ext, _name)
del _name
from .hapi.summary import flops, summary  # noqa: F401, E402
from .nn import ParamAttr  # noqa: F401, E402

__version__ = "0.1.0"
