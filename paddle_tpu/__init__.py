"""paddle_tpu: a TPU-native deep-learning framework.

Brand-new framework with the capabilities of the PaddlePaddle reference
(/root/reference), re-designed TPU-first: every op is a JAX/XLA computation,
autograd is a define-by-run tape over `jax.vjp`, the to_static compile path is
trace→XLA via `jax.jit`, and distribution is expressed with `jax.sharding`
meshes + XLA collectives instead of NCCL process groups.
"""
from __future__ import annotations

import jax as _jax

# float64/int64 parity with the reference (models still run fp32/bf16 on TPU).
_jax.config.update("jax_enable_x64", True)

from .core import dtype as _dtype_mod  # noqa: E402
from .core.dtype import (  # noqa: E402,F401
    bool_, uint8, int8, int16, int32, int64, float16, bfloat16, float32, float64,
    complex64, complex128, set_default_dtype, get_default_dtype,
)
from .core.tensor import Tensor, Parameter, is_tensor  # noqa: E402,F401
from .core.device import (  # noqa: E402,F401
    set_device, get_device, device_count, is_compiled_with_tpu,
)
from .core.generator import seed, default_generator, Generator  # noqa: E402,F401
from .autograd.grad_mode import no_grad, enable_grad, is_grad_enabled  # noqa: E402,F401
from .autograd.backward import grad  # noqa: E402,F401

from .ops import *  # noqa: E402,F401,F403
from .ops import linalg  # noqa: E402,F401
from . import autograd  # noqa: E402,F401

# framework subsystems
from . import nn  # noqa: E402,F401
from . import optimizer  # noqa: E402,F401
from . import amp  # noqa: E402,F401
from . import io  # noqa: E402,F401
from . import jit  # noqa: E402,F401
from .jit.api import to_static  # noqa: E402,F401
from . import utils  # noqa: E402,F401
from . import observability  # noqa: E402,F401  — arms the flight recorder
from . import device  # noqa: E402,F401
from .utils import flags as _flags  # noqa: E402
from .utils.flags import set_flags, get_flags  # noqa: E402,F401
from .framework_io import save, load  # noqa: E402,F401
from .framework_compat import (  # noqa: E402,F401
    CPUPlace, CUDAPinnedPlace, CUDAPlace, LazyGuard, ParamAttr, TPUPlace,
    batch, bool, check_shape, disable_signal_handler, dtype, finfo, flops,
    get_cuda_rng_state, get_rng_state, iinfo, set_cuda_rng_state,
    set_grad_enabled, set_printoptions, set_rng_state,
)

__version__ = "0.1.0"

# paddle-compat alias: DataParallel & distributed live in paddle_tpu.distributed
def __getattr__(name):
    if name == "distributed":
        import importlib
        return importlib.import_module(".distributed", __name__)
    if name == "DataParallel":
        from .distributed.parallel import DataParallel
        return DataParallel
    if name == "static":
        import importlib
        return importlib.import_module(".static", __name__)
    if name == "vision":
        import importlib
        return importlib.import_module(".vision", __name__)
    if name == "metric":
        import importlib
        return importlib.import_module(".metric", __name__)
    if name == "hapi":
        import importlib
        return importlib.import_module(".hapi", __name__)
    if name in ("Model", "summary"):
        from .hapi import Model, summary
        return {"Model": Model, "summary": summary}[name]
    if name in ("enable_static", "disable_static", "in_dynamic_mode"):
        from .static import framework as _sfw
        return getattr(_sfw, name)
    if name == "CompiledProgram":
        from .static import CompiledProgram
        return CompiledProgram
    if name in ("profiler", "distribution", "sparse", "quantization", "audio",
                "geometric", "text", "incubate", "inference", "models", "fft",
                "signal", "onnx"):
        import importlib
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")
