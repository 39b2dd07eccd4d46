"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle's capabilities.

Not a port: the reference's C++ PHI kernel library / executors / NCCL stack
(see /root/repo/SURVEY.md) is re-designed on jax/XLA/Pallas — ops lower to
StableHLO, the executor is XLA+PJRT, parallelism is GSPMD mesh sharding, and
hand-written kernels are Pallas. The public surface mirrors `import paddle`.
"""

from __future__ import annotations

from .version import full_version as __version__  # noqa: E402  (single source)
from .core.cache import configure_compile_cache as _configure_compile_cache

_configure_compile_cache()

from .core import (  # noqa: F401
    CPUPlace,
    CUDAPinnedPlace,
    CUDAPlace,
    Place,
    TPUPlace,
    Tensor,
    bfloat16,
    bool_ as bool,  # noqa: A004
    complex64,
    complex128,
    device_count,
    enable_grad,
    float16,
    float32,
    float64,
    get_device,
    get_flags,
    get_rng_state,
    int8,
    int16,
    int32,
    int64,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    is_grad_enabled,
    no_grad,
    seed,
    set_device,
    set_flags,
    set_rng_state,
    to_tensor,
    uint8,
)
from .core.autograd import set_grad_enabled  # noqa: F401
from .core.dtype import DType as dtype  # noqa: F401
from .core.tensor import Parameter  # noqa: F401
from .ops import *  # noqa: F401,F403
from .ops import sum, max, min, all, any, abs, slice  # noqa: F401,A004
from .ops.logic import is_tensor  # noqa: F401
from .ops.compat import (  # noqa: F401
    LazyGuard,
    add_n,
    batch,
    check_shape,
    complex,
    create_parameter,
    disable_signal_handler,
    finfo,
    iinfo,
    increment,
    is_complex,
    is_floating_point,
    is_integer,
    nan_to_num,
    nanquantile,
    polar,
    rank,
    reverse,
    sgn,
    shape,
    shard_index,
    squeeze_,
    tanh_,
    tolist,
    unsqueeze_,
)
from .framework.random import get_cuda_rng_state, set_cuda_rng_state  # noqa: F401

# Subsystem namespaces land here as they are built out (nn, optimizer, io,
# distributed, jit, ...). Each addition extends this import block.
from . import autograd  # noqa: F401,E402
from . import amp  # noqa: F401,E402
from . import nn  # noqa: F401,E402
from . import distributed  # noqa: F401,E402
from .distributed.parallel import DataParallel  # noqa: F401,E402
from . import io  # noqa: F401,E402
from . import data  # noqa: F401,E402
from . import framework  # noqa: F401,E402
from .framework.io import load, save  # noqa: F401,E402
from . import models  # noqa: F401,E402
from . import incubate  # noqa: F401,E402
from . import optimizer  # noqa: F401,E402
from . import regularizer  # noqa: F401,E402
from . import metric  # noqa: F401,E402
from . import hapi  # noqa: F401,E402
from . import static  # noqa: F401,E402
from . import jit  # noqa: F401,E402
from . import ir  # noqa: F401,E402
from . import linalg  # noqa: F401,E402
from . import tensor  # noqa: F401,E402
from .core.selected_rows import SelectedRows  # noqa: F401,E402
from .core.string_tensor import StringTensor  # noqa: F401,E402
from . import inference  # noqa: F401,E402
from . import observability  # noqa: F401,E402
from . import checkpoint  # noqa: F401,E402
from . import serving  # noqa: F401,E402
from . import profiler  # noqa: F401,E402
from . import vision  # noqa: F401,E402
from . import distribution  # noqa: F401,E402
from . import fft  # noqa: F401,E402
from . import signal  # noqa: F401,E402
from . import sparse  # noqa: F401,E402
from . import device  # noqa: F401,E402
from . import text  # noqa: F401,E402
from . import audio  # noqa: F401,E402
from . import geometric  # noqa: F401,E402
from . import quantization  # noqa: F401,E402
from . import utils  # noqa: F401,E402
from . import onnx  # noqa: F401,E402
from . import hub  # noqa: F401,E402
from . import version  # noqa: F401,E402


def flops(net, input_size, custom_ops=None, print_detail=False):
    """Forward-FLOPs of a Layer (reference: python/paddle/hapi/dynamic_flops.py)."""
    from .utils.flops import dynamic_flops

    return dynamic_flops(net, input_size, custom_ops=custom_ops, print_detail=print_detail)
from .hapi import Model, summary  # noqa: F401,E402
from .hapi import callbacks  # noqa: F401,E402
from .param_attr import ParamAttr  # noqa: F401,E402

# paddle.grad
from .core.autograd import grad  # noqa: F401,E402
from .nn.layer.layers import disable_static, enable_static, in_dynamic_mode  # noqa: F401,E402


def get_default_dtype():
    from .core.flags import flag_value

    return flag_value("default_dtype")


def set_default_dtype(d):
    from .core.dtype import convert_dtype

    set_flags({"default_dtype": convert_dtype(d)})


def set_printoptions(**kwargs):
    import numpy as np

    np.set_printoptions(**{k: v for k, v in kwargs.items() if k in ("precision", "threshold", "edgeitems", "linewidth")})
