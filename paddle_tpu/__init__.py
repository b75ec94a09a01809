"""paddle_tpu: a TPU-native deep-learning framework (JAX/XLA/Pallas/pjit).

Brand-new framework providing the capability surface of the reference
(PaddlePaddle, see SURVEY.md) with a TPU-first architecture:
  - eager Tensor API with tape autograd over jax.vjp (framework/),
  - whole-step compilation via jit/to_static (jit/),
  - SPMD distributed training over jax.sharding meshes (distributed/),
  - Pallas kernels for attention-class ops (ops/pallas/).
"""
from __future__ import annotations

__version__ = "0.1.0"

# TPU-native dtype policy: 64-bit types are canonicalized to 32-bit
# (framework/dtype.py) — int64 is emulated (slow) on TPU and x64 mode breaks
# Pallas lowering on this backend.  The reference defaults to int64 indices;
# user code keeps working, tensors just report int32.

import os as _os

if _os.environ.get("PADDLE_TPU_HELPER_CPU", "").lower() not in ("", "0", "false"):
    # launcher-marked helper rank: pin the CPU backend before anything can
    # claim the chip its trainer holds (framework/backend_guard)
    from .framework.backend_guard import pin_cpu as _pin_cpu
    _pin_cpu()

from .framework.tensor import Tensor, Parameter, to_tensor
from .framework import dtype as _dtype_mod
from .framework.dtype import (
    bfloat16, float16, float32, float64, int8, int16, int32, int64,
    uint8, bool_ as bool, complex64, complex128,
    set_default_dtype, get_default_dtype, finfo, iinfo,
)
from .framework.device import (
    set_device, get_device, device_count, CPUPlace, TPUPlace, CUDAPlace,
    is_compiled_with_cuda, is_compiled_with_xpu,
)
from .framework.tape import no_grad, enable_grad, set_grad_enabled, is_grad_enabled
from .framework.random import seed, get_rng_state, set_rng_state
from .framework.flags import set_flags, get_flags

from .tensor import *  # noqa: F401,F403  (functional tensor API)
from .tensor import linalg  # noqa: F401
from .tensor.logic import is_tensor  # noqa: F401

from . import autograd  # noqa: F401
from .autograd import grad  # noqa: F401

from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
from . import metric  # noqa: F401
from . import vision  # noqa: F401
from . import models  # noqa: F401
from .framework.io import save, load  # noqa: F401
from .hapi.model import Model  # noqa: F401
from . import distributed  # noqa: F401
from . import static  # noqa: F401
from . import incubate  # noqa: F401
from . import distribution  # noqa: F401
from . import fft  # noqa: F401
from . import signal  # noqa: F401
# stft/istft live in the signal module; the reference patches them onto
# Tensor too
Tensor.stft = lambda self, *a, **k: signal.stft(self, *a, **k)
Tensor.istft = lambda self, *a, **k: signal.istft(self, *a, **k)
Tensor.create_parameter = staticmethod(
    lambda *a, **k: create_parameter(*a, **k))
from . import geometric  # noqa: F401
from . import sparse  # noqa: F401
from . import profiler  # noqa: F401
from . import monitor  # noqa: F401
from . import quantization  # noqa: F401
from . import inference  # noqa: F401
from . import onnx  # noqa: F401
from . import device  # noqa: F401
from . import audio  # noqa: F401
from . import text  # noqa: F401
from . import regularizer  # noqa: F401
from . import callbacks  # noqa: F401
from . import utils  # noqa: F401
from .hapi import hub  # noqa: F401
from .tensor import linalg  # noqa: F401 (paddle.linalg alias)
from . import cost_model  # noqa: F401
from . import analysis  # noqa: F401


def disable_static():
    """Eager is the default and only eager/static switch is a no-op shim."""
    return None


def enable_static():
    raise NotImplementedError(
        "paddle_tpu is eager-first; use paddle_tpu.jit.to_static for "
        "whole-graph compilation (XLA replaces the static Program stack).")


def in_dynamic_mode() -> bool:
    return True


# ---------------------------------------------------------- top-level misc
# (the remaining reference python/paddle/__init__.py exports)
import math as _pymath
import numpy as _np

pi = _pymath.pi
e = _pymath.e
inf = float("inf")
nan = float("nan")
newaxis = None
dtype = _np.dtype                  # paddle.dtype('float32') etc.
from .framework.dtype import float8_e4m3fn, float8_e5m2  # noqa: E402,F401
from .tensor.linalg import cdist, dist  # noqa: E402,F401
from .nn import ParamAttr  # noqa: E402
from .distributed import DataParallel  # noqa: E402
from .framework.device import CUDAPinnedPlace  # noqa: E402
from .framework.random import (  # noqa: E402
    get_rng_state as get_cuda_rng_state, set_rng_state as set_cuda_rng_state,
)

# PIR dtype sentinels (reference: paddle.pstring / paddle.raw markers)
pstring = "pstring"
raw = "raw"


def shape(x):
    """1-D int32 tensor holding x's shape (reference paddle.shape op)."""
    return to_tensor(_np.asarray(x.shape, _np.int32))


def rank(x):
    """0-D tensor holding x's ndim (reference paddle.rank)."""
    return to_tensor(_np.asarray(x.ndim, _np.int32))


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """reference: paddle.create_parameter — a free-standing trainable
    Parameter with the default (or given) initializer."""
    from .framework.dtype import convert_dtype
    from .nn.initializer import XavierNormal, Constant
    init = default_initializer or (Constant(0.0) if is_bias
                                   else XavierNormal())
    data = init(tuple(shape), convert_dtype(dtype))
    return Parameter(data)


def batch(reader, batch_size, drop_last=False):
    """reference: paddle.batch — wrap a sample reader into a batch reader
    (legacy io surface; the modern path is paddle.io.DataLoader)."""
    def batch_reader():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batch_reader


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """reference: paddle.set_printoptions — numpy printer is the renderer."""
    kwargs = {}
    if precision is not None:
        kwargs["precision"] = precision
    if threshold is not None:
        kwargs["threshold"] = threshold
    if edgeitems is not None:
        kwargs["edgeitems"] = edgeitems
    if linewidth is not None:
        kwargs["linewidth"] = linewidth
    if sci_mode is not None:
        kwargs["suppress"] = not sci_mode
    _np.set_printoptions(**kwargs)


def summary(net, input_size=None, dtypes=None, input=None):
    """reference: paddle.summary — layer table + param counts (hapi)."""
    from .hapi.model import Model
    return Model(net).summary(input_size, dtypes)


def flops(net, input_size, custom_ops=None, print_detail=False):
    """reference: paddle.flops — rough per-layer FLOPs from a traced
    forward at ``input_size`` (MACs x2 for matmul/conv, element count for
    cheap ops)."""
    import numpy as _np2
    from . import nn as _nn
    total = [0]
    hooks = []

    def count(layer, inp, out):
        x = inp[0] if isinstance(inp, (list, tuple)) else inp
        o = out[0] if isinstance(out, (list, tuple)) else out
        if isinstance(layer, _nn.Linear):
            total[0] += 2 * int(_np2.prod(o.shape)) * layer.weight.shape[0]
        elif isinstance(layer, (_nn.Conv1D, _nn.Conv2D, _nn.Conv3D)):
            k = int(_np2.prod(layer.kernel_size))
            cin = layer.in_channels // layer.groups
            total[0] += 2 * int(_np2.prod(o.shape)) * k * cin
        else:
            total[0] += int(_np2.prod(o.shape))

    for sub in net.sublayers(include_self=True):
        if not sub.sublayers():
            hooks.append(sub.register_forward_post_hook(count))
    x = to_tensor(_np.zeros(input_size, _np.float32))
    net(x)
    for h in hooks:
        h.remove()
    if print_detail:
        print(f"Total FLOPs: {total[0]}")
    return total[0]


class _DLPackHolder:
    """Carrier implementing the modern __dlpack__ protocol (consumers like
    jax/numpy/torch>=2.1 take protocol objects, not bare capsules).  jax
    arrays only export the protocol on CPU/GPU, so TPU-resident arrays are
    staged through host memory first (DLPack has no TPU device type)."""

    def __init__(self, arr):
        try:
            platform = next(iter(arr.devices())).platform
        except Exception:
            platform = "cpu"
        if platform not in ("cpu", "gpu", "cuda", "rocm"):
            arr = _np.asarray(arr)       # device -> host copy
        self._arr = arr

    def __dlpack__(self, **kwargs):
        return self._arr.__dlpack__(**kwargs)

    def __dlpack_device__(self):
        return self._arr.__dlpack_device__()


def from_dlpack(ext):
    """reference: paddle.utils.dlpack.from_dlpack — accepts a protocol
    object (anything with __dlpack__) or a legacy PyCapsule."""
    import jax.numpy as _jnp
    if hasattr(ext, "__dlpack__"):
        arr = _jnp.from_dlpack(ext)
    else:
        # legacy capsule: modern jax refuses these; decode via torch
        import torch.utils.dlpack as _tdl
        arr = _jnp.asarray(_tdl.from_dlpack(ext).numpy())
    from .framework.tensor import wrap_array as _wrap
    return _wrap(arr)


def to_dlpack(x):
    """reference: paddle.utils.dlpack.to_dlpack."""
    return _DLPackHolder(x._data)


def disable_signal_handler():
    """reference: paddle.disable_signal_handler — the JAX runtime installs
    no paddle-style signal handlers; provided for API compatibility."""
    return None


def check_shape(shape):
    """Validate a shape argument (reference: paddle.check_shape)."""
    for s in list(shape):
        if not isinstance(s, (int, _np.integer)) or (s < -1):
            raise ValueError(f"invalid shape entry {s!r} in {shape!r}")
    return True


class LazyGuard:
    """reference: paddle.LazyGuard — delays parameter materialization in
    the reference's lazy-init mode.  Parameters here are numpy/jax arrays
    created eagerly and cheaply on host; the guard is a no-op context."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
