"""paddle_tpu — a TPU-native deep-learning framework.

Brand-new design with the capability surface of the reference (PaddlePaddle, mounted at
/root/reference — see SURVEY.md): eager tensors with tape autograd, a jit/compile path,
nn/optimizer/amp/io stacks, and a first-class distributed story (DP/TP/PP/SP/EP, ZeRO,
DTensor-style semi-auto sharding, sharded checkpoints) — all riding JAX/XLA/Pallas/pjit
instead of CUDA/NCCL.
"""
from __future__ import annotations

import sys as _sys
import time as _time

_import_t0, _jax_preimported = _time.perf_counter(), "jax" in _sys.modules

import jax as _jax  # noqa: E402

# float64/int64 parity with the reference (paddle supports fp64; indices are int64).
# TPU code paths use fp32/bf16 throughout; fp64 arrays are CPU-only like the reference's
# CPU-only kernels.
_jax.config.update("jax_enable_x64", True)


def _configure_compile_cache():
    """Place JAX's persistent compilation cache. ``JAX_COMPILATION_CACHE_DIR``
    wins when set (JAX reads it itself — nothing is set in code); otherwise
    the cache lives at ``<checkout>/.jax_cache``. The path is part of the
    cache key's environment, so it is FIXED: never a tempdir, pid or time.
    THE one place a cache directory is chosen — benchmark/run.py, the
    examples, __graft_entry__.py and chip_smoke.py all get it by importing
    this package. A config update does not initialise a backend.

    The cache's key takes the operations' metadata in. jax leaves it out by
    default, and an executable carries the metadata of the tree that
    COMPILED it: one that a tree without device scopes (``profiler.scope``)
    cached would be loaded by this one, and a profile would then name
    nothing (read on the v5e: a second process whose scope had another
    name showed the first one's). The price is a cold compile after an
    edit that moves a traced line."""
    import os
    _jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    _jax.config.update("jax_compilation_cache_dir",
                       os.path.join(checkout, ".jax_cache"))


_configure_compile_cache()

import numpy as _np  # noqa: E402

from .core import dtype as _dtype_mod  # noqa: E402
from .core.dtype import (  # noqa: E402,F401
    bool_ as bool, uint8, int8, int16, int32, int64, float16, bfloat16, float32,
    float64, complex64, complex128, float8_e4m3fn, float8_e5m2,
    set_default_dtype, get_default_dtype, finfo, iinfo,
)
from .core.tensor import (  # noqa: E402,F401
    Tensor, no_grad, enable_grad, is_grad_enabled, set_grad_enabled, dispatch,
    register_op,
)
from .core.device import (  # noqa: E402,F401
    CPUPlace, TPUPlace, CUDAPlace, XPUPlace, CustomPlace, Place,
    set_device, get_device, is_compiled_with_cuda, is_compiled_with_tpu,
)
from .core.random import seed, get_rng_state, set_rng_state, Generator  # noqa: E402,F401
from .core.flags import get_flags, set_flags  # noqa: E402,F401

from .ops import *  # noqa: E402,F401,F403
from . import ops as _ops  # noqa: E402
from .autograd import grad, PyLayer  # noqa: E402,F401
from .ops.logic import is_tensor  # noqa: E402,F401

__version__ = "0.1.0"

# ---------------------------------------------------------------------------
# lazy subpackages (keeps import light and cycle-free)
# ---------------------------------------------------------------------------
_LAZY_SUBMODULES = (
    "nn", "optimizer", "autograd", "amp", "jit", "io", "distributed", "vision",
    "static", "device", "profiler", "metric", "hapi", "incubate", "utils", "text",
    "sparse", "linalg", "fft", "signal", "distribution", "audio", "geometric",
    "tensor", "regularizer", "quantization", "inference", "onnx", "serving",
)


_LAZY_ATTRS = {"Model": ("hapi", "Model"), "summary": ("hapi", "summary")}


def __getattr__(name):
    if name in _LAZY_SUBMODULES:
        import importlib
        try:
            mod = importlib.import_module(f".{name}", __name__)
        except ModuleNotFoundError as e:
            # keep hasattr() probes working when an optional subpackage is absent
            if e.name == f"{__name__}.{name}":
                raise AttributeError(
                    f"module 'paddle_tpu' has no attribute {name!r}") from None
            raise
        globals()[name] = mod
        return mod
    if name in _LAZY_ATTRS:
        import importlib
        mod_name, attr = _LAZY_ATTRS[name]
        val = getattr(importlib.import_module(f".{mod_name}", __name__), attr)
        globals()[name] = val
        return val
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


# ---------------------------------------------------------------------------
# framework io (paddle.save / paddle.load)
# ---------------------------------------------------------------------------

def save(obj, path, protocol=4):
    from .framework_io import save as _save
    return _save(obj, path, protocol)


def load(path, **kwargs):
    from .framework_io import load as _load
    return _load(path, **kwargs)


# ---------------------------------------------------------------------------
# Tensor method surface
# ---------------------------------------------------------------------------

def _to_t(v):
    return v if isinstance(v, Tensor) else _ops.to_tensor(v)


def _bind(name, fn):
    setattr(Tensor, name, fn)


def _method(op_fn):
    def m(self, *args, **kwargs):
        return op_fn(self, *args, **kwargs)
    return m


def _inplace(op_fn):
    def m(self, *args, **kwargs):
        out = op_fn(self, *args, **kwargs)
        self._value = out._value
        self._node = out._node
        self._out_index = out._out_index
        if not out.stop_gradient:
            self.stop_gradient = False
        return self
    return m


_METHOD_NAMES = [
    # math
    "add", "subtract", "multiply", "divide", "floor_divide", "remainder", "mod",
    "pow", "maximum", "minimum", "fmax", "fmin", "abs", "neg", "sign", "floor",
    "ceil", "round", "trunc", "frac", "exp", "expm1", "log", "log2", "log10",
    "log1p", "sqrt", "rsqrt", "sin", "cos", "tan", "asin", "acos", "atan", "sinh",
    "cosh", "tanh", "asinh", "acosh", "atanh", "reciprocal", "square", "erf",
    "erfinv", "lgamma", "digamma", "angle", "conj", "rad2deg", "deg2rad", "lerp",
    "clip", "scale", "stanh", "atan2", "heaviside", "hypot", "isnan", "isinf",
    "isfinite", "nan_to_num", "sigmoid", "logaddexp",
    # reductions
    "sum", "mean", "prod", "max", "min", "amax", "amin", "std", "var", "median",
    "nanmedian", "nansum", "nanmean", "quantile", "logsumexp", "all", "any",
    "count_nonzero", "cumsum", "cumprod", "cummax", "cummin", "logcumsumexp",
    # linalg
    "matmul", "mm", "bmm", "mv", "dot", "norm", "dist", "cross", "cholesky",
    "inverse", "det", "t", "trace", "diagonal",
    # manipulation
    "reshape", "flatten", "squeeze", "unsqueeze", "transpose", "moveaxis",
    "swapaxes", "split", "chunk", "unbind", "tile", "expand", "expand_as",
    "broadcast_to", "flip", "rot90", "roll", "repeat_interleave", "gather",
    "gather_nd", "take_along_axis", "put_along_axis", "index_select",
    "index_sample", "index_add", "masked_select", "masked_fill", "scatter",
    "scatter_nd_add", "cast", "astype", "tensor_split", "as_strided",
    # search
    "argmax", "argmin", "argsort", "sort", "topk", "kthvalue", "mode",
    "searchsorted", "bucketize", "unique", "unique_consecutive", "bincount",
    "tril", "triu", "where", "nonzero",
    # logic
    "equal", "not_equal", "greater_than", "greater_equal", "less_than",
    "less_equal", "logical_and", "logical_or", "logical_xor", "logical_not",
    "bitwise_and", "bitwise_or", "bitwise_xor", "bitwise_not", "isclose",
    "allclose", "equal_all",
]

for _name in _METHOD_NAMES:
    if hasattr(_ops, _name):
        _bind(_name, _method(getattr(_ops, _name)))

_INPLACE_NAMES = [
    "add", "subtract", "multiply", "divide", "clip", "scale", "floor", "ceil",
    "round", "exp", "sqrt", "rsqrt", "reciprocal", "tanh", "sigmoid", "abs",
    "remainder", "pow", "cast", "squeeze", "unsqueeze", "reshape", "flatten",
    "tril", "triu", "masked_fill", "scatter", "index_add", "index_put", "lerp",
    "put_along_axis",
]
for _name in _INPLACE_NAMES:
    if hasattr(_ops, _name):
        _bind(_name + "_", _inplace(getattr(_ops, _name)))


# module-level in-place forms the reference exports in paddle.__all__
# (python/paddle/__init__.py: index_add_, index_put_) — thin wrappers over
# the bound Tensor methods
def index_add_(x, index, axis, value, name=None):
    return x.index_add_(index, axis, value)


def index_put_(x, indices, value, accumulate=False, name=None):
    return x.index_put_(indices, value, accumulate)


def _fill_(self, value):
    import jax.numpy as jnp
    self._value = jnp.full_like(self._value, value)
    return self


def _zero_(self):
    return _fill_(self, 0)


def _uniform_(self, min=-1.0, max=1.0):
    import jax.numpy as jnp
    from .core import random as _random
    self._value = _jax.random.uniform(_random.next_key(), self._value.shape,
                                      dtype=self._value.dtype, minval=min, maxval=max)
    return self


def _normal_(self, mean=0.0, std=1.0):
    from .core import random as _random
    self._value = (mean + std * _jax.random.normal(
        _random.next_key(), self._value.shape, dtype=self._value.dtype))
    return self


_bind("fill_", _fill_)
_bind("zero_", _zero_)
_bind("uniform_", _uniform_)
_bind("normal_", _normal_)


# operators -----------------------------------------------------------------
def _binop(fn, swap=False):
    def m(self, other):
        if swap:
            return fn(_to_t(other), self)
        return fn(self, other)
    return m


_bind("__add__", _binop(_ops.add))
_bind("__radd__", _binop(_ops.add, swap=True))
_bind("__sub__", _binop(_ops.subtract))
_bind("__rsub__", _binop(_ops.subtract, swap=True))
_bind("__mul__", _binop(_ops.multiply))
_bind("__rmul__", _binop(_ops.multiply, swap=True))
_bind("__truediv__", _binop(_ops.divide))
_bind("__rtruediv__", _binop(_ops.divide, swap=True))
_bind("__floordiv__", _binop(_ops.floor_divide))
_bind("__rfloordiv__", _binop(_ops.floor_divide, swap=True))
_bind("__mod__", _binop(_ops.remainder))
_bind("__rmod__", _binop(_ops.remainder, swap=True))
_bind("__pow__", _binop(_ops.pow))
_bind("__rpow__", _binop(_ops.pow, swap=True))
_bind("__matmul__", _binop(_ops.matmul))
_bind("__rmatmul__", _binop(_ops.matmul, swap=True))
_bind("__neg__", lambda self: _ops.neg(self))
_bind("__abs__", lambda self: _ops.abs(self))
_bind("__invert__", lambda self: _ops.logical_not(self)
      if self.dtype == _np.dtype(_np.bool_) else _ops.bitwise_not(self))
_bind("__eq__", _binop(_ops.equal))
_bind("__ne__", _binop(_ops.not_equal))
_bind("__lt__", _binop(_ops.less_than))
_bind("__le__", _binop(_ops.less_equal))
_bind("__gt__", _binop(_ops.greater_than))
_bind("__ge__", _binop(_ops.greater_equal))


def _and(self, other):
    if self.dtype == _np.dtype(_np.bool_):
        return _ops.logical_and(self, other)
    return _ops.bitwise_and(self, other)


def _or(self, other):
    if self.dtype == _np.dtype(_np.bool_):
        return _ops.logical_or(self, other)
    return _ops.bitwise_or(self, other)


def _xor(self, other):
    if self.dtype == _np.dtype(_np.bool_):
        return _ops.logical_xor(self, other)
    return _ops.bitwise_xor(self, other)


_bind("__and__", _and)
_bind("__or__", _or)
_bind("__xor__", _xor)
Tensor.__hash__ = lambda self: id(self)


def _norm_index(idx):
    """lists → arrays (fancy indexing); keep slices/Ellipsis/None/ints as-is."""
    import jax.numpy as jnp
    if isinstance(idx, list):
        return jnp.asarray(idx)
    if isinstance(idx, tuple):
        return tuple(_norm_index(e) for e in idx)
    return idx


def _getitem(self, idx):
    idx = _norm_index(idx)
    return dispatch(lambda v, i: v[i], (self, idx), {}, name="getitem")


def _setitem(self, idx, value):
    import jax.numpy as jnp
    idx = _norm_index(idx)

    def fn(v, i, val):
        val = jnp.asarray(val)
        return v.at[i].set(val.astype(v.dtype))
    out = dispatch(fn, (self, idx, value), {}, name="setitem")
    self._value = out._value
    self._node = out._node
    self._out_index = out._out_index
    if not out.stop_gradient:
        self.stop_gradient = False


_bind("__getitem__", _getitem)
_bind("__setitem__", _setitem)


def _tensor_backward(self, grad_tensor=None, retain_graph=False):
    from .autograd.backward import run_backward
    run_backward([self], [grad_tensor] if grad_tensor is not None else None,
                 retain_graph)


_bind("backward", _tensor_backward)


def _tensor_to(self, *args, **kwargs):
    """.to(dtype) / .to(place) / .to('tpu')"""
    out = self
    for a in list(args) + list(kwargs.values()):
        if isinstance(a, (str, _np.dtype)) and (
                isinstance(a, _np.dtype) or a in _dtype_mod._NAME_TO_DTYPE):
            out = _ops.cast(out, a)
        elif isinstance(a, type) or hasattr(a, "kind"):
            pass  # place moves are no-ops under a single default device
    return out


_bind("to", _tensor_to)
_bind("cpu", lambda self: self)
_bind("cuda", lambda self, *a, **k: self)
_bind("tpu", lambda self, *a, **k: self)
_bind("pin_memory", lambda self: self)

from . import version  # noqa: E402,F401
from . import callbacks  # noqa: E402,F401
from .core import string_tensor as strings  # noqa: E402,F401
from . import hub  # noqa: E402,F401
from . import sysconfig  # noqa: E402,F401

# ---------------------------------------------------------------------------
# top-level API long tail (constants, aliases, in-place wrappers) — closes the
# reference's paddle.__all__ surface (python/paddle/__init__.py)
# ---------------------------------------------------------------------------
import math as _math  # noqa: E402

inf = float("inf")
nan = float("nan")
pi = _math.pi
e = _math.e
newaxis = None
dtype = _np.dtype  # paddle.dtype is the dtype type object

# ParamAttr / flops resolve lazily (importing nn eagerly would defeat the
# lazy-submodule design above)
_LAZY_ATTRS.update({
    "ParamAttr": ("nn", "ParamAttr"),
    "flops": ("utils", "flops"),
})


_TOPLEVEL_INPLACE = [
    "abs", "acos", "addmm", "asin", "atan", "cast", "ceil", "clip", "cos",
    "cumsum", "cumprod", "digamma", "divide", "equal", "erf", "exp", "expm1",
    "flatten", "floor", "floor_divide", "frac", "gcd", "lcm", "lgamma", "log",
    "log2", "log10", "log1p", "logical_and", "logical_or", "logical_not",
    "logit", "masked_fill", "mod", "multiply", "nan_to_num", "neg", "pow",
    "reciprocal", "remainder", "renorm", "reshape", "round", "rsqrt",
    "scatter", "sigmoid", "sin", "sinc", "sinh", "sqrt", "square", "squeeze",
    "subtract", "t", "tan", "tanh", "transpose", "tril", "triu", "trunc",
    "unsqueeze", "bitwise_and", "bitwise_or", "bitwise_xor",
    "bitwise_not", "bitwise_invert", "copysign", "gammainc", "gammaincc",
    "gammaln", "hypot", "i0", "ldexp", "less_equal", "less_than", "less",
    "greater_equal", "greater_than", "multigammaln", "polygamma", "not_equal",
    "floor_mod",
]
_TOPLEVEL_INPLACE += ["bitwise_left_shift", "bitwise_right_shift",
                      "masked_scatter"]
for _n in _TOPLEVEL_INPLACE:
    if hasattr(_ops, _n) and not hasattr(_ops, _n + "_"):
        # _inplace (Tensor-method factory above) writes back into the first
        # argument AND propagates stop_gradient — reuse it for the top level
        _fn = _inplace(getattr(_ops, _n))
        _fn.__name__ = _n + "_"
        globals()[_n + "_"] = _fn


def where_(condition, x=None, y=None, name=None):
    """In-place on x (reference: paddle.where_ mutates x, not the mask)."""
    out = _ops.where(condition, x, y)
    x._value = out._value
    x._node = out._node
    x._out_index = out._out_index
    if not out.stop_gradient:
        x.stop_gradient = False
    return x


def rank(x):
    return _ops.to_tensor(len(x.shape))


def shape(x):
    return _ops.to_tensor(_np.asarray(x.shape, dtype="int32"))


def tolist(x):
    return x.numpy().tolist()


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


_static_mode = False


def enable_static():
    global _static_mode
    _static_mode = True


def disable_static():
    global _static_mode
    _static_mode = False


def in_dynamic_mode():
    return not _static_mode


def disable_signal_handler():
    pass  # no native signal handlers are installed


class LazyGuard:
    """Deferred parameter initialization (reference: python/paddle/base —
    LazyGuard / lazy_init). Under the guard, ``create_parameter`` produces
    ABSTRACT values (``jax.ShapeDtypeStruct``) and records the initializer;
    ``param.initialize()`` / ``layer.materialize()`` runs it later. An
    abstract model costs no host memory, which is what lets the full
    Llama-2-7B hybrid train step be AOT-compiled and memory-checked on a
    virtual mesh (tests/test_7b_scale.py) without a pod."""

    def __enter__(self):
        from .nn.layer_base import _LAZY_INIT
        _LAZY_INIT.depth += 1
        return self

    def __exit__(self, *exc):
        from .nn.layer_base import _LAZY_INIT
        _LAZY_INIT.depth -= 1
        return False


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    from .nn.layer_base import Parameter
    from .nn.initializer import Constant, XavierNormal
    init = default_initializer or (Constant(0.0) if is_bias
                                   else XavierNormal())
    from .core.dtype import convert_dtype
    return Parameter(init(list(shape), convert_dtype(dtype)), name=name)


def batch(reader, batch_size, drop_last=False):
    """Deprecated reader-batching helper (reference: paddle.batch)."""
    def gen():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return gen


def check_shape(shape):
    for s in shape:
        if s is not None and s < -1:
            raise ValueError(f"invalid dim {s} in shape {shape}")


def get_cuda_rng_state():
    return get_rng_state()


def set_cuda_rng_state(state):
    set_rng_state(state)


def from_dlpack(capsule):
    from .utils import dlpack as _dl
    return _dl.from_dlpack(capsule)


def to_dlpack(x):
    from .utils import dlpack as _dl
    return _dl.to_dlpack(x)


class CUDAPinnedPlace:
    """Pinned host memory place (no CUDA here; host arrays are the analog)."""

    def __repr__(self):
        return "CUDAPinnedPlace"


_LAZY_ATTRS.update({
    "DataParallel": ("distributed", "DataParallel"),
})

# pstring/raw (prototype string-tensor dtypes) are intentionally absent: the
# TPU build has no StringTensor analog (SURVEY.md §2.2 marks them niche).


# ---------------------------------------------------------------------------
# Tensor method parity: the reference monkey-patches ~394 functions onto
# Tensor (python/paddle/tensor/__init__.py tensor_method_func). Bind every
# top-level op that is not yet a method; `_`-suffixed names write back into
# self via the _inplace factory above.
# ---------------------------------------------------------------------------
_TENSOR_METHOD_PARITY = [
    'create_parameter', 'ormqr', 'cov', 'corrcoef', 'cond', 'cauchy_',
    'geometric_', 'lstsq', 't_', 'cholesky_inverse', 'histogram',
    'histogram_bin_edges', 'histogramdd', 'matrix_power',
    'matrix_transpose', 'qr', 'householder_product', 'pca_lowrank',
    'svd_lowrank', 'eigvals', 'eigvalsh', 'asin_', 'cumsum_', 'cumprod_',
    'logit', 'logit_', 'increment', 'log_', 'log2_', 'log10_', 'multiplex',
    'sinc', 'square_', 'reduce_as', 'multigammaln', 'multigammaln_',
    'nan_to_num_', 'hypot_', 'block_diag', 'add_n', 'inner', 'outer',
    'floor_divide_', 'mod_', 'floor_mod', 'floor_mod_', 'log1p_', 'addmm',
    'addmm_', 'kron', 'isin', 'isneginf', 'isposinf', 'isreal',
    'broadcast_shape', 'neg_', 'negative', 'lgamma_', 'gammaincc',
    'gammaincc_', 'gammainc', 'gammainc_', 'equal_', 'greater_equal_',
    'greater_than_', 'is_empty', 'less_equal_', 'less_than_', 'less',
    'less_', 'logical_and_', 'logical_not_', 'logical_or_', 'not_equal_',
    'is_tensor', 'concat', 'reverse', 'scatter_nd', 'shard_index', 'slice',
    'slice_scatter', 'hsplit', 'dsplit', 'vsplit', 'tensordot', 'stack',
    'strided_slice', 'transpose_', 'tan_', 'unstack', 'where_',
    'nanquantile', 'is_complex', 'is_integer', 'rank', 'real', 'imag',
    'is_floating_point', 'gammaln', 'gammaln_', 'digamma_', 'trunc_',
    'frac_', 'bitwise_and_', 'bitwise_or_', 'bitwise_xor_', 'bitwise_not_',
    'bitwise_invert', 'bitwise_invert_', 'broadcast_tensors', 'eig',
    'multi_dot', 'solve', 'cholesky_solve', 'triangular_solve', 'lu',
    'lu_unpack', 'cdist', 'as_complex', 'as_real', 'gcd', 'gcd_', 'lcm',
    'lcm_', 'diff', 'select_scatter', 'bernoulli_', 'exponential_',
    'index_put', 'take', 'sgn', 'frexp', 'ldexp', 'ldexp_', 'trapezoid',
    'cumulative_trapezoid', 'polar', 'vander', 'nextafter', 'unflatten',
    'view', 'view_as', 'unfold', 'i0', 'i0_', 'i0e', 'i1', 'i1e',
    'polygamma', 'polygamma_', 'diag_embed', 'diagflat', 'multinomial',
    'pinv', 'renorm', 'renorm_', 'acos_', 'atan_', 'cos_', 'sin_', 'sinc_',
    'sinh_', 'diag', 'copysign', 'copysign_', 'bitwise_left_shift',
    'bitwise_left_shift_', 'bitwise_right_shift', 'bitwise_right_shift_',
    'index_fill', 'atleast_1d', 'atleast_2d', 'atleast_3d',
    'diagonal_scatter', 'masked_scatter', 'masked_scatter_', 'combinations',
    'signbit', 'log_normal_'
]

for _n in _TENSOR_METHOD_PARITY:
    if hasattr(Tensor, _n):
        continue
    _fn = globals().get(_n)
    if _fn is None or not callable(_fn):
        continue
    _bind(_n, _method(_fn))

# in-place variants whose base op exists but had no eager wrapper yet
for _n in ["logical_xor", "atanh", "erfinv", "cosh", "acosh", "asinh",
           "index_fill"]:
    if hasattr(Tensor, _n) and not hasattr(Tensor, _n + "_"):
        _base = globals().get(_n) or getattr(_ops, _n, None)
        if _base is not None:
            _ip = _inplace(_base)
            _ip.__name__ = _n + "_"
            _bind(_n + "_", _ip)
            globals()[_n + "_"] = _ip

def _stft_method(self, *a, **k):
    from .signal import stft as _stft
    return _stft(self, *a, **k)


def _istft_method(self, *a, **k):
    from .signal import istft as _istft
    return _istft(self, *a, **k)


_bind("stft", _stft_method)
_bind("istft", _istft_method)


def create_tensor(dtype, name=None, persistable=False):
    """reference: tensor/creation.py create_tensor — an empty typed tensor."""
    import jax.numpy as _jnp
    from .core.dtype import convert_dtype as _cd
    t = Tensor(_jnp.zeros((0,), _cd(dtype)), stop_gradient=True)
    t.name = name
    t.persistable = persistable
    return t


def top_p_sampling(x, ps, threshold=None, topp_seed=None, seed=-1, k=0,
                   mode="truncated", return_top=False, name=None):
    """Nucleus sampling (reference: tensor/random.py top_p_sampling — GPU
    kernel): keep the smallest prefix of sorted probs with mass >= ps,
    renormalize, sample one id per row. Returns (values, ids)."""
    import jax as _jax
    import jax.numpy as _jnp
    from .core import random as _random
    if threshold is not None or topp_seed is not None or \
            k not in (0, None) or mode not in ("truncated", None) or \
            return_top:
        raise NotImplementedError(
            "top_p_sampling: threshold/topp_seed/k/mode/return_top are not "
            "supported on this backend; only plain nucleus sampling (use "
            "seed= for reproducibility)")
    key = _jax.random.PRNGKey(seed) if seed >= 0 else _random.next_key()

    def fn(probs, psv):
        order = _jnp.argsort(-probs, axis=-1)
        sp = _jnp.take_along_axis(probs, order, axis=-1)
        cum = _jnp.cumsum(sp, axis=-1)
        keep = (cum - sp) < psv.reshape(-1, 1)  # first index crossing ps kept
        masked = _jnp.where(keep, sp, 0.0)
        masked = masked / _jnp.sum(masked, axis=-1, keepdims=True)
        idx_sorted = _jax.random.categorical(key, _jnp.log(masked + 1e-20),
                                             axis=-1)
        ids = _jnp.take_along_axis(order, idx_sorted[:, None], axis=-1)
        vals = _jnp.take_along_axis(probs, ids, axis=-1)
        return vals, ids
    from .core.tensor import dispatch as _dispatch
    return _dispatch(fn, (x, ps), {}, name="top_p_sampling")


def _tensor_set_(self, source=None, shape=None, dtype=None):
    """reference: Tensor.set_ — re-point this tensor at source's data."""
    from .core.dtype import convert_dtype as _cd
    if source is not None:
        src = source._value if isinstance(source, Tensor) else source
        if shape is not None:
            src = src.reshape(shape)
        self._value = src.astype(_cd(dtype)) if dtype is not None else src
    elif shape is not None:
        import jax.numpy as _jnp
        self._value = _jnp.zeros(
            shape, _cd(dtype) if dtype is not None else self._value.dtype)
    self._node = None
    return self


def _tensor_resize_(self, shape, fill_zero=False):
    """reference: Tensor.resize_ — keep the flat prefix; growing beyond the
    current size requires fill_zero=True (reference raises otherwise)."""
    import numpy as _np
    import jax.numpy as _jnp
    n_new = int(_np.prod(shape)) if len(shape) else 1
    flat = self._value.reshape(-1)
    if n_new <= flat.shape[0]:
        self._value = flat[:n_new].reshape(shape)
    else:
        if not fill_zero:
            raise ValueError(
                "resize_: growing the tensor requires fill_zero=True")
        pad = _jnp.zeros((n_new - flat.shape[0],), flat.dtype)
        self._value = _jnp.concatenate([flat, pad]).reshape(shape)
    self._node = None
    return self


_bind("set_", _tensor_set_)
_bind("resize_", _tensor_resize_)
_bind("create_tensor", _method(lambda self, *a, **k: create_tensor(*a, **k)))
_bind("top_p_sampling", _method(top_p_sampling))

#: ``profiler.startup()``'s ``(import_s, jax_preimported)``: this file's wall,
#: first line to last, and whether jax (imported first thing) was loaded already
_IMPORTED = (_time.perf_counter() - _import_t0, _jax_preimported)
