"""paddle.quantization analog — QAT / PTQ framework.

Reference: python/paddle/quantization/ (QuantConfig in config.py, QAT in
qat.py, PTQ in ptq.py, observers in observer/, quanters in quanter/ — SURVEY.md
§2.6). TPU-native notes: fake-quant runs as a jax custom_vjp (straight-through
estimator) so it fuses into the compiled step; "convert" produces layers whose
weights are stored int8 + scale, computing int8→bf16 dequant inline (XLA fuses
the dequant into the matmul's operand load, the TPU analog of the reference's
quantized kernels).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, dispatch
from ..nn.layer_base import Layer

__all__ = [
    "QuantConfig", "QAT", "PTQ", "quanters", "observers",
    "AbsmaxObserver", "EMAObserver", "AVGObserver", "MSEObserver",
    "HistObserver", "PerChannelAbsmaxObserver",
    "FakeQuanterWithAbsMaxObserver", "FakeQuanterChannelWiseAbsMaxObserver",
    "quantize_linear", "dequantize_linear", "fake_quantize",
]


# ---------------------------------------------------------------------------
# fake-quant primitive with STE gradient
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _fake_quant(x, scale, qmin, qmax):
    s = jnp.maximum(scale, 1e-9)
    q = jnp.clip(jnp.round(x / s), qmin, qmax)
    return q * s


def _fake_quant_fwd(x, scale, qmin, qmax):
    s = jnp.maximum(scale, 1e-9)
    out = jnp.clip(jnp.round(x / s), qmin, qmax) * s
    mask = (x / s >= qmin) & (x / s <= qmax)
    return out, mask


def _fake_quant_bwd(res, g):
    mask = res
    # straight-through: pass gradients inside the clip range, zero outside
    return (g * mask.astype(g.dtype), None, None, None)


_fake_quant.defvjp(_fake_quant_fwd, _fake_quant_bwd)


def fake_quantize(x, scale, bit_length=8, name=None):
    """Simulated quantization with STE backward (reference:
    quanter/base_fake_quanter.py -> fake_quantize_dequantize kernels)."""
    qmax = float(2 ** (bit_length - 1) - 1)

    def fn(v, s):
        return _fake_quant(v, s.astype(v.dtype), -qmax, qmax)

    return dispatch(fn, (x, scale), {}, name="fake_quantize")


def quantize_linear(x, scale, zero_point=None, bit_length=8, axis=None,
                    name=None):
    """Real quantization to int8 (reference: tensor quantize_linear op)."""
    qmax = 2 ** (bit_length - 1) - 1

    def fn(v, s):
        if axis is not None:
            shape = [1] * v.ndim
            shape[axis] = -1
            s = s.reshape(shape)
        return jnp.clip(jnp.round(v / jnp.maximum(s, 1e-9)), -qmax, qmax) \
            .astype(jnp.int8)

    return dispatch(fn, (x, scale), {}, name="quantize_linear")


def dequantize_linear(x, scale, zero_point=None, axis=None, out_dtype="float32",
                      name=None):
    def fn(v, s):
        if axis is not None:
            shape = [1] * v.ndim
            shape[axis] = -1
            s = s.reshape(shape)
        return v.astype(s.dtype) * s

    return dispatch(fn, (x, scale), {}, name="dequantize_linear")


# ---------------------------------------------------------------------------
# observers (reference: quantization/observer/*)
# ---------------------------------------------------------------------------

class BaseObserver(Layer):
    """Collects activation/weight statistics and yields a quant scale."""

    def __init__(self, quant_bits=8):
        super().__init__()
        self.quant_bits = quant_bits
        self._scale = None

    def scale(self):
        if self._scale is None:
            raise RuntimeError(f"{type(self).__name__} observed no data yet")
        return self._scale

    def bit_length(self):
        return self.quant_bits

    def quant_axis(self):
        return None

    def observe(self, x):
        raise NotImplementedError

    def forward(self, x):
        self.observe(x)
        return x

    def _qmax(self):
        return float(2 ** (self.quant_bits - 1) - 1)


class AbsmaxObserver(BaseObserver):
    def observe(self, x):
        m = float(np.abs(np.asarray(x._value if isinstance(x, Tensor)
                                    else x)).max())
        self._scale = max(m, self._scale or 0.0) / 1.0
        self._scale = max(self._scale, 1e-9)

    def scale(self):
        super().scale()
        return self._scale / self._qmax()


class EMAObserver(BaseObserver):
    """Moving-average absmax (reference: FakeQuanterWithAbsMaxObserver's EMA)."""

    def __init__(self, quant_bits=8, moving_rate=0.9):
        super().__init__(quant_bits)
        self.moving_rate = moving_rate

    def observe(self, x):
        m = float(np.abs(np.asarray(x._value if isinstance(x, Tensor)
                                    else x)).max())
        if self._scale is None:
            self._scale = m
        else:
            self._scale = self.moving_rate * self._scale \
                + (1 - self.moving_rate) * m
        self._scale = max(self._scale, 1e-9)

    def scale(self):
        super().scale()
        return self._scale / self._qmax()


class AVGObserver(BaseObserver):
    def __init__(self, quant_bits=8):
        super().__init__(quant_bits)
        self._sum, self._n = 0.0, 0

    def observe(self, x):
        m = float(np.abs(np.asarray(x._value if isinstance(x, Tensor)
                                    else x)).max())
        self._sum += m
        self._n += 1
        self._scale = self._sum / self._n

    def scale(self):
        super().scale()
        return max(self._scale, 1e-9) / self._qmax()


class MSEObserver(BaseObserver):
    """Picks the clip that minimizes quantization MSE over observed batches."""

    def __init__(self, quant_bits=8, candidates=20):
        super().__init__(quant_bits)
        self.candidates = candidates
        self._samples = []
        self._n_stored = 0
        self._dirty = True

    _MAX_STORED = 1 << 20

    def observe(self, x):
        # cheap per-batch: subsample and stash; the clip search runs lazily in
        # scale(), so calibration is O(n_batches), not O(n^2)
        v = np.asarray(x._value if isinstance(x, Tensor) else x).ravel()
        if v.size > 65536:
            v = v[:: v.size // 65536]
        self._samples.append(v.astype(np.float32))
        self._n_stored += v.size
        if self._n_stored > self._MAX_STORED:
            data = np.concatenate(self._samples)
            data = data[:: max(data.size // (self._MAX_STORED // 2), 1)]
            self._samples = [data]
            self._n_stored = data.size
        self._dirty = True
        self._scale = self._scale or 1.0  # mark "has data"

    def _search(self):
        data = np.concatenate(self._samples)
        absmax = float(np.abs(data).max())
        qmax = self._qmax()
        best, best_err = absmax, np.inf
        for frac in np.linspace(0.3, 1.0, self.candidates):
            clip = max(absmax * frac, 1e-9)
            s = clip / qmax
            q = np.clip(np.round(data / s), -qmax, qmax) * s
            err = float(((data - q) ** 2).mean())
            if err < best_err:
                best, best_err = clip, err
        self._scale = max(best, 1e-9)
        self._dirty = False

    def scale(self):
        if not self._samples:
            super().scale()  # raises "observed no data yet"
        if self._dirty:
            self._search()
        return self._scale / self._qmax()


class HistObserver(BaseObserver):
    """Histogram percentile clipping (reference: observer/hist.py)."""

    def __init__(self, quant_bits=8, bins=2048, percent=0.999):
        super().__init__(quant_bits)
        self.bins = bins
        self.percent = percent
        self._hist = None
        self._range = None

    def observe(self, x):
        v = np.abs(np.asarray(x._value if isinstance(x, Tensor) else x)).ravel()
        m = float(v.max()) if v.size else 0.0
        if self._hist is None:
            self._range = max(m, 1e-9)
            self._hist = np.histogram(v, bins=self.bins,
                                      range=(0, self._range))[0].astype(float)
        else:
            if m > self._range:  # stretch: rebin old histogram
                ratio = m / self._range
                idx = (np.arange(self.bins) / ratio).astype(int)
                new_hist = np.zeros(self.bins)
                np.add.at(new_hist, idx, self._hist)
                self._hist = new_hist
                self._range = m
            self._hist += np.histogram(v, bins=self.bins,
                                       range=(0, self._range))[0]
        cdf = np.cumsum(self._hist) / max(self._hist.sum(), 1)
        cut = int(np.searchsorted(cdf, self.percent))
        self._scale = max((cut + 1) / self.bins * self._range, 1e-9)

    def scale(self):
        super().scale()
        return self._scale / self._qmax()


class PerChannelAbsmaxObserver(BaseObserver):
    def __init__(self, quant_bits=8, quant_axis=-1):
        super().__init__(quant_bits)
        self._axis = quant_axis

    def quant_axis(self):
        return self._axis

    def observe(self, x):
        v = np.abs(np.asarray(x._value if isinstance(x, Tensor) else x))
        axes = tuple(i for i in range(v.ndim) if i != self._axis % v.ndim)
        m = v.max(axis=axes)
        self._scale = m if self._scale is None else np.maximum(self._scale, m)
        self._scale = np.maximum(self._scale, 1e-9)

    def scale(self):
        super().scale()
        return self._scale / self._qmax()


# ---------------------------------------------------------------------------
# quanters — trainable fake-quant wrappers used during QAT
# ---------------------------------------------------------------------------

class FakeQuanterWithAbsMaxObserver(Layer):
    """Activation quanter: EMA absmax scale + STE fake-quant each forward."""

    def __init__(self, moving_rate=0.9, bit_length=8, dtype="float32",
                 name=None):
        super().__init__()
        self._observer = EMAObserver(bit_length, moving_rate)
        self.bit_length = bit_length

    def forward(self, x):
        # eval before any training step still needs a scale: bootstrap the
        # observer from the first tensor it sees (reference initializes the
        # scale buffer similarly)
        if self.training or self._observer._scale is None:
            self._observer.observe(x)
        from ..ops.creation import to_tensor
        return fake_quantize(x, to_tensor(np.float32(self._observer.scale())),
                             self.bit_length)

    def scale(self):
        return self._observer.scale()

    def bit_len(self):
        return self.bit_length


class FakeQuanterChannelWiseAbsMaxObserver(Layer):
    """Weight quanter: per-output-channel absmax (recomputed each forward,
    since weights change under training)."""

    def __init__(self, bit_length=8, quant_axis=-1, dtype="float32", name=None):
        super().__init__()
        self.bit_length = bit_length
        self._axis = quant_axis
        self._observer = PerChannelAbsmaxObserver(bit_length, quant_axis)

    def forward(self, w):
        qmax = float(2 ** (self.bit_length - 1) - 1)
        axis = self._axis

        def fn(v):
            ax = tuple(i for i in range(v.ndim) if i != axis % v.ndim)
            s = jnp.maximum(jnp.max(jnp.abs(v), axis=ax, keepdims=True),
                            1e-9) / qmax
            return _fake_quant(v, s, -qmax, qmax)

        self._observer.observe(w)
        return dispatch(fn, (w,), {}, name="fake_channel_quant")

    def scale(self):
        return self._observer.scale()


class quanters:
    FakeQuanterWithAbsMaxObserver = FakeQuanterWithAbsMaxObserver
    FakeQuanterChannelWiseAbsMaxObserver = FakeQuanterChannelWiseAbsMaxObserver


class observers:
    AbsmaxObserver = AbsmaxObserver
    EMAObserver = EMAObserver
    AVGObserver = AVGObserver
    MSEObserver = MSEObserver
    HistObserver = HistObserver
    PerChannelAbsmaxObserver = PerChannelAbsmaxObserver


# ---------------------------------------------------------------------------
# QuantConfig (reference: quantization/config.py)
# ---------------------------------------------------------------------------

class QuantConfig:
    def __init__(self, activation=None, weight=None):
        self._global_activation = activation
        self._global_weight = weight
        self._layer_cfg = {}   # layer instance id -> (act, w)
        self._type_cfg = {}    # layer class -> (act, w)
        self._name_cfg = {}    # sublayer name -> (act, w)

    def add_layer_config(self, layer, activation=None, weight=None):
        layers = layer if isinstance(layer, (list, tuple)) else [layer]
        for l in layers:
            self._layer_cfg[id(l)] = (activation, weight)

    def add_type_config(self, layer_type, activation=None, weight=None):
        types = layer_type if isinstance(layer_type, (list, tuple)) \
            else [layer_type]
        for t in types:
            self._type_cfg[t] = (activation, weight)

    def add_name_config(self, names, activation=None, weight=None):
        names = names if isinstance(names, (list, tuple)) else [names]
        for n in names:
            self._name_cfg[n] = (activation, weight)

    def _config_for(self, name, layer):
        if id(layer) in self._layer_cfg:
            return self._layer_cfg[id(layer)]
        if name in self._name_cfg:
            return self._name_cfg[name]
        for t, cfg in self._type_cfg.items():
            if isinstance(layer, t):
                return cfg
        return (self._global_activation, self._global_weight)


def _make(factory):
    if factory is None:
        return None
    if isinstance(factory, type):
        return factory()
    if callable(factory) and not isinstance(factory, Layer):
        return factory()
    return factory


# ---------------------------------------------------------------------------
# quantized layer wrappers + converted (deploy) layers
# ---------------------------------------------------------------------------

class QuantedLinear(Layer):
    """QAT wrapper (reference: nn/quant/qat/linear.py QuantedLinear)."""

    def __init__(self, linear, act_quanter=None, weight_quanter=None):
        super().__init__()
        self._inner = linear
        self.activation_quanter = _make(act_quanter)
        self.weight_quanter = _make(weight_quanter) \
            or FakeQuanterChannelWiseAbsMaxObserver()

    def forward(self, x):
        from ..nn import functional as F
        if self.activation_quanter is not None:
            x = self.activation_quanter(x)
        w = self.weight_quanter(self._inner.weight)
        return F.linear(x, w, self._inner.bias)


class QuantedConv2D(Layer):
    def __init__(self, conv, act_quanter=None, weight_quanter=None):
        super().__init__()
        self._inner = conv
        self.activation_quanter = _make(act_quanter)
        self.weight_quanter = _make(weight_quanter) \
            or FakeQuanterChannelWiseAbsMaxObserver(quant_axis=0)

    def forward(self, x):
        from ..nn import functional as F
        if self.activation_quanter is not None:
            x = self.activation_quanter(x)
        w = self.weight_quanter(self._inner.weight)
        c = self._inner
        return F.conv2d(x, w, c.bias, c._stride, c._padding, c._dilation,
                        c._groups, c._data_format)


class QuantizedLinearInfer(Layer):
    """Deploy form: int8 weights + per-channel scales, dequant fused into the
    matmul operand (the XLA analog of a quantized inference kernel).

    With an activation scale (from PTQ calibration) the input is quantized
    too — W8A8: int8×int8 matmul accumulated in int32, rescaled once."""

    def __init__(self, linear, weight_scale, act_scale=None):
        super().__init__()
        w = linear.weight
        scale_np = np.asarray(weight_scale, dtype=np.float32)
        self.w_int8 = quantize_linear(w, Tensor(scale_np), axis=-1)
        self.scales = Tensor(scale_np)
        self.act_scale = None if act_scale is None \
            else float(np.asarray(act_scale))
        self.bias = linear.bias

    def forward(self, x):
        from ..nn import functional as F
        if self.act_scale is not None:
            a_s = self.act_scale

            def fn(xv, q, s, b):
                xq = jnp.clip(jnp.round(xv / a_s), -127, 127)
                y = jnp.matmul(xq.astype(jnp.int32),
                               q.astype(jnp.int32)).astype(s.dtype)
                y = y * (a_s * s)[None, :]
                if b is not None:
                    y = y + b
                return y

            return dispatch(fn, (x, self.w_int8, self.scales, self.bias), {},
                            name="quantized_linear_w8a8")
        w = dequantize_linear(self.w_int8, self.scales, axis=-1)
        return F.linear(x, w, self.bias)


class QuantizedConv2DInfer(Layer):
    """Deploy conv: int8 weights (per-out-channel scale, axis 0), inline
    dequant fused into the conv operand load. Only the int8 weight + bias are
    retained — the fp32 weight is dropped."""

    def __init__(self, conv, weight_scale):
        super().__init__()
        scale_np = np.asarray(weight_scale, dtype=np.float32)
        self.w_int8 = quantize_linear(conv.weight, Tensor(scale_np), axis=0)
        self.scales = Tensor(scale_np)
        self.bias = conv.bias
        self._cfg = (conv._stride, conv._padding, conv._dilation,
                     conv._groups, conv._data_format)

    def forward(self, x):
        from ..nn import functional as F
        w = dequantize_linear(self.w_int8, self.scales, axis=0)
        stride, padding, dilation, groups, fmt = self._cfg
        return F.conv2d(x, w, self.bias, stride, padding, dilation, groups,
                        fmt)


class _ObserverWrapper(Layer):
    """PTQ stage: observe activations, pass through unchanged."""

    def __init__(self, inner, act_observer):
        super().__init__()
        self._inner = inner
        self.act_observer = _make(act_observer)

    def forward(self, x):
        if self.act_observer is not None:
            self.act_observer.observe(x)
        return self._inner(x)


# ---------------------------------------------------------------------------
# QAT / PTQ drivers
# ---------------------------------------------------------------------------

def _swap_sublayers(model, swap_fn):
    """Walk the layer tree, replacing sublayers where swap_fn returns non-None."""
    for name, child in list(model._sub_layers.items()):
        replaced = swap_fn(name, child)
        if replaced is not None:
            model.add_sublayer(name, replaced)
        else:
            _swap_sublayers(child, swap_fn)
    return model


class QAT:
    """Quantization-aware training driver (reference: quantization/qat.py)."""

    def __init__(self, config: QuantConfig):
        self.config = config

    def quantize(self, model, inplace=False):
        from ..nn.layer.common import Linear
        from ..nn.layer.conv import Conv2D
        if not inplace:
            import copy
            model = copy.deepcopy(model)

        def swap(name, layer):
            act, w = self.config._config_for(name, layer)
            if isinstance(layer, Linear):
                return QuantedLinear(layer, act, w)
            if isinstance(layer, Conv2D):
                return QuantedConv2D(layer, act, w)
            return None

        return _swap_sublayers(model, swap)

    def convert(self, model, inplace=False):
        """QAT model -> deploy model with int8 weights."""
        if not inplace:
            import copy
            model = copy.deepcopy(model)

        def swap(name, layer):
            if isinstance(layer, QuantedLinear):
                return QuantizedLinearInfer(layer._inner,
                                            layer.weight_quanter.scale())
            if isinstance(layer, QuantedConv2D):
                return QuantizedConv2DInfer(layer._inner,
                                            layer.weight_quanter.scale())
            return None

        return _swap_sublayers(model, swap)


class PTQ:
    """Post-training quantization driver (reference: quantization/ptq.py).

    Usage: q = PTQ(config); model = q.quantize(model); run calibration
    batches; model = q.convert(model)."""

    def __init__(self, config: QuantConfig):
        self.config = config

    def quantize(self, model, inplace=False):
        from ..nn.layer.common import Linear
        from ..nn.layer.conv import Conv2D
        if not inplace:
            import copy
            model = copy.deepcopy(model)

        def swap(name, layer):
            if isinstance(layer, (Linear, Conv2D)):
                act, _ = self.config._config_for(name, layer)
                return _ObserverWrapper(layer, act or AbsmaxObserver)
            return None

        return _swap_sublayers(model, swap)

    def convert(self, model, inplace=False):
        from ..nn.layer.common import Linear
        if not inplace:
            import copy
            model = copy.deepcopy(model)

        def swap(name, layer):
            if isinstance(layer, _ObserverWrapper) \
                    and isinstance(layer._inner, Linear):
                w = layer._inner.weight.numpy()
                scales = np.maximum(np.abs(w).max(axis=0), 1e-9) / 127.0
                # calibration result -> W8A8; without it, weight-only
                act_scale = None
                if layer.act_observer is not None \
                        and layer.act_observer._scale is not None:
                    act_scale = layer.act_observer.scale()
                return QuantizedLinearInfer(layer._inner, scales,
                                            act_scale=act_scale)
            if isinstance(layer, _ObserverWrapper):
                return layer._inner
            return None

        return _swap_sublayers(model, swap)


class BaseQuanter(Layer):
    """Abstract quanter contract (reference: quantization/base_quanter.py):
    forward simulates quantization; scales/zero_points/bit_length describe
    the produced quantization parameters."""

    def forward(self, input):
        raise NotImplementedError

    def scales(self):
        raise NotImplementedError

    def zero_points(self):
        raise NotImplementedError

    def quant_axis(self):
        return -1

    def bit_length(self):
        return 8


class QuanterFactory:
    """Deferred quanter constructor (reference: quantization/factory.py
    QuanterFactory — holds args, instantiates per layer)."""

    def __init__(self, cls, *args, **kwargs):
        self.partial_class = cls
        self.args = args
        self.kwargs = kwargs

    def _instance(self, layer=None):
        return self.partial_class(*self.args, **self.kwargs)

    def __call__(self, *args, **kwargs):
        return QuanterFactory(self.partial_class, *args, **kwargs)


def quanter(class_name):
    """Register a quanter class under a factory name (reference:
    quantization/factory.py quanter decorator): the decorated class gains a
    same-named factory in this module, so configs can reference it lazily."""
    def wrapper(cls):
        factory = QuanterFactory(cls)
        globals()[class_name] = factory
        import sys
        setattr(sys.modules[__name__], class_name, factory)
        return cls
    return wrapper


__all__ += ["BaseQuanter", "quanter", "QuanterFactory"]
