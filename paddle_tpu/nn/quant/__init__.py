"""paddle.nn.quant analog — quantized layers + weight-only helpers.

Reference: python/paddle/nn/quant/ (qat layer wrappers, and the weight-only
GEMM helpers weight_quantize/weight_only_linear used for LLM inference).
TPU-native: weight-only int8 keeps weights in HBM at half the bytes and
dequantizes inline — XLA fuses the scale-multiply into the matmul, which is the
memory-bandwidth win the reference gets from its cutlass weight-only kernels.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ...core.tensor import Tensor, dispatch
from ..layer_base import Layer
from ...quantization import (  # noqa: F401
    QuantedLinear, QuantedConv2D, QuantizedLinearInfer,
    FakeQuanterWithAbsMaxObserver, FakeQuanterChannelWiseAbsMaxObserver,
    quantize_linear, dequantize_linear, fake_quantize,
)

__all__ = [
    "QuantedLinear", "QuantedConv2D", "QuantizedLinearInfer",
    "FakeQuanterWithAbsMaxObserver", "FakeQuanterChannelWiseAbsMaxObserver",
    "quantize_linear", "dequantize_linear", "fake_quantize",
    "weight_quantize", "weight_dequantize", "weight_only_linear", "llm_int8_linear",
    "WeightOnlyLinear", "quantize_linears_for_inference",
]


def weight_quantize(weight, algo="weight_only_int8", group_size=-1):
    """Per-out-channel weight quantization.

    int8: returns (int8 Tensor [in, out], scales float Tensor [out]).
    int4: two values pack into each int8 byte along the input dim — returns
    (int8 Tensor [ceil(in/2), out] with row 2k in the low nibble and row 2k+1
    in the high nibble, scales [out]); odd input dims are zero-padded.
    Reference: nn/quant/quantized_linear.py weight_quantize."""
    if algo not in ("weight_only_int8", "llm.int8", "weight_only_int4"):
        raise NotImplementedError(f"unknown weight_quantize algo {algo!r}")
    w = weight.numpy() if isinstance(weight, Tensor) else np.asarray(weight)
    if algo == "weight_only_int4":
        scales = np.maximum(np.abs(w).max(axis=0), 1e-9).astype(np.float32) / 7.0
        q = np.clip(np.round(w / scales[None, :]), -8, 7).astype(np.int8)
        if q.shape[0] % 2:
            q = np.concatenate([q, np.zeros((1, q.shape[1]), np.int8)])
        packed = ((q[0::2] & 0x0F) | ((q[1::2] & 0x0F) << 4)).astype(np.int8)
        return Tensor(packed), Tensor(scales)
    scales = np.maximum(np.abs(w).max(axis=0), 1e-9).astype(np.float32) / 127.0
    q = np.clip(np.round(w / scales[None, :]), -127, 127).astype(np.int8)
    return Tensor(q), Tensor(scales)


def _nibbles(p):
    """Sign-extended (low, high) int4 nibbles of a packed int8 tensor —
    THE unpacking convention (row 2k low, row 2k+1 high); shared by
    weight_dequantize and weight_only_linear."""
    low = jnp.right_shift(jnp.left_shift(p, 4), 4)
    high = jnp.right_shift(p, 4)
    return low, high


def _unpack_int4(p, n_in=None):
    """[rows, out] packed int8 -> [2*rows, out] int4 values, truncated to
    n_in rows."""
    low, high = _nibbles(p)
    q = jnp.stack([low, high], axis=1).reshape(-1, p.shape[-1])
    return q if n_in is None else q[:n_in]


def weight_dequantize(quant_weight, scale, algo="weight_only_int8",
                      in_features=None):
    """Inverse of weight_quantize. For int4, pass ``in_features`` to strip
    the zero-pad row of odd input dims (otherwise the padded [2*rows, out]
    shape is returned)."""
    def fn(q, s):
        if algo == "weight_only_int4":
            q = _unpack_int4(q, in_features)
        return q.astype(s.dtype) * s[None, :]

    return dispatch(fn, (quant_weight, scale), {}, name="weight_dequantize")


def weight_only_linear(x, weight, bias=None, weight_scale=None,
                       weight_dtype="int8", group_size=-1):
    """y = x @ dequant(w) + b; the dequant fuses into the matmul operand.
    weight_dtype='int4' consumes the packed layout from weight_quantize —
    computed as TWO half-size matmuls on the low/high nibbles (even/odd
    input rows), which avoids materializing the interleave-unpacked
    [in, out] matrix the stack+reshape form costs per call.
    Reference: nn/quant/quantized_linear.py weight_only_linear."""
    def fn(xv, q, s, b):
        sb = s.astype(xv.dtype)
        if weight_dtype == "int4":
            n_in = xv.shape[-1]
            from ...core.flags import flag_value
            from ...ops.kernels.int4_matmul import (int4_matmul,
                                                    int4_matmul_tileable)
            rows = int(np.prod(xv.shape[:-1]))
            # decode-shaped GEMMs only: the kernel keeps whole x row-blocks
            # in VMEM, so many-row (prefill/training) calls would blow the
            # scoped-vmem budget — those are compute-bound anyway and keep
            # the split-nibble path
            use_pallas = (flag_value("use_pallas_int4")
                          and jax.default_backend() == "tpu"
                          and rows <= 128
                          and int4_matmul_tileable(n_in, q.shape[-1]))
            if use_pallas:
                # fused dequant-matmul: packed bytes stream straight to the
                # MXU with in-register nibble extraction (halves int8's
                # weight traffic; ~1.4x its decode GEMM on v5e). The kernel
                # has no VJP of its own, so a custom_vjp supplies the
                # x-gradient via the split-nibble dequant matmul (small-
                # batch fine-tune/eval graphs differentiate through this).
                @jax.custom_vjp
                def _mm(x2d):
                    return int4_matmul(x2d, q, s)

                def _mm_fwd(x2d):
                    return _mm(x2d), None

                def _mm_bwd(_, dy):
                    low, high = _nibbles(q)
                    sd = s.astype(dy.dtype)
                    dxe = jnp.matmul(dy, (low.astype(dy.dtype)
                                          * sd[None, :]).T)
                    dxo = jnp.matmul(dy, (high.astype(dy.dtype)
                                          * sd[None, :]).T)
                    # W rows interleave low/high nibbles: dx[2i]=dxe[i],
                    # dx[2i+1]=dxo[i], truncated to odd in_features
                    dx = jnp.stack([dxe, dxo], axis=-1).reshape(
                        dy.shape[:-1] + (2 * low.shape[0],))[..., :n_in]
                    return (dx,)

                _mm.defvjp(_mm_fwd, _mm_bwd)
                lead = xv.shape[:-1]
                y = _mm(xv.reshape(-1, n_in))
                y = y.reshape(lead + (q.shape[-1],))
            else:
                low, high = _nibbles(q)
                x_even = xv[..., 0::2]
                x_odd = xv[..., 1::2]
                if n_in % 2:  # odd in_features: pad row pairs with nothing
                    x_odd = jnp.pad(x_odd,
                                    [(0, 0)] * (xv.ndim - 1) + [(0, 1)])
                y = (jnp.matmul(x_even, low.astype(xv.dtype) * sb[None, :])
                     + jnp.matmul(x_odd, high.astype(xv.dtype) * sb[None, :]))
        else:
            w = q.astype(xv.dtype) * sb[None, :]
            y = jnp.matmul(xv, w)
        if b is not None:
            y = y + b
        return y

    return dispatch(fn, (x, weight, weight_scale, bias), {},
                    name="weight_only_linear")


def llm_int8_linear(x, weight, bias=None, weight_scale=None, threshold=6.0):
    """LLM.int8 decomposition (reference: nn/quant/quantized_linear.py
    llm_int8_linear): inlier activation columns are themselves quantized to
    int8 (per-row dynamic scale) and multiplied against the int8 weights —
    the int8×int8 path — while outlier columns (|x| > threshold) run in full
    precision against the dequantized weights."""
    def fn(xv, q, s, b):
        w = q.astype(xv.dtype) * s.astype(xv.dtype)[None, :]
        absx = jnp.max(jnp.abs(xv), axis=tuple(range(xv.ndim - 1)))
        outlier = absx > threshold
        x_main = jnp.where(outlier, 0.0, xv)
        x_out = jnp.where(outlier, xv, 0.0)
        # dynamic per-row int8 quantization of the inlier activations
        row_scale = jnp.maximum(
            jnp.max(jnp.abs(x_main), axis=-1, keepdims=True), 1e-9) / 127.0
        xq = jnp.clip(jnp.round(x_main / row_scale), -127, 127)
        # int8 x int8 accumulated in int32, then rescaled (XLA lowers this to
        # the TPU int matmul path); outliers take the fp route
        y_main = jnp.matmul(xq.astype(jnp.int32),
                            q.astype(jnp.int32)).astype(xv.dtype)
        y_main = y_main * row_scale * s.astype(xv.dtype)[None, :]
        y = y_main + jnp.matmul(x_out, w)
        if b is not None:
            y = y + b
        return y

    return dispatch(fn, (x, weight, weight_scale, bias), {},
                    name="llm_int8_linear")


class WeightOnlyLinear(Layer):
    """Deploy-form Linear with weight-only quantized STORAGE: the fp weight
    is dropped; forward streams the int8/int4 weight and fuses dequant into
    the matmul operand load. On a weight-bandwidth-bound decode step this
    halves (int8) or quarters (int4) the HBM bytes per token — the TPU
    analog of the reference's cutlass weight-only GEMM serving path
    (nn/quant/quantized_linear.py weight_only_linear + paddlenlp
    WeightOnlyLinear)."""

    def __init__(self, linear, weight_dtype="int8"):
        super().__init__()
        from ..layer_base import Parameter
        q, s = weight_quantize(linear.weight,
                               algo=f"weight_only_{weight_dtype}")
        # device-resident storage: weight_quantize computes host-side
        # (numpy); a numpy-backed param would be re-uploaded on EVERY jitted
        # call
        self.quant_weight = Parameter(jnp.asarray(q._value), trainable=False)
        self.weight_scale = Parameter(jnp.asarray(s._value), trainable=False)
        self.bias = linear.bias
        self.weight_dtype = weight_dtype
        self.in_features = int(linear.weight.shape[0])
        self.out_features = int(linear.weight.shape[1])

    def forward(self, x):
        return weight_only_linear(x, self.quant_weight, self.bias,
                                  self.weight_scale, self.weight_dtype)


def quantize_linears_for_inference(layer, weight_dtype="int8",
                                   skip=lambda name, lin: False):
    """Swap every ``nn.Linear`` in the tree (in place) for
    :class:`WeightOnlyLinear` deploy storage. ``skip(qualified_name,
    linear)`` exempts layers (e.g. tiny heads). Returns the layer and the
    number of swaps."""
    from ..layer import common as _common
    n = [0]

    def visit(l, prefix):
        for name, sub in list(l._sub_layers.items()):
            qual = f"{prefix}{name}"
            if isinstance(sub, _common.Linear) and not skip(qual, sub):
                l.add_sublayer(name, WeightOnlyLinear(
                    sub, weight_dtype=weight_dtype))
                n[0] += 1
            elif isinstance(sub, Layer):
                visit(sub, qual + ".")

    visit(layer, "")
    return layer, n[0]


class Stub(Layer):
    """Quantization insertion point (reference: nn/quant/stub.py Stub): a
    no-op layer the QAT pass replaces with the configured quanter."""

    def __init__(self, observer=None):
        super().__init__()
        self._observer = observer

    def forward(self, input):
        return input


__all__.append("Stub")
